"""The gated delta rule (ops/delta_rule.py, the op ``gated_delta_rule`` and
its grad op), under a decay a value head and under one a key channel, and
the ungated form of ``short_conv``: the chunked computation against the
token-by-token recurrence, value and every cotangent, in float32 on the
CPU."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import decoder_ops, delta_rule, registry

from delta_rule_reference import (  # noqa: F401  (exact_products: autouse)
    build_rule, channel_operands, channel_recurrence, cotangents, eqns_of,
    exact_products, operands, recurrence, rel, weighted_sum)


#: (tokens, chunk, batch, key heads, value heads, AMP type or None, decay)
CASES = {
    # several chunks with a ragged last one (53 = 3 x 16 + 5, 70 = 2 x 32
    # + 6), whole chunks, a sequence under one chunk, the published chunk
    "ragged_53_by_16": (53, 16, 2, 2, 4, None, 0.5),
    "whole_48_by_16": (48, 16, 2, 2, 4, None, 0.5),
    "ragged_70_by_32": (70, 32, 2, 2, 4, None, 0.5),
    "under_a_chunk": (7, 16, 2, 2, 4, None, 0.5),
    "whole_96_by_64": (96, 64, 2, 2, 4, None, 0.5),
    # one value head a key head; one row; whole and ragged chunks of 64
    "one_value_head_a_key_head": (53, 16, 2, 2, 2, None, 0.5),
    "one_value_head_whole_chunks": (64, 16, 2, 3, 3, None, 0.5),
    "one_row": (48, 16, 1, 2, 4, None, 0.5),
    "whole_128_by_64": (128, 64, 2, 1, 2, None, 0.5),
    "ragged_150_by_64": (150, 64, 1, 2, 2, None, 0.5),
    # g down to -200 a token: exp(G_i - G_j) underflows within a few tokens
    "underflow": (53, 16, 2, 2, 4, None, 200.0),
    "underflow_one_value_head_by_64": (150, 64, 1, 2, 2, None, 200.0),
    # bf16 q, k, v under AMP: every contraction but the inverse's in bf16
    "bf16_ragged_53_by_16": (53, 16, 2, 2, 4, "bfloat16", 0.5),
    "bf16_one_value_head_by_64": (128, 64, 2, 2, 2, "bfloat16", 0.5),
    "bf16_underflow": (70, 32, 2, 2, 4, "bfloat16", 200.0),
}


@pytest.mark.parametrize("case", CASES)
def test_chunked_equals_the_recurrence_value_and_all_five_cotangents(case):
    """The chunked form and the backward it carries (written by hand)
    against the recurrence and ``jax.grad`` of it.  The recurrence has no
    padded tail, so equal cotangents at a ragged length say that the tail
    writes nothing, decays nothing and hands nothing back; under AMP the
    inputs are the same bf16 numbers on both sides and the distance is
    bf16's rounding of the contractions."""
    t, chunk, b, hk, hv, low, decay = CASES[case]
    xs = operands(t, seed=t, b=b, hk=hk, hv=hv, decay=decay)
    if low:
        xs = tuple(a.astype(low) for a in xs[:3]) + xs[3:]
    exact = tuple(a.astype(jnp.float32) for a in xs)
    scale = xs[0].shape[-1] ** -0.5

    def rule(*a):
        return delta_rule.chunked(*a, chunk=chunk).astype(jnp.float32)

    def stated(*a):
        return recurrence(*a, scale)

    want = jax.jit(stated)(*exact)
    wants = cotangents(stated)(*exact)
    with fluid.amp.amp_guard(low, keep_activations=True) if low \
            else contextlib.nullcontext():
        got = jax.jit(lambda *a: delta_rule.chunked(*a, chunk=chunk))(*xs)
        grads = cotangents(rule)(*xs)
    assert got.shape == want.shape == xs[2].shape
    assert got.dtype == xs[2].dtype
    assert [g.dtype for g in grads] == [a.dtype for a in xs]
    for g in (got,) + grads:
        assert bool(jnp.isfinite(g).all())
    if low:
        assert rel(got, want) < 0.02
        for name, g, w in zip("q k v g beta".split(), grads, wants):
            assert rel(g, w) < 0.03, name
        return
    np.testing.assert_allclose(got, want, atol=2e-5 if decay > 1 else 5e-6)
    # a float32 running sum near -6,000 (64 tokens of g near -100) is exact
    # to 5e-4, and so is every exp(G_i - G_j) made from it
    near = 1e-3 if chunk * decay > 4000 else 2e-5
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        np.testing.assert_allclose(g, w, atol=near * float(
            jnp.abs(w).max()) + 1e-6, err_msg=name)


def products(jaxpr):
    return [e for e in eqns_of(jaxpr) if e.primitive.name == "dot_general"]


def walks_and_ladder(xs, chunk, chunks):
    """(the backward's jaxpr, what a [C, C] x [C, C] product is, how many
    the ladder holds) of ``chunked`` over ``xs``, after the assertions
    both kinds of decay share: one walk forward, two backward
    (the second reversed), two products in each, and no [C, C] x [C, C]
    product outside the ladder that makes the inverse."""
    def rule(*a):
        return delta_rule.chunked(*a, chunk=chunk)

    forward = jax.make_jaxpr(rule)(*xs)
    out, vjp = jax.vjp(rule, *xs)
    backward = jax.make_jaxpr(vjp)(out)
    for jaxpr, walks in ((forward, 1), (backward, 2)):
        scans = [e for e in eqns_of(jaxpr) if e.primitive.name == "scan"]
        assert len(scans) == walks
        for scan in scans:
            assert len(products(scan.params["jaxpr"])) == 2
            assert scan.params["length"] == chunks
        assert [scan.params["reverse"] for scan in scans] == \
            [False, True][:walks]

    def square(eqn):
        return all(v.aval.shape[-2:] == (chunk, chunk) for v in eqn.invars)

    ladder = 2 * (int(np.log2(chunk)) - 1)
    assert len([e for e in products(forward) if square(e)]) == ladder
    assert len([e for e in products(backward) if square(e)]) == ladder
    return backward, square, ladder


def test_each_walk_holds_two_products_and_the_inverse_goes_back_in_none():
    """The mechanism, read off the jaxprs at a small size: the forward is
    one ``scan`` whose body holds exactly two ``dot_general``; the backward
    is two (the walk again, and the walk from the last chunk to the
    first), two products each; and outside the ladder that makes the
    inverse again (two [C, C] x [C, C] products a level, log2(C) - 1
    levels) the backward has NO such product: the closed form
    ``-T^T dT T^T`` is taken as ``-(T^T dZ) Z^T``.  Nothing differentiates
    through a scan or the ladder (autodiff's backward of it alone is four
    such products a level), and a product that slips back into a walk
    fails here."""
    chunk = 16          # no other width of the operands is 16
    xs = operands(48, seed=0, b=1, hk=2, hv=4, dk=8, dv=12)
    _, square, ladder = walks_and_ladder(xs, chunk, 3)
    through_the_ladder = jax.make_jaxpr(
        jax.vjp(delta_rule.unit_lower_inverse,
                jnp.zeros((chunk, chunk)))[1])(jnp.zeros((chunk, chunk)))
    assert len([e for e in products(through_the_ladder) if square(e)]) \
        == 2 * ladder


def test_the_inverses_cotangent_in_closed_form_is_autodiffs():
    """``z = (I + a)^{-1} x``: ``solve_cotangents`` against ``jax.vjp``
    through the five levels of ``unit_lower_inverse`` and the product, and
    against the closed form as it is usually written, ``dA = -T^T dT T^T``
    with ``dT = dz x^T``, below the diagonal."""
    rng = np.random.RandomState(5)
    a = jnp.asarray(np.tril(rng.randn(3, 64, 64) * 0.2, -1), jnp.float32)
    x = jnp.asarray(rng.randn(3, 64, 24), jnp.float32)
    dz = jnp.asarray(rng.randn(3, 64, 24), jnp.float32)

    def through_the_levels(a, x, dz):
        z, vjp = jax.vjp(
            lambda a, x: delta_rule.unit_lower_inverse(a) @ x, a, x)
        return z, vjp(dz)

    z, (want_a, want_x) = jax.jit(through_the_levels)(a, x, dz)
    inv = jax.jit(delta_rule.unit_lower_inverse)(a)
    got_x, got_a = jax.jit(delta_rule.solve_cotangents)(inv, z, dz)
    np.testing.assert_allclose(got_x, want_x,
                               atol=2e-5 * float(jnp.abs(want_x).max()))
    want_a = np.tril(np.asarray(want_a), -1)
    np.testing.assert_allclose(np.tril(np.asarray(got_a), -1), want_a,
                               atol=2e-5 * np.abs(want_a).max())
    t64 = np.asarray(inv, np.float64)
    written = -np.swapaxes(t64, -1, -2) @ (
        np.asarray(dz, np.float64) @ np.swapaxes(np.asarray(x, np.float64),
                                                 -1, -2)
    ) @ np.swapaxes(t64, -1, -2)
    np.testing.assert_allclose(np.tril(np.asarray(got_a), -1),
                               np.tril(written, -1),
                               atol=2e-5 * np.abs(want_a).max())


def test_a_decay_that_underflows_inside_a_chunk_gives_zeros_not_nans():
    """g down to -200 a token: ``exp(G_i - G_j)`` underflows within a few
    tokens and its mirror above the diagonal would overflow; value and
    every cotangent stay finite and equal the recurrence's."""
    q, k, v, g, beta = operands(53, seed=1)
    g = g * 400.0
    assert float(jnp.cumsum(g, 1).min()) < -1000
    scale = q.shape[-1] ** -0.5

    def rule(*a):
        return delta_rule.chunked(*a, chunk=16)

    def stated(*a):
        return recurrence(*a, scale)

    want = jax.jit(stated)(q, k, v, g, beta)
    got = jax.jit(rule)(q, k, v, g, beta)
    np.testing.assert_allclose(got, want, atol=2e-5)
    grads = cotangents(rule)(q, k, v, g, beta)
    wants = cotangents(stated)(q, k, v, g, beta)
    for got_g, want_g in zip(grads, wants):
        assert bool(jnp.isfinite(got_g).all())
        np.testing.assert_allclose(got_g, want_g, atol=2e-5 * float(
            jnp.abs(want_g).max()) + 1e-6)


def test_the_l2_norm_and_a_stated_scale_are_the_recurrences():
    q, k, v, g, beta = operands(40, seed=2)
    k = k * 3.0
    want = jax.jit(lambda *a: recurrence(*a, 0.25, norm_eps=1e-6))(
        q, k, v, g, beta)
    got = jax.jit(lambda *a: delta_rule.chunked(
        *a, chunk=16, scale=0.25, norm_eps=1e-6))(q, k, v, g, beta)
    np.testing.assert_allclose(got, want, atol=5e-6)


@pytest.mark.parametrize("c", [1, 2, 5, 16, 48, 64])
def test_unit_lower_inverse_is_the_inverse(c):
    rng = np.random.RandomState(c)
    a = jnp.asarray(np.tril(rng.randn(3, c, c), -1), jnp.float32)
    inv = jax.jit(delta_rule.unit_lower_inverse)(a)
    want = np.linalg.inv(np.eye(c) + np.asarray(a, np.float64))
    np.testing.assert_allclose(inv, want, atol=1e-4 * np.abs(want).max())
    assert not np.any(np.triu(np.asarray(inv), 1))


def test_nothing_leaks_from_the_future_or_across_sequences():
    """Perturbing token 21 of sequence 0 (inside the second chunk) leaves
    its outputs before 21 and every output of sequence 1 unchanged, and
    moves outputs in its own chunk and in the chunks after it: the state
    carries it on."""
    q, k, v, g, beta = operands(53, seed=3)
    rule = jax.jit(lambda *a: delta_rule.chunked(*a, chunk=16))
    base = rule(q, k, v, g, beta)
    moved = rule(q, k, v.at[0, 21].add(1.0), g, beta)
    np.testing.assert_array_equal(moved[1], base[1])
    np.testing.assert_array_equal(moved[0, :21], base[0, :21])
    changed = np.any(np.asarray(moved[0] != base[0]), (1, 2))
    assert changed[21] and changed[31] and changed[32] and changed[52]


def test_low_precision_inputs_give_their_own_type_and_stay_close():
    """bf16 q, k, v under AMP: the output is bf16, gates' cotangents stay
    float32, and the values are the float32 ones to bf16's rounding."""
    q, k, v, g, beta = operands(48, seed=4)

    def rule(*a):
        return delta_rule.chunked(*a, chunk=16, norm_eps=1e-6)

    def rule_and_cotangents(*a):
        got, vjp = jax.vjp(rule, *a)
        return got, vjp(jnp.ones_like(got))

    want = jax.jit(rule)(q, k, v, g, beta)
    low = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    with fluid.amp.amp_guard("bfloat16", keep_activations=True):
        got, grads = jax.jit(rule_and_cotangents)(*low, g, beta)
    assert got.dtype == jnp.bfloat16
    assert [x.dtype for x in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=0.06,
                               rtol=0.05)


@pytest.mark.parametrize("t", [5, 53])
def test_the_grad_op_equals_jax_grad_of_the_forward(t):
    """The op and its grad op through the executor against ``jax.grad`` of
    ``delta_rule.chunked``: all five inputs' gradients; the backward is
    the op's own and is counted as the one written by hand, not as a
    call."""
    names, _, out = build_rule(t, norm_eps=1e-6)
    assert tuple(out.shape[1:]) == (t, 4, 12)
    weights = np.cos(np.arange(12, dtype="float32"))
    loss = layers.reduce_sum(layers.elementwise_mul(
        out, layers.assign(weights)))
    fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    xs = operands(t, seed=t)
    got = exe.run(feed={n: np.asarray(x) for n, x in zip(names, xs)},
                  fetch_list=[out] + [n + "@GRAD" for n in names])

    def forward(*a):
        return delta_rule.chunked(*a, chunk=16, norm_eps=1e-6)

    np.testing.assert_allclose(got[0], jax.jit(forward)(*xs), atol=1e-6)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(forward(*a) * weights),
                            range(5)))(*xs)
    for name, g, w in zip(names, got[1:], want):
        np.testing.assert_allclose(g, w, atol=1e-5, err_msg=name)
    assert {k: v for k, v in fluid.profiler.counters().items()
            if k.startswith("ops.delta_rule")} == {
        'ops.delta_rule.calls{chunk="16",dim="12",key_heads="2",path="xla",'
        'value_heads="4"}': 1,
        'ops.delta_rule.grad_calls{chunk="16",path="by_hand"}': 1}


class Op:
    """What an infer rule reads of the op ``gated_delta_rule``."""

    def __init__(self, **attrs):
        self.attrs, self.type = attrs, "gated_delta_rule"
        self.inputs = {s: [s.lower()] for s in ("Q", "K", "V", "G", "Beta")}

    def attr(self, name, default=None):
        return self.attrs.get(name, default)


def test_infer_rule_and_layer_of_the_delta_rule():
    from paddle_tpu import analysis
    from paddle_tpu.ops.registry import get_infer_rule

    rule = get_infer_rule("gated_delta_rule")
    q, v = ((2, 64, 16, 128), "bfloat16"), ((2, 64, 32, 128), "bfloat16")
    gate = ((2, 64, 32), "float32")
    ins = {"Q": [q], "K": [q], "V": [v], "G": [gate], "Beta": [gate]}
    assert rule(Op(chunk=64), ins) == {"Out": [v]}
    for wrong, said in (
            ({"K": [((2, 64, 8, 128), "bfloat16")]}, "must be alike"),
            ({"V": [((2, 64, 24, 128), "bfloat16")]}, "a multiple of"),
            ({"V": [((2, 32, 32, 128), "bfloat16")]}, "the same tokens"),
            ({"G": [((2, 64, 16), "float32")]}, "one number a token"),
            ({"Beta": [((2, 64), "float32")]}, "one number a token")):
        with pytest.raises(registry.InferMismatch, match=said):
            rule(Op(), {**ins, **wrong})
    with pytest.raises(registry.InferMismatch, match="not positive"):
        rule(Op(chunk=0), ins)
    _, _, out = build_rule(16)
    report = analysis.verify_program(fluid.default_main_program(),
                                     fetch_list=[out])
    assert not report.errors, report.format()
    op = fluid.default_main_program().global_block().ops[-1]
    assert op.type == "gated_delta_rule" and "norm_eps" not in op.attrs \
        and op.attrs["chunk"] == 16 and op.attrs["scale"] == 0.0


# -- a decay that is a vector along the key ---------------------------------

#: (tokens, chunk, decay): g down to -decay a token and channel
CHANNEL_CASES = {
    "whole_64_by_16": (64, 16, 0.5),
    "ragged_53_by_16": (53, 16, 0.5),
    "published_chunk_and_sub": (150, 64, 0.5),
    "sub_blocks_of_two": (40, 8, 0.5),
    "sub_blocks_of_one": (21, 4, 0.5),
    # a chunk's G reaches -240 and -3,000: exp(-G) is inf in float32 (over
    # 88.7), so no form that divides by a decay could give these
    "exp_of_minus_g_overflows": (64, 16, 30.0),
    "exp_of_minus_g_overflows_ragged_by_64": (150, 64, 100.0),
}


@pytest.mark.parametrize("case", CHANNEL_CASES)
def test_channel_decay_equals_the_recurrence_and_all_five_cotangents(case):
    """The chunked form under a decay a key channel, its sub-block scores
    and the backward it carries (written by hand) against the recurrence
    and ``jax.grad`` of that, at a chunk that does and does not divide T."""
    t, chunk, decay = CHANNEL_CASES[case]
    xs = channel_operands(t, decay, seed=t)
    if decay > 1:
        worst = float(jnp.min(jnp.cumsum(xs[3][:, :chunk], 1)))
        assert not np.isfinite(np.exp(np.float32(-worst)))

    def rule(*a):
        return delta_rule.chunked(*a, chunk=chunk, scale=0.3)

    def stated(*a):
        return channel_recurrence(*a, 0.3)

    want, got = jax.jit(stated)(*xs), jax.jit(rule)(*xs)
    wants = cotangents(stated)(*xs)
    grads = cotangents(rule)(*xs)
    assert got.shape == want.shape and got.dtype == xs[2].dtype
    assert [g.shape for g in grads] == [a.shape for a in xs]
    for g in (got,) + grads:
        assert bool(jnp.isfinite(g).all())
    near = 2e-4 if decay > 1 else 2e-5
    np.testing.assert_allclose(got, want, atol=near * float(
        jnp.abs(want).max()))
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        np.testing.assert_allclose(g, w, atol=near * float(
            jnp.abs(w).max()) + 1e-6, err_msg=name)


@pytest.mark.parametrize("hk,hv", [(2, 2), (2, 4)])
def test_a_channel_decay_constant_along_the_key_is_the_scalar_path(hk, hv):
    """``g`` [B, T, Hv] spread over the key channels: the channel path
    gives what the scalar path gives, value and cotangents (g's summed over
    the channels), a key head serving two value heads too."""
    q, k, v, g, beta = operands(53, seed=3, hk=hk, hv=hv)
    spread = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))

    def scalar(*a):
        return delta_rule.chunked(*a, chunk=16)

    def channel(q, k, v, g, beta):
        return delta_rule.chunked(q, k, v, g, beta, chunk=16)

    np.testing.assert_allclose(jax.jit(channel)(q, k, v, spread, beta),
                               jax.jit(scalar)(q, k, v, g, beta), atol=5e-6)
    want = cotangents(scalar)(q, k, v, g, beta)
    got = cotangents(channel)(q, k, v, spread, beta)
    got = got[:3] + (jnp.sum(got[3], -1), got[4])
    for name, a, w in zip("q k v g beta".split(), got, want):
        np.testing.assert_allclose(a, w, atol=2e-5 * float(
            jnp.abs(w).max()) + 1e-6, err_msg=name)


def test_no_tensor_of_a_whole_chunks_pairs_by_channel_is_ever_made():
    """Forward and backward at the published chunk, whose sub-blocks are
    the published 16: the largest thing with a key-channel axis beside two
    token axes is the diagonal tiles' [C / sub, sub, sub, dk]; nothing is
    [C, C, dk] a chunk, and a chunk that is not four sub-blocks is refused
    under a channel decay alone."""
    c, dk = 64, 8
    sub = delta_rule.sub_block(c)
    assert sub == 16
    xs = channel_operands(2 * c, 0.5, b=1, h=2, dk=dk, dv=4)

    def rule(*a):
        return delta_rule.chunked(*a, chunk=c)

    def shapes(jaxpr, out):
        for eqn in jaxpr.eqns:
            out.update(tuple(v.aval.shape) for v in eqn.outvars)
            for sub_jaxpr in jax.core.jaxprs_in_params(eqn.params):
                shapes(sub_jaxpr, out)
        return out

    seen = shapes(jax.make_jaxpr(jax.grad(weighted_sum(rule), range(5)))(
        *xs).jaxpr, set())
    assert any(s[-4:] == (c // sub, sub, sub, dk) for s in seen)
    assert not any(len(s) >= 3 and s[-3:] == (c, c, dk) for s in seen)
    assert not any(len(s) >= 4 and s[-4:-1] == (c // sub, sub, c)
                   and s[-1] == dk for s in seen)
    with pytest.raises(ValueError, match="is not cut into 4 sub-blocks"):
        delta_rule.chunked(*xs, chunk=6)
    scalar = operands(53, seed=1)
    assert delta_rule.chunked(*scalar, chunk=6).shape == scalar[2].shape


@pytest.mark.parametrize("t", [16, 53])
def test_the_op_and_its_grad_op_under_a_channel_decay(t):
    """The op with G [B, T, Hv, dk] through the executor against the
    recurrence and ``jax.grad`` of it; counted as a call, a channel call
    and a backward written by hand."""
    names = ("q", "k", "v", "g", "beta")
    shapes = ([t, 3, 8], [t, 3, 8], [t, 3, 12], [t, 3, 8], [t, 3])
    data = [layers.data(name=n, shape=s, dtype="float32")
            for n, s in zip(names, shapes)]
    for d in data:
        d.stop_gradient = False
    out = layers.gated_delta_rule(*data, chunk=16, scale=0.3)
    assert tuple(out.shape[1:]) == (t, 3, 12)
    op = fluid.default_main_program().global_block().ops[-1]
    assert "sub" not in op.attrs and op.attrs["chunk"] == 16
    weights = np.cos(np.arange(12, dtype="float32"))
    loss = layers.reduce_sum(layers.elementwise_mul(
        out, layers.assign(weights)))
    fluid.backward.append_backward(loss)
    xs = channel_operands(t, 0.5, seed=t)
    got = fluid.Executor(fluid.TPUPlace()).run(
        feed={n: np.asarray(x) for n, x in zip(names, xs)},
        fetch_list=[out] + [n + "@GRAD" for n in names])
    np.testing.assert_allclose(
        got[0], jax.jit(lambda *a: channel_recurrence(*a, 0.3))(*xs),
        atol=1e-5)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(channel_recurrence(*a, 0.3) * weights),
        range(5)))(*xs)
    for name, g, w in zip(names, got[1:], want):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(
            jnp.abs(w).max()) + 1e-6, err_msg=name)
    assert {k: v for k, v in fluid.profiler.counters().items()
            if k.startswith("ops.delta_rule")} == {
        'ops.delta_rule.calls{chunk="16",dim="12",key_heads="3",path="xla",'
        'value_heads="3"}': 1,
        'ops.delta_rule.channel_calls{chunk="16",dim="8",key_heads="3",'
        'sub="4"}': 1,
        'ops.delta_rule.grad_calls{chunk="16",path="by_hand"}': 1}


def test_under_a_channel_decay_each_walk_holds_two_products_too():
    """The channel path's mechanism, read off the jaxprs as the scalar
    path's is: one walk forward, two backward, two products each, no
    [C, C] x [C, C] product outside the ladder.  A scan's transpose (a
    third and fourth product in the reverse walk, stacked residuals), a
    differentiated ladder (four such products a level) or a closed form
    taken as ``-T^T dT T^T`` fails here; and from the forward the backward
    keeps the five chunked operands and nothing else."""
    chunk = 16          # no other width of the operands is 16; sub is 4
    xs = channel_operands(48, 0.5, b=1, h=2, dk=8, dv=12)
    backward, _, _ = walks_and_ladder(xs, chunk, 3)
    n, (b, _, h, dk), dv = 3, xs[0].shape, xs[2].shape[-1]
    assert sorted(v.aval.shape for v in backward.jaxpr.constvars
                  if v.aval.shape) == sorted(
        [(n, b, h, chunk, dk)] * 3 + [(n, b, h, chunk, dv),
                                      (n, b, h, chunk)])


@pytest.mark.parametrize("case", ["whole", "overflows", "bf16"])
def test_the_scores_cotangents_alone_are_autodiffs(case):
    """``_pair_scores_bwd`` against ``jax.vjp`` of ``_pair_scores``: both
    score matrices at once, a cotangent that is dense (what it holds above
    the diagonal is not read), every cotangent: each ``x``'s, the keys',
    the running sums'.  Where ``exp(-G)`` overflows too, and with the
    products' inputs in bf16 (the same roundings on both sides but for the
    order of two sums)."""
    c, dk = 16, 8
    decay, low = {"whole": (0.5, None), "overflows": (30.0, None),
                  "bf16": (0.5, "bfloat16")}[case]
    rng = np.random.RandomState(7)
    q, k = (jnp.asarray(rng.randn(2, 3, c, dk), jnp.float32)
            for _ in range(2))
    gsum = jnp.cumsum(jnp.asarray(-decay * rng.rand(2, 3, c, dk),
                                  jnp.float32), -2)
    if decay > 1:
        assert float(gsum.min()) < -89      # exp(-G) is inf in float32
    dms = tuple(jnp.asarray(rng.randn(2, 3, c, c), jnp.float32)
                for _ in range(2))

    def autodiffs(x0, x1, k, g, dms):
        scores, vjp = jax.vjp(
            lambda x0, x1, k, g: delta_rule._pair_scores(
                low, (x0, x1), k, g), x0, x1, k, g)
        return scores, vjp(list(dms))

    scores, (want_x0, want_x1, want_k, want_g) = jax.jit(autodiffs)(
        k, q, k, gsum, dms)
    assert not np.any(np.triu(np.asarray(scores[0]), 1))
    (got_x0, got_x1), got_k, got_g = jax.jit(
        lambda xs, k, g, dms: delta_rule._pair_scores_bwd(
            low, xs, k, g, dms))((k, q), k, gsum, dms)
    near = 2e-2 if low else 2e-5
    for name, got, want in (("x0", got_x0, want_x0), ("x1", got_x1, want_x1),
                            ("k", got_k, want_k), ("gsum", got_g, want_g)):
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(got, want, atol=near * float(
            jnp.abs(want).max()), err_msg=name)


@pytest.mark.parametrize("t,chunk", [(53, 16), (128, 64)])
def test_channel_decay_under_amp_stays_close_to_the_recurrence(t, chunk):
    """bf16 q, k, v under AMP and a decay a key channel, as the cell runs
    it: every contraction but the inverse's and the diagonal tiles' in
    bf16, value and all five cotangents the recurrence's to bf16's
    rounding, the gates' cotangents float32."""
    xs = channel_operands(t, 0.5, seed=t)
    xs = tuple(a.astype(jnp.bfloat16) for a in xs[:3]) + xs[3:]
    exact = tuple(a.astype(jnp.float32) for a in xs)

    def rule(*a):
        return delta_rule.chunked(*a, chunk=chunk, scale=0.3).astype(
            jnp.float32)

    def stated(*a):
        return channel_recurrence(*a, 0.3)

    want = jax.jit(stated)(*exact)
    wants = cotangents(stated)(*exact)
    with fluid.amp.amp_guard("bfloat16", keep_activations=True):
        got = jax.jit(lambda *a: delta_rule.chunked(
            *a, chunk=chunk, scale=0.3))(*xs)
        grads = cotangents(rule)(*xs)
    assert got.dtype == jnp.bfloat16
    assert [g.dtype for g in grads] == [a.dtype for a in xs]
    assert rel(got, want) < 0.02
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        assert bool(jnp.isfinite(g).all())
        assert rel(g, w) < 0.03, name


def test_infer_rule_takes_a_decay_a_key_channel():
    from paddle_tpu.ops.registry import get_infer_rule

    rule = get_infer_rule("gated_delta_rule")
    q, v = ((2, 64, 16, 128), "bfloat16"), ((2, 64, 32, 96), "bfloat16")
    ins = {"Q": [q], "K": [q], "V": [v],
           "G": [((2, 64, 32, 128), "float32")],
           "Beta": [((2, 64, 32), "float32")]}
    assert rule(Op(chunk=64), ins) == {"Out": [v]}
    assert rule(Op(), ins) == {"Out": [v]}
    for wrong in ((2, 64, 32, 96), (2, 64, 16, 128), (2, 64, 32, 1)):
        with pytest.raises(registry.InferMismatch,
                           match="or \\[2, 64, 32, 128\\], one a key channel"):
            rule(Op(), {**ins, "G": [(wrong, "float32")]})
    with pytest.raises(registry.InferMismatch, match="one number a token"):
        rule(Op(), {**ins, "Beta": [((2, 64, 32, 128), "float32")]})
    with pytest.raises(registry.InferMismatch, match="is not positive"):
        rule(Op(chunk=0), ins)


# -- the filter alone, followed by SiLU -------------------------------------

def filter_then_silu(x, w):
    taps, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[:, j] * padded[:, j:j + t]
                           for j in range(taps)))


@pytest.mark.parametrize("t", [1, 3, 13, 64])
def test_ungated_short_conv_is_the_filter_and_silu_gradients_too(t):
    """``gated=False`` through the executor at T under the four taps, at T
    no multiple of 8 and at the tiny cell's: output and both gradients;
    the call is counted with ``gated="0"``, its backward not at all."""
    b, c, taps = 2, 8, 4
    rng = np.random.RandomState(t)
    x = layers.data(name="x", shape=[t, c], dtype="float32")
    x.stop_gradient = False
    out = layers.short_conv(
        x, taps, gated=False, param_attr=fluid.ParamAttr(
            name="filter", initializer=fluid.initializer.
            NormalInitializer(0.0, 0.5)))
    assert tuple(out.shape[1:]) == (t, c)
    op = fluid.default_main_program().global_block().ops[-1]
    assert op.type == "short_conv" and op.attrs["gated"] is False
    weights = np.cos(np.arange(c, dtype="float32"))
    loss = layers.reduce_sum(layers.elementwise_mul(
        out, layers.assign(weights)))
    fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    filt = jnp.asarray(np.asarray(fluid.global_scope().get("filter")))
    assert filt.shape == (c, taps)
    feed = {"x": rng.randn(b, t, c).astype("float32")}
    got = exe.run(feed=feed, fetch_list=[out, "x@GRAD", "filter@GRAD"])
    xs = jnp.asarray(feed["x"])
    np.testing.assert_allclose(got[0], jax.jit(filter_then_silu)(xs, filt),
                               atol=1e-6)
    want = jax.jit(jax.grad(
        lambda x, f: jnp.sum(filter_then_silu(x, f) * weights),
        (0, 1)))(xs, filt)
    np.testing.assert_allclose(got[1], want[0], atol=1e-5)
    np.testing.assert_allclose(got[2], want[1], atol=1e-5)
    assert {k: v for k, v in fluid.profiler.counters().items()
            if k.startswith("ops.short_conv")} == {
        f'ops.short_conv.calls{{channels="{c}",gated="0",path="xla",'
        f'taps="4"}}': 1}


def test_the_two_forms_share_one_filter_and_the_gated_one_is_unchanged():
    """``causal_filter`` is what both forms sum; the gated form is the
    gates around it, bit for bit what the op computed before it had a
    second form."""
    c, t = 4, 12
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, t, 3 * c), jnp.float32)
    w = jnp.asarray(rng.randn(c, 3), jnp.float32)
    acc = decoder_ops.causal_filter(x[..., :c] * x[..., 2 * c:], w)
    np.testing.assert_array_equal(decoder_ops.gated_short_conv(x, w),
                                  x[..., c:2 * c] * acc)
    np.testing.assert_array_equal(
        decoder_ops.silu_short_conv(x[..., :c], w),
        jax.nn.silu(decoder_ops.causal_filter(x[..., :c], w)))
    low = decoder_ops.silu_short_conv(x[..., :c].astype(jnp.bfloat16), w)
    assert low.dtype == jnp.bfloat16


def test_infer_rule_of_the_ungated_short_convolution():
    from paddle_tpu.ops.registry import get_infer_rule

    class Op:
        def __init__(self, **attrs):
            self.attrs, self.inputs, self.type = attrs, {}, "short_conv"

        def attr(self, name, default=None):
            return self.attrs.get(name, default)

    rule = get_infer_rule("short_conv")
    x = ((2, 16, 96), "bfloat16")
    assert rule(Op(gated=False), {"X": [x], "Filter": [((96, 4), "float32")]}
                ) == {"Out": [x]}
    with pytest.raises(registry.InferMismatch, match="gated is off"):
        rule(Op(gated=False), {"X": [x], "Filter": [((32, 4), "float32")]})
    # the gated form's rule is what it was
    assert rule(Op(), {"X": [x], "Filter": [((32, 3), "float32")]}) == {
        "Out": [((2, 16, 32), "bfloat16")]}
