"""The gated delta rule (ops/delta_rule.py, the op ``gated_delta_rule`` and
its grad op), under a decay a value head and under one a key channel, and
the ungated form of ``short_conv``: the chunked computation against the
token-by-token recurrence, value and every cotangent, in float32 on the
CPU."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import decoder_ops, delta_rule, registry


def recurrence(q, k, v, g, beta, scale, norm_eps=0.0):
    """The rule as it is stated: one token after another, one [dk, dv]
    state a value head.  q, k: [B, T, Hk, dk]; v: [B, T, Hv, dv]; g, beta:
    [B, T, Hv]."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    if norm_eps:
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + norm_eps)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + norm_eps)
    q = jnp.repeat(q, hv // hk, 2) * scale
    k = jnp.repeat(k, hv // hk, 2)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, u)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, out = jax.lax.scan(token, jnp.zeros((b, hv, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1)


def operands(t, seed=0, b=2, hk=2, hv=4, dk=8, dv=12, decay=0.5):
    rng = np.random.RandomState(seed)
    k = rng.randn(b, t, hk, dk)
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        rng.randn(b, t, hk, dk), k / np.linalg.norm(k, axis=-1,
                                                    keepdims=True),
        rng.randn(b, t, hv, dv), -decay * rng.rand(b, t, hv),
        rng.rand(b, t, hv)))


def weighted_sum(fn):
    """A scalar of ``fn``'s output that weighs every element differently."""
    def loss(*xs):
        out = fn(*xs)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                                     ).reshape(out.shape))
    return loss


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def rel(got, want):
    """The distance of two arrays as a share of the second's norm."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


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

    want = recurrence(*exact, scale)
    wants = jax.grad(weighted_sum(lambda *a: recurrence(*a, scale)),
                     range(5))(*exact)
    with fluid.amp.amp_guard(low, keep_activations=True) if low \
            else contextlib.nullcontext():
        got = delta_rule.chunked(*xs, chunk=chunk)
        grads = jax.grad(weighted_sum(rule), range(5))(*xs)
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


def eqns_of(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    yield from eqns_of(sub)


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
    z, vjp = jax.vjp(lambda a, x: delta_rule.unit_lower_inverse(a) @ x, a, x)
    want_a, want_x = vjp(dz)
    inv = delta_rule.unit_lower_inverse(a)
    got_x, got_a = delta_rule.solve_cotangents(inv, z, dz)
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
    want = recurrence(q, k, v, g, beta, scale)
    got = delta_rule.chunked(q, k, v, g, beta, chunk=16)
    np.testing.assert_allclose(got, want, atol=2e-5)
    grads = jax.grad(weighted_sum(
        lambda *a: delta_rule.chunked(*a, chunk=16)), range(5))(
            q, k, v, g, beta)
    wants = jax.grad(weighted_sum(lambda *a: recurrence(*a, scale)),
                     range(5))(q, k, v, g, beta)
    for got_g, want_g in zip(grads, wants):
        assert bool(jnp.isfinite(got_g).all())
        np.testing.assert_allclose(got_g, want_g, atol=2e-5 * float(
            jnp.abs(want_g).max()) + 1e-6)


def test_the_l2_norm_and_a_stated_scale_are_the_recurrences():
    q, k, v, g, beta = operands(40, seed=2)
    k = k * 3.0
    want = recurrence(q, k, v, g, beta, 0.25, norm_eps=1e-6)
    got = delta_rule.chunked(q, k, v, g, beta, chunk=16, scale=0.25,
                             norm_eps=1e-6)
    np.testing.assert_allclose(got, want, atol=5e-6)


@pytest.mark.parametrize("c", [1, 2, 5, 16, 48, 64])
def test_unit_lower_inverse_is_the_inverse(c):
    rng = np.random.RandomState(c)
    a = jnp.asarray(np.tril(rng.randn(3, c, c), -1), jnp.float32)
    inv = delta_rule.unit_lower_inverse(a)
    want = np.linalg.inv(np.eye(c) + np.asarray(a, np.float64))
    np.testing.assert_allclose(inv, want, atol=1e-4 * np.abs(want).max())
    assert not np.any(np.triu(np.asarray(inv), 1))


def test_nothing_leaks_from_the_future_or_across_sequences():
    """Perturbing token 21 of sequence 0 (inside the second chunk) leaves
    its outputs before 21 and every output of sequence 1 unchanged, and
    moves outputs in its own chunk and in the chunks after it: the state
    carries it on."""
    q, k, v, g, beta = operands(53, seed=3)
    base = delta_rule.chunked(q, k, v, g, beta, chunk=16)
    moved = delta_rule.chunked(q, k, v.at[0, 21].add(1.0), g, beta, chunk=16)
    np.testing.assert_array_equal(moved[1], base[1])
    np.testing.assert_array_equal(moved[0, :21], base[0, :21])
    changed = np.any(np.asarray(moved[0] != base[0]), (1, 2))
    assert changed[21] and changed[31] and changed[32] and changed[52]


def test_low_precision_inputs_give_their_own_type_and_stay_close():
    """bf16 q, k, v under AMP: the output is bf16, gates' cotangents stay
    float32, and the values are the float32 ones to bf16's rounding."""
    q, k, v, g, beta = operands(48, seed=4)
    want = delta_rule.chunked(q, k, v, g, beta, chunk=16, norm_eps=1e-6)
    low = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    with fluid.amp.amp_guard("bfloat16", keep_activations=True):
        got, vjp = jax.vjp(lambda *a: delta_rule.chunked(
            *a, chunk=16, norm_eps=1e-6), *low, g, beta)
        grads = vjp(jnp.ones_like(got))
    assert got.dtype == jnp.bfloat16
    assert [x.dtype for x in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=0.06,
                               rtol=0.05)


def build_rule(t, hk=2, hv=4, dk=8, dv=12, chunk=16, channel=False,
               **attrs):
    names = ("q", "k", "v", "g", "beta")
    shapes = ([t, hk, dk], [t, hk, dk], [t, hv, dv],
              [t, hv, dk] if channel else [t, hv], [t, hv])
    data = [layers.data(name=n, shape=s, dtype="float32")
            for n, s in zip(names, shapes)]
    for d in data:
        d.stop_gradient = False
    out = layers.gated_delta_rule(*data, chunk=chunk, **attrs)
    return names, data, out


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

    np.testing.assert_allclose(got[0], forward(*xs), atol=1e-6)
    want = jax.grad(lambda *a: jnp.sum(forward(*a) * weights), range(5))(*xs)
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

def channel_recurrence(q, k, v, g, beta, scale):
    """The rule token by token with ``g`` [B, T, H, dk]: the state's ROWS
    decay, each key channel by its own number.  q, k: [B, T, H, dk]."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, u)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t * scale)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, out = jax.lax.scan(token, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1)


def channel_operands(t, decay, seed=0, b=2, h=3, dk=8, dv=12):
    q, k, v, _, beta = operands(t, seed=seed, b=b, hk=h, hv=h, dk=dk, dv=dv)
    g = -decay * np.random.RandomState(seed + 1).rand(b, t, h, dk)
    return q, k, v, jnp.asarray(g, jnp.float32), beta


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
    wants = jax.jit(jax.grad(weighted_sum(stated), range(5)))(*xs)
    grads = jax.jit(jax.grad(weighted_sum(rule), range(5)))(*xs)
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
    want = jax.jit(jax.grad(weighted_sum(scalar), range(5)))(
        q, k, v, g, beta)
    got = jax.jit(jax.grad(weighted_sum(channel), range(5)))(
        q, k, v, spread, beta)
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
    np.testing.assert_allclose(got[0], channel_recurrence(*xs, 0.3),
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
    scores, vjp = jax.vjp(
        lambda x0, x1, k, g: delta_rule._pair_scores(low, (x0, x1), k, g),
        k, q, k, gsum)
    assert not np.any(np.triu(np.asarray(scores[0]), 1))
    want_x0, want_x1, want_k, want_g = vjp(list(dms))
    (got_x0, got_x1), got_k, got_g = delta_rule._pair_scores_bwd(
        low, (k, q), k, gsum, dms)
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

    want = stated(*exact)
    wants = jax.grad(weighted_sum(stated), range(5))(*exact)
    with fluid.amp.amp_guard("bfloat16", keep_activations=True):
        got = delta_rule.chunked(*xs, chunk=chunk, scale=0.3)
        grads = jax.grad(weighted_sum(rule), range(5))(*xs)
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
    np.testing.assert_allclose(got[0], filter_then_silu(xs, filt), atol=1e-6)
    want = jax.grad(lambda x, f: jnp.sum(filter_then_silu(x, f) * weights),
                    (0, 1))(xs, filt)
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


# -- the scalar rule's Pallas kernels (ops/pallas_delta_rule.py), interpreted

#: (tokens, batch, key heads, value heads, AMP type or None, decay, norm_eps)
KERNEL_CASES = {
    # eight whole chunks, two grid steps of four: the carried state and dS
    # cross the chunks of a step and the steps
    "two_value_heads_whole_512": (512, 1, 1, 2, None, 0.5, 1e-6),
    # 500 = 7 x 64 + 52: the padded tail writes and decays nothing
    "two_value_heads_ragged_500": (500, 1, 1, 2, None, 0.5, 0.0),
    # one value head a key head: a pair is two neighbouring key heads;
    # three chunks are padded to a grid step's four
    "one_value_head_ragged_150": (150, 1, 2, 2, None, 0.5, 1e-6),
    # g down to -200 a token: exp(G_i - G_j) underflows within a few tokens
    "underflow_two_value_heads": (512, 1, 1, 2, None, 200.0, 1e-6),
    "underflow_one_value_head": (150, 1, 2, 2, None, 200.0, 0.0),
    # bf16 q, k, v under AMP: every contraction but the inverse's in bf16;
    # two pairs of value heads, two rows
    "bf16_two_value_heads": (200, 2, 2, 4, "bfloat16", 0.5, 1e-6),
    "bf16_one_value_head": (150, 1, 2, 2, "bfloat16", 0.5, 0.0),
}
_KERNEL_RUNS = {}


def both_paths(xs, low, eps):
    """{path: (out, five cotangents)} of ``xs`` through ``chunked`` with
    the flash gate closed ('xla': the twin) and open ('pallas': the
    kernels, interpreted on this CPU)."""
    import os

    from paddle_tpu.ops import kernel_choice

    runs, name = {}, kernel_choice.SWITCHES["flash"]
    before = os.environ.get(name)
    try:
        for path, flag in (("xla", "0"), ("pallas", "1")):
            os.environ[name] = flag

            def rule(*a):       # a path its own: jax keeps a trace
                return delta_rule.chunked(*a, chunk=64, norm_eps=eps)

            with fluid.amp.amp_guard(low, keep_activations=True) \
                    if low else contextlib.nullcontext():
                jaxpr = jax.make_jaxpr(rule)(*xs)
                assert any(e.primitive.name == "pallas_call"
                           for e in eqns_of(jaxpr)) is (path == "pallas")

                def loss(*a):       # the value beside it: one forward
                    out = rule(*a)
                    return weighted_sum(
                        lambda: out.astype(jnp.float32))(), out

                (_, out), grads = jax.value_and_grad(
                    loss, range(5), has_aux=True)(*xs)
                runs[path] = (out, grads)
    finally:
        os.environ.pop(name) if before is None \
            else os.environ.__setitem__(name, before)
    return runs


def kernel_runs(case):
    """(operands, ``both_paths``) of a case, made once a case."""
    if case not in _KERNEL_RUNS:
        t, b, hk, hv, low, decay, eps = KERNEL_CASES[case]
        xs = operands(t, seed=t, b=b, hk=hk, hv=hv, dk=128, dv=128,
                      decay=decay)
        if low:
            xs = tuple(a.astype(low) for a in xs[:3]) + xs[3:]
        _KERNEL_RUNS[case] = xs, both_paths(xs, low, eps)
    return _KERNEL_RUNS[case]


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernels_equal_the_xla_rule_value_and_all_five_cotangents(case):
    """The three kernels against ``_rule``, their twin and oracle: the
    same operands, types and shapes out, nothing but finite numbers (a
    decay that underflows inside a chunk gives zeros), and the distance
    float32's reordering of sums (under AMP, bf16's rounding of products
    whose operands differ in their last float32 bits)."""
    xs, runs = kernel_runs(case)
    (want, wants), (got, grads) = runs["xla"], runs["pallas"]
    low = KERNEL_CASES[case][4]
    assert got.shape == want.shape and got.dtype == want.dtype == xs[2].dtype
    assert [g.dtype for g in grads] == [a.dtype for a in xs]
    for g in (got,) + grads:
        assert bool(jnp.isfinite(g).all())
    near = 0.02 if low else 2e-5
    assert rel(got, want) < near
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        assert g.shape == w.shape, name
        assert rel(g, w) < near, name


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernels_equal_the_recurrence_value_and_all_five_cotangents(case):
    """And against what ``_rule`` itself is held to: the rule token by
    token and ``jax.grad`` of it, which has no chunk, no inverse and no
    padded tail."""
    xs, runs = kernel_runs(case)
    t, _, _, _, low, decay, eps = KERNEL_CASES[case]
    exact = tuple(a.astype(jnp.float32) for a in xs)
    scale = 128 ** -0.5
    want = recurrence(*exact, scale, eps)
    wants = jax.grad(weighted_sum(lambda *a: recurrence(*a, scale, eps)),
                     range(5))(*exact)
    got, grads = runs["pallas"]
    # a float32 running sum near -6,000 (64 tokens of g near -100) is exact
    # to 5e-4, and so is every exp(G_i - G_j) made from it
    near = 0.03 if low else 1e-3 if decay > 1 else 5e-5
    assert rel(got, want) < near
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        assert rel(g, w) < near, name


#: what ``supported`` refuses: the reason, then (key heads, value heads,
#: dk, dv, chunk, channel decay)
REFUSALS = {
    "chunk": (1, 2, 128, 128, 32, False),
    "width": (1, 2, 64, 128, 64, False),
    "value_width": (1, 2, 128, 8, 64, False),
    "heads": (1, 4, 128, 128, 64, False),
    "odd_heads": (1, 1, 128, 128, 64, False),
    # a decay a key channel: its kernels' own reasons
    "channel_chunk": (2, 2, 128, 128, 32, True),
    "channel_width": (2, 2, 64, 128, 64, True),
    "channel_wide_keys": (2, 2, 256, 128, 64, True),
    "channel_heads": (1, 2, 128, 128, 64, True),
    "channel_odd_heads": (3, 3, 128, 128, 64, True),
}


@pytest.mark.parametrize("case", REFUSALS)
def test_what_the_kernels_refuse_is_counted_and_the_xla_rule_runs(
        monkeypatch, case):
    """With the gate open where a kernel would be compiled, operands the
    kernels do not take reach ``ops.delta_rule.declined{why}``, the op and
    its grad op are counted on the XLA path, no ``pallas_call`` is lowered,
    and the result is the XLA path's with the gate closed."""
    from paddle_tpu.ops import kernel_choice, pallas_delta_rule

    hk, hv, dk, dv, chunk, channel = REFUSALS[case]
    why = case.removeprefix("channel_")
    why = {"value_width": "width", "wide_keys": "width",
           "odd_heads": "heads"}.get(why, why)
    t = 40
    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "1")
    monkeypatch.setattr(kernel_choice, "interpret",
                        lambda stated=None: False)
    xs = operands(t, seed=1, b=1, hk=hk, hv=hv, dk=dk, dv=dv)
    if channel:
        xs = xs[:3] + (jnp.repeat(xs[3][..., None], dk, -1),) + xs[4:]
    assert pallas_delta_rule.supported(*xs[:4], chunk) == why
    names, data, out = build_rule(t, hk=hk, hv=hv, dk=dk, dv=dv,
                                  chunk=chunk, channel=channel)
    loss = layers.reduce_sum(out)
    fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    feed = {n: np.asarray(x) for n, x in zip(names, xs)}
    text = exe.lower_step(fluid.default_main_program(), feed,
                          [out, "q@GRAD"]).as_text(debug_info=True)
    assert "pallas_call" not in text
    got, _ = exe.run(feed=feed, fetch_list=[out, "q@GRAD"])
    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "0")
    np.testing.assert_allclose(
        got, delta_rule.chunked(*xs, chunk=chunk), atol=1e-6)
    counted = {k: v for k, v in fluid.profiler.counters().items()
               if k.startswith("ops.delta_rule.")
               and not k.startswith("ops.delta_rule.channel_calls")}
    assert counted.pop(f'ops.delta_rule.declined{{why="{why}"}}') >= 1
    assert all('path="xla"' in k or 'path="by_hand"' in k
               for k in counted), counted
    assert any(k.startswith("ops.delta_rule.grad_calls") for k in counted)


def test_a_refusal_where_the_kernels_are_interpreted_is_not_counted(
        monkeypatch):
    """Off the TPU an open gate interprets the kernels, for tests and the
    benchmark's rehearsals: a rule too small for them (chunks of 16, heads
    of 8, as the cells' ``tiny`` sizes are) runs the XLA path and says so
    in ``calls{path}``, and no ``declined`` is counted."""
    from paddle_tpu.ops import kernel_choice, pallas_delta_rule

    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "1")
    assert kernel_choice.interpret()
    t = 40
    xs = operands(t, seed=2, b=1, hk=2, hv=4, dk=8, dv=8)
    assert pallas_delta_rule.supported(*xs[:4], 16) == "chunk"
    names, _, out = build_rule(t, hk=2, hv=4, dk=8, dv=8, chunk=16)
    fluid.backward.append_backward(layers.reduce_sum(out))
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(feed={n: np.asarray(x) for n, x in zip(names, xs)},
            fetch_list=[out, "q@GRAD"])
    counted = {k for k in fluid.profiler.counters()
               if k.startswith("ops.delta_rule.")}
    assert counted == {
        'ops.delta_rule.calls{chunk="16",dim="8",key_heads="2",path="xla",'
        'value_heads="4"}',
        'ops.delta_rule.grad_calls{chunk="16",path="by_hand"}'}


def test_the_op_and_its_grad_op_take_the_kernels_and_count_them(monkeypatch):
    """Through the executor with the gate open: the op lowers
    ``delta_rule_fwd``, its grad op ``delta_rule_states`` and
    ``delta_rule_bwd`` and not the forward again, each under the op's own
    name scope; both are counted ``path="pallas"``, nothing is declined,
    and the five gradients are the XLA path's."""
    from paddle_tpu.ops import kernel_choice

    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "1")
    t = 70
    names, _, out = build_rule(t, hk=1, hv=2, dk=128, dv=128, chunk=64,
                               norm_eps=1e-6)
    weights = np.cos(np.arange(128, dtype="float32"))
    loss = layers.reduce_sum(layers.elementwise_mul(
        out, layers.assign(weights)))
    fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    xs = operands(t, seed=t, b=1, hk=1, hv=2, dk=128, dv=128)
    feed = {n: np.asarray(x) for n, x in zip(names, xs)}
    fetch = [out] + [n + "@GRAD" for n in names]
    text = exe.lower_step(fluid.default_main_program(), feed,
                          fetch).as_text(debug_info=True)
    for kernel, op in (("delta_rule_fwd", "gated_delta_rule"),
                       ("delta_rule_states", "gated_delta_rule_grad"),
                       ("delta_rule_bwd", "gated_delta_rule_grad")):
        assert f'"jit(fn)/{op}/' in text
        assert re.search(rf'"jit\(fn\)/{op}/[^"]*{kernel}\)?/pallas_call"',
                         text), kernel
    assert not re.search(
        r'"jit\(fn\)/gated_delta_rule_grad/[^"]*delta_rule_fwd/', text)
    got = exe.run(feed=feed, fetch_list=fetch)
    # once for the text above, once for the run
    assert {k: v for k, v in fluid.profiler.counters().items()
            if k.startswith("ops.delta_rule")} == {
        'ops.delta_rule.calls{chunk="64",dim="128",key_heads="1",'
        'path="pallas",value_heads="2"}': 2,
        'ops.delta_rule.grad_calls{chunk="64",path="pallas"}': 2}
    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "0")

    def forward(*a):
        return delta_rule.chunked(*a, chunk=64, norm_eps=1e-6)

    assert rel(got[0], forward(*xs)) < 2e-5
    want = jax.grad(lambda *a: jnp.sum(forward(*a) * weights), range(5))(*xs)
    for name, g, w in zip(names, got[1:], want):
        assert rel(g, w) < 2e-5, name
