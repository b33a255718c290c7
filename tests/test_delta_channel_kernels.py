"""The channel-decay delta rule's Pallas kernels
(``ops/pallas_delta_rule.py`` ``channel_rule``: ``delta_channel_fwd``,
``delta_channel_states``, ``delta_channel_bwd``), interpreted on the CPU:
against ``delta_rule._channel_rule`` (their twin), against the rule token by
token, against the scalar rule's kernels where the decay is constant along
the key, and through the op and its grad op.  A file of its own beside
``test_delta_rule_kernels.py``: together the two are more than the 300 s of
one worker that a file of ``tests/`` may take (docs/COVERAGE.md); a case
interprets and compiles for ten to thirty seconds."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import delta_rule
from delta_rule_reference import (  # noqa: F401  (exact_products: autouse)
    both_paths, build_rule, channel_operands, channel_recurrence,
    cotangents, exact_products, operands, rel)



#: (tokens, batch, heads, AMP type or None, decay, norm_eps): g down to
#: -decay a token and channel
CHANNEL_KERNEL_CASES = {
    # eight whole chunks, two grid steps of four: the carried state and dS
    # cross the chunks of a step and the steps
    "whole_512": (512, 1, 2, None, 0.5, 1e-6),
    # 150 = 2 x 64 + 22: three chunks padded to a grid step's four, the
    # padded tail writes and decays nothing
    "ragged_150": (150, 1, 2, None, 0.5, 0.0),
    # exp(G_i - G_j) underflows within two tokens (inside a sub-block) and
    # only after twenty (across one): zeros, never inf * 0
    "underflow_inside_a_sub_block": (150, 1, 2, None, 200.0, 1e-6),
    "underflow_across_sub_blocks": (150, 1, 2, None, 10.0, 0.0),
    # bf16 q, k, v under AMP: the off-diagonal tiles' and the state's
    # products in bf16; two pairs of heads, two rows
    "bf16_two_pairs_two_rows": (100, 2, 4, "bfloat16", 0.5, 1e-6),
    # values twice as wide as the keys (``VALUE_WIDTH``)
    "values_of_256": (100, 1, 2, None, 0.5, 1e-6),
}
VALUE_WIDTH = {"values_of_256": 256}
_CHANNEL_KERNEL_RUNS = {}


def channel_kernel_runs(case):
    if case not in _CHANNEL_KERNEL_RUNS:
        t, b, h, low, decay, eps = CHANNEL_KERNEL_CASES[case]
        xs = channel_operands(t, decay, seed=t, b=b, h=h, dk=128,
                              dv=VALUE_WIDTH.get(case, 128))
        if low:
            xs = tuple(a.astype(low) for a in xs[:3]) + xs[3:]
        _CHANNEL_KERNEL_RUNS[case] = xs, both_paths(xs, low, eps)
    return _CHANNEL_KERNEL_RUNS[case]


@pytest.mark.parametrize("case", CHANNEL_KERNEL_CASES)
def test_the_channel_kernels_equal_the_xla_rule_and_all_five_cotangents(
        case):
    """The three kernels of a decay a key channel against
    ``_channel_rule``, their twin and oracle: types and shapes, nothing
    but finite numbers, and the distance float32's reordering of sums
    (under AMP, bf16's rounding of operands that differ in their last
    float32 bits: the kernels split a tile's exponent in three, the twin
    in two)."""
    xs, runs = channel_kernel_runs(case)
    (want, wants), (got, grads) = runs["xla"], runs["pallas"]
    low, decay = CHANNEL_KERNEL_CASES[case][3:5]
    assert got.shape == want.shape and got.dtype == want.dtype == xs[2].dtype
    assert [g.dtype for g in grads] == [a.dtype for a in xs]
    for g in (got,) + grads:
        assert bool(jnp.isfinite(g).all())
    # the kernels make the running sum of g themselves, in another order
    # than ``jnp.cumsum``: near -6,000 a float32 sum is exact to 5e-4, and
    # so is every exp(G_i - G_j) made from it
    near = 0.02 if low else 1e-3 if decay > 1 else 2e-5
    assert rel(got, want) < near
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        assert g.shape == w.shape, name
        assert rel(g, w) < near, name


@pytest.mark.parametrize("case", CHANNEL_KERNEL_CASES)
def test_the_channel_kernels_equal_the_recurrence_and_all_five_cotangents(
        case):
    """And against the rule token by token with the state's rows each
    decayed by its own number, and ``jax.grad`` of it."""
    xs, runs = channel_kernel_runs(case)
    t, _, _, low, decay, eps = CHANNEL_KERNEL_CASES[case]
    exact = tuple(a.astype(jnp.float32) for a in xs)

    def stated(q, k, *rest):
        if eps:
            q, k = delta_rule.l2norm(q, eps), delta_rule.l2norm(k, eps)
        return channel_recurrence(q, k, *rest, 128 ** -0.5)

    want = jax.jit(stated)(*exact)
    wants = cotangents(stated)(*exact)
    got, grads = runs["pallas"]
    near = 0.03 if low else 1e-3 if decay > 1 else 5e-5
    assert rel(got, want) < near
    for name, g, w in zip("q k v g beta".split(), grads, wants):
        assert rel(g, w) < near, name


def test_a_decay_constant_along_the_key_is_the_scalar_kernels(monkeypatch,
                                                              t=150):
    """Kernel against kernel: ``g`` [B, T, H] spread over the key channels
    runs the channel rule's kernels and gives what the scalar rule's give,
    value and cotangents (g's summed over the channels)."""
    from paddle_tpu.ops import kernel_choice

    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "1")
    q, k, v, g, beta = operands(t, seed=t, b=1, hk=2, hv=2, dk=128, dv=128)
    spread = jnp.broadcast_to(g[..., None], g.shape + (128,))

    def rule(*a):
        return delta_rule.chunked(*a, chunk=64, norm_eps=1e-6)

    for xs, kernel in (((q, k, v, g, beta), "delta_rule_fwd"),
                       ((q, k, v, spread, beta), "delta_channel_fwd")):
        assert kernel in str(jax.make_jaxpr(rule)(*xs))
    def out_and_grads(*a):      # one program an operand set
        out, vjp = jax.vjp(rule, *a)
        weights = jnp.cos(jnp.arange(out.size, dtype=jnp.float32))
        return out, vjp(weights.reshape(out.shape))

    out, want = jax.jit(out_and_grads)(q, k, v, g, beta)
    spread_out, got = jax.jit(out_and_grads)(q, k, v, spread, beta)
    np.testing.assert_allclose(spread_out, out, atol=5e-6)
    got = got[:3] + (jnp.sum(got[3], -1), got[4])
    for name, a, w in zip("q k v g beta".split(), got, want):
        assert rel(a, w) < 2e-5, name


def test_the_op_and_its_grad_op_take_the_channel_kernels_and_count_them(
        monkeypatch):
    """Through the executor with the gate open, under a decay a key
    channel: the op lowers ``delta_channel_fwd``, its grad op
    ``delta_channel_states`` and ``delta_channel_bwd`` and not the forward
    again; both are counted ``path="pallas"``, ``channel_calls`` as ever,
    nothing is declined, and the five gradients are the XLA path's."""
    from paddle_tpu.ops import kernel_choice

    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "1")
    t = 70
    names, _, out = build_rule(t, hk=2, hv=2, dk=128, dv=128, chunk=64,
                               channel=True, norm_eps=1e-6)
    weights = np.cos(np.arange(128, dtype="float32"))
    loss = layers.reduce_sum(layers.elementwise_mul(
        out, layers.assign(weights)))
    fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    xs = channel_operands(t, 0.5, seed=t, b=1, h=2, dk=128, dv=128)
    feed = {n: np.asarray(x) for n, x in zip(names, xs)}
    fetch = [out] + [n + "@GRAD" for n in names]
    text = exe.lower_step(fluid.default_main_program(), feed,
                          fetch).as_text(debug_info=True)
    for kernel, op in (("delta_channel_fwd", "gated_delta_rule"),
                       ("delta_channel_states", "gated_delta_rule_grad"),
                       ("delta_channel_bwd", "gated_delta_rule_grad")):
        assert re.search(rf'"jit\(fn\)/{op}/[^"]*{kernel}\)?/pallas_call"',
                         text), kernel
    assert not re.search(
        r'"jit\(fn\)/gated_delta_rule_grad/[^"]*delta_channel_fwd/', text)
    assert "delta_rule_fwd" not in text
    got = exe.run(feed=feed, fetch_list=fetch)
    # once for the text above, once for the run
    assert {k: v for k, v in fluid.profiler.counters().items()
            if k.startswith("ops.delta_rule")} == {
        'ops.delta_rule.calls{chunk="64",dim="128",key_heads="2",'
        'path="pallas",value_heads="2"}': 2,
        'ops.delta_rule.channel_calls{chunk="64",dim="128",key_heads="2",'
        'sub="16"}': 2,
        'ops.delta_rule.grad_calls{chunk="64",path="pallas"}': 2}
    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "0")

    def forward(*a):
        return delta_rule.chunked(*a, chunk=64, norm_eps=1e-6)

    assert rel(got[0], jax.jit(forward)(*xs)) < 2e-5
    want = jax.jit(jax.grad(lambda *a: jnp.sum(forward(*a) * weights),
                            range(5)))(*xs)
    for name, g, w in zip(names, got[1:], want):
        assert rel(g, w) < 2e-5, name
