"""The scalar delta rule's Pallas kernels (``ops/pallas_delta_rule.py``:
``delta_rule_fwd``, ``delta_rule_states``, ``delta_rule_bwd``), interpreted
on the CPU: against ``delta_rule._rule`` (their twin), against the rule token
by token, what they refuse, and through the op and its grad op.  The last
section of ``tests/test_delta_rule.py`` until PR 63, a file of its own under
the rule that no file of ``tests/`` is more than 300 s of one worker
(docs/COVERAGE.md); the channel rule's kernels are
``tests/test_delta_channel_kernels.py``'s."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import delta_rule

from delta_rule_reference import (  # noqa: F401  (exact_products: autouse)
    both_paths, build_rule, cotangents, exact_products, operands, recurrence,
    rel)

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

    def stated(*a):
        return recurrence(*a, scale, eps)

    want = jax.jit(stated)(*exact)
    wants = cotangents(stated)(*exact)
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
        got, jax.jit(lambda *a: delta_rule.chunked(*a, chunk=chunk))(*xs),
        atol=1e-6)
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

    assert rel(got[0], jax.jit(forward)(*xs)) < 2e-5
    want = jax.jit(jax.grad(lambda *a: jnp.sum(forward(*a) * weights),
                            range(5)))(*xs)
    for name, g, w in zip(names, got[1:], want):
        assert rel(g, w) < 2e-5, name
