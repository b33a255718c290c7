"""Fused Pallas kernel layer (ops/pallas_fused.py, ISSUE 12) — streaming
softmax-cross-entropy (fwd+bwd, hard/soft labels), fused momentum/adam
sweeps, and the tp-sharded shard_map lowerings — all in interpret mode on
the CPU mesh (the same kernel code compiles natively on a TPU VM).

Acceptance oracles:
 - kernel outputs AND gradients match the unfused registry-op math within
   1e-6 (fp32), including ignore_index and soft labels;
 - a guarded + dynamically-fp16-loss-scaled ``run_steps`` window trains
   identically fused vs unfused (the ISSUE 6 window-equivalence pattern);
 - a dp2×tp2 sharded windowed transformer with ``PADDLE_TPU_FUSED=1``
   strict-verifies, equals the single-device run at equal global batch,
   and leaves mesh-labeled ``ops.fused.*`` dispatch counters;
 - the ``PADDLE_TPU_FUSED=0`` kill-switch restores the exact unfused
   lowering (tools/fused_smoke.py, run here as a tier-1 subprocess).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
import paddle_tpu.fluid.executor as _executor
from paddle_tpu.fluid import amp, fault, guardian
from paddle_tpu.ops import pallas_fused as pf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def clean_slate():
    fault.clear()
    guardian.disable()
    amp.disable()
    yield
    fault.clear()
    guardian.disable()
    amp.disable()


def _snapshot(scope):
    return {k: np.asarray(scope.get(k)) for k in scope.keys()
            if scope.get(k) is not None}


def _restore(scope, snap):
    for k, v in snap.items():
        scope.set(k, v)


# ---------------------------------------------------------------------------
# kernel-level: streaming softmax-xent vs the jnp reference
# ---------------------------------------------------------------------------


def _ref_hard(x, lab, ignore=-100):
    lse = jax.scipy.special.logsumexp(x.astype(jnp.float32), axis=1,
                                      keepdims=True)
    loss = lse - jnp.take_along_axis(x.astype(jnp.float32),
                                     lab.astype(jnp.int64), axis=1)
    if ignore >= 0:
        loss = jnp.where(lab == ignore, 0.0, loss)
    return loss


def test_xent_hard_matches_reference():
    """Odd vocab (100) runs as one whole-dim block; loss AND grad within
    1e-6 of the XLA logsumexp formulation."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(size=(8, 100)).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 100, size=(8, 1)).astype(np.int32))
    loss, lse = pf.softmax_xent(x, lab)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(_ref_hard(x, lab)),
                               rtol=1e-6, atol=1e-6)
    g = jax.grad(lambda x: jnp.sum(pf.softmax_xent(x, lab)[0]))(x)
    gr = jax.grad(lambda x: jnp.sum(_ref_hard(x, lab)))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-6, atol=1e-6)


def test_xent_ignore_index():
    """Ignored rows: zero loss AND zero gradient, exactly."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(size=(6, 32)).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 32, size=(6, 1)).astype(np.int32))
    lab = lab.at[2, 0].set(7)
    loss, _ = pf.softmax_xent(x, lab, False, 7)
    assert float(loss[2, 0]) == 0.0
    g = jax.grad(lambda x: jnp.sum(pf.softmax_xent(x, lab, False, 7)[0]))(x)
    assert float(jnp.abs(g[2]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(loss),
                               np.asarray(_ref_hard(x, lab, 7)),
                               rtol=1e-6, atol=1e-6)


def test_xent_soft_labels_match_reference():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.normal(size=(8, 48)).astype(np.float32))
    y = jax.nn.softmax(jnp.asarray(
        rng.normal(size=(8, 48)).astype(np.float32)), axis=1)
    loss, _ = pf.softmax_xent(x, y, True)
    ref = -jnp.sum(y * jax.nn.log_softmax(x, axis=-1), -1, keepdims=True)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    g = jax.grad(lambda x: jnp.sum(pf.softmax_xent(x, y, True)[0]))(x)
    gr = jax.grad(lambda x: jnp.sum(
        -jnp.sum(y * jax.nn.log_softmax(x, -1), -1)))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("soft", [False, True])
def test_xent_blocks_hanging_over_the_edge(soft):
    """300 rows in blocks of 256 and 700 classes in blocks of 512: the last
    block of either dim hangs over the array's edge (Transformer-base's
    30,000 classes do the same on the chip).  What is read past the edge is
    unspecified and must not reach the loss or the gradient."""
    rng = np.random.RandomState(12)
    x = jnp.asarray(rng.normal(size=(300, 700)).astype(np.float32))
    if soft:
        lab = jax.nn.softmax(jnp.asarray(
            rng.normal(size=(300, 700)).astype(np.float32)), axis=1)

        def ref(a):
            return -jnp.sum(lab * jax.nn.log_softmax(a, -1), -1,
                            keepdims=True)
    else:
        lab = jnp.asarray(rng.randint(0, 700, size=(300, 1))
                          .astype(np.int32))

        def ref(a):
            return _ref_hard(a, lab)

    def fused(a):
        return pf.softmax_xent(a, lab, soft, -100, 256, 512)[0]

    np.testing.assert_allclose(np.asarray(fused(x)), np.asarray(ref(x)),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda a: jnp.sum(fused(a)))(x)
    gr = jax.grad(lambda a: jnp.sum(ref(a)))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-6, atol=1e-6)


def _smoothed(lab, v, eps):
    """The distribution the reference's programs write down:
    ``label_smooth(one_hot(lab))`` in float32."""
    return ((1.0 - eps) * jax.nn.one_hot(lab[:, 0], v, dtype=jnp.float32)
            + eps / v)


def _ref_smoothed(x, lab, eps, ignore=-100):
    y = _smoothed(lab, x.shape[1], eps)
    loss = -jnp.sum(y * jax.nn.log_softmax(x.astype(jnp.float32), -1), -1,
                    keepdims=True)
    if ignore >= 0:
        loss = jnp.where(lab == ignore, 0.0, loss)
    return loss


@pytest.mark.parametrize("ignore", [-100, 7])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_xent_smoothed_hard_labels(dtype, ignore):
    """``smooth_epsilon`` over the int32 label column gives what the soft
    kernel gives when fed the dense ``(1 - eps) * onehot + eps / V``, and
    what plain float32 jax.numpy gives: loss and dX, at 1,000 classes in
    blocks of 512 (the last block hangs over the edge, and what is read
    there must not reach the row sum either), 300 rows in blocks of 256."""
    rng = np.random.RandomState(21)
    eps, (r, v) = 0.1, (300, 1000)
    x = jnp.asarray(3.0 * rng.normal(size=(r, v)), jnp.float32).astype(dtype)
    lab = jnp.asarray(rng.randint(0, v, size=(r, 1)).astype(np.int32))
    lab = lab.at[5, 0].set(7)

    def fused(a):
        return pf.softmax_xent(a, lab, False, ignore, 256, 512, None, eps)[0]

    def dense(a):
        loss = pf.softmax_xent(a, _smoothed(lab, v, eps), True, -100, 256,
                               512)[0]
        # a soft label knows no ignore_index
        return jnp.where(lab == ignore, 0.0, loss) if ignore >= 0 else loss

    def ref(a):
        return _ref_smoothed(a, lab, eps, ignore)

    tol = dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(fused(x)), np.asarray(dense(x)),
                               **tol)
    np.testing.assert_allclose(np.asarray(fused(x)), np.asarray(ref(x)),
                               **tol)
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(r, 1)).astype(np.float32))
    g, gd, gr = (jax.grad(lambda a, f=f: jnp.sum(w * f(a)))(x)
                 for f in (fused, dense, ref))
    assert g.dtype == x.dtype
    if ignore >= 0:
        assert float(fused(x)[5, 0]) == 0.0
        assert float(jnp.abs(g[5].astype(jnp.float32)).max()) == 0.0
    # bf16 dX: both kernels round the same float32 value; plain jnp's dX
    # comes back through the cast of x and rounds the same way
    gtol = (dict(rtol=1e-6, atol=1e-6) if dtype == "float32"
            else dict(rtol=1e-2, atol=1e-6))
    for other in (gd, gr):
        np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)),
                                   np.asarray(other.astype(jnp.float32)),
                                   **gtol)


def test_xent_without_smoothing_is_the_hard_call_it_was():
    """``smooth_epsilon == 0`` adds nothing to the hard-label call: the
    same jaxpr as a call that never names it (three columns out of the
    forward kernel, no row sum, the one-hot target backward), which is what
    the six decoder cells lower.  ``eps > 0`` is another program."""
    rng = np.random.RandomState(22)
    x = jnp.asarray(rng.normal(size=(300, 1000)).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 1000, size=(300, 1)).astype(np.int32))

    def jaxpr(*eps):
        return str(jax.make_jaxpr(jax.value_and_grad(lambda a: jnp.sum(
            pf.softmax_xent(a, lab, False, 3, 256, 512, None, *eps)[0])))(x))

    plain = jaxpr()
    assert jaxpr(0.0) == plain
    assert jaxpr(0.1) != plain
    assert "reduce_sum" in plain          # the picked logit's row reduction
    assert jaxpr(0.1).count("reduce_sum") > plain.count("reduce_sum")


def test_xent_bf16_logits():
    """bf16 logits: fp32 accumulation inside the kernel — operand-rounding
    tolerance only (matches the unfused loss-boundary fp32 cast)."""
    rng = np.random.RandomState(3)
    x32 = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
    x = x32.astype(jnp.bfloat16)
    lab = jnp.asarray(rng.randint(0, 64, size=(8, 1)).astype(np.int32))
    loss, _ = pf.softmax_xent(x, lab)
    ref = _ref_hard(x.astype(jnp.float32), lab)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda x: jnp.sum(pf.softmax_xent(x, lab)[0]))(x)
    assert g.dtype == jnp.bfloat16


def test_xent_backward_is_pallas():
    """The vjp must run the streaming kernels, not a jnp fallback: the
    backward jaxpr contains pallas_call primitives (fwd partial + bwd)."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 64, size=(8, 1)).astype(np.int32))
    jaxpr = str(jax.make_jaxpr(
        jax.grad(lambda x: jnp.sum(pf.softmax_xent(x, lab)[0])))(x))
    assert jaxpr.count("pallas_call") >= 2


def test_xent_softmax_output_path():
    """The op-level entry reconstructs Softmax as exp(x - lse): it must
    equal jax.nn.softmax, and gradients THROUGH the softmax output must
    flow (the lse cotangent path in the custom vjp)."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 32, size=(4, 1)).astype(np.int32))

    def sm_fused(x):
        _, lse = pf.softmax_xent(x, lab)
        return jnp.exp(x - lse)

    np.testing.assert_allclose(np.asarray(sm_fused(x)),
                               np.asarray(jax.nn.softmax(x, -1)),
                               rtol=1e-6, atol=1e-6)
    g = jax.grad(lambda x: jnp.sum(sm_fused(x) ** 2))(x)
    gr = jax.grad(lambda x: jnp.sum(jax.nn.softmax(x, -1) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# op-level: the grad op takes the forward's Lse (no second forward kernel)
# ---------------------------------------------------------------------------


def _xent_grad_counts():
    name = "ops.softmax_xent.grad_calls"
    return {k[len(name):]: v for k, v in fluid.profiler.counters().items()
            if k.startswith(name)}


def _loss_op_ctx(logits, label, attrs, slots=("Softmax", "Loss", "Lse")):
    """The grad op's context as the executor makes it: the forward's
    inputs, the outputs named in ``slots``, ``Loss@GRAD``, and nothing
    where an output has no gradient."""
    from paddle_tpu.ops.registry import ExecContext, get_op_def

    fwd = get_op_def("softmax_with_cross_entropy")
    ins = {"Logits": [logits], "Label": [label]}
    outs = fwd.fn(ExecContext(fwd.type, dict(ins), {}, attrs))
    rng = np.random.RandomState(17)
    dloss = jnp.asarray(rng.normal(size=outs["Loss"].shape), jnp.float32)
    ins.update({s: [outs[s]] for s in slots})
    ins.update({"Loss@GRAD": [dloss], "Softmax@GRAD": [None]})
    return fwd, ExecContext(fwd.type + "_grad", ins,
                            {"Logits@GRAD": ["dx"]}, attrs)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case,shape,attrs", [
    ("hard", (24, 96), {}),
    ("smoothed", (24, 96), {"smooth_epsilon": 0.1}),
    ("ignore_index", (24, 96), {"ignore_index": 5}),
    ("smoothed_ignore_index", (24, 96), {"smooth_epsilon": 0.1,
                                         "ignore_index": 5}),
    ("ragged_last_column_block", (16, 700), {}),
    ("ragged_smoothed", (16, 700), {"smooth_epsilon": 0.1}),
    ("three_dims_ragged_rows", (3, 100, 40), {}),
])
def test_loss_grad_from_lse_is_the_generic_grad_bit_for_bit(
        monkeypatch, case, shape, attrs, dtype):
    """The registered grad (the backward kernel on the forward's Lse)
    against ``run_grad_generic`` (the forward kernel again, then the same
    backward kernel): the same bits, and it is counted as ``from_lse``."""
    from paddle_tpu.ops import registry

    monkeypatch.setenv("PADDLE_TPU_FUSED", "1")
    rng = np.random.RandomState(len(case))
    logits = jnp.asarray(3.0 * rng.normal(size=shape), dtype)
    label = rng.randint(0, shape[-1], size=shape[:-1] + (1,))
    label[..., :2, 0] = 5                       # rows that an ignore drops
    fwd, ctx = _loss_op_ctx(logits, jnp.asarray(label, jnp.int64), attrs)
    assert ctx.input("Lse").shape == shape[:-1] + (1,)
    assert ctx.input("Lse").dtype == jnp.float32
    got = fwd.grad_fn(ctx)
    assert _xent_grad_counts() == {'{path="from_lse"}': 1}
    want = registry.run_grad_generic(fwd, ctx)
    assert set(got) == set(want) == {"Logits@GRAD"}
    dx, ref = got["Logits@GRAD"], want["Logits@GRAD"][0]
    assert dx.dtype == ref.dtype == logits.dtype and dx.shape == shape
    assert np.abs(np.asarray(ref, np.float32)).max() > 0
    np.testing.assert_array_equal(np.asarray(dx, np.float32),
                                  np.asarray(ref, np.float32))
    if "ignore_index" in attrs:
        assert not np.asarray(dx, np.float32)[..., :2, :].any()


def test_lse_is_the_rows_logsumexp_on_both_paths(monkeypatch):
    """The slot holds the kernels' ``m + log(l)`` on the Pallas path and
    the true ``logsumexp`` on the XLA path: float32 ``[..., 1]`` both."""
    from paddle_tpu.ops.registry import ExecContext, get_op_def

    rng = np.random.RandomState(8)
    logits = jnp.asarray(rng.normal(size=(2, 6, 50)), jnp.bfloat16)
    label = jnp.asarray(rng.randint(0, 50, size=(2, 6, 1)), jnp.int64)
    want = jax.nn.logsumexp(logits.astype(jnp.float32), -1, keepdims=True)
    for switch in ("0", "1"):
        monkeypatch.setenv("PADDLE_TPU_FUSED", switch)
        out = get_op_def("softmax_with_cross_entropy").fn(ExecContext(
            "softmax_with_cross_entropy",
            {"Logits": [logits], "Label": [label]}, {}, {}))
        assert out["Lse"].shape == (2, 6, 1)
        assert out["Lse"].dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out["Lse"]), np.asarray(want),
                                   rtol=1e-6)


def test_the_loss_ops_infer_rule_and_layer_give_lse():
    """The rule's third output and the layer's third variable: float32
    ``logits.shape[:-1] + (1,)`` whatever the logits' type, no gradient;
    the built program verifies with it."""
    from paddle_tpu import analysis
    from paddle_tpu.ops.registry import get_infer_rule

    class Op:
        type, attrs = "softmax_with_cross_entropy", {}

        def attr(self, name, default=None):
            return self.attrs.get(name, default)

    rule = get_infer_rule("softmax_with_cross_entropy")
    logits, label = ((4, 9, 50), "bfloat16"), ((4, 9, 1), "int64")
    assert rule(Op(), {"Logits": [logits], "Label": [label]}) == {
        "Softmax": [logits], "Loss": [((4, 9, 1), "bfloat16")],
        "Lse": [((4, 9, 1), "float32")]}
    x = fluid.layers.data(name="x", shape=[9, 50], dtype="float32")
    y = fluid.layers.data(name="y", shape=[9, 1], dtype="int64")
    loss = fluid.layers.softmax_with_cross_entropy(x, y)
    op = fluid.default_main_program().global_block().ops[-1]
    assert sorted(op.outputs) == ["Loss", "Lse", "Softmax"]
    lse = fluid.default_main_program().global_block().var(op.output("Lse")[0])
    assert (tuple(lse.shape), lse.dtype, lse.stop_gradient) == \
        (tuple(loss.shape), "float32", True)
    report = analysis.verify_program(fluid.default_main_program(),
                                     fetch_list=[loss, lse])
    assert not report.errors, report.format()


def _trained_loss(kind):
    """A loss over fed logits with its backward; returns (feed, the
    gradient the mean loss has in the logits)."""
    rows, width = 12, 40
    rng = np.random.RandomState(4)
    x = fluid.layers.data(name="x", shape=[width], dtype="float32")
    x.stop_gradient = False
    ids = rng.randint(0, width, size=(rows, 1)).astype("int64")
    feed = {"x": rng.normal(size=(rows, width)).astype("float32")}
    target = np.zeros((rows, width))
    np.put_along_axis(target, ids, 1.0, axis=-1)
    if kind == "soft_label":
        target = 0.9 * target + 0.1 / width
        y = fluid.layers.data(name="y", shape=[width], dtype="float32")
        feed["y"] = target.astype("float32")
    else:
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        feed["y"] = ids
    loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
        x, y, soft_label=kind == "soft_label"))
    fluid.backward.append_backward(loss)
    if kind == "no_lse_slot":
        # a program built, or saved, before the op had the slot
        for op in fluid.default_main_program().global_block().ops:
            if op.type == "softmax_with_cross_entropy":
                del op.outputs["Lse"]
            elif op.type == "softmax_with_cross_entropy_grad":
                del op.inputs["Lse"]
    p = np.exp(feed["x"] - feed["x"].max(-1, keepdims=True))
    return feed, (p / p.sum(-1, keepdims=True) - target) / rows


@pytest.mark.parametrize("kind,switch,path,kernel_traces", [
    ("hard", "1", "from_lse", 1),
    ("soft_label", "1", "generic", 2),     # its residual also needs sum(y)
    ("closed_gate", "0", "generic", 0),    # the XLA twin and its vjp
    ("no_lse_slot", "1", "generic", 2),
])
def test_loss_grad_op_falls_back_where_it_cannot_take_lse(
        monkeypatch, kind, switch, path, kernel_traces):
    """Through the executor: every kind trains with the gradient it always
    had; only hard labels under an open gate with an Lse to read take the
    backward kernel alone, and the counters say which."""
    monkeypatch.setenv("PADDLE_TPU_FUSED", switch)
    feed, want = _trained_loss(kind)
    exe = fluid.Executor(fluid.CPUPlace())
    got, = exe.run(feed=feed, fetch_list=["x@GRAD"])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-8)
    assert _xent_grad_counts() == {f'{{path="{path}"}}': 1}
    traced = sum(v for k, v in fluid.profiler.counters().items()
                 if k.startswith("ops.fused.softmax_xent"))
    assert traced == kernel_traces


# ---------------------------------------------------------------------------
# kernel-level: fused optimizer sweeps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,fusable", [
    ((512, 512, 3, 3), False), ((256, 64, 1, 1), False),
    ((64, 3, 7, 7), False), ((2048, 1000), False), ((512, 30000), False),
    ((33, 7), False),
    ((512, 2048), True), ((30000, 512), True), ((512,), True),
    ((30000,), True), ((64,), True)])
def test_sweep_taken_only_where_its_view_is_no_copy(shape, fusable):
    """The rule of the fused optimizer sweep: a tensor of two or more dims
    with a ragged last dim (every OIHW convolution filter, ResNet's
    ``[2048, 1000]`` fc, the Transformer's ``[512, 30000]`` projection)
    has no 2-D view the chip's tiled layout already is, so the update
    keeps XLA's lowering; lane-aligned matrices and every 1-D tensor keep
    the sweep."""
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    assert (pf._sweep_view(shape) is not None) is fusable
    assert pf.opt_declined(x, x) == (None if fusable else "layout")


@pytest.mark.parametrize("shape", [(231,), (256, 128), (10,),
                                   (1500, 256), (120000,)])
def test_fused_adam_matches_formula(shape):
    """Lane-aligned AND ragged shapes (the [1, n] single-row path), and
    two whose last row block / column block hangs over the edge."""
    rng = np.random.RandomState(6)
    p, g, m1, m2 = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
                    for _ in range(4))
    m2 = jnp.abs(m2)
    po, m1o, m2o = pf.fused_adam(p, g, m1, m2, jnp.float32(0.01),
                                 0.9, 0.999, 1e-8)
    m1r = 0.9 * m1 + 0.1 * g
    m2r = 0.999 * m2 + 0.001 * g * g
    pr = p - 0.01 * m1r / (jnp.sqrt(m2r) + 1e-8)
    for got, ref, n in ((po, pr, "p"), (m1o, m1r, "m1"), (m2o, m2r, "m2")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6, err_msg=n)


@pytest.mark.parametrize("nesterov", [False, True])
def test_fused_momentum_matches_formula(nesterov):
    rng = np.random.RandomState(7)
    p, g, v = (jnp.asarray(rng.normal(size=(16, 128)).astype(np.float32))
               for _ in range(3))
    po, vo = pf.fused_momentum(p, g, v, jnp.float32(0.05), 0.9, nesterov)
    vr = 0.9 * v + g
    pr = p - (g + 0.9 * vr) * 0.05 if nesterov else p - 0.05 * vr
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(po), np.asarray(pr),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# op-level: fused vs unfused training, counters, kill-switch
# ---------------------------------------------------------------------------


def _build_xent_model(opt, seed=11):
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=x, size=32, act="relu")
    logits = fluid.layers.fc(input=h, size=10, act=None)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    opt.minimize(loss)
    return loss


def test_fused_training_matches_unfused(monkeypatch):
    """4 Adam steps through the op registry: PADDLE_TPU_FUSED=1 produces
    the same loss trajectory and final params as =0 within 1e-6, and the
    dispatch counters prove the fused kernels were actually on the path."""
    rng = np.random.RandomState(0)
    xa = rng.normal(size=(8, 16)).astype(np.float32)
    la = rng.randint(0, 10, size=(8, 1)).astype(np.int64)
    loss = _build_xent_model(fluid.optimizer.Adam(learning_rate=0.01))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = _executor._global_scope
    init = _snapshot(scope)

    runs = {}
    params = {}
    for fused in ("0", "1"):
        monkeypatch.setenv("PADDLE_TPU_FUSED", fused)
        _restore(scope, init)
        out = []
        for _ in range(4):
            (l,) = exe.run(fluid.default_main_program(),
                           feed={"x": xa, "label": la}, fetch_list=[loss])
            out.append(float(np.asarray(l).reshape(-1)[0]))
        runs[fused] = out
        params[fused] = _snapshot(scope)
    np.testing.assert_allclose(runs["1"], runs["0"], rtol=0, atol=1e-6)
    for k, v in params["0"].items():
        np.testing.assert_allclose(params["1"][k], v, rtol=1e-6,
                                   atol=1e-6, err_msg=k)
    c = fluid.profiler.counters()
    assert c.get('ops.fused.softmax_xent{target="hard"}', 0) > 0
    assert c.get("ops.fused.adam", 0) > 0


def test_conv_filter_declines_the_sweep_and_equals_unfused(monkeypatch):
    """A momentum program over a conv filter and a batch-norm scale under
    PADDLE_TPU_FUSED=1: the filter's update is declined for its layout
    (counted) and is the unfused run's bit for bit; the 1-D scale takes
    the sweep."""
    x = fluid.layers.data(name="x", shape=[3, 8, 8], dtype="float32")
    h = fluid.layers.conv2d(x, num_filters=4, filter_size=3, padding=1,
                            bias_attr=False)
    h = fluid.layers.batch_norm(
        h, bias_attr=fluid.ParamAttr(trainable=False))
    loss = fluid.layers.mean(h * h)
    fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    prog = fluid.default_main_program()
    (filt,) = [p.name for p in prog.global_block().all_parameters()
               if len(p.shape) == 4]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = _executor._global_scope
    init = _snapshot(scope)
    feed = {"x": np.random.RandomState(3).normal(
        size=(2, 3, 8, 8)).astype(np.float32)}

    declined = 'ops.fused.declined{kind="momentum",why="layout"}'
    got = {}
    for fused in ("0", "1"):
        monkeypatch.setenv("PADDLE_TPU_FUSED", fused)
        _restore(scope, init)
        before = fluid.profiler.counters()
        exe.lower_step(prog, feed, [loss])     # one trace of the step
        after = fluid.profiler.counters()
        for _ in range(3):
            exe.run(prog, feed=feed, fetch_list=[loss])
        got[fused] = np.asarray(scope.get(filt))
        delta = {k: after.get(k, 0) - before.get(k, 0)
                 for k in ("ops.fused.momentum", declined)}
        assert delta == ({"ops.fused.momentum": 1, declined: 1}
                         if fused == "1" else
                         {"ops.fused.momentum": 0, declined: 0})
    assert not np.array_equal(got["1"], init[filt])
    np.testing.assert_array_equal(got["1"], got["0"])


def test_guarded_fp16_scaled_window_fused_matches_unfused(monkeypatch):
    """The ISSUE 6 window-equivalence oracle with the fused kernels on the
    path: a guardian-gated + dynamically-fp16-loss-scaled 8-step run_steps
    window trains identically (losses, params within 1e-6; the power-of-
    two loss-scale trajectory EXACTLY) fused vs unfused."""
    amp.enable("float16", init_loss_scale=2.0 ** 8, growth_interval=3)
    guardian.install(guardian.GuardianConfig(policy="skip"))
    loss = _build_xent_model(
        fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9), seed=5)
    prog = fluid.default_main_program()
    assert prog._loss_scale_vars is not None
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = _executor._global_scope
    init = _snapshot(scope)

    rng = np.random.RandomState(2)
    xs = rng.normal(size=(8, 8, 16)).astype(np.float32)
    ys = rng.randint(0, 10, size=(8, 8, 1)).astype(np.int64)

    results = {}
    params = {}
    for fused in ("0", "1"):
        monkeypatch.setenv("PADDLE_TPU_FUSED", fused)
        _restore(scope, init)
        guardian.install(guardian.GuardianConfig(policy="skip"))
        (l,) = exe.run_steps(prog, feed={"x": xs, "label": ys},
                             fetch_list=[loss], n_steps=8,
                             feed_per_step=True)
        guardian.flush()
        results[fused] = float(np.asarray(l).reshape(-1)[0])
        params[fused] = _snapshot(scope)
    assert abs(results["1"] - results["0"]) < 1e-6
    scale_name, good_name = prog._loss_scale_vars
    for name in (scale_name, good_name):
        np.testing.assert_array_equal(params["1"][name], params["0"][name],
                                      err_msg=name)
    for k, v in params["0"].items():
        np.testing.assert_allclose(params["1"][k], v, rtol=1e-5,
                                   atol=1e-6, err_msg=k)
    c = fluid.profiler.counters()
    assert c.get('ops.fused.softmax_xent{target="hard"}', 0) > 0
    assert c.get("ops.fused.momentum", 0) > 0


# ---------------------------------------------------------------------------
# tp-sharded lowerings (dp2×tp2 on the 8 forced CPU devices)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,spec,why", [
    ((512, 2048), (None, "tp"), None),        # local [512, 1024]
    ((512, 30000), (None, "tp"), "layout"),   # local [512, 15000]
    ((64, 128), (None, "tp"), "layout"),      # local [64, 64]
    ((64, 128), ("tp", None), None),          # local [32, 128]
    ((30000,), ("tp",), None)])               # local [15000]: 1-D
def test_sweep_rule_reads_the_local_shard(shape, spec, why):
    """Under a mesh the sweep runs inside ``shard_map`` on the local
    shard of the param's spec, so that shard's shape decides."""
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.parallel import spmd

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    with spmd.mesh_scope(mesh), spmd.param_spec_scope({"w": P(*spec)}):
        assert pf.opt_declined(x, x, "w") == why
        assert pf.opt_declined(x, x) == pf.opt_declined(x, x, "other")
    assert pf.opt_declined(x, x, "w") == pf.opt_declined(x, x)


def test_xent_sharded_matches_single_device():
    """The cross-shard logsumexp exchange: tp-sharded vocab loss + grad
    equal the single-device kernel."""
    from paddle_tpu.parallel import mesh_from_spec

    mesh = mesh_from_spec("dp2,tp2")
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 64, size=(8, 1)).astype(np.int32))
    loss, lse = jax.jit(
        lambda x: pf.softmax_xent_sharded(x, lab, mesh))(x)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(_ref_hard(x, lab)),
                               rtol=1e-6, atol=1e-6)
    g = jax.jit(jax.grad(
        lambda x: jnp.sum(pf.softmax_xent_sharded(x, lab, mesh)[0])))(x)
    gr = jax.grad(lambda x: jnp.sum(_ref_hard(x, lab)))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-6, atol=1e-6)
    # soft labels shard over tp too
    y = jax.nn.softmax(jnp.asarray(
        rng.normal(size=(8, 64)).astype(np.float32)), axis=1)
    loss_s, _ = jax.jit(
        lambda x: pf.softmax_xent_sharded(x, y, mesh, True))(x)
    ref_s = -jnp.sum(y * jax.nn.log_softmax(x, -1), -1, keepdims=True)
    np.testing.assert_allclose(np.asarray(loss_s), np.asarray(ref_s),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("spec", ["dp2,tp2", "tp4"])
def test_xent_sharded_smoothing_spreads_over_the_global_width(spec):
    """Smoothed hard labels under a mesh: ``eps / V`` is over the GLOBAL
    width although each shard's kernel sees ``V / tp`` columns, and the row
    sum of the logits is exchanged like the picked logit.  Loss and dX
    equal the unsharded kernel's and plain jax.numpy's."""
    from paddle_tpu.parallel import mesh_from_spec

    mesh = mesh_from_spec(spec)
    rng = np.random.RandomState(9)
    eps = 0.1
    x = jnp.asarray(3.0 * rng.normal(size=(8, 64)).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 64, size=(8, 1)).astype(np.int32))
    lab = lab.at[1, 0].set(5)

    def sharded(a):
        return pf.softmax_xent_sharded(a, lab, mesh, False, 5,
                                       smooth_epsilon=eps)[0]

    def single(a):
        return pf.softmax_xent(a, lab, False, 5, smooth_epsilon=eps)[0]

    ref = _ref_smoothed(x, lab, eps, 5)
    loss = jax.jit(sharded)(x)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(single(x)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    g = jax.jit(jax.grad(lambda a: jnp.sum(sharded(a))))(x)
    gs = jax.grad(lambda a: jnp.sum(single(a)))(x)
    gr = jax.grad(lambda a: jnp.sum(_ref_smoothed(a, lab, eps, 5)))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gs),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("spec", ["dp2,tp2", "tp4"])
@pytest.mark.parametrize("eps", [0.0, 0.1])
def test_loss_grad_from_lse_under_a_mesh(monkeypatch, spec, eps):
    """Under an active mesh the grad op takes the same way out through the
    sharded backward: the generic grad's bits, the unsharded gradient."""
    from paddle_tpu.ops import registry
    from paddle_tpu.parallel import mesh_from_spec, spmd

    monkeypatch.setenv("PADDLE_TPU_FUSED", "1")
    rng = np.random.RandomState(12)
    logits = jnp.asarray(3.0 * rng.normal(size=(8, 64)), jnp.float32)
    label = jnp.asarray(rng.randint(0, 64, size=(8, 1)), jnp.int64)
    attrs = {"smooth_epsilon": eps, "ignore_index": int(label[1, 0])}
    fwd, ctx = _loss_op_ctx(logits, label, attrs)
    single = fwd.grad_fn(ctx)["Logits@GRAD"]
    with spmd.mesh_scope(mesh_from_spec(spec)):
        fwd, ctx = _loss_op_ctx(logits, label, attrs)
        got = jax.jit(lambda: fwd.grad_fn(ctx)["Logits@GRAD"])()
        want = jax.jit(
            lambda: registry.run_grad_generic(fwd, ctx)["Logits@GRAD"][0])()
    assert _xent_grad_counts() == {'{path="from_lse"}': 2}
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got), np.asarray(single),
                               rtol=1e-6, atol=1e-7)
    assert not np.asarray(got)[1].any() and np.asarray(got)[0].any()


def test_flash_sharded_matches_full_attention():
    """Head-sharded flash attention under shard_map (interpret mode):
    output and grads match the XLA full-softmax reference."""
    from paddle_tpu.parallel import mesh_from_spec
    from paddle_tpu.parallel.ring_attention import full_attention

    mesh = mesh_from_spec("dp2,tp2")
    rng = np.random.RandomState(9)
    q, k, v = (jnp.asarray(rng.normal(size=(4, 2, 32, 8)).astype(np.float32))
               for _ in range(3))
    bias = np.zeros((4, 1, 1, 32), np.float32)
    bias[:, :, :, -3:] = -1e9
    bias = jnp.asarray(bias)
    out = jax.jit(lambda q, k, v: pf.flash_attention_sharded(
        q, k, v, bias, None, True, mesh, "tp"))(q, k, v)
    ref = full_attention(q, k, v, True, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    gf = jax.jit(jax.grad(lambda q, k, v: jnp.sum(pf.flash_attention_sharded(
        q, k, v, bias, None, True, mesh, "tp") ** 2), argnums=(0, 1, 2)))(
        q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(full_attention(
        q, k, v, True, bias=bias) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=n)


def test_sharded_window_transformer_fused_acceptance(monkeypatch):
    """ISSUE 12 acceptance: a dp2×tp2 sharded windowed transformer run
    with PADDLE_TPU_FUSED=1 strict-verifies, dispatches with the fused
    kernels active (mesh-labeled ops.fused.* counters > 0), and the
    tp-sharded softmax-xent result equals the single-device result at
    equal global batch."""
    from paddle_tpu import analysis
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import ShardedWindowRunner, mesh_from_spec

    monkeypatch.setenv("PADDLE_TPU_FUSED", "1")
    monkeypatch.setenv("PADDLE_TPU_VERIFY", "strict")
    fluid.default_main_program().random_seed = 7
    fluid.default_startup_program().random_seed = 7
    cfg = transformer.Config(
        "t", src_vocab_size=64, tgt_vocab_size=64, d_model=16, d_inner=32,
        n_head=2, n_layer=1, dropout=0.0, label_smooth=0.0)
    src, tgt, lbl, loss = transformer.build(cfg, src_len=8, tgt_len=8,
                                            lr=1e-3)
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = _executor._global_scope
    init = _snapshot(scope)

    rng = np.random.RandomState(1)
    bs, n = 8, 2
    feeds = {"src_word": rng.randint(1, 64, size=(n, bs, 8))
             .astype(np.int64),
             "tgt_word": rng.randint(1, 64, size=(n, bs, 8))
             .astype(np.int64),
             "lbl_word": rng.randint(1, 64, size=(n, bs, 8, 1))
             .astype(np.int64)}

    # single-device (fused) reference at equal global batch
    seq = []
    for i in range(n):
        (l,) = exe.run(prog, feed={k: v[i] for k, v in feeds.items()},
                       fetch_list=[loss])
        seq.append(float(np.asarray(l).reshape(-1)[0]))

    _restore(scope, init)
    mesh = mesh_from_spec("dp2,tp2")
    # strict pre-compile verify with the mesh: no new AN findings
    analysis.check_before_compile(
        prog, feed={k: v[0] for k, v in feeds.items()},
        fetch_list=[loss.name], mesh=mesh, kind="run_steps")
    runner = ShardedWindowRunner(prog, ["src_word", "tgt_word", "lbl_word"],
                                 [loss.name], mesh, n_steps=n,
                                 feed_per_step=True)
    assert runner.donate
    (l,) = runner.run(feeds)
    par = float(np.asarray(l).reshape(-1)[0])
    assert np.isfinite(par)
    np.testing.assert_allclose(par, seq[-1], rtol=5e-4, atol=5e-4)
    # the vocab dim really sharded over tp through the spec table
    tp_sharded = [nm for nm, s in runner.specs.items()
                  if s is not None and "tp" in tuple(s)]
    assert tp_sharded
    c = fluid.profiler.counters()
    assert c.get(
        'ops.fused.softmax_xent{mesh="dp2xtp2",target="hard"}', 0) > 0
    assert c.get('ops.fused.adam{mesh="dp2xtp2"}', 0) > 0


# ---------------------------------------------------------------------------
# gate precedence + tooling
# ---------------------------------------------------------------------------


def test_fused_gate_precedence(monkeypatch):
    """PADDLE_TPU_FUSED: 0 closes the gate, 1 opens it, unset (or auto)
    it is the backend's; nothing else has a say (ops/kernel_choice.py)."""
    from paddle_tpu.ops import kernel_choice

    monkeypatch.setenv("PADDLE_TPU_FUSED", "0")
    assert kernel_choice.gate("fused") is False
    monkeypatch.setenv("PADDLE_TPU_FUSED", "1")
    assert kernel_choice.gate("fused") is True
    monkeypatch.delenv("PADDLE_TPU_FUSED")
    assert kernel_choice.gate("fused") is (jax.default_backend() == "tpu")
    monkeypatch.setenv("PADDLE_TPU_FUSED", "0")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernel_choice.gate("fused") is False     # closed on a TPU too
    monkeypatch.setenv("PADDLE_TPU_FUSED", "auto")
    assert kernel_choice.gate("fused") is True


def test_fused_smoke_tool():
    """tools/fused_smoke.py: guarded 16-step fused window, counters,
    kill-switch bitwise restore — the tier-1 CI oracle, < 5 s."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fused_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["ok"] and report["killswitch_bitwise"]
    assert report["ops_fused_softmax_xent"] > 0
    assert report["ops_fused_adam"] > 0
