"""``ops/ssd.py`` and the op ``ssd_scan``: the chunked selective state-space
scan and its hand-written backward against the token-by-token float32
recurrence, on the CPU at small sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import registry, ssd

NAMES = ("u", "delta", "a", "b", "c", "d")


def recurrence(u, delta, a, b, c, d, groups):
    """The scan as it is stated, a token at a time, float32."""
    bsz, t, h, p = u.shape
    n, rep = b.shape[-1] // groups, h // groups
    bh, ch = (jnp.repeat(x.reshape(bsz, t, groups, n), rep, 2)
              for x in (b, c))

    def token(state, xs):
        u_t, dt, b_t, c_t = xs
        state = jnp.exp(dt * a)[..., None, None] * state \
            + (dt[..., None] * u_t)[..., None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) \
            + d[:, None] * u_t

    _, y = jax.lax.scan(token, jnp.zeros((bsz, h, p, n), jnp.float32),
                        tuple(jnp.swapaxes(x, 0, 1)
                              for x in (u, delta, bh, ch)))
    return jnp.swapaxes(y, 0, 1)


def operands(seed=0, bsz=2, t=50, h=8, p=4, g=2, n=6, a_range=(1.0, 16.0),
             dt_range=(0.001, 0.3)):
    rng = np.random.RandomState(seed)
    f32 = jnp.float32
    return (jnp.asarray(rng.randn(bsz, t, h, p), f32),
            jnp.asarray(rng.uniform(*dt_range, (bsz, t, h)), f32),
            -jnp.asarray(rng.uniform(*a_range, (h,)), f32),
            jnp.asarray(rng.randn(bsz, t, g * n), f32),
            jnp.asarray(rng.randn(bsz, t, g * n), f32),
            jnp.asarray(rng.randn(h), f32)), g


def both_gradients(ops, groups, chunk, seed=1):
    w = jnp.asarray(np.random.RandomState(seed).randn(*ops[0].shape),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        mine = jax.jit(jax.grad(lambda *o: jnp.sum(
            ssd.chunked(*o, chunk=chunk, groups=groups) * w),
            argnums=range(6)))(*ops)
        want = jax.jit(jax.grad(
            lambda *o: jnp.sum(recurrence(*o, groups) * w),
            argnums=range(6)))(*ops)
    return mine, want


@pytest.mark.parametrize("chunk", [8, 16])
def test_forward_and_every_gradient_equal_the_recurrences(chunk):
    """Fifty tokens: several chunks and a padded tail at either size."""
    ops, g = operands()
    with jax.default_matmul_precision("highest"):
        y = jax.jit(lambda *o: ssd.chunked(*o, chunk=chunk, groups=g))(*ops)
        want = jax.jit(lambda *o: recurrence(*o, g))(*ops)
    assert y.shape == want.shape and y.dtype == jnp.float32
    np.testing.assert_allclose(y, want, atol=2e-5)
    mine, wanted = both_gradients(ops, g, chunk)
    for name, m, w in zip(NAMES, mine, wanted):
        assert m.shape == w.shape, name
        assert float(jnp.abs(m - w).max()) <= 2e-5 * float(
            jnp.abs(w).max()) + 1e-6, name
        assert float(jnp.abs(w).max()) > 0.1, name


def test_the_backward_by_hand_is_the_chunked_forwards_own_derivative():
    """``_scan``'s custom backward against autodiff THROUGH the chunked
    forward (the decorated function's own body, the walk's scan and all)."""
    rng = np.random.RandomState(3)
    f32 = jnp.float32
    n, bsz, g, r, c, p, k = 3, 1, 2, 2, 8, 4, 5
    args = (jnp.asarray(rng.randn(n, bsz, g, r, c, p), f32),
            jnp.asarray(rng.uniform(0.01, 0.3, (n, bsz, g, r, c)), f32),
            -jnp.asarray(rng.uniform(1, 4, (g, r)), f32),
            jnp.asarray(rng.randn(n, bsz, g, c, k), f32),
            jnp.asarray(rng.randn(n, bsz, g, c, k), f32),
            jnp.asarray(rng.randn(g, r), f32))
    w = jnp.asarray(rng.randn(n, bsz, g, r, c, p), f32)
    with jax.default_matmul_precision("highest"):
        by_hand = jax.jit(jax.grad(
            lambda *o: jnp.sum(ssd._scan(None, *o) * w),
            argnums=range(6)))(*args)
        by_jax = jax.jit(jax.grad(
            lambda *o: jnp.sum(ssd._scan.fun(None, *o) * w),
            argnums=range(6)))(*args)
    for name, m, want in zip(NAMES, by_hand, by_jax):
        np.testing.assert_allclose(m, want, rtol=2e-5, atol=2e-5,
                                   err_msg=name)


def test_a_group_shared_by_eight_heads():
    ops, g = operands(seed=4, bsz=1, t=32, h=16, p=4, g=2, n=8)
    assert ops[0].shape[2] // g == 8
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            jax.jit(lambda *o: ssd.chunked(*o, chunk=16, groups=g))(*ops),
            jax.jit(lambda *o: recurrence(*o, g))(*ops), atol=2e-5)
    mine, wanted = both_gradients(ops, g, 16)
    for name, m, w in zip(NAMES, mine, wanted):
        assert float(jnp.abs(m - w).max()) <= 2e-5 * float(
            jnp.abs(w).max()) + 1e-6, name
    with pytest.raises(ValueError, match="do not divide over 3 groups"):
        ssd.chunked(*ops, chunk=16, groups=3)


@pytest.mark.parametrize("a,dt", [(16.0, 0.1), (1.0, 0.001)])
def test_decays_near_zero_and_near_one_stay_finite(a, dt):
    """``A`` 16 with a step of 0.1: a chunk of 128 tokens decays by
    exp(-204), which underflows to zeros and never to ``inf * 0``; ``A`` 1
    with 0.001: a chunk forgets an eighth."""
    ops, g = operands(seed=5, bsz=1, t=256, h=4, p=4, g=1, n=8,
                      a_range=(a, a), dt_range=(dt, dt))
    with jax.default_matmul_precision("highest"):
        y = jax.jit(lambda *o: ssd.chunked(*o, chunk=128, groups=g))(*ops)
        want = jax.jit(lambda *o: recurrence(*o, g))(*ops)
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(y, want, atol=3e-5 * float(
        jnp.abs(want).max()) + 1e-5)
    mine, wanted = both_gradients(ops, g, 128)
    for name, m, w in zip(NAMES, mine, wanted):
        assert bool(jnp.all(jnp.isfinite(m))), name
        assert float(jnp.abs(m - w).max()) <= 1e-4 * float(
            jnp.abs(w).max()) + 1e-5, name


def test_under_amp_the_contractions_round_and_the_state_does_not():
    ops, g = operands(seed=6, bsz=1, t=64, h=4, p=8, g=2, n=8)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *o: recurrence(*o, g))(*ops)
    fluid.amp.enable("bfloat16", keep_activations=True)
    try:
        low = jax.jit(lambda *o: ssd.chunked(*o, chunk=16, groups=g))(
            ops[0].astype(jnp.bfloat16), *ops[1:])
    finally:
        fluid.amp.disable()
    assert low.dtype == jnp.bfloat16
    err = float(jnp.abs(low.astype(jnp.float32) - want).max())
    assert 1e-4 < err < 0.05 * float(jnp.abs(want).max())


def test_scan_flops_count_the_recurrence():
    # decay, rank-one write, read: three multiply-adds an element of [P, N]
    assert ssd.scan_flops(1, 64, 64, 128) == 2 * 3 * 64 * 64 * 128
    assert ssd.scan_flops(8192, 64, 64, 128) / 8192 / 1e6 \
        == pytest.approx(3.1, abs=0.05)


def test_the_op_through_a_program_its_grad_op_and_counters():
    """``fluid.layers.ssd_scan`` in a program with ``append_backward``:
    output and all six gradients against the recurrence; the forward counts
    ``ops.ssd.scans``, the grad op ``ops.ssd.grad_scans{path="by_hand"}``."""
    from paddle_tpu.fluid import profiler

    ops, g = operands(seed=7, bsz=2, t=24, h=4, p=4, g=2, n=4)
    feed = dict(zip(NAMES, (np.asarray(o) for o in ops)))
    var = {}
    for name, o in feed.items():
        var[name] = layers.data(name=name, shape=list(o.shape),
                                dtype="float32", append_batch_size=False)
        var[name].stop_gradient = False
    out = layers.ssd_scan(*(var[n] for n in NAMES), chunk=8, groups=g)
    assert tuple(out.shape) == ops[0].shape
    loss = layers.reduce_sum(layers.square(out))
    fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    with jax.default_matmul_precision("highest"):
        got = exe.run(feed=feed, fetch_list=[out] + [
            n + "@GRAD" for n in NAMES])
        want = jax.jit(lambda *o: recurrence(*o, g))(*ops)
        grads = jax.jit(jax.grad(
            lambda *o: jnp.sum(recurrence(*o, g) ** 2),
            argnums=range(6)))(*ops)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    for name, m, w in zip(NAMES, got[1:], grads):
        np.testing.assert_allclose(np.asarray(m).reshape(w.shape), w,
                                   rtol=1e-4, atol=1e-4, err_msg=name)
    counted = {k: v for k, v in profiler.counters().items()
               if k.startswith("ops.ssd.")}
    assert counted == {
        'ops.ssd.scans{chunk="8",dim="4",groups="2",heads="4",path="xla",'
        'state="4"}': 1,
        'ops.ssd.grad_scans{chunk="8",path="by_hand"}': 1}


def test_infer_rule_of_the_scan():
    rule = registry.get_infer_rule("ssd_scan")

    class Op:
        type = "ssd_scan"
        inputs = {s: [s.lower()] for s in ("U", "Delta", "A", "B", "C", "D")}

        def __init__(self, **attrs):
            self.attrs = attrs

        def attr(self, name, default=None):
            return self.attrs.get(name, default)

    u = ((2, 16, 8, 4), "bfloat16")
    ins = {"U": [u], "Delta": [((2, 16, 8), "float32")],
           "A": [((8,), "float32")], "D": [((8,), "float32")],
           "B": [((2, 16, 12), "bfloat16")], "C": [((2, 16, 12), "bfloat16")]}
    assert rule(Op(chunk=8, groups=2), ins) == {"Out": [u]}
    for change, attrs, said in (
            ({"Delta": [((2, 16, 4), "float32")]}, {}, "one step a token"),
            ({}, {"groups": 3}, "multiple of the 3 groups"),
            ({"A": [((4,), "float32")]}, {}, r"must be \[8\], one number"),
            ({"C": [((2, 16, 10), "bfloat16")]}, {}, "B and C alike"),
            ({}, {"chunk": 0}, "chunk 0 is not positive")):
        with pytest.raises(registry.InferMismatch, match=said):
            rule(Op(**{"chunk": 8, "groups": 2, **attrs}), {**ins, **change})
