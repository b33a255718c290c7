"""The selective state-space scan's Pallas kernels (``ops/pallas_ssd.py``:
``ssd_scan_fwd``, ``ssd_scan_states``, ``ssd_scan_bwd``), interpreted on
the CPU: against ``ssd._scan`` (their twin, through ``ssd.chunked`` with the
flash gate closed), against the scan token by token, and through the op and
its grad op.  A file of its own beside ``test_ssd_scan.py``, so that it runs
on another worker; a case interprets and compiles for a few seconds."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
import test_ssd_scan as stated
from paddle_tpu.fluid import layers
from paddle_tpu.ops import kernel_choice, pallas_ssd, ssd

NAMES = stated.NAMES
FLASH = kernel_choice.SWITCHES["flash"]

#: case -> (tokens, rows, heads, their width, groups, state, AMP type or
#: None, A's range, the step's range)
CASES = {
    # four whole chunks, two grid steps of two, two groups of two heads:
    # the carried state and dS cross the chunks of a step and the steps,
    # dB / dC sum over a group's heads and not over its neighbour's
    "whole_512": (512, 1, 4, 64, 2, 128, None, (1.0, 4.0), (0.001, 0.1)),
    # 300 = 2 x 128 + 44: three chunks padded to two steps' four, the
    # padded tail decays and writes nothing
    "ragged_300": (300, 1, 4, 64, 2, 128, None, (1.0, 4.0), (0.001, 0.1)),
    # exp(-1.6 (t - s)) underflows forty tokens apart, a chunk's own decay
    # exp(-204) is zero: zeros, never inf * 0, in the cotangents too
    "underflow_inside_a_chunk": (300, 1, 2, 64, 1, 128, None, (16.0, 16.0),
                                 (0.1, 0.1)),
    # a chunk forgets an eighth
    "decay_near_one": (300, 1, 2, 64, 1, 128, None, (1.0, 1.0),
                       (0.001, 0.001)),
    # a head fills a tile of 128 lanes; a group of one head and of two
    "heads_of_128_alone": (200, 1, 2, 128, 2, 128, None, (1.0, 4.0),
                           (0.001, 0.1)),
    "heads_of_128_in_twos": (200, 1, 4, 128, 2, 128, None, (1.0, 4.0),
                             (0.001, 0.1)),
    # the cell's group: eight heads of 64 read one B and C; two rows
    "eight_heads_a_group_two_rows": (256, 2, 8, 64, 1, 128, None, (1.0, 4.0),
                                     (0.001, 0.1)),
    # bf16 u, b, c under AMP: every contraction in bf16, the state's
    # products among them; a state of 256
    "bf16_two_groups": (300, 1, 4, 64, 2, 128, "bfloat16", (1.0, 4.0),
                        (0.001, 0.1)),
    "bf16_heads_of_128_state_of_256": (200, 2, 2, 128, 1, 256, "bfloat16",
                                       (1.0, 4.0), (0.001, 0.1)),
}
_RUNS = {}


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


def amp_of(low):
    return fluid.amp.amp_guard(low, keep_activations=True) if low \
        else contextlib.nullcontext()


def out_and_cotangents(xs, groups, low):
    """(out, the six cotangents) of ``ssd.chunked`` at chunks of 128 as the
    gate stands, one program."""
    def both(*a):
        with amp_of(low):
            out, vjp = jax.vjp(
                lambda *o: ssd.chunked(*o, chunk=128, groups=groups), *a)
        weights = jnp.cos(jnp.arange(out.size, dtype=jnp.float32))
        return out, vjp(weights.reshape(out.shape).astype(out.dtype))

    return jax.jit(both)(*xs)


def runs(case, monkeypatch):
    """{path: (out, six cotangents)} with the gate closed ('xla': the twin)
    and open ('pallas': the kernels, interpreted), once a case."""
    if case not in _RUNS:
        t, bsz, h, p, g, n, low, a_range, dt_range = CASES[case]
        xs, _ = stated.operands(seed=t + h, bsz=bsz, t=t, h=h, p=p, g=g, n=n,
                                a_range=a_range, dt_range=dt_range)
        if low:
            xs = tuple(x.astype(low) if i in (0, 3, 4) else x
                       for i, x in enumerate(xs))
        found = {}
        for path, flag in (("xla", "0"), ("pallas", "1")):
            monkeypatch.setenv(FLASH, flag)
            with amp_of(low):
                text = str(jax.make_jaxpr(lambda *o: ssd.chunked(
                    *o, chunk=128, groups=g))(*xs))
            assert ("ssd_scan_fwd" in text) is (path == "pallas")
            found[path] = out_and_cotangents(xs, g, low)
        _RUNS[case] = xs, found
    return _RUNS[case]


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_equal_the_xla_scan_and_all_six_cotangents(case,
                                                               monkeypatch):
    """Types and shapes, nothing but finite numbers, and the distance
    float32's reordering of sums allows (under AMP: bf16's rounding of
    operands rounded at another place: the kernels put the step on the
    scores, the twin on ``u``)."""
    xs, found = runs(case, monkeypatch)
    (want, wants), (got, grads) = found["xla"], found["pallas"]
    low = CASES[case][6]
    assert got.shape == want.shape and got.dtype == want.dtype == xs[0].dtype
    assert [g.dtype for g in grads] == [x.dtype for x in xs]
    for g in (got,) + tuple(grads):
        assert bool(jnp.isfinite(g).all())
    near = 0.02 if low else 2e-5
    assert rel(got, want) < near
    for name, g, w in zip(NAMES, grads, wants):
        assert g.shape == w.shape, name
        assert rel(g, w) < near, name


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_equal_the_recurrence_and_all_six_cotangents(
        case, monkeypatch):
    """And against the scan as it is stated, a token at a time in float32,
    and ``jax.grad`` of it."""
    xs, found = runs(case, monkeypatch)
    g, low = CASES[case][4], CASES[case][6]
    exact = tuple(x.astype(jnp.float32) for x in xs)

    def both(*a):
        out, vjp = jax.vjp(lambda *o: stated.recurrence(*o, g), *a)
        weights = jnp.cos(jnp.arange(out.size, dtype=jnp.float32))
        return out, vjp(weights.reshape(out.shape))

    with jax.default_matmul_precision("highest"):
        want, wants = jax.jit(both)(*exact)
    got, grads = found["pallas"]
    near = 0.03 if low else 1e-4
    assert rel(got, want) < near
    for name, a, w in zip(NAMES, grads, wants):
        assert rel(a, w) < near, name


def test_an_underflowing_decay_gives_zeros_and_no_nan(monkeypatch):
    """``A`` 16 at a step of 0.1: nothing of a chunk's first tokens is left
    at its end, so what the next chunk reads of the state is what the last
    few tokens wrote, and every cotangent is finite."""
    xs, found = runs("underflow_inside_a_chunk", monkeypatch)
    got, grads = found["pallas"]
    _, delta, a = xs[:3]
    assert float(jnp.exp(128 * delta[0, 0, 0] * a[0])) == 0.0
    for g in (got,) + tuple(grads):
        assert bool(jnp.isfinite(g).all())
    assert float(jnp.abs(grads[1]).max()) > 0


#: why -> (heads, their width, groups, state, chunk)
REFUSALS = {"chunk": (4, 64, 2, 128, 64), "width_of_a_head": (4, 32, 1, 128,
                                                              128),
            "width_of_the_state": (4, 64, 2, 64, 128),
            "width_of_vmem": (64, 128, 1, 128, 128),
            "heads": (2, 64, 2, 128, 128)}


@pytest.mark.parametrize("case", REFUSALS)
def test_what_the_kernels_refuse_is_counted_and_runs_the_xla_scan(
        case, monkeypatch):
    """``supported`` gives its reason; where a kernel would have been
    compiled (not interpreted) the op counts ``ops.ssd.declined{why}``,
    lowers no kernel and counts ``path="xla"``."""
    h, p, g, n, chunk = REFUSALS[case]
    why = case.split("_")[0]
    xs, _ = stated.operands(seed=1, bsz=1, t=2 * chunk, h=h, p=p, g=g, n=n)
    u, delta, _, b, c, _ = xs
    assert pallas_ssd.supported(u, delta, b, c, chunk, g) == why
    if h * p > 1024:
        return      # a scan this wide is not run on the CPU
    monkeypatch.setenv(FLASH, "1")
    monkeypatch.setattr(kernel_choice, "interpret",
                        lambda stated=None: False)
    args = [layers.data(name=name, shape=list(x.shape), dtype="float32",
                        append_batch_size=False) for name, x in zip(NAMES, xs)]
    out = layers.ssd_scan(*args, chunk=chunk, groups=g)
    before = dict(fluid.profiler.counters())
    exe = fluid.Executor(fluid.TPUPlace())
    feed = {name: np.asarray(x) for name, x in zip(NAMES, xs)}
    text = exe.lower_step(fluid.default_main_program(), feed,
                          [out]).as_text(debug_info=True)
    assert "pallas_call" not in text
    moved = {k: v - before.get(k, 0)
             for k, v in fluid.profiler.counters().items()
             if k.startswith("ops.ssd.") and v != before.get(k, 0)}
    assert moved == {
        f'ops.ssd.declined{{why="{why}"}}': 1,
        f'ops.ssd.scans{{chunk="{chunk}",dim="{p}",groups="{g}",'
        f'heads="{h}",path="xla",state="{n}"}}': 1}


def test_the_cells_scan_is_supported():
    shaped = jax.ShapeDtypeStruct
    u = shaped((1, 8192, 64, 64), jnp.bfloat16)
    delta = shaped((1, 8192, 64), jnp.float32)
    bc = shaped((1, 8192, 8 * 128), jnp.bfloat16)
    assert pallas_ssd.supported(u, delta, bc, bc, 128, 8) == ""


def test_the_op_and_its_grad_op_take_the_kernels_and_count_them(monkeypatch):
    """Through the executor with the gate open: the op lowers
    ``ssd_scan_fwd``, its grad op ``ssd_scan_states`` and ``ssd_scan_bwd``
    and not the forward again; both are counted ``path="pallas"``, nothing
    is declined, and the six gradients are the XLA path's."""
    monkeypatch.setenv(FLASH, "1")
    t, h, p, g, n = 140, 4, 64, 2, 128
    xs, _ = stated.operands(seed=9, bsz=1, t=t, h=h, p=p, g=g, n=n)
    feed = {name: np.asarray(x) for name, x in zip(NAMES, xs)}
    var = {}
    for name, x in feed.items():
        var[name] = layers.data(name=name, shape=list(x.shape),
                                dtype="float32", append_batch_size=False)
        var[name].stop_gradient = False
    out = layers.ssd_scan(*(var[name] for name in NAMES), chunk=128, groups=g)
    weights = np.cos(np.arange(t * h * p, dtype="float32")).reshape(
        1, t, h, p)
    loss = layers.reduce_sum(layers.elementwise_mul(
        out, layers.assign(weights)))
    fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    fetch = [out] + [name + "@GRAD" for name in NAMES]
    before = dict(fluid.profiler.counters())
    text = exe.lower_step(fluid.default_main_program(), feed,
                          fetch).as_text(debug_info=True)
    for kernel, op in (("ssd_scan_fwd", "ssd_scan"),
                       ("ssd_scan_states", "ssd_scan_grad"),
                       ("ssd_scan_bwd", "ssd_scan_grad")):
        assert re.search(rf'"jit\(fn\)/{op}/[^"]*{kernel}\)?/pallas_call"',
                         text), kernel
    assert not re.search(r'"jit\(fn\)/ssd_scan_grad/[^"]*ssd_scan_fwd/', text)
    got = exe.run(feed=feed, fetch_list=fetch)
    # once for the text above, once for the run
    assert {k: v - before.get(k, 0)
            for k, v in fluid.profiler.counters().items()
            if k.startswith("ops.ssd.") and v != before.get(k, 0)} == {
        f'ops.ssd.scans{{chunk="128",dim="{p}",groups="{g}",heads="{h}",'
        f'path="pallas",state="{n}"}}': 2,
        'ops.ssd.grad_scans{chunk="128",path="pallas"}': 2}
    monkeypatch.setenv(FLASH, "0")

    def both(*a):
        y, vjp = jax.vjp(lambda *o: ssd.chunked(*o, chunk=128, groups=g), *a)
        return (y,) + vjp(jnp.asarray(weights))

    want = jax.jit(both)(*xs)
    for name, a, w in zip(("out",) + NAMES, got, want):
        assert rel(np.asarray(a).reshape(w.shape), w) < 2e-5, name
