"""The selective state-space scan's three Pallas kernels as the benchmark
counts them: the family files' FLOPs from a call's declared operands, their
events through ``trace_reduce.kernel_roofline`` and
``tracing.reduce_trace``'s labels, and the four per-layer metrics that read
them.  CPU only."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import hlo, plugins, tracing  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "nemotron_twotower_30b_a3b.resident"
FAMILIES = ("ssd_scan_fwd", "ssd_scan_states", "ssd_scan_bwd")
ROOFLINES = tuple(f + "_roofline" for f in FAMILIES)
NEW = ROOFLINES + ("ssd_scan_pallas_calls",)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
C = 128

#: (batch, tokens, heads, their width, groups, state): Nemotron as run, two
#: rows of heads of 128 two a group, and a state of 256 a group of one head
SHAPES = {"nemotron": (1, 8192, 64, 64, 8, 128),
          "two_rows_heads_of_128": (2, 4096, 8, 128, 4, 128),
          "a_head_a_group_state_of_256": (1, 1024, 4, 128, 4, 256)}


def call_of(family, b, t, h, p, g, n, low="bf16"):
    """(kernel, operands, results) as ``ops/pallas_ssd`` declares them."""
    chunks, rep, wide = t // C, h // g, h * p // g
    u, bc = ((b, t, h * p), low), ((b, t, g * n), low)
    rows = ((b, g, 2 * rep + -2 * rep % 8, t), "f32")
    lanes = ((b, g, chunks, 3, wide), "f32")
    states = ((b, g, chunks, n, wide), low)
    operands = [u, bc, bc, rows, lanes]
    return {
        "ssd_scan_fwd": (family, operands, [u]),
        "ssd_scan_states": (family, operands, [states]),
        "ssd_scan_bwd": (family, operands + [states, u],
                         [u, bc, bc, rows, ((b, g, rep, t), "f32"), lanes]),
    }[family]


def by_hand(family, b, t, h, p, g, n):
    """2 x (the multiply-accumulates of the contractions the family's file
    lists), a head and chunk, written out; what a group's heads share is
    counted once a group."""
    rep = h // g
    shared, scores, state = 2 * C * C * n / rep, 2 * C * C * p, 2 * C * n * p
    each = {
        # C B^T | M x | C S^T, (e x)^T B
        "ssd_scan_fwd": shared + scores + 2 * state,
        # (e x)^T B
        "ssd_scan_states": state,
        # C B^T, dcb B, dcb^T C | dY x^T, M^T dY | B dS^T, C^T (gamma dY),
        # (gamma dY) S, (e x) dS, C S^T
        "ssd_scan_bwd": 3 * shared + 2 * scores + 5 * state,
    }[family]
    return float(b * (t // C) * h * each)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("family", FAMILIES)
def test_flops_are_the_contractions_of_every_chunked_form(family, shape):
    """From the declared shapes alone: the group's heads, their width and
    the state's among them."""
    kernel, operands, results = call_of(family, *SHAPES[shape])
    mod = plugins.load("kernels", family)
    assert mod.KERNEL == kernel
    assert mod.flops(tuple(operands), tuple(results)) \
        == pytest.approx(by_hand(family, *SHAPES[shape]), rel=1e-12)


def test_the_count_is_under_what_the_kernels_own_products_multiply():
    """Never more than the kernel does: heads of 64 go through the scores'
    products and the write two to a tile of 128 lanes, each against the
    whole tile, so those cost the kernel twice what is counted; and the
    forward's count is within a tenth of the recurrence's own
    ``scan_flops`` (which ``ssm_scan_mfu_pct`` reads: a decay, a write and
    a read an element of the state, where the chunked form has the write,
    the read and the scores), so the two shares of a peak say one thing."""
    from paddle_tpu.ops import ssd

    b, t, h, p, g, n = SHAPES["nemotron"]
    chunk_heads = b * (t // C) * h
    counted = by_hand("ssd_scan_fwd", b, t, h, p, g, n)
    done = chunk_heads * (2 * C * C * n / (h // g) + 2 * C * C * (2 * p)
                          + 2 * C * n * p + 2 * C * n * (2 * p))
    assert counted < done < 2 * counted
    assert 1.0 < counted / ssd.scan_flops(t, h, p, n) < 1.1


def stablehlo_call(kernel, operands, results):
    def tensor(t):
        shape, ty = t
        return "tensor<" + "x".join(map(str, shape)) + "x" + ty + ">"

    ins = ", ".join(map(tensor, operands))
    outs = ", ".join(map(tensor, results))
    if len(results) > 1:
        outs = "(" + outs + ")"
    args = ", ".join(f"%{i}" for i in range(len(operands)))
    return (f"    %r = stablehlo.custom_call @tpu_custom_call({args}) "
            f'{{backend_config = {{}}, kernel_name = "{kernel}"}} : '
            f"({ins}) -> {outs}")


def event_text(i, operands, results):
    def hlo_tensor(t):
        shape, ty = t
        return ty + "[" + ",".join(map(str, shape)) + "]{" + ",".join(
            map(str, reversed(range(len(shape))))) + "}"

    outs = ", ".join(map(hlo_tensor, results))
    if len(results) > 1:
        outs = "(" + outs + ")"
    return (f"%custom-call.{i} = " + outs
            + " custom-call(" + ", ".join(map(hlo_tensor, operands))
            + '), custom_call_target="tpu_custom_call"')


def test_a_steps_nine_calls_are_found_labelled_and_read():
    """Three scan layers' forward, states and backward calls as a lowered
    step declares them and as a trace shows them, each event ten times its
    least time: three families counted (events equal to calls), their
    shares 10, the labels ``kernel:ssd_scan_*`` and none
    ``kernel:unknown``, and the three readers give what the reduction
    holds."""
    shape = SHAPES["nemotron"]
    calls = [call_of(f, *shape) for f in FAMILIES]
    text = "\n".join(stablehlo_call(*c) for c in calls for _ in range(3))
    found, unknown = tracing.calls_of_step(text)
    assert not unknown
    assert [c.family for c in found] == [f for f in FAMILIES
                                         for _ in range(3)]
    assert len({c.signature for c in found}) == 3
    events, at = [], 0.0
    for i, (family, operands, results) in enumerate(
            c for c in calls for _ in range(3)):
        assert found[i].flops == pytest.approx(by_hand(family, *shape))
        took_ns = max(found[i].flops / PEAKS["bf16_flops_per_s"],
                      found[i].declared_bytes / PEAKS["hbm_bytes_per_s"]) \
            / 0.1 * 1e9
        events.append(tr.Event(event_text(i, operands, results), at,
                               took_ns))
        at += 2 * took_ns
    roof = tr.kernel_roofline(events, found, 1,
                              lambda e: hlo.event_call(e.name), PEAKS)
    for family in FAMILIES:
        assert roof["families"][family]["events"] == 3
        assert roof["families"][family]["counted"]
    label = tracing.event_label({}, found)
    assert set(tr.time_by_label(events, label)) == {
        "kernel:" + f for f in FAMILIES}
    run = {"roofline": roof}
    assert {n: plugins.load("layer_metrics", n).value(run)
            for n in ROOFLINES} == {n: pytest.approx(10.0) for n in ROOFLINES}


@pytest.mark.parametrize("name", ROOFLINES)
def test_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """The parent's case (no such kernel in the step) and an untraced run:
    None, never an error; and a family whose events are not its calls is
    withheld."""
    reader = plugins.load("layer_metrics", name)
    other = {"families": {"delta_rule_fwd": {"pct": 9.7, "counted": True}}}
    for run in ({}, {"roofline": None}, {"roofline": other}):
        assert reader.value(run) is None
    withheld = {"families": {name[:-len("_roofline")]: {
        "events": 20, "calls": 21, "counted": False}}}
    assert reader.value({"roofline": withheld}) is None


@pytest.mark.parametrize("case", ["kernels", "xla", "declined", "none"])
def test_the_calls_reader_sums_both_passes_of_the_scan(case):
    """``ssd_scan_pallas_calls``: the op's and the grad op's lowerings on
    the kernels (twice the layers, for each lowering); 0 where every scan
    ran the XLA path (the parent of the PR that brought the kernels, and a
    scan the kernels refuse); None where the program has no scan."""
    from paddle_tpu import observe

    reader = plugins.load("layer_metrics", "ssd_scan_pallas_calls")
    reg = observe.registry()
    xla = [("ops.ssd.scans", {"chunk": "128", "path": "xla"}, 9),
           ("ops.ssd.grad_scans", {"chunk": "128", "path": "by_hand"}, 9)]
    counted = {
        "kernels": [("ops.ssd.scans", {"chunk": "128", "path": "pallas"}, 9),
                    ("ops.ssd.grad_scans", {"chunk": "128",
                                            "path": "pallas"}, 9)],
        "xla": xla,
        "declined": xla + [("ops.ssd.declined", {"why": "chunk"}, 9)],
        "none": []}[case]
    for name, labels, times in counted:
        reg.inc(name, times, labels=labels)
    assert reader.value({}) == {"kernels": 18, "xla": 0, "declined": 0,
                                "none": None}[case]


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_lists_the_cell_by_name(name):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "step_ms_p95" and entry["better"] == "higher"
    if name.endswith("_roofline"):
        assert (entry["layer"], entry["unit"], entry["source"]) == (
            "Pallas kernels", "%", "device_trace")
    else:
        assert (entry["layer"], entry["unit"], entry["source"]) == (
            "token mixers", "count", "program_counter")
    assert CELL in {w["name"] for w in BENCH["workloads"]}
    moved, = [m for m in BENCH["end_to_end"] if m["name"] == "step_ms_p95"]
    assert "workloads" not in moved
