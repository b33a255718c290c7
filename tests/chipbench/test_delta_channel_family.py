"""The channel-decay delta rule's three Pallas kernels as the benchmark
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
CELL = "kimi_linear_48b_a3b.resident"
FAMILIES = ("delta_channel_fwd", "delta_channel_states", "delta_channel_bwd")
ROOFLINES = tuple(f + "_roofline" for f in FAMILIES)
NEW = ROOFLINES + ("delta_channel_pallas_calls",)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
C = 64

#: (batch, tokens, heads, dk, dv): Kimi-Linear as run, two rows of fewer
#: heads, and values twice as wide as keys
SHAPES = {"kimi_linear": (1, 2048, 32, 128, 128),
          "two_rows": (2, 4096, 8, 128, 128),
          "wide_values": (1, 1024, 4, 128, 256)}


def call_of(family, b, t, h, dk, dv, low="bf16"):
    """(kernel, operands, results) as ``ops/pallas_delta_rule`` declares
    them."""
    n, pairs = t // C, h // 2
    qk, v = ((b, t, h * dk), "f32"), ((b, t, h * dv), low)
    cols = ((b, pairs, n * 128, 128), "f32")
    states = ((b, pairs, n, 2, dv, dk), low)
    operands = [qk, qk, v, qk, cols]
    return {
        "delta_channel_fwd": (family, operands, [v]),
        "delta_channel_states": (family, operands, [states]),
        "delta_channel_bwd": (family, operands + [states, v],
                              [qk, qk, v, qk, cols]),
    }[family]


def by_hand(family, b, t, h, dk, dv):
    """2 x (the multiply-accumulates of the contractions the family's file
    lists), a head and chunk, written out: a score matrix made by the split
    is its six off-diagonal [16, 16] tiles."""
    tiles = 2 * 6 * 16 * 16 * dk
    square_k, square_v, state = 2 * C * C * dk, 2 * C * C * dv, \
        2 * C * dk * dv
    each = {
        # K K^T, Q K^T by tiles, W | U, P V' | W S, (q gamma) S, E^T V'
        "delta_channel_fwd": 2 * tiles + square_k + 2 * square_v + 3 * state,
        # K K^T by tiles, W | U | W S, E^T V'
        "delta_channel_states": tiles + square_k + square_v + 2 * state,
        # the system (both score matrices by tiles, W | U) and V'; T^T dW,
        # dW W^T, two sides of two score matrices' cotangents by tiles |
        # P^T dO, T^T dU, dU U^T, dO V'^T | E dS, (q gamma)^T dO, W^T dV',
        # dV' S^T, dO S^T, V' dS^T
        "delta_channel_bwd": (2 + 4) * tiles + (1 + 2) * square_k
        + (1 + 4) * square_v + (1 + 6) * state,
    }[family]
    return float(b * (t // C) * h * each)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("family", FAMILIES)
def test_flops_are_the_contractions_of_every_chunked_form(family, shape):
    """From the declared shapes alone, the heads' two widths among them."""
    b, t, h, dk, dv = SHAPES[shape]
    kernel, operands, results = call_of(family, b, t, h, dk, dv)
    mod = plugins.load("kernels", family)
    assert mod.KERNEL == kernel
    assert mod.flops(tuple(operands), tuple(results)) \
        == by_hand(family, b, t, h, dk, dv)


def test_the_count_is_under_what_the_kernels_own_products_multiply():
    """Never more than the kernel does: its one stacked product a pair for
    the off-diagonal tiles alone streams 96 rows a head and score matrix
    against all 128 tokens, four times the six tiles that are counted; and
    the ladder that makes the inverse (ten [128, 128] products a pair at
    six passes) is not counted at all."""
    b, t, h, dk, dv = SHAPES["kimi_linear"]
    pairs = b * (t // C) * (h // 2)
    counted_tiles = 2 * 2 * 6 * 16 * 16 * dk * 2 * pairs
    stacked = 2 * (4 * 96) * 128 * dk * pairs
    assert stacked >= 4 * counted_tiles
    ladder = 10 * 6 * 2 * 128 ** 3 * pairs
    counted = by_hand("delta_channel_fwd", b, t, h, dk, dv)
    assert 0.03 < counted / ladder < 0.12


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


def test_a_steps_twelve_calls_are_found_labelled_and_read():
    """Four delta layers' forward, states and backward calls as a lowered
    step declares them and as a trace shows them, each event ten times its
    least time: three families counted (events equal to calls), their
    shares 10, the labels ``kernel:delta_channel_*`` and none
    ``kernel:unknown``, and the three readers give what the reduction
    holds."""
    shape = SHAPES["kimi_linear"]
    calls = [call_of(f, *shape) for f in FAMILIES]
    text = "\n".join(stablehlo_call(*c) for c in calls for _ in range(4))
    found, unknown = tracing.calls_of_step(text)
    assert not unknown
    assert [c.family for c in found] == [f for f in FAMILIES
                                         for _ in range(4)]
    assert len({c.signature for c in found}) == 3
    events, at = [], 0.0
    for i, (family, operands, results) in enumerate(
            c for c in calls for _ in range(4)):
        assert found[i].flops == by_hand(family, *shape)
        took_ns = max(found[i].flops / PEAKS["bf16_flops_per_s"],
                      found[i].declared_bytes / PEAKS["hbm_bytes_per_s"]) \
            / 0.1 * 1e9
        events.append(tr.Event(event_text(i, operands, results), at,
                               took_ns))
        at += 2 * took_ns
    roof = tr.kernel_roofline(events, found, 1,
                              lambda e: hlo.event_call(e.name), PEAKS)
    for family in FAMILIES:
        assert roof["families"][family]["events"] == 4
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
        "events": 27, "calls": 28, "counted": False}}}
    assert reader.value({"roofline": withheld}) is None


@pytest.mark.parametrize("case", ["kernels", "xla", "scalar", "none"])
def test_the_calls_reader_sums_both_passes_of_a_channel_decay_rule(case):
    """``delta_channel_pallas_calls``: the op's and the grad op's lowerings
    on the kernels where a decay a key channel was lowered (twice the
    layers, for each lowering); 0 where every such layer ran the XLA path
    (the parent of the PR that brought the kernels); None where the rule
    decays by one number a head, or where there is no rule."""
    from paddle_tpu import observe

    reader = plugins.load("layer_metrics", "delta_channel_pallas_calls")
    reg = observe.registry()
    channel = ("ops.delta_rule.channel_calls", {"chunk": "64", "sub": "16"})
    counted = {
        "kernels": [channel + (8,),
                    ("ops.delta_rule.calls", {"path": "pallas"}, 8),
                    ("ops.delta_rule.grad_calls", {"path": "pallas"}, 8)],
        "xla": [channel + (8,),
                ("ops.delta_rule.calls", {"path": "xla"}, 8),
                ("ops.delta_rule.grad_calls", {"path": "by_hand"}, 8),
                ("ops.delta_rule.declined", {"why": "chunk"}, 8)],
        "scalar": [("ops.delta_rule.calls", {"path": "pallas"}, 6),
                   ("ops.delta_rule.grad_calls", {"path": "pallas"}, 6)],
        "none": []}[case]
    for name, labels, times in counted:
        reg.inc(name, times, labels=labels)
    assert reader.value({}) == {"kernels": 16, "xla": 0, "scalar": None,
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
