"""The scalar delta rule's three Pallas kernels as the benchmark counts
them: the family files' FLOPs from a call's declared operands, their events
through ``trace_reduce.kernel_roofline`` and ``tracing.reduce_trace``'s
labels, and the four per-layer metrics that read them.  CPU only."""

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
CELL = "qwen3_next_80b_a3b.resident"
FAMILIES = ("delta_rule_fwd", "delta_rule_states", "delta_rule_bwd")
ROOFLINES = tuple(f + "_roofline" for f in FAMILIES)
NEW = ROOFLINES + ("delta_rule_pallas_calls",)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
C = 64

#: (batch, tokens, key heads, value heads, dk, dv): Qwen3-Next as run, one
#: value head a key head, and heads of 256
SHAPES = {"qwen3_next": (1, 8192, 16, 32, 128, 128),
          "one_value_head": (2, 4096, 8, 8, 128, 128),
          "wide": (1, 2048, 4, 8, 256, 128)}


def call_of(family, b, t, hk, hv, dk, dv, low="bf16"):
    """(kernel, operands, results) as ``ops/pallas_delta_rule`` declares
    them."""
    n, pairs = t // C, hv // 2
    qk, v = ((b, t, hk * dk), "f32"), ((b, t, hv * dv), low)
    cols, rows = ((b, pairs, n * 128, 128), "f32"), \
        ((b, pairs, n, 8, 128), "f32")
    states = ((b, pairs, n, 2, dk, dv), low)
    operands = [qk, qk, v, cols, rows]
    return {
        "delta_rule_fwd": (family, operands, [v]),
        "delta_rule_states": (family, operands, [states]),
        "delta_rule_bwd": (family, operands + [states, v],
                           [qk, qk, v, cols, rows]),
    }[family]


def by_hand(family, b, t, hk, hv, dk, dv):
    """2 x (the multiply-accumulates of the contractions the family's file
    lists), a value head and chunk, written out."""
    square_k, square_v, state = 2 * C * C * dk, 2 * C * C * dv, \
        2 * C * dk * dv
    each = {
        # K K^T, Q K^T, W | U, P V' | W S, Q S, K^T (e V')
        "delta_rule_fwd": 3 * square_k + 2 * square_v + 3 * state,
        # K K^T, W | U | W S, K^T (e V')
        "delta_rule_states": 2 * square_k + square_v + 2 * state,
        # the system (K K^T, Q K^T, W | U) and V'; P^T dO, T^T dU, dU U^T,
        # dO V'^T | T^T dW, dW W^T, dP K, dP^T Q, (X + X^T) K | K dS,
        # Q^T (gamma dO), W^T dV', dV' S^T, (gamma dO) S^T, (e V') dS^T
        "delta_rule_bwd": (3 + 5) * square_k + (1 + 4) * square_v
        + (1 + 6) * state,
    }[family]
    return float(b * (t // C) * hv * each)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("family", FAMILIES)
def test_flops_are_the_contractions_of_every_chunked_form(family, shape):
    """From the declared shapes alone, the heads' widths among them.  The
    forward's ``dk`` is read off ``Hk * dk`` and the pairs: where two value
    heads of 256 a key head and one of 128 declare the same shapes, the
    smaller count."""
    b, t, hk, hv, dk, dv = SHAPES[shape]
    kernel, operands, results = call_of(family, b, t, hk, hv, dk, dv)
    mod = plugins.load("kernels", family)
    assert mod.KERNEL == kernel
    if (family, shape) == ("delta_rule_fwd", "wide"):
        dk = 128
    assert mod.flops(tuple(operands), tuple(results)) \
        == by_hand(family, b, t, hk, hv, dk, dv)


def test_the_count_is_a_tenth_of_what_the_ladder_alone_multiplies():
    """Why a reading far under 100 is no fault: the ladder that makes the
    inverse is ten [128, 128] x [128, 128] products a pair at six passes,
    and none of it is counted."""
    b, t, hk, hv, dk, dv = SHAPES["qwen3_next"]
    ladder = 10 * 6 * 2 * 128 ** 3 * b * (t // C) * (hv // 2)
    counted = by_hand("delta_rule_fwd", b, t, hk, hv, dk, dv)
    assert 0.05 < counted / ladder < 0.12


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
    """Three delta layers' forward, states and backward calls as a lowered
    step declares them and as a trace shows them, each event ten times its
    least time (the bytes' at these shapes: the counted products are under
    half of it): three families counted (events equal to calls), their
    shares 10, the labels ``kernel:delta_rule_*`` and none
    ``kernel:unknown``, and the three readers give what the reduction
    holds."""
    shape = SHAPES["qwen3_next"]
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
        assert found[i].flops == by_hand(family, *shape)
        took_ns = max(found[i].flops / PEAKS["bf16_flops_per_s"],
                      found[i].declared_bytes / PEAKS["hbm_bytes_per_s"]) / 0.1 * 1e9
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
    other = {"families": {"flash_fwd": {"pct": 9.7, "counted": True}}}
    for run in ({}, {"roofline": None}, {"roofline": other}):
        assert reader.value(run) is None
    withheld = {"families": {name[:-len("_roofline")]: {
        "events": 20, "calls": 21, "counted": False}}}
    assert reader.value({"roofline": withheld}) is None


def test_the_calls_reader_sums_both_passes_and_prints_what_was_declined(
        capsys):
    """``delta_rule_pallas_calls``: the op's and the grad op's lowerings on
    the kernels; a layer on the XLA path counts for nothing, and every
    ``ops.delta_rule.*`` counter is printed with its labels."""
    from paddle_tpu import observe

    reader = plugins.load("layer_metrics", "delta_rule_pallas_calls")
    reg = observe.registry()
    for name, labels, times in (
            ("ops.delta_rule.calls", {"path": "pallas", "chunk": "64"}, 3),
            ("ops.delta_rule.grad_calls", {"path": "pallas"}, 3),
            ("ops.delta_rule.calls", {"path": "xla", "chunk": "16"}, 2),
            ("ops.delta_rule.grad_calls", {"path": "by_hand"}, 2),
            ("ops.delta_rule.declined", {"why": "chunk"}, 2)):
        reg.inc(name, times, labels=labels)
    assert reader.value({}) == 6
    printed = capsys.readouterr().out
    assert 'ops.delta_rule.declined{why="chunk"} = 2' in printed


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
