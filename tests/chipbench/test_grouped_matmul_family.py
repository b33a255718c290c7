"""The expert layer's grouped-product kernels as the benchmark counts them:
the two family files' FLOPs from a call's declared operands, their events
through ``trace_reduce.kernel_roofline`` and ``tracing.reduce_trace``'s
labels, and the three per-layer metrics that read them.  CPU only."""

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
DECODER_CELLS = {"keye_vl_2_0_30b_a3b.resident", "trinity_mini.resident",
                 "lfm2_8b_a1b.resident"}
NEW = ("grouped_matmul_roofline", "grouped_matmul_t_roofline",
       "grouped_matmul_time_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

#: the cells' products: rows, hidden, expert width, experts held
SHAPES = {"keye": (65536, 2048, 768, 16), "trinity": (49152, 2048, 1024, 8),
          "lfm2": (32768, 2048, 1792, 8)}


def tables(m, g):
    """The three scalar-prefetch operands as a lowered call declares them."""
    steps = m // 512 + g - 1
    return [((g + 1,), "i32"), ((steps,), "i32"), ((steps,), "i32")]


def call_of(form, m, d, f, g):
    """(kernel, operands, results) of one of a layer's six products."""
    bf = "bf16"
    rows_d, rows_f = ((m, d), bf), ((m, f), bf)
    up, down = ((g, d, f), bf), ((g, f, d), bf)
    return {
        "up": ("grouped_matmul", [rows_d, up], [rows_f]),
        "down": ("grouped_matmul", [rows_f, down], [rows_d]),
        "up_rows_cotangent": ("grouped_matmul", [rows_f, up], [rows_d]),
        "down_rows_cotangent": ("grouped_matmul", [rows_d, down], [rows_f]),
        "up_weights_gradient": ("grouped_matmul_t", [rows_d, rows_f], [up]),
        "down_weights_gradient": ("grouped_matmul_t", [rows_f, rows_d],
                                  [down]),
    }[form]


FORMS = ("up", "down", "up_rows_cotangent", "down_rows_cotangent",
         "up_weights_gradient", "down_weights_gradient")


@pytest.mark.parametrize("cell", sorted(SHAPES))
@pytest.mark.parametrize("form", FORMS)
def test_flops_are_one_contraction_over_every_row(cell, form):
    """2 * M * K * N whichever way the weights are contracted, with the
    scalar-prefetch operands in the call and skipped in the count."""
    m, d, f, g = SHAPES[cell]
    kernel, operands, results = call_of(form, m, d, f, g)
    family = plugins.load("kernels", kernel)
    assert family.KERNEL == kernel
    assert family.flops(tuple(tables(m, g) + operands), tuple(results)) \
        == 2.0 * m * d * f


def stablehlo_call(kernel, operands, results):
    def tensor(t):
        shape, ty = t
        return "tensor<" + "x".join(map(str, shape)) + "x" + ty + ">"

    ins = ", ".join(map(tensor, operands))
    outs = ", ".join(map(tensor, results))
    args = ", ".join(f"%{i}" for i in range(len(operands)))
    return (f"    %r = stablehlo.custom_call @tpu_custom_call({args}) "
            f'{{backend_config = {{}}, kernel_name = "{kernel}"}} : '
            f"({ins}) -> {outs}")


def event_text(i, operands, results):
    def hlo_tensor(t):
        shape, ty = t
        return {"i32": "s32"}.get(ty, ty) + "[" + ",".join(
            map(str, shape)) + "]{1,0}"

    return (f"%custom-call.{i} = " + ", ".join(map(hlo_tensor, results))
            + " custom-call(" + ", ".join(map(hlo_tensor, operands))
            + '), custom_call_target="tpu_custom_call"')


def test_a_layers_products_are_found_labelled_and_read():
    """The six products of an LFM2 layer as a lowered step declares them
    and as a trace shows them, each event at 80% of the MXU's peak: both
    families counted (events equal to calls), their shares 80, the labels
    ``kernel:grouped_matmul*`` and none ``kernel:unknown``, and the three
    readers give what the reduction holds."""
    m, d, f, g = SHAPES["lfm2"]
    calls = [call_of(form, m, d, f, g) for form in FORMS]
    text = "\n".join(stablehlo_call(k, tables(m, g) + ops, res)
                     for k, ops, res in calls)
    found, unknown = tracing.calls_of_step(text)
    assert not unknown
    assert [c.family for c in found] == ["grouped_matmul"] * 4 \
        + ["grouped_matmul_t"] * 2
    assert len({c.signature for c in found}) == 6
    assert {c.flops for c in found} == {2.0 * m * d * f}
    took_ns = 2.0 * m * d * f / 197e12 / 0.8 * 1e9
    events = [tr.Event(event_text(i, tables(m, g) + ops, res),
                       i * 2 * took_ns, took_ns)
              for i, (_, ops, res) in enumerate(calls)]
    # an op of XLA's beside them
    events.append(tr.Event("%fusion.7 = bf16[8]{0} fusion(%p)",
                           12 * took_ns, took_ns))
    roof = tr.kernel_roofline(events, found, 1,
                              lambda e: hlo.event_call(e.name), PEAKS)
    assert roof["families"]["grouped_matmul"]["events"] == 4
    assert roof["families"]["grouped_matmul_t"]["events"] == 2
    assert all(fam["counted"] for fam in roof["families"].values())
    label = tracing.event_label({"fusion.7": "moe_experts"}, found)
    by_label = tr.time_by_label(events, label)
    assert by_label == {
        "kernel:grouped_matmul": pytest.approx(4 * took_ns / 1e9),
        "kernel:grouped_matmul_t": pytest.approx(2 * took_ns / 1e9),
        "op:moe_experts": pytest.approx(took_ns / 1e9)}
    run = {"roofline": roof, "time_by_label": by_label,
           "labelled_busy_s": 7 * took_ns / 1e9}
    value = {n: plugins.load("layer_metrics", n).value(run) for n in NEW}
    assert value == {"grouped_matmul_roofline": pytest.approx(80.0),
                     "grouped_matmul_t_roofline": pytest.approx(80.0),
                     "grouped_matmul_time_pct": pytest.approx(600.0 / 7)}


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """The parent's case (no such kernel in the step) and an untraced run:
    None, never an error."""
    reader = plugins.load("layer_metrics", name)
    other = {"families": {"flash_fwd": {"pct": 9.7, "counted": True}}}
    for run in ({}, {"roofline": None}, {"roofline": other},
                {"time_by_label": {"kernel:unknown": 1.0, "op:mul": 2.0},
                 "labelled_busy_s": 3.0, "roofline": other}):
        assert reader.value(run) is None
    withheld = {"families": {name[:-len("_roofline")]: {
        "events": 41, "calls": 42, "counted": False}}}
    if name.endswith("_roofline"):
        assert reader.value({"roofline": withheld}) is None


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_lists_the_three_decoder_cells_by_name(name):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert DECODER_CELLS <= set(entry["workloads"])
    assert entry["layer"] == "expert layer"
    assert entry["moves"] == "step_ms_p95" and entry["unit"] == "%"
    assert entry["source"] == "device_trace"
    assert entry["better"] == ("lower" if name.endswith("time_pct")
                               else "higher")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert DECODER_CELLS <= cells
    # each of those cells reports the end-to-end metric these move
    moved, = [m for m in BENCH["end_to_end"] if m["name"] == "step_ms_p95"]
    assert "workloads" not in moved
