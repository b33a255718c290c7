"""``keye_vl_2_0_30b_a3b.resident``: the cell rehearsed through the one
command, the control of its comparison at the rehearsal's size, the FLOPs
its configuration states, and the readers of its per-layer metrics.  CPU
only."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import check, cuts, hlo, peaks, plugins  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402

CELL = "keye_vl_2_0_30b_a3b.resident"
CONFIG = "configs/keye_vl_2_0_30b_a3b"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SIZES = json.load(open(os.path.join(ROOT, "chipbench", CONFIG,
                                    "config.json")))
FAMILIES = {"sparse_flash_fwd": 2, "sparse_flash_dq": 3,
            "sparse_flash_dkv": 4}


def run_tool(tool, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", tool),
         "--workload", CELL, "--rehearse", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout.strip().splitlines()


# -- the configuration ------------------------------------------------------

def test_every_width_is_the_published_one_and_the_cut_is_within_the_floors():
    entry = [c for c in BENCH["configs"]
             if c["name"] == "keye_vl_2_0_30b_a3b"][0]
    assert cuts.problems(SIZES, entry) == []
    assert SIZES["reduced"] == ["num_hidden_layers", "num_experts",
                                "num_local_experts", "vocab_size"]
    assert [SIZES[k] for k in SIZES["reduced"]] == [4, 16, 16, 18992]
    assert SIZES["published"] == {"num_hidden_layers": 48,
                                  "num_experts": 128,
                                  "num_local_experts": 128,
                                  "vocab_size": 151936}
    widths = {"hidden_size": 2048, "num_attention_heads": 32,
              "num_key_value_heads": 4, "head_dim": 128,
              "moe_intermediate_size": 768, "num_experts_per_tok": 8,
              "intermediate_size": 6144, "rope_theta": 10000000}
    assert {k: SIZES[k] for k in widths} == widths
    assert SIZES["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    tiny = SIZES["tiny"]
    assert tiny["seq_len"] >= 4 * tiny["sa_config"]["topk"]
    assert tiny["num_experts"] < tiny["published"]["num_experts"]
    spec = plugins.load(CONFIG, "reference").param_spec(SIZES)
    n = 0
    for _, shape, _ in spec:
        k = 1
        for d in shape:
            k *= d
        n += k
    assert n == 465_390_592          # 5.58 GB resident at 12 B a parameter


def test_stated_flops_by_hand():
    """Per layer and sequence, forward, in GFLOP: projections 309,
    attention over the selected pairs 240 (1,792 keys a query on average),
    index projections 37 and index scores 69, router 4, the experts held
    77; the head 637; three times that for a step."""
    flops = plugins.load(CONFIG, "flops")
    t, d = 8192, 2048
    selected = 2048 * 2049 // 2 + (t - 2048) * 2048
    assert selected / t == pytest.approx(1792.1, abs=0.1)
    layer = (2 * t * d * (4096 + 4096 + 512 + 512)
             + 2 * 2 * selected * 32 * 128
             + 2 * t * d * (1024 + 64 + 16) + 2 * (t * (t + 1) // 2) * 1024
             + 2 * t * d * 128
             + 2 * (t * 8 * 16 // 128) * 3 * d * 768)
    assert [round(x / 1e9) for x in (
        2 * t * d * 9216, 4 * selected * 4096, 2 * t * d * 1104,
        (t * (t + 1)) * 1024, 2 * t * d * 128,
        2 * 8192 * 3 * d * 768)] == [309, 241, 37, 69, 4, 77]
    assert flops.forward_flops(SIZES) == 4 * layer + 2 * t * d * 18992
    assert flops.train_flops_per_sample(SIZES) == 3 * flops.forward_flops(
        SIZES)
    assert flops.train_flops_per_sample(SIZES) / 1e12 == pytest.approx(
        10.76, abs=0.01)


# -- (f) the cell through the one command -------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    lines = run_tool("run.py", "--seed", "2147489999", "--seconds", "1",
                     "--trace", "1")
    return lines, json.loads(lines[-1])


def test_rehearsal_is_correct_and_prints_the_cut(rehearsal):
    lines, last = rehearsal
    assert lines[0] == (
        "cut: num_hidden_layers 4 of 48 (depth), num_experts 16 of 128 "
        "(experts_held), num_local_experts 16 of 128 (experts_held), "
        "vocab_size 18992 of 151936 (vocabulary); one of 8 chips that share "
        "a layer: " + SIZES["deployment"]["how"])
    assert last["correct"] is True, lines
    assert last["failed"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0


def test_rehearsal_says_which_path_each_layer_took(rehearsal):
    lines, last = rehearsal
    said = [l for l in lines if l.startswith("counters: ")]
    assert any('ops.sparse_attention.calls{path="pallas",seq="64",'
               'topk="16"}' in l for l in said), said
    assert any('ops.moe.calls{held="4",path="ragged_dot",routed="8"}' in l
               for l in said), said
    assert not any("declined" in l for l in said)
    # two layers in each program that was lowered: the kernels' backward
    # is their own, the expert layer's vjp traces its forward once more
    calls = last["metrics"]["sparse_attention_pallas_calls"]["value"]
    assert calls > 0 and calls % 2 == 0
    assert f" = {2 * calls}" in next(l for l in said if "ops.moe.calls" in l)
    assert any(l.startswith("flops per sample: ") and "stated by "
               f"chipbench/{CONFIG}/flops.py" in l for l in lines)


# -- the control ------------------------------------------------------------

@pytest.fixture(scope="module")
def readings():
    rows = [json.loads(l) for l in run_tool(
        "check_seeds.py", "--seeds", "1,2147489999", "--control-seeds",
        "1,2,3") if l.startswith("{")]
    return ({**SIZES, **SIZES["tiny"]}["limits"],
            [r for r in rows if r["kind"] == "program"],
            [r for r in rows if r["kind"] == "control_fp8"])


def test_the_fp8_control_is_not_correct_and_the_program_is(readings):
    limits, program, control = readings
    assert len(program) == 2 and len(control) == 3
    for row in program:
        assert check.decide(row, limits) is True, row
    for row in control:
        assert check.decide(row, limits) is False, row
        assert row["grad_rel"] > limits["grad_rel"]
    assert min(r["grad_rel"] for r in control) > \
        3 * max(r["grad_rel"] for r in program)


# -- kernel families and metric readers ------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_sparse_flash_family_counts_causal_tiles(family):
    mod = plugins.load("kernels", family)
    assert mod.KERNEL == family
    q = ((32, 8192, 128), "bf16")
    kv = ((4, 8192, 128), "bf16")
    sel = ((1, 8192, 8192), "i8")
    want = 2 * FAMILIES[family] * 32 * 8192 * 8192 * 128 / 2
    assert mod.flops((q, kv, kv, sel), (q,)) == want
    assert mod.flops((q, kv, kv), (q,)) == want
    # compute-bound on the v5e at these shapes: the least time is FLOPs
    pk = peaks.peaks_for("TPU v5 lite")
    call = hlo.CustomCall(family, (q, kv, kv, sel), (q,))
    assert peaks.least_seconds(want, hlo.declared_bytes(call), pk) == \
        pytest.approx(want / 197e12)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_roofline_reader_reads_its_own_family(family):
    metric = plugins.load("layer_metrics", family + "_roofline")
    roof = tr.kernel_roofline(
        [tr.Event("call." + f, 10.0 * i, 4.0 + i)
         for i, f in enumerate(sorted(FAMILIES))],
        [tr.Call(f, "sig." + f, 0.0, 819) for f in sorted(FAMILIES)], 1,
        lambda e: ("sig." + e.name.split(".")[1], 819),
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    i = sorted(FAMILIES).index(family)
    assert metric.value({"roofline": roof}) == pytest.approx(100 / (4.0 + i))
    assert metric.value({"roofline": None}) is None
    assert metric.value({"steps": 3}) is None
    other = {"families": {"flash_fwd": {"pct": 9.7, "counted": True}}}
    assert metric.value({"roofline": other}) is None


def test_time_shares_read_the_new_op_types_labels():
    run = {"time_by_label": {
        "op:sparse_indexer": 2.0, "op:sparse_attention": 0.5,
        "op:sparse_attention_grad": 0.5, "kernel:sparse_flash_fwd": 1.0,
        "kernel:sparse_flash_dq": 1.0, "kernel:sparse_flash_dkv": 1.0,
        "op:moe_experts": 1.0, "op:moe_experts_grad": 1.5,
        "kernel:unknown": 0.5, "kernel:adam": 1.0, "op:mul": 10.0},
        "labelled_busy_s": 20.0, "workload": "no_such_cell"}
    value = {n: plugins.load("layer_metrics", n).value(run) for n in (
        "index_select_time_pct", "sparse_attention_time_pct",
        "moe_time_pct")}
    # no trace of that cell to read again: the op's own labels alone, and
    # the catch-all label is nobody's
    assert value == {"index_select_time_pct": pytest.approx(10.0),
                     "sparse_attention_time_pct": pytest.approx(20.0),
                     "moe_time_pct": pytest.approx(12.5)}


def custom_call(name, start, dur):
    return tr.Event(
        f"%{name} = bf16[64,8]{{1,0}} custom-call(bf16[64,4]{{1,0}} %p.1), "
        'custom_call_target="tpu_custom_call"', start, dur)


def test_moe_share_counts_the_grouped_products_by_their_instruction(
        monkeypatch, tmp_path):
    """XLA's own grouped-product custom calls carry no scope and no kernel
    name; the reader finds them in the trace by the name XLA gives their
    instructions and takes no other unnamed custom call."""
    moe_share = plugins.load("layer_metrics", "moe_time_pct")
    events = [
        custom_call("ragged-dot-none.46", 0.0, 2e9),
        custom_call("ragged-dot-metadata", 3e9, 1e9),
        custom_call("somebody_elses_kernel.3", 5e9, 4e9),
        tr.Event("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %ragged-dot-x), "
                 "kind=kLoop", 10e9, 8e9),
    ]
    assert moe_share.grouped_product_s(events) == (pytest.approx(3.0), 2)
    pb = tmp_path / "t.xplane.pb"
    pb.write_bytes(b"")
    monkeypatch.setattr(moe_share.program_spans, "newest_trace",
                        lambda cell: str(pb) if cell == "a_cell" else None)
    monkeypatch.setattr(moe_share.trace_reduce, "read", lambda path: tr.Trace(
        {"/device:TPU:0": events}, []))
    run = {"time_by_label": {"op:moe_experts": 1.0, "kernel:unknown": 7.0,
                             "op:mul": 8.0},
           "labelled_busy_s": 16.0, "workload": "a_cell"}
    assert moe_share.value(run) == pytest.approx(100.0 * (1.0 + 3.0) / 16.0)


@pytest.mark.parametrize("name", [
    "index_select_time_pct", "sparse_attention_time_pct", "moe_time_pct",
    "sparse_attention_pallas_calls"])
def test_readers_find_nothing_in_a_program_without_the_ops(name):
    """The parent's traced run of another cell: no such label, no such
    counter; the reader returns nothing and does not raise."""
    from paddle_tpu import observe

    observe.reset()
    run = {"time_by_label": {"op:mul": 2.0, "kernel:unknown": 1.0},
           "labelled_busy_s": 3.0, "workload": "another_cell"}
    assert plugins.load("layer_metrics", name).value(run) is None
    assert plugins.load("layer_metrics", name).value({"steps": 3}) is None
