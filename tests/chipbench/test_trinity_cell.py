"""``trinity_mini.resident``: the cell rehearsed through the one command,
the control of its comparison at the rehearsal's size, the FLOPs its
configuration states, the window kernels' families and the readers of its
per-layer metrics.  CPU only."""

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

CELL = "trinity_mini.resident"
CONFIG = "configs/trinity_mini"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SIZES = json.load(open(os.path.join(ROOT, "chipbench", CONFIG,
                                    "config.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FAMILIES = {"window_flash_fwd": 2, "window_flash_dq": 3,
            "window_flash_dkv": 4}
ALL_FLASH = sorted(FAMILIES) + ["sparse_flash_fwd", "sparse_flash_dq",
                                "sparse_flash_dkv"]


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
    entry = [c for c in BENCH["configs"] if c["name"] == "trinity_mini"][0]
    assert cuts.problems(SIZES, entry) == []
    assert SIZES["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert [SIZES[k] for k in SIZES["reduced"]] == [5, 8, 25024]
    assert SIZES["published"] == {"num_hidden_layers": 32,
                                  "num_experts": 128, "vocab_size": 200192}
    assert SIZES["deployment"]["chips_sharing_a_layer"] == 16
    assert SIZES["vocab_size"] * 8 == SIZES["published"]["vocab_size"]
    widths = {"hidden_size": 2048, "num_attention_heads": 32,
              "num_key_value_heads": 4, "head_dim": 128,
              "intermediate_size": 6144, "moe_intermediate_size": 1024,
              "num_experts_per_tok": 8, "num_shared_experts": 1,
              "sliding_window": 2048, "global_attn_every_n_layers": 4,
              "num_dense_layers": 2, "route_scale": 2.826,
              "load_balance_coeff": 0.001, "rope_theta": 10000,
              "rms_norm_eps": 1e-05}
    assert {k: SIZES[k] for k in widths} == widths
    tiny = SIZES["tiny"]
    assert tiny["seq_len"] == 4 * tiny["sliding_window"]
    assert tiny["num_experts"] < tiny["published"]["num_experts"]
    n = 0
    for _, shape, _ in plugins.load(CONFIG, "reference").param_spec(SIZES):
        k = 1
        for d in shape:
            k *= d
        n += k
    assert n == 504_147_200          # 6.05 GB resident at 12 B a parameter


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file_as_published():
    row = next(json.loads(l) for l in open(CATALOG)
               if '"name": "Trinity-Mini"' in l)
    assert SIZES["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if SIZES.get(k, "absent") != v)
    assert differs == sorted(SIZES["reduced"])
    assert {k: row["config"][k] for k in differs} == SIZES["published"]


def test_layers_held_are_the_sources_entries_one_to_five():
    """``layer_offset`` 1 with the published ``num_dense_layers`` and
    ``global_attn_every_n_layers``: the builder's rule gives what the
    source's own list names, and a dense layer first."""
    build = plugins.load(CONFIG, "build")
    flops = plugins.load(CONFIG, "flops")
    cfg = build.config_of(SIZES)
    assert SIZES["layer_offset"] == 1 and cfg.num_layers == 5
    held = SIZES["layer_types"][1:6]
    assert held == ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention",
                    "sliding_attention"]
    assert [build.KINDS[bool(cfg.layer_window(i))] for i in range(5)] == held
    assert [cfg.layer_window(i) for i in range(5)] == [2048, 2048, 0, 2048,
                                                       2048]
    assert [cfg.layer_is_dense(i) for i in range(5)] == [True] + [False] * 4
    assert flops.layer_kinds(SIZES) == [
        (cfg.layer_window(i), cfg.layer_is_dense(i)) for i in range(5)]
    assert len(SIZES["layer_types"]) == 32
    with pytest.raises(ValueError, match="the builder's rule"):
        build.config_of({**SIZES, "layer_types": ["full_attention"] * 32})


def test_stated_flops_by_hand():
    """Per sequence of 6,144 tokens, forward, in GFLOP.  A layer's
    projections (q, gate, o, k, v) 335; attention over the band 172 (1,707
    keys a query on average) and over the causal half 309; the dense
    feed-forward 464; the router 3, the shared expert 77, the experts held
    39 (3,072 expected assignments); the head 630; three times the sum for a
    step: 12.7 TFLOP (17.5 at the 8,192 tokens ISSUE 34 first named)."""
    flops = plugins.load(CONFIG, "flops")
    assert flops.train_flops_per_sample({**SIZES, "seq_len": 8192}) / 1e12 \
        == pytest.approx(17.52, abs=0.01)
    assert flops.pairs(8192, 2048) == 14_681_088
    assert flops.pairs(8192, 0) == 33_558_528
    t, d = SIZES["seq_len"], 2048
    assert t == 6144
    band = 2048 * 2049 // 2 + (t - 2048) * 2048
    causal = t * (t + 1) // 2
    assert flops.pairs(t, 2048) == band == 10_486_784
    assert flops.pairs(t, 0) == causal == 18_877_440
    assert band / t == pytest.approx(1706.8, abs=0.1)
    parts = [2 * t * d * (3 * 4096 + 2 * 512), 4 * band * 4096,
             4 * causal * 4096, 2 * 3 * t * d * 6144, 2 * t * d * 128,
             2 * 3 * t * d * 1024, 2 * (t * 8 * 8 // 128) * 3 * d * 1024,
             2 * t * d * 25024]
    assert [round(x / 1e9) for x in parts] == [335, 172, 309, 464, 3, 77,
                                               39, 630]
    proj, win, glob, dense, router, shared, held, head = parts
    assert flops.forward_flops(SIZES) == (
        5 * proj + 4 * win + glob + dense + 4 * (router + shared + held)
        + head)
    assert flops.train_flops_per_sample(SIZES) == 3 * flops.forward_flops(
        SIZES)
    assert flops.train_flops_per_sample(SIZES) / 1e12 == pytest.approx(
        12.73, abs=0.01)


# -- the cell through the one command -------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    lines = run_tool("run.py", "--seed", "2147489999", "--seconds", "1",
                     "--trace", "1")
    return lines, json.loads(lines[-1])


def test_rehearsal_is_correct_and_prints_the_cut(rehearsal):
    lines, last = rehearsal
    assert lines[0] == (
        "cut: num_hidden_layers 5 of 32 (depth), num_experts 8 of 128 "
        "(experts_held), vocab_size 25024 of 200192 (vocabulary); one of 16 "
        "chips that share a layer: " + SIZES["deployment"]["how"])
    assert last["correct"] is True, lines
    assert last["failed"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["dispatches_per_step"]["value"] == 1


def test_rehearsal_says_which_path_each_layer_took(rehearsal):
    """The window label on 4 layers of each program lowered and none on 1;
    the sigmoid router's calls (the vjp traces the forward again) and one
    bias update a routed layer."""
    lines, last = rehearsal
    said = next(l for l in lines if l.startswith("counters: "))
    found = dict(kv.rsplit(" = ", 1) for kv in said[len("counters: "):]
                 .split(", ops."))
    found = {("" if k.startswith("ops.") else "ops.") + k: int(v)
             for k, v in found.items()}
    windowed = found['ops.sparse_attention.calls{path="pallas",seq="64",'
                     'topk="0",window="16"}']
    plain = found['ops.sparse_attention.calls{path="pallas",seq="64",'
                  'topk="0"}']
    assert windowed == 4 * plain and plain > 0
    assert last["metrics"]["window_attention_pallas_calls"]["value"] \
        == windowed
    assert found['ops.moe.calls{held="4",path="ragged_dot",routed="8",'
                 'score="sigmoid"}'] == 2 * windowed
    assert found["ops.moe.bias_updates"] == windowed
    assert "declined" not in said
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

Q = ((32, 8192, 128), "bf16")
KV = ((4, 8192, 128), "bf16")
ROW = ((32, 8192, 1), "f32")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_window_family_counts_the_band_from_the_declared_shapes(family):
    """The band's table [16, 5] says 4 tiles of 512 behind the diagonal:
    2,048 keys a query, 14.68M pairs at 8,192 tokens, never more than the
    70 whole tiles the kernel walks (at the cell's 6,144: [12, 5], 10.49M
    pairs, 50 tiles)."""
    mod = plugins.load("kernels", family)
    assert mod.KERNEL == family
    table = ((16, 5), "i32")
    band = 2048 * 2049 // 2 + (8192 - 2048) * 2048
    want = 2 * FAMILIES[family] * 32 * band * 128
    assert mod.flops((table, Q, KV, KV), (Q, ROW)) == want
    assert band <= 70 * 512 * 512
    q6 = ((32, 6144, 128), "bf16")
    assert mod.band_pairs((((12, 5), "i32"), q6))[1] == 10_486_784 \
        <= 50 * 512 * 512
    # a window off the tile: the table is one wider and the count is of
    # the tiles' multiple below it: never over the window's own pairs + 1
    assert mod.band_pairs((((16, 6), "i32"), Q))[1] == \
        2560 * 2561 // 2 + (8192 - 2560) * 2560
    assert mod.band_pairs((((16, 1), "i32"), Q))[1] == 8192
    assert mod.band_pairs((((16, 16), "i32"), Q))[1] <= 8192 * 8193 // 2
    # compute-bound on the v5e at these shapes: the least time is FLOPs
    pk = peaks.peaks_for("TPU v5 lite")
    call = hlo.CustomCall(family, (table, Q, KV, KV), (Q, ROW))
    assert peaks.least_seconds(want, hlo.declared_bytes(call), pk) == \
        pytest.approx(want / 197e12)


def test_window_and_global_calls_have_signatures_of_their_own():
    """A trace event is matched to a family by its call's signature: the
    window call on the same q, k, v differs from the selection-less global
    one by its table operand, so neither family is withheld."""
    window = hlo.CustomCall("window_flash_fwd",
                            (((16, 5), "i32"), Q, KV, KV), (Q, ROW))
    plain = hlo.CustomCall("sparse_flash_fwd", (Q, KV, KV), (Q, ROW))
    assert hlo.signature(window) != hlo.signature(plain)
    assert hlo.signature(window).endswith(
        "<-s32[16,5],bf16[32,8192,128],bf16[4,8192,128],bf16[4,8192,128]")


def test_lowered_window_calls_are_the_families(monkeypatch):
    """The kernels' names and operand order, as the family files read
    them, from a lowering at a small size (interpret mode has no
    ``tpu_custom_call``, so the names are read off the jaxpr)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_sparse_flash as psf

    monkeypatch.setattr(psf, "BLOCK", 16)
    q = jnp.ones((1, 4, 64, 128), jnp.float32)
    k = jnp.ones((1, 2, 64, 128), jnp.float32)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: psf.sparse_flash_attention(
            q, k, v, None, None, True, 24).sum(), (0, 1, 2)))(q, k, k))
    for family in FAMILIES:
        assert f"name={family}" in jaxpr or f"{family}" in jaxpr, family
    assert "sparse_flash_fwd" not in jaxpr


@pytest.mark.parametrize("family", ALL_FLASH)
def test_family_roofline_reader_reads_its_own_family(family):
    metric = plugins.load("layer_metrics", family + "_roofline")
    roof = tr.kernel_roofline(
        [tr.Event("call." + f, 10.0 * i, 4.0 + i)
         for i, f in enumerate(ALL_FLASH)],
        [tr.Call(f, "sig." + f, 0.0, 819) for f in ALL_FLASH], 1,
        lambda e: ("sig." + e.name.split(".")[1], 819),
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    i = ALL_FLASH.index(family)
    assert metric.value({"roofline": roof}) == pytest.approx(100 / (4.0 + i))
    assert metric.value({"roofline": None}) is None
    assert metric.value({"steps": 3}) is None
    other = {"families": {"flash_fwd": {"pct": 9.7, "counted": True}}}
    assert metric.value({"roofline": other}) is None


def test_window_time_share_reads_the_three_families_labels():
    run = {"time_by_label": {
        "kernel:window_flash_fwd": 1.0, "kernel:window_flash_dq": 1.0,
        "kernel:window_flash_dkv": 2.0, "kernel:sparse_flash_fwd": 1.0,
        "op:sparse_attention_grad": 0.5, "op:mul": 10.0},
        "labelled_busy_s": 20.0, "workload": "no_such_cell"}
    value = {n: plugins.load("layer_metrics", n).value(run) for n in (
        "window_attention_time_pct", "sparse_attention_time_pct")}
    assert value == {"window_attention_time_pct": pytest.approx(20.0),
                     "sparse_attention_time_pct": pytest.approx(7.5)}


def test_every_metric_of_the_cell_has_its_reader_and_lists_the_cell():
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g]
              if "workloads" not in m or CELL in m["workloads"]}
    new = {"window_flash_fwd_roofline", "window_flash_dq_roofline",
           "window_flash_dkv_roofline", "window_attention_time_pct",
           "window_attention_pallas_calls"}
    shared = {"tokens_per_s_per_chip", "dispatches_per_step",
              "pallas_roofline_pct", "xent_fwd_roofline",
              "xent_bwd_roofline", "adam_roofline", "moe_time_pct",
              "sparse_attention_time_pct", "sparse_flash_fwd_roofline",
              "sparse_flash_dq_roofline", "sparse_flash_dkv_roofline"}
    assert new | shared <= listed
    assert not {"index_select_time_pct", "sparse_attention_pallas_calls",
                "images_per_s_per_chip"} & listed
    for m in BENCH["per_layer"]:
        if m["name"] in new:
            assert CELL in m["workloads"]
            assert plugins.load("layer_metrics", m["name"]) is not None
    # found by name: later PRs add cells and configurations after these
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["config"] == "trinity_mini"
    assert [c["name"] for c in BENCH["configs"]].count("trinity_mini") == 1


@pytest.mark.parametrize("name", [
    "window_attention_time_pct", "window_attention_pallas_calls",
    "window_flash_fwd_roofline"])
def test_readers_find_nothing_in_a_program_without_a_window(name):
    """The parent's traced run, or another cell's: no such label, no call
    with a ``window`` label; the reader returns nothing and does not
    raise."""
    from paddle_tpu import observe

    observe.reset()
    observe.registry().inc("ops.sparse_attention.calls", labels={
        "path": "pallas", "seq": "8192", "topk": "2048"})
    run = {"time_by_label": {"op:mul": 2.0, "kernel:sparse_flash_fwd": 1.0},
           "labelled_busy_s": 3.0, "workload": "another_cell",
           "roofline": {"families": {"sparse_flash_fwd": {"pct": 30.0}}}}
    assert plugins.load("layer_metrics", name).value(run) is None
    assert plugins.load("layer_metrics", name).value({"steps": 3}) is None
    observe.reset()
