"""``mellum2_12b_a2_5b.resident``: the cell rehearsed through the one
command, the control of its comparison at the rehearsal's size, its
parameters and the FLOPs its configuration states, the window families at
a band of three tiles and the readers of its per-layer metrics.  CPU
only.  Entries of ``BENCHMARK.json`` are found by name, never by place."""

import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import check, cuts, hlo, peaks, plugins  # noqa: E402

CELL = "mellum2_12b_a2_5b.resident"
NAME = "mellum2_12b_a2_5b"
CONFIG = "configs/" + NAME
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SIZES = json.load(open(os.path.join(ROOT, "chipbench", CONFIG,
                                    "config.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FAMILIES = {"window_flash_fwd": 2, "window_flash_dq": 3,
            "window_flash_dkv": 4}
NEW = {"global_mixer_time_pct": "token mixers",
       "window_interior_tiles_pct": "sparse attention ops",
       "yarn_global_layers": "token mixers"}


def run_tool(tool, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", tool),
         "--workload", CELL, "--rehearse", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout.strip().splitlines()


def entry_of(group, name):
    found = [e for e in BENCH[group] if e["name"] == name]
    assert len(found) == 1, (group, name)
    return found[0]


# -- the configuration ------------------------------------------------------

def test_every_width_is_the_published_one_and_the_cut_is_within_the_floors():
    entry = entry_of("configs", NAME)
    assert cuts.problems(SIZES, entry) == []
    assert SIZES["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert SIZES["published"] == {"num_hidden_layers": 28,
                                  "num_experts": 64, "vocab_size": 98304}
    assert [SIZES[k] for k in SIZES["reduced"]] == [4, 8, 12288]
    assert SIZES["deployment"]["chips_sharing_a_layer"] * 8 == 64
    assert SIZES["vocab_size"] * 8 == SIZES["published"]["vocab_size"]
    assert (SIZES["layer_offset"], SIZES["expert_offset"]) == (0, 0)
    widths = {"hidden_size": 2304, "num_attention_heads": 32,
              "num_key_value_heads": 4, "head_dim": 128,
              "moe_intermediate_size": 896, "intermediate_size": 7168,
              "num_experts_per_tok": 8, "sliding_window": 1024,
              "rms_norm_eps": 1e-06, "norm_topk_prob": True,
              "tie_word_embeddings": False, "model_type": "mellum",
              "max_window_layers": 0, "use_sliding_window": True,
              "seq_len": 8192, "batch_per_chip": 1, "check_batch": 1}
    assert {k: SIZES[k] for k in widths} == widths
    assert SIZES["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert SIZES["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 500000}
    build = plugins.load(CONFIG, "build")
    cfg = build.config_of(SIZES)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads,
            cfg.num_routed, cfg.experts_held, cfg.experts_per_token,
            cfg.expert_width, cfg.window, cfg.global_every, cfg.rope_theta,
            cfg.rope_global, cfg.shared_width, cfg.dense_layers,
            cfg.attn_gate, cfg.post_norms, cfg.router_score, cfg.norm_topk,
            cfg.residual, cfg.mtp_depth, cfg.mixers, cfg.rotary_dims) == (
        2304, 128, 32, 4, 64, 8, 8, 896, 1024, 4, 500000, True, 0, 0, False,
        False, "softmax", True, "sequential", 0, None, 0)
    assert [cfg.layer_window(i) for i in range(4)] == [1024, 1024, 1024, 0]
    assert len(cfg.global_rotary.inv_freq) == 64
    assert cfg.global_rotary.scale == pytest.approx(
        128 ** -0.5 * 1.2772588722239782 ** 2, rel=1e-12)
    # the layers held are the source's entries 0-3, and the builder's rule
    # gives every published layer its kind
    assert SIZES["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert build.global_every(SIZES) == 4
    later = build.config_of({**SIZES, "layer_offset": 1})
    assert [later.layer_window(i) for i in range(4)] == [1024, 1024, 0, 1024]
    with pytest.raises(ValueError, match="no full_attention layer every"):
        build.config_of({**SIZES, "layer_types": [
            "sliding_attention", "full_attention", "full_attention"]})
    with pytest.raises(ValueError, match="nothing else"):
        build.config_of({**SIZES, "mlp_layer_types": ["dense"] * 28})
    with pytest.raises(ValueError, match="nothing else"):
        build.config_of({**SIZES, "tie_word_embeddings": True})
    tiny = {**SIZES, **SIZES["tiny"]}
    assert tiny["num_experts"] < tiny["published"]["num_experts"]
    assert tiny["sliding_window"] < tiny["seq_len"]
    small = build.config_of(tiny)
    assert [bool(small.layer_window(i)) for i in range(4)] == [
        True, True, True, False]
    assert len(small.global_rotary.inv_freq) == 8


def test_parameters_as_run_add_up_to_the_count_the_equations_give():
    n = {name: math.prod(shape) for name, shape, _ in
         plugins.load(CONFIG, "reference").param_spec(SIZES)}
    assert sum(n.values()) == 340_350_208     # 4.08 GB resident at 12 B

    def under(p, keys):
        return sum(n[f"{p}_{k}"] for k in keys)

    for p in ("l0", "l1", "l2", "l3"):
        assert under(p, ("q_w", "q_norm", "k_w", "k_norm", "v_w", "o_w")) \
            == 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 + 256 \
            == 21_233_920
        assert under(p, ("attn_norm", "moe_norm", "router_w")) == 152_064
        assert [n[f"{p}_{k}"] for k in ("w1", "w3", "w2")] \
            == [8 * 2304 * 896] * 3
        assert under(p, ("w1", "w3", "w2")) == 8 * 6_193_152
    assert n["tok_emb"] + n["lm_head_w"] + n["final_norm"] == 56_625_408
    assert 4 * (21_233_920 + 152_064 + 8 * 6_193_152) + 56_625_408 \
        == 340_350_208


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file_as_published():
    row = next(json.loads(l) for l in open(CATALOG)
               if '"name": "Mellum2-12B-A2.5B-Instruct"' in l)
    assert SIZES["source"] == row["source_url"] \
        == entry_of("configs", NAME)["source"]
    differs = sorted(k for k, v in row["config"].items()
                     if SIZES.get(k, "absent") != v)
    assert differs == sorted(SIZES["reduced"])
    assert {k: row["config"][k] for k in differs} == SIZES["published"]


def test_stated_flops_by_hand():
    """Per sequence of 8,192 tokens, forward, in GFLOP.  A layer's four
    projections 347.9; a window layer's pairs (960.06 keys a query) 128.9,
    the global layer's causal half 549.8; a router 2.4; the experts held
    101.5 (8,192 expected assignments, live rows only); the head 463.9:
    391.5 MFLOP a token, 9.62 TFLOP a step.  The padded grouped products
    WALK all 65,536 rows a layer: 99.1 MFLOP a token and layer, 811.7
    GFLOP."""
    flops = plugins.load(CONFIG, "flops")
    t, d = SIZES["seq_len"], 2304
    assert flops.layer_windows(SIZES) == [1024, 1024, 1024, 0]
    band = 1024 * 1025 // 2 + (t - 1024) * 1024
    assert (flops.pairs(t, 1024), flops.pairs(t, 0)) == (
        band, t * (t + 1) // 2) == (7_864_832, 33_558_528)
    assert band / t == pytest.approx(960.06, abs=0.01)
    parts = [2 * t * d * (2 * 4096 + 2 * 512), 2 * 2 * band * 32 * 128,
             2 * 2 * flops.pairs(t, 0) * 32 * 128, 2 * t * d * 64,
             2 * (t * 8 * 8 // 64) * 3 * d * 896, 2 * t * d * 12288]
    assert [round(x / 1e9, 1) for x in parts] == [
        347.9, 128.9, 549.8, 2.4, 101.5, 463.9]
    projections, window, full, router, held, head = parts
    assert flops.forward_flops(SIZES) == (
        4 * (projections + router + held) + 3 * window + full + head)
    assert flops.forward_flops(SIZES) / t / 1e6 == pytest.approx(391.5,
                                                                 abs=0.1)
    assert flops.train_flops_per_sample(SIZES) / 1e12 == pytest.approx(
        9.62, abs=0.01)
    rows = t * SIZES["num_experts_per_tok"]
    assert (rows, t * 8 * 8 // 64) == (65_536, 8_192)
    assert 2 * rows * 3 * d * 896 / t / 1e6 == pytest.approx(99.1, abs=0.1)


# -- the cell through the one command -------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    lines = run_tool("run.py", "--seed", "2147489999", "--seconds", "1",
                     "--trace", "1")
    return lines, json.loads(lines[-1])


def test_rehearsal_is_correct_and_prints_the_cut(rehearsal):
    lines, last = rehearsal
    assert lines[0] == (
        "cut: num_hidden_layers 4 of 28 (depth), num_experts 8 of 64 "
        "(experts_held), vocab_size 12288 of 98304 (vocabulary); one of 8 "
        "chips that share a layer: " + SIZES["deployment"]["how"])
    assert last["correct"] is True, lines
    assert last["failed"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["dispatches_per_step"]["value"] == 1
    assert last["metrics"]["ops_without_scope"]["value"] == 0


def test_rehearsal_says_which_tables_band_and_paths_ran(rehearsal):
    """One global layer with a table of its own a program built; a program
    lowered has three window calls and one global call on the Pallas path,
    six rotaries from ``theta`` and two from the table, the band's tiles by
    kind, softmax routers (no ``score`` label); nothing declined."""
    lines, last = rehearsal
    said = next(l for l in lines if l.startswith("counters: models."))

    def count(name):        # the labels hold commas: by the whole name
        return int(said[said.index(name + " = ") + len(name) + 3:]
                   .split(",", 1)[0])

    programs = count('models.decoder.rotary{kind="global",scope="layer3",'
                     'table="given"}')
    assert programs > 0 and said.count("models.decoder.rotary{") == 1
    assert last["metrics"]["yarn_global_layers"]["value"] == 1
    assert count('models.decoder.blocks{mixer="attention",'
                 'residual="sequential",where="trunk"}') == 4 * programs
    full = count('ops.sparse_attention.calls{path="pallas",seq="64",'
                 'topk="0"}')
    band = count('ops.sparse_attention.calls{path="pallas",seq="64",'
                 'topk="0",window="16"}')
    assert band == 3 * full and full > 0
    assert last["metrics"]["window_attention_pallas_calls"]["value"] == band
    assert count('ops.rotary.calls{dims="16",pairing="half",scaled="0"}') \
        == 6 * full
    assert count('ops.rotary.calls{dims="16",pairing="half",scaled="1"}') \
        == 2 * full
    # 64 tokens are one tile of 512: an edge tile a call, none interior
    for kernel in ("fwd", "dq", "dkv"):
        assert count('ops.sparse_attention.tiles{kernel="window_flash_'
                     f'{kernel}",kind="edge"}}') == band
        assert count('ops.sparse_attention.tiles{kernel="window_flash_'
                     f'{kernel}",kind="interior"}}') == 0
    assert count('ops.moe.calls{held="4",path="ragged_dot",routed="8"}') \
        == 2 * 4 * full
    assert "declined" not in said and "bias_updates" not in said
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
def test_window_family_counts_a_band_of_three_tiles(family):
    """The band's table [16, 3] says 2 tiles of 512 behind the diagonal:
    1,024 keys a query, 7.86M pairs at 8,192 tokens, which is
    ``flops.py``'s own count and never more than the 45 whole tiles the
    kernel walks; compute-bound on the v5e, so the least time is the
    FLOPs'."""
    mod = plugins.load("kernels", family)
    table = ((16, 3), "i32")
    band = plugins.load(CONFIG, "flops").pairs(8192, 1024)
    assert mod.band_pairs((table, Q)) == (32, band, 128)
    assert band == 7_864_832 <= 45 * 512 * 512
    want = 2 * FAMILIES[family] * 32 * band * 128
    assert mod.flops((table, Q, KV, KV), (Q, ROW)) == want
    pk = peaks.peaks_for("TPU v5 lite")
    call = hlo.CustomCall(family, (table, Q, KV, KV), (Q, ROW))
    assert peaks.least_seconds(want, hlo.declared_bytes(call), pk) == \
        pytest.approx(want / 197e12)


def test_a_window_of_two_tiles_walks_a_band_of_three_one_interior():
    """``ceil(1023 / 512) + 1`` = 3 tiles a row: 45 live of the 136 causal
    ones at 8,192 tokens, 15 of them interior (Trinity's 2,048 over 6,144:
    50 live, 30 interior)."""
    from paddle_tpu.ops import pallas_sparse_flash as psf

    assert psf.band_tiles(1024, 512, 16) == 3
    assert sum(min(j + 1, 3) for j in range(16)) == 45
    assert psf.band_tiles(2048, 512, 12) == 5
    assert sum(min(j + 1, 5) for j in range(12)) == 50


def scoped_run(by, labels=None):
    from chipbench import scope_time

    return {"scope_time": scope_time.Table(by, {}),
            "labelled_busy_s": sum(by.values()), "workload": CELL,
            "time_by_label": labels or {}, "device_kind": "TPU v5 lite",
            "samples_per_step": 1, "steps_traced": 4, "chips": 1}


def test_time_shares_split_the_mixers_between_the_global_and_the_band():
    run = scoped_run({
        ("mul", "layer0.mixer"): 3.0,
        ("sparse_attention", "layer1.mixer"): 2.0,
        ("sparse_attention_grad", "layer2.mixer"): 1.0,
        ("mul", "layer3.mixer.global"): 1.5,
        ("sparse_attention", "layer3.mixer.global"): 2.0,
        ("rotary_embedding", "layer3.mixer.global"): 0.5,
        ("elementwise_add", "layer3.mixer"): 0.5,
        ("moe_experts", "layer1.ffn"): 5.5, ("mul", "head"): 4.0})
    value = {n: plugins.load("layer_metrics", n).value(run) for n in (
        "global_mixer_time_pct", "mixer_time_pct", "ffn_time_pct",
        "head_time_pct")}
    assert value == {"global_mixer_time_pct": pytest.approx(20.0),
                     "mixer_time_pct": pytest.approx(52.5),
                     "ffn_time_pct": pytest.approx(27.5),
                     "head_time_pct": pytest.approx(20.0)}


def test_counter_readers_read_the_band_and_the_layers(capsys):
    from paddle_tpu import observe

    observe.reset()
    reg = observe.registry()
    for _ in range(2):      # the cell's two builds: one layer, two counts
        reg.inc("models.decoder.rotary", labels={
            "kind": "global", "table": "given", "scope": "layer3"})
    reg.inc("ops.rotary.calls", labels={
        "dims": "128", "pairing": "half", "scaled": "1"})
    for kernel in ("fwd", "dq", "dkv"):
        for kind, n in (("interior", 45), ("edge", 90)):
            reg.inc("ops.sparse_attention.tiles", n, labels={
                "kernel": f"window_flash_{kernel}", "kind": kind})
        reg.inc("ops.sparse_attention.tiles", 136, labels={
            "kernel": f"sparse_flash_{kernel}", "kind": "edge"})
    reg.inc("ops.moe.column_tiles", labels={
        "kernel": "grouped_matmul", "width": "896", "tile": "512",
        "tiles": "2", "ragged": "1"})
    assert plugins.load("layer_metrics", "window_interior_tiles_pct").value(
        {}) == pytest.approx(100.0 / 3)
    assert plugins.load("layer_metrics", "yarn_global_layers").value({}) == 1
    said = capsys.readouterr().out
    for part in ('ops.sparse_attention.tiles{kernel="window_flash_dkv",'
                 'kind="interior"} = 45',
                 'models.decoder.rotary{kind="global",scope="layer3",'
                 'table="given"} = 2',
                 'ops.rotary.calls{dims="128",pairing="half",scaled="1"} = 1',
                 'ops.moe.column_tiles{kernel="grouped_matmul",ragged="1",'
                 'tile="512",tiles="2",width="896"} = 1'):
        assert part in said, part
    observe.reset()


def test_every_metric_of_the_cell_has_its_reader_and_lists_the_cell():
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g]
              if "workloads" not in m or CELL in m["workloads"]}
    shared = {"tokens_per_s_per_chip", "dispatches_per_step",
              "pallas_roofline_pct", "xent_fwd_roofline",
              "xent_bwd_roofline", "adam_roofline", "moe_time_pct",
              "sparse_attention_time_pct", "sparse_flash_fwd_roofline",
              "sparse_flash_dq_roofline", "sparse_flash_dkv_roofline",
              "window_flash_fwd_roofline", "window_flash_dq_roofline",
              "window_flash_dkv_roofline", "window_attention_time_pct",
              "window_attention_pallas_calls", "grouped_matmul_roofline",
              "grouped_matmul_t_roofline", "grouped_matmul_time_pct",
              "mixer_time_pct", "ffn_time_pct", "head_time_pct",
              "head_mfu_pct", "scoped_time_pct", "ops_without_scope",
              "mfu_pct", "step_ms_p95", "peak_hbm_gib", "setup_s"}
    assert set(NEW) | shared <= listed
    assert not {"index_select_time_pct", "sparse_attention_pallas_calls",
                "images_per_s_per_chip", "flash_fwd_roofline",
                "latent_proj_time_pct", "latent_mixer_blocks",
                "mtp_time_pct", "short_conv_time_pct", "short_conv_calls",
                "delta_rule_time_pct", "delta_mixer_blocks"} & listed
    for name, layer in NEW.items():
        m = entry_of("per_layer", name)
        assert CELL in m["workloads"] and m["layer"] == layer
        assert m["moves"] == "step_ms_p95"
        assert plugins.load("layer_metrics", name) is not None
    for name in listed:
        kind = "metrics" if any(m["name"] == name
                                for m in BENCH["end_to_end"]) \
            else "layer_metrics"
        assert plugins.load(kind, name) is not None, name
    cell = entry_of("workloads", CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "resident" \
        and cell["config"] == NAME and len(cell["why"]) <= 200
    assert len(entry_of("configs", NAME)["why"]) <= 200
    assert entry_of("configs", NAME)["file"] == \
        f"chipbench/{CONFIG}/config.json"


@pytest.mark.parametrize("name", sorted(NEW))
def test_readers_find_nothing_in_a_program_without_the_record(name):
    """The parent's traced run, or another cell's: no ``.global`` path, no
    window kernel's tiles, no ``models.decoder.rotary``; the reader returns
    nothing and does not raise."""
    from paddle_tpu import observe

    observe.reset()
    reg = observe.registry()
    reg.inc("models.decoder.blocks", labels={
        "mixer": "attention", "residual": "sequential", "where": "trunk"})
    reg.inc("ops.sparse_attention.tiles", 136, labels={
        "kernel": "sparse_flash_fwd", "kind": "edge"})
    reg.inc("ops.sparse_attention.tiles", 0, labels={
        "kernel": "window_flash_fwd", "kind": "interior"})
    reader = plugins.load("layer_metrics", name)
    run = scoped_run({("mul", "layer0.mixer"): 2.0, ("mul", "head"): 1.0},
                     {"op:mul": 3.0})
    assert reader.value({**run, "workload": "keye_vl_2_0_30b_a3b.resident"}) \
        is None
    assert reader.value({"scope_time": None, "workload": "x"}) is None
    assert reader.value({"steps": 3}) is None
    observe.reset()
