"""``sdar_30b_a3b_chat.resident``: the cell rehearsed through the one
command, the control of its comparison at the rehearsal's size, its
parameters and the FLOPs its configuration states, the block-rule families'
counts, the readers of its per-layer metrics and the record of its lowered
step.  CPU only.  Entries of ``BENCHMARK.json`` are found by name, never by
place."""

import glob
import hashlib
import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import check, cuts, hlo, peaks, plugins  # noqa: E402
from chipbench import step_gauges  # noqa: E402

CELL = "sdar_30b_a3b_chat.resident"
NAME = "sdar_30b_a3b_chat"
CONFIG = "configs/" + NAME
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SIZES = json.load(open(os.path.join(ROOT, "chipbench", CONFIG,
                                    "config.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FAMILIES = {"blockdiff_flash_fwd": 2, "blockdiff_flash_dq": 3,
            "blockdiff_flash_dkv": 4}
NEW = {"blockdiff_attention_time_pct": "sparse attention ops",
       "blockdiff_flash_fwd_roofline": "Pallas kernels",
       "blockdiff_flash_dq_roofline": "Pallas kernels",
       "blockdiff_flash_dkv_roofline": "Pallas kernels",
       "blockdiff_attention_pallas_calls": "sparse attention ops",
       "blockdiff_interior_tiles_pct": "sparse attention ops",
       "diffusion_masked_tokens_pct": "model blocks"}


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
    assert SIZES["published"] == {"num_hidden_layers": 48,
                                  "num_experts": 128, "vocab_size": 151936}
    assert [SIZES[k] for k in SIZES["reduced"]] == [4, 16, 18992]
    assert SIZES["deployment"]["chips_sharing_a_layer"] * 16 == 128
    assert SIZES["vocab_size"] * 8 == SIZES["published"]["vocab_size"]
    widths = {"hidden_size": 2048, "num_attention_heads": 32,
              "num_key_value_heads": 4, "head_dim": 128,
              "moe_intermediate_size": 768, "intermediate_size": 6144,
              "num_experts_per_tok": 8, "rope_theta": 1000000,
              "rms_norm_eps": 1e-06, "norm_topk_prob": True,
              "tie_word_embeddings": False, "model_type": "sdar_moe",
              "decoder_sparse_step": 1, "mlp_only_layers": [],
              "rope_scaling": None, "sliding_window": None,
              "seq_len": 4096, "batch_per_chip": 1, "check_batch": 1}
    assert {k: SIZES[k] for k in widths} == widths
    assert SIZES["block_diffusion"] == {
        "block_length": 4, "mask_token_id": 18991, "noise_floor": 0.001}
    for said in ("block_length", "noise_level", "loss_weight",
                 "no_logit_shift", "rotary_positions", "mask_token",
                 "qk_norm", "rotary", "router", "router_aux_loss",
                 "optimizer", "init", "seq_len", "why"):
        assert SIZES["assumed"][said], said
    build = plugins.load(CONFIG, "build")
    cfg = build.config_of(SIZES)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads,
            cfg.num_routed, cfg.experts_held, cfg.experts_per_token,
            cfg.expert_width, cfg.window, cfg.index_topk, cfg.rope_theta,
            cfg.shared_width, cfg.dense_layers, cfg.attn_gate,
            cfg.router_score, cfg.norm_topk, cfg.qk_norm, cfg.mixers,
            cfg.tie_head, tuple(cfg.block_diffusion)) == (
        2048, 128, 32, 4, 128, 16, 8, 768, 0, 0, 1000000, 0, 0, False,
        "softmax", True, True, None, False, (4, 18991))
    with pytest.raises(ValueError, match="nothing else"):
        build.config_of({**SIZES, "mlp_only_layers": [0]})
    with pytest.raises(ValueError, match="nothing else"):
        build.config_of({**SIZES, "use_sliding_window": True})
    tiny = {**SIZES, **SIZES["tiny"]}
    assert tiny["num_experts"] < tiny["published"]["num_experts"]
    assert tuple(build.config_of(tiny).block_diffusion) == (4, 127)
    # a trainer counts the data's tokens: half the positions a step walks
    feed = build.make_feed(tiny, 2, np.random.RandomState(0))
    assert {k: v.shape for k, v in feed.items()} == {
        "tokens": (2, 64), "noised": (2, 64), "weights": (2, 64)}
    assert feed["tokens"].max() < 127 and (feed["noised"] == 127).any()
    again = build.make_feed(tiny, 2, np.random.RandomState(0))
    assert all(np.array_equal(feed[k], again[k]) for k in feed)


def test_parameters_as_run_add_up_to_the_count_the_equations_give():
    n = {name: math.prod(shape) for name, shape, _ in
         plugins.load(CONFIG, "reference").param_spec(SIZES)}
    assert sum(n.values()) == 456_346_624      # 5.70 GB resident at 12.5 B

    def under(p, keys):
        return sum(n[f"{p}_{k}"] for k in keys)

    for p in ("l0", "l1", "l2", "l3"):
        assert under(p, ("q_w", "k_w", "v_w", "o_w")) \
            == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
        assert under(p, ("q_norm", "k_norm")) == 256
        assert under(p, ("attn_norm", "moe_norm")) == 4_096
        assert n[f"{p}_router_w"] == 2048 * 128 == 262_144
        assert [n[f"{p}_{k}"] for k in ("w1", "w3", "w2")] \
            == [16 * 2048 * 768] * 3
        assert under(p, ("w1", "w3", "w2")) == 16 * 4_718_592
        assert sum(v for k, v in n.items() if k.startswith(p + "_")) \
            == 94_638_336
    assert n["tok_emb"] == n["lm_head_w"] == 38_895_616
    assert 4 * 94_638_336 + 2 * 38_895_616 + 2_048 == 456_346_624


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file_as_published():
    row = next(json.loads(l) for l in open(CATALOG)
               if '"name": "SDAR-30B-A3B-Chat"' in l)
    assert SIZES["source"] == row["source_url"] \
        == entry_of("configs", NAME)["source"]
    differs = sorted(k for k, v in row["config"].items()
                     if SIZES.get(k, "absent") != v)
    assert differs == sorted(SIZES["reduced"])
    assert {k: row["config"][k] for k in differs} == SIZES["published"]
    assert row["not_given"] == ["block length", "noise schedule"]


def test_stated_flops_by_hand():
    """Per document of 4,096 tokens, 8,192 positions, forward.  A position
    and layer: the four projections 37.75 MFLOP, the router 0.52, the
    expected live expert rows (8 x 16 / 128 = 1 a position) 9.44: 47.71,
    1.563 TFLOP over 8,192 x 4.  Attention: 16,793,600 pairs a head (a
    query of block b counts 4 (b + 1) keys, clean or noised) of the
    20,971,520 that 80 tiles hold: 275.1 GFLOP a layer.  The head reads the
    4,096 noised rows: 0.319 TFLOP.  2.98 TFLOP forward, 8.95 a step, 728
    MFLOP a data token."""
    flops = plugins.load(CONFIG, "flops")
    t, d = SIZES["seq_len"], 2048
    assert flops.rule_pairs(SIZES) == 2 * 16 * (1024 * 1025 // 2) \
        == 16_793_600 <= 80 * 512 * 512
    row = [2 * d * (2 * 4096 + 2 * 512), 2 * d * 128, 2 * 3 * d * 768]
    assert [round(x / 1e6, 2) for x in row] == [37.75, 0.52, 9.44]
    assert sum(row) / 1e6 == pytest.approx(47.71, abs=0.01)
    attention = 2 * 2 * 16_793_600 * 32 * 128
    head = 2 * t * d * 18992
    assert attention / 1e9 == pytest.approx(275.1, abs=0.1)
    assert head / 1e12 == pytest.approx(0.319, abs=0.001)
    assert flops.forward_flops(SIZES) == 4 * (2 * t * sum(row) + attention) \
        + head
    assert flops.forward_flops(SIZES) / 1e12 == pytest.approx(2.98, abs=0.01)
    assert flops.train_flops_per_sample(SIZES) / 1e12 == pytest.approx(
        8.95, abs=0.01)
    assert flops.forward_flops(SIZES) / t / 1e6 == pytest.approx(728, abs=1)
    assert 4 * attention / flops.forward_flops(SIZES) == pytest.approx(
        0.37, abs=0.005)
    # expected assignments an expert held: an eighth of the deployment's
    assert 2 * t * 8 // 128 == 512 and 8 * 2 * t * 8 // 128 == 4096


# -- the cell through the one command -------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    lines = run_tool("run.py", "--seed", "2147489999", "--seconds", "1",
                     "--trace", "1")
    return lines, json.loads(lines[-1])


def test_rehearsal_is_correct_and_prints_the_cut(rehearsal):
    lines, last = rehearsal
    assert lines[0] == (
        "cut: num_hidden_layers 4 of 48 (depth), num_experts 16 of 128 "
        "(experts_held), vocab_size 18992 of 151936 (vocabulary); one of 8 "
        "chips that share a layer: " + SIZES["deployment"]["how"])
    assert last["correct"] is True, lines
    assert last["failed"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["dispatches_per_step"]["value"] == 1
    assert last["metrics"]["ops_without_scope"]["value"] == 0
    assert last["metrics"]["moe_gauged_layers"]["value"] == 4


def test_rehearsal_says_which_rule_and_paths_ran(rehearsal):
    """Every lowering of the four layers took the block-rule kernels (one
    tile a copy at 64 tokens: a clean edge tile and two noised ones, none
    interior), nothing declined, softmax routers, the FLOPs stated."""
    lines, last = rehearsal
    said = next(l for l in lines if l.startswith("counters: ops."))

    def count(name):        # the labels hold commas: by the whole name
        return int(said[said.index(name + " = ") + len(name) + 3:]
                   .split(",", 1)[0])

    calls = count('ops.sparse_attention.calls{block="4",path="pallas",'
                  'seq="128",topk="0"}')
    assert calls > 0 and calls % 4 == 0
    assert said.count("ops.sparse_attention.calls{") == 1
    assert last["metrics"]["blockdiff_attention_pallas_calls"]["value"] \
        == calls
    for kernel in ("fwd", "dq", "dkv"):
        assert count('ops.sparse_attention.tiles{kernel="blockdiff_flash_'
                     f'{kernel}",kind="edge"}}') == 3 * calls
        assert count('ops.sparse_attention.tiles{kernel="blockdiff_flash_'
                     f'{kernel}",kind="interior"}}') == 0
    assert count('ops.moe.calls{held="4",path="ragged_dot",routed="8"}') \
        == 2 * calls
    assert "declined" not in said and "score=" not in said
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
def test_rule_family_counts_no_more_than_the_rule_needs(family):
    """From q [32, 8192, 128] two copies of 4,096: ``L (L + 1)`` pairs a
    head, what blocks of ONE token would need and so never more than the
    rule's own count at any block length (0.07% under it at 4), and never
    more than the 80 whole tiles the walk's table states; compute-bound on
    the v5e, so the least time is the FLOPs'."""
    mod = plugins.load("kernels", family)
    steps = 640 if family.endswith("dkv") else 80
    table = ((5, steps), "i32")
    needed = plugins.load(CONFIG, "flops").rule_pairs(SIZES)
    assert mod.KERNEL == family
    assert mod.rule_pairs((table, Q)) == (32, 4096 * 4097, 128)
    assert 0.999 * needed < 4096 * 4097 <= needed <= 80 * 512 * 512
    want = 2 * FAMILIES[family] * 32 * 4096 * 4097 * 128
    assert mod.flops((table, Q, KV, KV), (Q, ROW)) == want
    pk = peaks.peaks_for("TPU v5 lite")
    call = hlo.CustomCall(family, (table, Q, KV, KV), (Q, ROW))
    assert peaks.least_seconds(want, hlo.declared_bytes(call), pk) == \
        pytest.approx(want / 197e12)


def test_counter_readers_read_the_rule_and_find_nothing_without_it(capsys):
    from paddle_tpu import observe

    calls = plugins.load("layer_metrics", "blockdiff_attention_pallas_calls")
    tiles = plugins.load("layer_metrics", "blockdiff_interior_tiles_pct")
    observe.reset()
    reg = observe.registry()
    # another cell's program, or the parent's: nothing to read, no raise
    reg.inc("ops.sparse_attention.calls", 12, labels={
        "path": "pallas", "seq": "8192", "topk": "2048"})
    reg.inc("ops.sparse_attention.tiles", 136, labels={
        "kernel": "sparse_flash_fwd", "kind": "edge"})
    assert calls.value({}) is None and tiles.value({}) is None
    reg.inc("ops.sparse_attention.calls", 12, labels={
        "path": "pallas", "seq": "8192", "topk": "0", "block": "4"})
    reg.inc("ops.sparse_attention.calls", 2, labels={
        "path": "xla", "seq": "8192", "topk": "0", "block": "4"})
    for kernel in ("fwd", "dq", "dkv"):
        for kind, n in (("interior", 56), ("edge", 24)):
            reg.inc("ops.sparse_attention.tiles", 12 * n, labels={
                "kernel": f"blockdiff_flash_{kernel}", "kind": kind})
    assert calls.value({}) == 12
    assert tiles.value({}) == pytest.approx(70.0)
    said = capsys.readouterr().out
    assert 'ops.sparse_attention.tiles{kernel="blockdiff_flash_dkv",' \
        'kind="interior"} = 672' in said
    observe.reset()


def test_time_and_roofline_readers(capsys):
    run = {"time_by_label": {"kernel:blockdiff_flash_fwd": 1.0,
                             "kernel:blockdiff_flash_dq": 1.5,
                             "kernel:blockdiff_flash_dkv": 2.5,
                             "kernel:grouped_matmul": 3.0, "op:mul": 2.0},
           "labelled_busy_s": 10.0,
           "roofline": {"families": {
               "blockdiff_flash_fwd": {"pct": 61.0},
               "blockdiff_flash_dq": {"pct": 58.0},
               "blockdiff_flash_dkv": {"events": 3, "calls": 4}}}}

    def value(name):
        return plugins.load("layer_metrics", name).value(run)

    assert value("blockdiff_attention_time_pct") == pytest.approx(50.0)
    assert value("blockdiff_flash_fwd_roofline") == 61.0
    assert value("blockdiff_flash_dq_roofline") == 58.0
    assert value("blockdiff_flash_dkv_roofline") is None    # withheld
    other = {"time_by_label": {"kernel:sparse_flash_fwd": 1.0},
             "labelled_busy_s": 2.0, "roofline": {"families": {}}}
    for name in NEW:
        if name.startswith("blockdiff_") and "calls" not in name \
                and "tiles" not in name:
            reader = plugins.load("layer_metrics", name)
            assert reader.value(other) is None and reader.value({}) is None


def test_the_masked_share_is_read_from_the_windows_step_gauges(monkeypatch,
                                                              capsys):
    """Median over the steps called inside the window of live over rows;
    nothing where the program publishes no such gauge (the parent, another
    cell) or has no reader of gauges at all."""
    reader = plugins.load("layer_metrics", "diffusion_masked_tokens_pct")
    live = 'ops.weighted_mean.live_rows{scope="head"}'
    rows = 'ops.weighted_mean.rows{scope="head"}'
    ring = [(0, "a", 0.5, {live: 1.0, rows: 4096.0}),      # before
            (1, "b", 1.5, {live: 2048.0, rows: 4096.0}),
            (2, "c", 2.5, {live: 2076.0, rows: 4096.0,
                           'ops.moe.rows{scope="layer0.ffn"}': 65536.0}),
            (3, "d", 3.5, {live: 1024.0, rows: 4096.0}),
            (4, "e", 9.0, {live: 4096.0, rows: 4096.0})]   # after
    monkeypatch.setattr(step_gauges, "entries", lambda since=None: ring)
    run = {"stamps": [1.0, 2.0, 3.0, 4.0]}
    assert reader.value(run) == pytest.approx(50.0)
    assert "loss-bearing tokens over 3 steps" in capsys.readouterr().out
    monkeypatch.setattr(step_gauges, "entries", lambda since=None: [
        (1, "b", 1.5, {'ops.moe.rows{scope="layer0.ffn"}': 65536.0})])
    assert reader.value(run) is None
    monkeypatch.setattr(step_gauges, "entries", lambda since=None: None)
    assert reader.value(run) is None


def test_every_metric_of_the_cell_has_its_reader_and_lists_the_cell():
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g]
              if "workloads" not in m or CELL in m["workloads"]}
    shared = {"tokens_per_s_per_chip", "dispatches_per_step",
              "pallas_roofline_pct", "xent_fwd_roofline",
              "xent_bwd_roofline", "adam_roofline", "moe_time_pct",
              "moe_live_rows_pct", "moe_live_rows_range_pct",
              "moe_gauged_layers", "sparse_attention_time_pct",
              "grouped_matmul_roofline", "grouped_matmul_t_roofline",
              "grouped_matmul_time_pct", "mixer_time_pct", "ffn_time_pct",
              "head_time_pct", "head_mfu_pct", "scoped_time_pct",
              "ops_without_scope", "adopted_time_pct", "mfu_pct",
              "step_ms_p95", "peak_hbm_gib", "setup_s", "device_idle_pct"}
    assert set(NEW) | shared <= listed
    assert not {"index_select_time_pct", "sparse_attention_pallas_calls",
                "images_per_s_per_chip", "flash_fwd_roofline",
                "sparse_flash_fwd_roofline", "window_flash_fwd_roofline",
                "window_attention_pallas_calls", "latent_mixer_blocks",
                "mtp_time_pct", "short_conv_calls", "delta_mixer_blocks",
                "ssm_mixer_blocks"} & listed
    for name, layer in NEW.items():
        m = entry_of("per_layer", name)
        assert m["workloads"] == [CELL] and m["layer"] == layer
        assert m["moves"] == "step_ms_p95"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
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
    assert BENCH["workloads"][-1]["name"] == CELL and \
        BENCH["configs"][-1]["name"] == NAME and len(BENCH["workloads"]) == 11
    assert [m["name"] for m in BENCH["per_layer"][-len(NEW):]] == list(NEW)


# -- the record of the lowered step ------------------------------------------

def test_the_step_lowers_to_the_text_on_record():
    """As ``test_decoder_steps_lower_alike.py`` holds the seven decoders it
    names: the step at ``tiny`` under the harness's bf16 AMP, the XLA paths,
    against the newest ``chipbench/testdata/lowered_text/<name>.pr<N>.json``.
    A PR that changes this lowering on purpose adds a record of its own."""
    import paddle_tpu.fluid as fluid

    found = {int(re.search(r"\.pr(\d+)\.json$", p).group(1)): p
             for p in glob.glob(os.path.join(
                 ROOT, "chipbench", "testdata", "lowered_text",
                 NAME + ".pr*.json"))}
    want = json.load(open(found[max(found)]))
    sizes = {**SIZES, **SIZES["tiny"]}
    build = plugins.load(CONFIG, "build")
    fluid.amp.enable("bfloat16", keep_activations=True)
    try:
        built = build.build(fluid, sizes)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        feed = build.make_feed(sizes, 1, np.random.RandomState(0))
        text = exe.lower_step(fluid.default_main_program(), feed,
                              [built["loss"]]).as_text()
    finally:
        fluid.amp.disable()
    got = {"characters": len(text),
           "sha256": hashlib.sha256(text.encode()).hexdigest()}
    assert got == {k: want[k] for k in got}, (got, want)
