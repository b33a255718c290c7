"""``nemotron_twotower_30b_a3b.resident``: its configuration against the
catalog's row and the cut's rules, its parameters and stated FLOPs by hand,
the cell's rehearsal on the CPU (``correct``, with the new count metrics)
and the float8 control refused, and the readers of its per-layer metrics.
CPU only, and nothing here counts the benchmark's cells, configurations or
metrics: later PRs append theirs.  The program against ``reference.py``
gradient by gradient, and the 16 shares of its expert layer, are in
``tests/test_decoder_lm_ssm.py``; the scan in ``tests/test_ssd_scan.py``."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import check, cuts, plugins  # noqa: E402

CELL = "nemotron_twotower_30b_a3b.resident"
NAME = "nemotron_twotower_30b_a3b"
CONFIG = "configs/" + NAME
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SIZES = json.load(open(os.path.join(ROOT, "chipbench", CONFIG,
                                    "config.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("ssm_scan_time_pct", "ssm_mixer_time_pct", "ssm_mixer_blocks",
       "ssm_scan_mfu_pct", "moe_ungated_layers")


def entry_of(group, name):
    """Found by name, never by place: later PRs append theirs."""
    found = [e for e in BENCH[group] if e["name"] == name]
    assert len(found) == 1, (group, name)
    return found[0]


# -- the configuration ------------------------------------------------------

def test_every_width_is_the_published_one_and_the_cut_is_within_the_floors():
    entry = entry_of("configs", NAME)
    assert cuts.problems(SIZES, entry) == []
    assert SIZES["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert SIZES["published"] == {"num_hidden_layers": 52,
                                  "n_routed_experts": 128,
                                  "vocab_size": 131072}
    assert [SIZES[k] for k in SIZES["reduced"]] == [7, 8, 16384]
    assert cuts.line(SIZES).startswith(
        "cut: num_hidden_layers 7 of 52 (depth), n_routed_experts 8 of 128 "
        "(experts_held), vocab_size 16384 of 131072 (vocabulary); one of 16 "
        "chips")
    assert SIZES["deployment"]["chips_sharing_a_layer"] * 8 == 128
    assert SIZES["vocab_size"] * 8 == SIZES["published"]["vocab_size"]
    assert (SIZES["layer_offset"], SIZES["expert_offset"]) == (6, 0)
    widths = {"hidden_size": 2688, "mamba_num_heads": 64,
              "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
              "conv_kernel": 4, "chunk_size": 128, "use_conv_bias": True,
              "num_attention_heads": 32, "num_key_value_heads": 2,
              "head_dim": 128, "moe_intermediate_size": 1856,
              "moe_shared_expert_intermediate_size": 3712,
              "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
              "layer_norm_epsilon": 1e-05, "mlp_hidden_act": "relu2",
              "model_type": "nemotron_h", "seq_len": 8192,
              "batch_per_chip": 1, "check_batch": 1}
    assert {k: SIZES[k] for k in widths} == widths
    assert SIZES["seq_len"] == SIZES["assumed"]["seq_len"]
    build = plugins.load(CONFIG, "build")
    cfg = build.config_of(SIZES)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads,
            cfg.num_routed, cfg.experts_held, cfg.experts_per_token,
            cfg.expert_width, cfg.shared_width, cfg.dense_layers,
            cfg.router_score, cfg.route_scale, cfg.route_norm_eps,
            cfg.route_bias_coeff, cfg.norm_topk, cfg.rope_global,
            cfg.qk_norm, cfg.expert_gate, cfg.attn_gate, cfg.residual,
            cfg.mtp_depth, cfg.tie_head, cfg.rms_eps, cfg.window) == (
        2688, 128, 32, 2, 128, 8, 6, 1856, 3712, 0, "sigmoid", 2.5, 1e-20,
        0.001, True, False, False, False, False, "sequential", 0, False,
        1e-05, 0)
    assert tuple(cfg.ssm) == (64, 64, 8, 128, 4, 128, True)
    # published layers 6-12: EMEMEM*
    assert [(cfg.layer_mixer(i), cfg.layer_parts(i)) for i in range(7)] == [
        (None, "ffn"), ("ssm", "mixer")] * 3 + [("attention", "mixer")]
    whole = build.layers_of(SIZES)
    assert len(whole) == 52
    assert [sum(m == kind for m, _ in whole)
            for kind in ("ssm", None, "attention")] == [23, 23, 6]
    # the unit that repeats: 7 layers ending in attention, four in a row
    pattern = SIZES["hybrid_override_pattern"]
    assert [len(u) + 1 for u in pattern.split("*")[:-1]] == [6, 7, 7, 7, 7, 9]
    first = build.config_of({**SIZES, "layer_offset": 0})
    assert [first.layer_mixer(i) for i in range(7)] == [
        "ssm", None, "ssm", None, "ssm", "attention", None]
    with pytest.raises(ValueError, match="nothing else"):
        build.config_of({**SIZES, "mlp_hidden_act": "silu"})
    with pytest.raises(ValueError, match="for each published layer"):
        build.config_of({**SIZES, "hybrid_override_pattern": "ME-"})
    # what is left out is said, not guessed
    assert "NOT BUILT" in SIZES["assumed"]["second_tower"]
    for key in ("no_rotary", "projection_order", "bias_rule", "init",
                "optimizer", "inner_width"):
        assert SIZES["assumed"][key]


def test_parameters_by_hand():
    """ISSUE 58's count, from the sizes: a state-space layer 38,744,896
    (projection in 27,697,152, out 11,010,048, filter 30,720, the rest
    6,976), the attention layer 23,399,040, a routed layer 100,125,312
    (router 344,064, shared 19,955,712, 8 experts of 9,977,856), embedding
    + head + final norm 88,083,072."""
    import math

    ref = plugins.load(CONFIG, "reference")
    spec = ref.param_spec(SIZES)
    n = {name: math.prod(shape) for name, shape, _ in spec}
    assert len(n) == len(spec) == 3 * 9 + 3 * 6 + 5 + 3

    def layer(i):
        return sum(v for k, v in n.items() if k.startswith(f"l{i}_"))

    assert n["l1_ssm_in_w"] == 2688 * 10304 == 27_697_152
    assert n["l1_o_w"] == 4096 * 2688 == 11_010_048
    assert n["l1_conv_w"] + n["l1_conv_b"] == 6144 * 4 + 6144 == 30_720
    assert layer(1) - 27_697_152 - 11_010_048 - 30_720 == 6_976
    assert layer(1) == layer(3) == layer(5) == 38_744_896
    assert layer(6) == 23_399_040
    assert (n["l0_router_w"], n["l0_shared_w1"] + n["l0_shared_w2"],
            n["l0_w1"] + n["l0_w2"]) == (344_064, 19_955_712, 8 * 9_977_856)
    assert layer(0) == layer(2) == layer(4) == 100_125_312
    assert n["tok_emb"] + n["lm_head_w"] + n["final_norm"] == 88_083_072
    assert sum(n.values()) == 528_092_736
    # resident at 12 B a parameter: 5.90 GiB, 37% of the chip's 15.75
    assert 12 * 528_092_736 / 2 ** 30 == pytest.approx(5.90, abs=0.005)


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file_as_published():
    row = next(json.loads(line) for line in open(CATALOG)
               if '"name": "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16"'
               in line)
    assert SIZES["source"] == row["source_url"] \
        == entry_of("configs", NAME)["source"]
    differs = sorted(k for k, v in row["config"].items()
                     if SIZES.get(k, "absent") != v)
    assert differs == sorted(SIZES["reduced"])
    assert {k: row["config"][k] for k in differs} == SIZES["published"]


def test_stated_flops_by_hand():
    """Per token, forward, in MFLOP.  A state-space mixer: projection in
    and out 77.4, the scan as the recurrence states it 3.1; attention's
    four products 46.8, its causal pairs 16,384 FLOP a pair over the 32
    heads at 128 + 128 wide ((T + 1) / 2 pairs a token); a routed layer:
    router 0.7, the shared expert 39.9, the experts held 7.5 (three eighths
    of an assignment a token, two matrices); the head 88.1."""
    from paddle_tpu.ops import ssd

    flops = plugins.load(CONFIG, "flops")
    t, d = SIZES["seq_len"], 2688
    p = flops.parts(SIZES)
    assert flops.layer_letters(SIZES) == "EMEMEM*"
    assert p["ssm_products"] == t * (d * 10304 + 4096 * d)
    assert p["ssm_scan"] == t * 64 * 3 * 64 * 128
    assert 2 * p["ssm_scan"] == ssd.scan_flops(t, 64, 64, 128)
    assert p["attn_products"] == t * (d * (4096 + 2 * 256) + 4096 * d)
    assert p["attn_pairs"] == flops.pairs(t) * 32 * (128 + 128)
    assert (p["router"], p["shared"]) == (t * d * 128, 2 * t * d * 3712)
    assert p["experts"] == (t * 6 * 8 // 128) * 2 * d * 1856
    assert p["head"] == t * d * 16384
    per_token = {k: round(2 * v / t / 1e6, 1) for k, v in p.items()
                 if k != "attn_pairs"}
    assert per_token == {"ssm_products": 77.4, "ssm_scan": 3.1,
                         "attn_products": 46.8, "router": 0.7,
                         "shared": 39.9, "experts": 7.5, "head": 88.1}
    assert flops.scan_flops(SIZES) == 3 * 2 * p["ssm_scan"]
    assert flops.forward_flops(SIZES) == 2 * (
        3 * (p["ssm_products"] + p["ssm_scan"]) + p["attn_products"]
        + p["attn_pairs"] + 3 * (p["router"] + p["shared"] + p["experts"])
        + p["head"])
    assert flops.train_flops_per_sample(SIZES) \
        == 3 * flops.forward_flops(SIZES)
    # 588 MFLOP a token forward, 14.4 TFLOP a step (ISSUE 58 rounds its own
    # parts up to "about 594" and 14.6)
    assert flops.forward_flops(SIZES) / t / 1e6 == pytest.approx(587.9,
                                                                 abs=0.1)
    assert flops.train_flops_per_sample(SIZES) / 1e12 \
        == pytest.approx(14.45, abs=0.01)
    # what the padded grouped products WALK against what is live
    assert (t * 6, t * 6 * 8 // 128) == (49_152, 3_072)


# -- the cell's rehearsal, and the control ---------------------------------

def run_harness(script, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", script),
         "--workload", CELL, "--rehearse", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout.strip().splitlines()


@pytest.fixture(scope="module")
def rehearsal():
    """``run.py --rehearse --trace 1``: the whole run of the cell at the
    ``tiny`` size on the CPU, Pallas interpreted."""
    lines = run_harness("run.py", "--seed", "2147489999", "--trace", "1")
    return lines, json.loads(lines[-1])


def test_the_rehearsal_is_correct_and_counts_what_three_blocks_lower(
        rehearsal):
    lines, out = rehearsal
    assert lines[0] == cuts.line(SIZES)
    limits = SIZES["tiny"]["limits"]
    assert set(limits) == set(SIZES["limits"]) == set(check.KEYS)
    assert out["correct"] is True and out["failed"] == 0
    compared = {k: v["value"] for k, v in out["compared"].items()}
    assert check.decide(compared, limits) is True
    assert 0 < compared["grad_rel"] < limits["grad_rel"]
    value = {k: v["value"] for k, v in out["metrics"].items()}
    # two programs built (the step and the compared step), three blocks
    # each; three programs lowered (both and the traced step), in each
    # three layers traced by the op and by its grad op
    assert value["ssm_mixer_blocks"] == 2 * 3
    assert value["moe_ungated_layers"] == 3 * 3 * 2
    assert value["moe_gauged_layers"] == 3
    assert value["short_conv_calls"] == 3 * 3
    assert value["ops_without_scope"] == 0
    assert value["compiles_in_window"] == 0
    assert value["dispatches_per_step"] == 1
    said = "\n".join(lines)
    for part in ('models.decoder.ssm{conv_bias="1",groups="2",heads="8",'
                 'state="16"} = 6',
                 'ops.ssd.scans{chunk="16",dim="8",groups="2",heads="8",'
                 'path="xla",state="16"} = 9',
                 'ops.ssd.grad_scans{chunk="16",path="by_hand"} = 9',
                 'ops.sparse_attention.calls{path="pallas",seq="64",'
                 'topk="0"} = 3'):
        assert part in said, part
    assert all(SIZES["limits_why"][k] for k in check.KEYS)


def test_the_fp8_control_is_called_not_correct():
    lines = run_harness("check_seeds.py", "--control-seeds", "3000000019")
    control = next(json.loads(line) for line in lines
                   if line.startswith("{"))
    limits = SIZES["tiny"]["limits"]
    assert control["kind"] == "control_fp8"
    assert check.decide(control, limits) is False, control
    assert control["grad_rel"] > 2 * limits["grad_rel"]


# -- the readers of its per-layer metrics ----------------------------------

def scoped_run(by, labels=None):
    """A traced run as the readers see it: time by (op type, path), and by
    the first level's label."""
    from chipbench import scope_time

    return {"scope_time": scope_time.Table(by, {}),
            "labelled_busy_s": sum(by.values()), "workload": CELL,
            "time_by_label": labels or {}, "device_kind": "TPU v5 lite",
            "samples_per_step": 1, "steps_traced": 4, "chips": 1}


def test_time_shares_read_the_ssm_path_and_the_scans_label():
    run = scoped_run({
        ("mul", "layer1.mixer"): 4.0,
        ("ssd_scan_grad", "layer1.mixer.ssm"): 3.0,
        ("ssd_scan", "layer3.mixer.ssm"): 1.0,
        ("short_conv", "layer3.mixer.ssm"): 1.0,
        ("sparse_attention", "layer6.mixer"): 1.0,
        ("moe_experts", "layer0.ffn"): 6.0, ("mul", "head"): 4.0},
        {"op:ssd_scan_grad": 3.0, "op:ssd_scan": 1.0, "op:mul": 8.0})
    value = {n: plugins.load("layer_metrics", n).value(run) for n in (
        "ssm_scan_time_pct", "ssm_mixer_time_pct", "mixer_time_pct",
        "ffn_time_pct")}
    assert value == {"ssm_scan_time_pct": pytest.approx(20.0),
                     "ssm_mixer_time_pct": pytest.approx(25.0),
                     "mixer_time_pct": pytest.approx(50.0),
                     "ffn_time_pct": pytest.approx(30.0)}
    flops = plugins.load(CONFIG, "flops")
    reader = plugins.load("layer_metrics", "ssm_scan_mfu_pct")
    assert reader.needed(CELL) == 3 * flops.scan_flops(SIZES) \
        == 3 * 2 * 3 * SIZES["seq_len"] * 64 * 3 * 64 * 128
    # needed FLOPs x steps over the op's seconds and the v5e's 197 TFLOP/s
    assert reader.value(run) == pytest.approx(
        100.0 * reader.needed(CELL) * 4 / (4.0 * 197e12))
    assert reader.needed("kimi_linear_48b_a3b.resident") is None
    assert reader.needed("no_such_cell") is None


def test_counter_readers_sum_what_the_program_counted(capsys):
    from paddle_tpu import observe

    observe.reset()
    reg = observe.registry()
    for _ in range(3):
        reg.inc("models.decoder.blocks", labels={
            "mixer": "ssm", "residual": "sequential", "where": "trunk",
            "parts": "mixer"})
        reg.inc("models.decoder.blocks", labels={
            "mixer": "none", "residual": "sequential", "where": "trunk",
            "parts": "ffn"})
        reg.inc("ops.moe.ungated_layers", 2)
    reg.inc("ops.ssd.scans", labels={"heads": "64", "dim": "64",
                                     "groups": "8", "state": "128",
                                     "chunk": "128", "path": "xla"})
    assert plugins.load("layer_metrics", "ssm_mixer_blocks").value({}) == 3
    said = capsys.readouterr().out
    assert said.startswith("counters: models.decoder.blocks{")
    assert 'ops.ssd.scans{chunk="128",dim="64",groups="8",heads="64",' \
        'path="xla",state="128"} = 1' in said
    assert plugins.load("layer_metrics", "moe_ungated_layers").value({}) == 6
    assert plugins.load("layer_metrics", "delta_mixer_blocks").value({}) \
        is None
    observe.reset()


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_the_new_paths(name):
    """The parent's traced run, or another cell's: gated experts, a delta
    mixer and no scan; the reader returns nothing and does not raise."""
    from paddle_tpu import observe

    observe.reset()
    observe.registry().inc("models.decoder.blocks", labels={
        "mixer": "delta", "residual": "sequential", "where": "trunk"})
    observe.registry().inc("ops.moe.calls", labels={"held": "8"})
    reader = plugins.load("layer_metrics", name)
    run = scoped_run({("mul", "layer0.mixer"): 2.0,
                      ("gated_delta_rule", "layer0.mixer.delta"): 1.0,
                      ("mul", "head"): 1.0},
                     {"op:mul": 3.0, "op:gated_delta_rule": 1.0})
    assert reader.value({**run, "workload": "qwen3_next_80b_a3b.resident"}) \
        is None
    assert reader.value({"scope_time": None, "workload": "x"}) is None
    assert reader.value({"steps": 3}) is None
    observe.reset()


def test_every_metric_the_cell_lists_has_its_reader():
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g]
              if "workloads" not in m or CELL in m["workloads"]}
    shared = {"tokens_per_s_per_chip", "dispatches_per_step",
              "pallas_roofline_pct", "xent_fwd_roofline",
              "xent_bwd_roofline", "adam_roofline", "moe_time_pct",
              "sparse_attention_time_pct", "sparse_flash_fwd_roofline",
              "sparse_flash_dq_roofline", "sparse_flash_dkv_roofline",
              "grouped_matmul_roofline", "grouped_matmul_t_roofline",
              "grouped_matmul_time_pct", "moe_live_rows_pct",
              "moe_live_rows_range_pct", "moe_gauged_layers",
              "short_conv_time_pct", "short_conv_calls", "mixer_time_pct",
              "ffn_time_pct", "head_time_pct", "head_mfu_pct",
              "scoped_time_pct", "ops_without_scope", "mfu_pct",
              "step_ms_p95", "peak_hbm_gib", "setup_s"}
    assert set(NEW) | shared <= listed
    # nothing to read here: no indexer, window, latent or delta layer, no
    # multi-token module, no images
    assert not {"index_select_time_pct", "window_attention_time_pct",
                "window_flash_fwd_roofline", "images_per_s_per_chip",
                "flash_fwd_roofline", "mtp_time_pct", "latent_mixer_blocks",
                "latent_proj_time_pct", "delta_rule_time_pct",
                "delta_mixer_blocks", "delta_rule_mfu_pct",
                "sparse_attention_pallas_calls",
                "global_mixer_time_pct", "yarn_global_layers"} & listed
    for name in NEW:
        m = entry_of("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "step_ms_p95"
        assert m["layer"] == ("expert layer" if name.startswith("moe")
                              else "token mixers")
    for name in listed:
        kind = "metrics" if any(m["name"] == name
                                for m in BENCH["end_to_end"]) \
            else "layer_metrics"
        assert plugins.load(kind, name) is not None, name
    cell = entry_of("workloads", CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "resident" \
        and cell["config"] == NAME and len(cell["why"]) <= 200
    assert f"{SIZES['seq_len']:,} tokens" in cell["why"]
    entry = entry_of("configs", NAME)
    assert len(entry["why"]) <= 200
    assert entry["file"] == f"chipbench/{CONFIG}/config.json"
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
