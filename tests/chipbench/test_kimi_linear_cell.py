"""``kimi_linear_48b_a3b.resident``: its configuration against the catalog's
row and the cut's rules, its parameters and stated FLOPs by hand, the
program against ``reference.py`` through the harness's comparison at the
``tiny`` size with the float8 control refused, the shares of its expert
layer over all 32 chips, and the readers of its per-layer metrics.  CPU
only, and nothing here counts the benchmark's cells, configurations or
metrics: later PRs append theirs."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import check, cuts, plugins  # noqa: E402

CELL = "kimi_linear_48b_a3b.resident"
NAME = "kimi_linear_48b_a3b"
CONFIG = "configs/" + NAME
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SIZES = json.load(open(os.path.join(ROOT, "chipbench", CONFIG,
                                    "config.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("delta_gate_time_pct", "delta_channel_decay_blocks")
RUNGS = (4096, 3072, 2048)


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
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert SIZES["published"] == {"num_hidden_layers": 27,
                                  "num_experts": 256, "vocab_size": 163840}
    assert [SIZES[k] for k in SIZES["reduced"]] == [5, 8, 20480]
    assert SIZES["deployment"]["chips_sharing_a_layer"] * 8 == 256
    assert SIZES["vocab_size"] * 8 == SIZES["published"]["vocab_size"]
    assert (SIZES["layer_offset"], SIZES["expert_offset"]) == (0, 0)
    widths = {"hidden_size": 2304, "num_attention_heads": 32,
              "num_key_value_heads": 32, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "intermediate_size": 9216,
              "moe_intermediate_size": 1024, "num_experts_per_token": 8,
              "num_shared_experts": 1, "first_k_dense_replace": 1,
              "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-05,
              "mla_use_nope": True, "moe_renormalize": True,
              "moe_router_activation_func": "sigmoid",
              "tie_word_embeddings": False, "model_type": "kimi_linear",
              "batch_per_chip": 1, "check_batch": 1, "delta_chunk": 64}
    assert {k: SIZES[k] for k in widths} == widths
    linear = SIZES["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
    # one rung of ISSUE 52's rule stands, and the file says which reading
    # decided
    assert SIZES["seq_len"] == SIZES["assumed"]["seq_len"]
    assert SIZES["seq_len"] in RUNGS and SIZES["seq_len"] % 512 == 0
    assert "peak_hbm_gib" in SIZES["assumed"]["why"]
    build = plugins.load(CONFIG, "build")
    cfg = build.config_of(SIZES)
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads,
            cfg.num_routed, cfg.experts_held, cfg.experts_per_token,
            cfg.expert_width, cfg.shared_width, cfg.dense_layers,
            cfg.dense_width, cfg.router_score, cfg.route_scale,
            cfg.route_norm_eps, cfg.route_bias_coeff, cfg.norm_topk,
            cfg.attn_gate, cfg.residual, cfg.mtp_depth, cfg.tie_head,
            cfg.rms_eps) == (
        2304, 192, 32, 32, 256, 8, 8, 1024, 1024, 1, 9216, "sigmoid", 2.446,
        1e-20, 0.001, True, False, "sequential", 0, False, 1e-05)
    assert tuple(cfg.latent) == (512, 128, 64, 128, None, False, 0.0, False,
                                 False)
    assert tuple(cfg.delta) == (32, 32, 128, 128, 4, 64)
    assert tuple(cfg.delta_gates) == (128, "sigmoid", 128)
    # published layers 1-5: the dense one, then delta, delta, latent, delta
    assert [cfg.layer_mixer(i) for i in range(5)] == [
        "delta", "delta", "delta", "latent", "delta"]
    assert [cfg.layer_is_dense(i) for i in range(5)] == [True] + [False] * 4
    mixers = build.mixers_of(SIZES)
    assert (len(mixers), mixers.count("latent"), mixers[-1]) == (
        27, 7, "latent")
    later = build.config_of({**SIZES, "layer_offset": 22})
    assert [later.layer_mixer(i) for i in range(5)] == [
        "delta", "latent", "delta", "delta", "latent"]
    with pytest.raises(ValueError, match="nothing else"):
        build.config_of({**SIZES, "mla_use_nope": False})
    with pytest.raises(ValueError, match="divide the layers"):
        build.config_of({**SIZES, "linear_attn_config": {
            **linear, "kda_layers": linear["kda_layers"][1:]}})
    tiny = {**SIZES, **SIZES["tiny"]}
    assert tiny["num_experts"] < tiny["published"]["num_experts"]
    assert tiny["seq_len"] > tiny["delta_chunk"]
    small = build.config_of(tiny)
    assert small.latent.value != small.head_dim     # as at the full size
    assert small.delta.chunk == 16


def test_the_record_of_a_run_starts_with_the_cut():
    dep = SIZES["deployment"]
    assert cuts.line(SIZES) == (
        "cut: num_hidden_layers 5 of 27 (depth), num_experts 8 of 256 "
        "(experts_held), vocab_size 20480 of 163840 (vocabulary); one of 32 "
        "chips that share a layer: " + dep["how"])


def test_parameters_as_run_add_up_to_the_count_the_equations_give():
    n = {name: math.prod(shape) for name, shape, _ in
         plugins.load(CONFIG, "reference").param_spec(SIZES)}
    # 7.23 GB resident at 12 B each
    assert sum(n.values()) == 602_433_408

    def under(p, keys):
        return sum(n[f"{p}_{k}"] for k in keys)

    delta = ("attn_norm", "qkv_w", "b_w", "g1_w", "g2_w", "conv_w", "f1_w",
             "f2_w", "dt_bias", "a_log", "delta_norm", "o_w")
    for p in ("l0", "l1", "l2", "l4"):
        assert under(p, delta) == 39_516_576
        assert [n[f"{p}_{k}"] for k in ("qkv_w", "b_w", "f1_w", "f2_w",
                                        "conv_w", "dt_bias", "a_log",
                                        "o_w")] == [
            28_311_552, 73_728, 294_912, 524_288, 49_152, 4_096, 32,
            9_437_184]
        assert (n[f"{p}_g1_w"], n[f"{p}_g2_w"]) == (294_912, 524_288)
    assert under("l3", ("attn_norm", "q_w", "kva_w", "kv_norm", "kvb_w",
                        "o_w")) == 29_117_184
    assert [n[f"l3_{k}"] for k in ("q_w", "kva_w", "kvb_w", "o_w")] == [
        2304 * 32 * 192, 2304 * 576, 512 * 32 * 256, 32 * 128 * 2304]
    assert under("l0", ("mlp_norm", "mlp_w1", "mlp_w3", "mlp_w2")) \
        == 63_703_296
    for p in ("l1", "l2", "l3", "l4"):
        assert under(p, ("moe_norm", "shared_w1", "shared_w3", "shared_w2",
                         "router_w")) == 7_670_016
        assert under(p, ("w1", "w3", "w2")) == 8 * 7_077_888
    assert n["tok_emb"] == n["lm_head_w"] == 47_185_920
    assert 4 * 39_516_576 + 29_117_184 + 63_703_296 \
        + 4 * (7_670_016 + 56_623_104) + 2 * 47_185_920 + 2304 \
        == 602_433_408


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file_as_published():
    row = next(json.loads(line) for line in open(CATALOG)
               if '"name": "Kimi-Linear-48B-A3B-Instruct"' in line)
    assert SIZES["source"] == row["source_url"] \
        == entry_of("configs", NAME)["source"]
    differs = sorted(k for k, v in row["config"].items()
                     if SIZES.get(k, "absent") != v)
    assert differs == sorted(SIZES["reduced"])
    assert {k: row["config"][k] for k in differs} == SIZES["published"]
    # the nested group whole
    assert SIZES["linear_attn_config"] == row["config"]["linear_attn_config"]


def test_stated_flops_by_hand():
    """Per token, forward, in MFLOP.  A delta mixer: [q | k | v] 56.6, the
    step's column 0.1, two pairs through rank 128 3.3, the output 18.9; the
    rule as the recurrence states it 3.1; the latent layer's four products
    58.2, its causal pairs 20,480 FLOP a pair over the 32 heads at 192 + 128
    wide ((T + 1) / 2 pairs a token); the dense layer 127.4; a routed layer:
    router 1.2, the
    shared expert 14.2, the experts held 3.5 (a quarter of an assignment a
    token); the head 94.4."""
    flops = plugins.load(CONFIG, "flops")
    t, d = SIZES["seq_len"], 2304
    p = flops.parts(SIZES)
    assert flops.layer_kinds(SIZES) == [("delta", True), ("delta", False),
                                        ("delta", False), ("latent", False),
                                        ("delta", False)]
    assert p["delta_products"] == t * (d * (3 * 4096 + 32)
                                       + 2 * (d * 128 + 128 * 4096)
                                       + 4096 * d)
    assert p["delta_rule"] == t * 32 * 3 * 128 * 128
    assert p["latent_products"] == t * (d * 32 * 192 + d * 576
                                        + 512 * 32 * 256 + 32 * 128 * d)
    assert p["latent_pairs"] == flops.pairs(t) * 32 * (192 + 128)
    assert p["dense"] == 3 * t * d * 9216
    assert (p["router"], p["shared"]) == (t * d * 256, 3 * t * d * 1024)
    assert p["experts"] == (t * 8 * 8 // 256) * 3 * d * 1024
    assert p["head"] == t * d * 20480
    per_token = {k: round(2 * v / t / 1e6, 1) for k, v in p.items()
                 if k != "latent_pairs"}
    assert per_token == {"delta_products": 78.9, "delta_rule": 3.1,
                         "latent_products": 58.2, "dense": 127.4,
                         "router": 1.2, "shared": 14.2, "experts": 3.5,
                         "head": 94.4}
    assert flops.rule_flops(SIZES) == 4 * 2 * p["delta_rule"]
    assert flops.forward_flops(SIZES) == 2 * (
        4 * (p["delta_products"] + p["delta_rule"]) + p["latent_products"]
        + p["latent_pairs"] + p["dense"]
        + 4 * (p["router"] + p["shared"] + p["experts"]) + p["head"])
    assert flops.train_flops_per_sample(SIZES) \
        == 3 * flops.forward_flops(SIZES)
    at_4k = flops.train_flops_per_sample({**SIZES, "seq_len": 4096})
    assert at_4k / 1e12 == pytest.approx(8.92, abs=0.01)    # ISSUE 52's 8.9
    # what the padded grouped products WALK against what is live
    assert (t * 8, t * 8 * 8 // 256) == (8 * t, t // 4)


# -- the program against the reference, through the harness ----------------

@pytest.fixture(scope="module")
def readings():
    """``check_seeds.py`` at the ``tiny`` size: one step of the program
    (bf16 AMP, through ``Executor.run`` and ``check.program``) and the
    float8 control (``check.control``), each against ``reference.py``."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "check_seeds.py"),
         "--workload", CELL, "--rehearse", "--seeds", "2147489999",
         "--control-seeds", "3000000019"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    rows = [json.loads(line) for line in lines if line.startswith("{")]
    return lines[0], {r["kind"]: r for r in rows}


def test_the_program_is_correct_at_the_tiny_size_and_the_control_is_not(
        readings):
    first, rows = readings
    assert first == cuts.line(SIZES)
    limits = SIZES["tiny"]["limits"]
    assert set(limits) == set(SIZES["limits"]) == set(check.KEYS)
    program, control = rows["program"], rows["control_fp8"]
    assert check.decide(program, limits) is True, program
    assert check.decide(control, limits) is False, control
    assert control["grad_rel"] > limits["grad_rel"] \
        > program["grad_rel"] > 0
    assert control["grad_rel"] > 3 * program["grad_rel"]
    assert all(SIZES["limits_why"][k] for k in check.KEYS)


def test_the_32_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """A sigmoid router 64 wide with 8 a token and a selection bias,
    renormalized with the eps, times the scaling factor; 2 experts held by
    each of 32 chips (``expert_offset`` 0, 2, ..., 62): the program's
    shares, and the shared expert counted ONCE, add up to what the cell's
    reference gives for the UNCUT layer (every expert held)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    ref = plugins.load(CONFIG, "reference")
    routed, held, k, n, d, f = 64, 2, 8, 24, 16, 8
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    wr = jnp.asarray(rng.randn(d, routed), jnp.float32)
    w1, w3 = (jnp.asarray(0.3 * rng.randn(routed, d, f), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(0.3 * rng.randn(routed, f, d), jnp.float32)
    s1, s3, s2 = (jnp.asarray(0.3 * rng.randn(*s), jnp.float32)
                  for s in ((d, f), (d, f), (f, d)))
    bias = jnp.asarray(0.2 * rng.randn(routed), jnp.float32)
    scale, eps = SIZES["routed_scaling_factor"], \
        SIZES["assumed"]["route_norm_eps"]
    with jax.default_matmul_precision("highest"):
        shared = ref.feed_forward(x, s1, s3, s2)
        whole = shared + ref.routed(x, wr, bias, w1, w3, w2, k, scale,
                                    eps)[0]
        total, seen = shared, 0
        for off in range(0, routed, held):
            part = moe.routed_experts(
                x, wr, w1[off:off + held], w3[off:off + held],
                w2[off:off + held], top_k=k, expert_offset=off,
                score="sigmoid", bias=bias, norm_eps=eps, scale=scale)
            mine, counts = ref.routed(
                x, wr, bias, w1[off:off + held], w3[off:off + held],
                w2[off:off + held], k, scale, eps, off)
            np.testing.assert_allclose(part, mine, atol=1e-5)
            total, seen = total + part, seen + 1
    assert seen == SIZES["deployment"]["chips_sharing_a_layer"] == 32
    assert int(counts.sum()) == n * k          # over ALL the router's experts
    np.testing.assert_allclose(total, whole, atol=3e-5)
    assert float(jnp.abs(whole - shared).max()) > 0.1 \
        and float(jnp.abs(shared).max()) > 0.1


# -- the readers of its per-layer metrics ----------------------------------

def scoped_run(by, labels=None):
    """A traced run as the readers see it: time by (op type, path), and by
    the first level's label."""
    from chipbench import scope_time

    return {"scope_time": scope_time.Table(by, {}),
            "labelled_busy_s": sum(by.values()), "workload": CELL,
            "time_by_label": labels or {}, "device_kind": "TPU v5 lite",
            "samples_per_step": 1, "steps_traced": 4, "chips": 1}


def test_time_shares_read_the_gates_path_beneath_the_delta_path():
    run = scoped_run({
        ("mul", "layer0.mixer"): 4.0,
        ("gated_delta_rule_grad", "layer1.mixer.delta"): 3.0,
        ("mul", "layer1.mixer.delta.gates"): 1.0,
        ("softplus_grad", "layer2.mixer.delta.gates"): 0.5,
        ("adam", "layer4.mixer.delta.gates"): 0.5,
        ("split", "layer3.mixer.latent"): 1.0,
        ("moe_experts", "layer1.ffn"): 6.0, ("mul", "head"): 4.0},
        {"op:gated_delta_rule_grad": 3.0})
    value = {n: plugins.load("layer_metrics", n).value(run) for n in (
        "delta_gate_time_pct", "delta_mixer_time_pct", "mixer_time_pct",
        "latent_proj_time_pct", "delta_rule_time_pct")}
    assert value == {"delta_gate_time_pct": pytest.approx(10.0),
                     "delta_mixer_time_pct": pytest.approx(25.0),
                     "mixer_time_pct": pytest.approx(50.0),
                     "latent_proj_time_pct": pytest.approx(5.0),
                     "delta_rule_time_pct": pytest.approx(15.0)}
    flops = plugins.load(CONFIG, "flops")
    reader = plugins.load("layer_metrics", "delta_rule_mfu_pct")
    assert reader.needed(CELL) == 3 * flops.rule_flops(SIZES) \
        == 3 * 2 * 4 * SIZES["seq_len"] * 32 * 3 * 128 * 128
    assert 0 < reader.value(run) < 100


def test_channel_decay_blocks_reads_the_counter_and_prints_the_others(
        capsys):
    from paddle_tpu import observe

    observe.reset()
    reg = observe.registry()
    for _ in range(4):
        reg.inc("models.decoder.blocks", labels={
            "mixer": "delta", "residual": "sequential", "where": "trunk"})
        reg.inc("models.decoder.delta", labels={
            "decay": "channel", "gate": "sigmoid"})
    reg.inc("models.decoder.delta", labels={"decay": "scalar",
                                            "gate": "silu"})
    reg.inc("models.decoder.blocks", labels={
        "mixer": "latent", "residual": "sequential", "where": "trunk"})
    reg.inc("models.decoder.latent", labels={
        "rotary": "0", "head_norm": "0", "value": "128"})
    reg.inc("ops.delta_rule.channel_calls", labels={
        "key_heads": "32", "dim": "128", "chunk": "64", "sub": "16"})
    reg.inc("ops.delta_rule.grad_calls", labels={"chunk": "64",
                                                 "path": "vjp"})
    reg.inc("ops.sparse_attention.declined", labels={"why": "value_width"})
    reader = plugins.load("layer_metrics", "delta_channel_decay_blocks")
    assert reader.value({}) == 4
    said = capsys.readouterr().out
    assert said.startswith("counters: models.decoder.blocks{")
    for part in ('models.decoder.delta{decay="channel",gate="sigmoid"} = 4',
                 'models.decoder.latent{head_norm="0",rotary="0",'
                 'value="128"} = 1',
                 'ops.delta_rule.channel_calls{chunk="64",dim="128",'
                 'key_heads="32",sub="16"} = 1',
                 'ops.delta_rule.grad_calls{chunk="64",path="vjp"} = 1',
                 'ops.sparse_attention.declined{why="value_width"} = 1'):
        assert part in said, part
    assert plugins.load("layer_metrics", "delta_mixer_blocks").value({}) == 4
    assert plugins.load("layer_metrics", "latent_mixer_blocks").value({}) \
        == 1
    observe.reset()


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_ranked_gates(name):
    """The parent's traced run, or another cell's: a delta mixer whose
    decay is one number a head and whose gates come from the projection in
    has no such path and no such counter value; the reader returns nothing
    and does not raise."""
    from paddle_tpu import observe

    observe.reset()
    observe.registry().inc("models.decoder.blocks", labels={
        "mixer": "delta", "residual": "sequential", "where": "trunk"})
    observe.registry().inc("models.decoder.delta", labels={
        "decay": "scalar", "gate": "silu"})
    reader = plugins.load("layer_metrics", name)
    run = scoped_run({("mul", "layer0.mixer"): 2.0,
                      ("gated_delta_rule", "layer0.mixer.delta"): 1.0,
                      ("mul", "head"): 1.0}, {"op:mul": 3.0})
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
              "sparse_attention_time_pct", "sparse_attention_pallas_calls",
              "grouped_matmul_roofline", "grouped_matmul_t_roofline",
              "grouped_matmul_time_pct", "short_conv_time_pct",
              "short_conv_calls", "mixer_time_pct", "ffn_time_pct",
              "head_time_pct", "head_mfu_pct", "scoped_time_pct",
              "ops_without_scope", "latent_proj_time_pct",
              "latent_mixer_blocks", "delta_rule_time_pct",
              "delta_mixer_time_pct", "delta_mixer_blocks",
              "delta_rule_mfu_pct", "mfu_pct", "step_ms_p95",
              "peak_hbm_gib", "setup_s"}
    assert set(NEW) | shared <= listed
    # the attention kernels decline a value narrower than the key, so their
    # shares have nothing to read here; nor has what the model has not
    assert not {"sparse_flash_fwd_roofline", "sparse_flash_dq_roofline",
                "sparse_flash_dkv_roofline", "index_select_time_pct",
                "window_attention_time_pct", "window_flash_fwd_roofline",
                "window_attention_pallas_calls", "images_per_s_per_chip",
                "flash_fwd_roofline", "mtp_time_pct",
                "global_mixer_time_pct", "yarn_global_layers"} & listed
    for name in NEW:
        m = entry_of("per_layer", name)
        assert CELL in m["workloads"] and m["layer"] == "token mixers"
        assert m["moves"] == "step_ms_p95"
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
