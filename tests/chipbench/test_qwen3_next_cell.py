"""``qwen3_next_80b_a3b.resident``: the cell rehearsed through the one
command, the control of its comparison at the rehearsal's size, its
parameters and the FLOPs its configuration states, the flash families at a
head width of 256 and the readers of its per-layer metrics.  CPU only."""

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

CELL = "qwen3_next_80b_a3b.resident"
NAME = "qwen3_next_80b_a3b"
CONFIG = "configs/" + NAME
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SIZES = json.load(open(os.path.join(ROOT, "chipbench", CONFIG,
                                    "config.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FAMILIES = {"sparse_flash_fwd": 2, "sparse_flash_dq": 3,
            "sparse_flash_dkv": 4}
NEW = ("delta_rule_time_pct", "delta_mixer_time_pct", "delta_mixer_blocks",
       "delta_rule_mfu_pct")


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
    assert SIZES["published"] == {"num_hidden_layers": 48,
                                  "num_experts": 512, "vocab_size": 151936}
    held = SIZES["num_experts"]
    assert [SIZES[k] for k in SIZES["reduced"]] == [4, held, 18992]
    # 16 held as one of 32 chips, or the guide's floor of 8 as one of 64
    assert held in (16, 8)
    assert SIZES["deployment"]["chips_sharing_a_layer"] * held == 512
    assert SIZES["vocab_size"] * 8 == SIZES["published"]["vocab_size"]
    widths = {"hidden_size": 2048, "num_attention_heads": 16,
              "num_key_value_heads": 2, "head_dim": 256,
              "linear_num_key_heads": 16, "linear_num_value_heads": 32,
              "linear_key_head_dim": 128, "linear_value_head_dim": 128,
              "linear_conv_kernel_dim": 4, "full_attention_interval": 4,
              "moe_intermediate_size": 512,
              "shared_expert_intermediate_size": 512,
              "num_experts_per_tok": 10, "partial_rotary_factor": 0.25,
              "rope_theta": 10000000, "rms_norm_eps": 1e-06,
              "norm_topk_prob": True, "tie_word_embeddings": False,
              "model_type": "qwen3_next", "seq_len": 8192,
              "batch_per_chip": 1, "delta_chunk": 64}
    assert {k: SIZES[k] for k in widths} == widths
    build = plugins.load(CONFIG, "build")
    cfg = build.config_of(SIZES)
    assert (cfg.head_dim, cfg.num_heads, cfg.num_kv_heads, cfg.num_routed,
            cfg.experts_held, cfg.experts_per_token, cfg.shared_width,
            cfg.shared_gate, cfg.attn_gate, cfg.rotary_dims, cfg.residual,
            cfg.mtp_depth, cfg.router_score) == (
        256, 16, 2, 512, held, 10, 512, True, True, 64, "sequential", 0,
        "softmax")
    assert tuple(cfg.delta) == (16, 32, 128, 128, 4, 64)
    assert [cfg.layer_mixer(i) for i in range(4)] == [
        "delta", "delta", "delta", "attention"]
    assert build.mixers_of(SIZES).count("attention") == 12
    with pytest.raises(ValueError, match="nothing else"):
        build.config_of({**SIZES, "decoder_sparse_step": 2})
    with pytest.raises(ValueError, match="an even whole number"):
        build.config_of({**SIZES, "partial_rotary_factor": 0.3})
    tiny = {**SIZES, **SIZES["tiny"]}
    assert tiny["num_experts"] < tiny["published"]["num_experts"]
    assert tiny["seq_len"] > tiny["delta_chunk"]    # the state is carried
    small = build.config_of(tiny)
    assert small.rotary_dims == 4 and small.delta.chunk == 16


def test_parameters_as_run_add_up_to_the_count_the_equations_give():
    n = {name: math.prod(shape) for name, shape, _ in
         plugins.load(CONFIG, "reference").param_spec(SIZES)}
    held = SIZES["num_experts"]
    # 5.09 GB resident at 12 B each with 16 held
    assert sum(n.values()) == {16: 424_340_544, 8: 323_677_248}[held]

    def under(p, keys):
        return sum(n[f"{p}_{k}"] for k in keys)

    for p in ("l0", "l1", "l2"):
        assert under(p, ("qkvz_w", "ba_w", "conv_w", "dt_bias", "a_log",
                         "delta_norm", "o_w")) == 33_718_464
        assert [n[f"{p}_{k}"] for k in ("qkvz_w", "ba_w", "conv_w",
                                        "o_w")] == [
            25_165_824, 131_072, 32_768, 8_388_608]
    assert under("l3", ("q_w", "q_norm", "k_w", "k_norm", "v_w", "gate_w",
                        "o_w")) == 27_263_488
    for p in ("l0", "l1", "l2", "l3"):
        assert under(p, ("attn_norm", "moe_norm", "shared_w1", "shared_w3",
                         "shared_w2", "shared_gate_w", "router_w")) \
            == 4_200_448
        assert under(p, ("w1", "w3", "w2")) == held * 3_145_728
    assert n["tok_emb"] + n["lm_head_w"] + n["final_norm"] == 77_793_280
    assert 3 * 33_718_464 + 27_263_488 + 4 * 4_200_448 \
        + 4 * 16 * 3_145_728 + 77_793_280 == 424_340_544


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file_as_published():
    row = next(json.loads(l) for l in open(CATALOG)
               if '"name": "Qwen3-Next-80B-A3B-Instruct"' in l)
    assert SIZES["source"] == row["source_url"] \
        == entry_of("configs", NAME)["source"]
    differs = sorted(k for k, v in row["config"].items()
                     if SIZES.get(k, "absent") != v)
    assert differs == sorted(SIZES["reduced"])
    assert {k: row["config"][k] for k in differs} == SIZES["published"]


def test_stated_flops_by_hand():
    """Per sequence of 8,192 tokens, forward, in GFLOP.  A delta mixer: the
    projections in and out 551.9, the rule as the recurrence states it
    25.8; the attention layer: its five products 446.7, the causal pairs at
    256 + 256 wide 549.8; a routed feed-forward: router 17.2, the gated
    shared expert 51.6, the experts held 16.1 (2,560 expected assignments);
    the head 637.3; three delta layers, one attention layer, four
    feed-forwards: 452.4 MFLOP a token, 11.12 TFLOP a step."""
    if SIZES["num_experts"] != 16:
        pytest.skip("the numbers below are 16 experts held")
    flops = plugins.load(CONFIG, "flops")
    t, d = SIZES["seq_len"], 2048
    assert t == 8192 and flops.pairs(t) == 33_558_528
    assert flops.delta_layers(SIZES) == 3
    parts = [2 * t * d * (2 * 2048 + 2 * 4096 + 64) + 2 * t * 4096 * d,
             2 * t * 32 * 3 * 128 * 128,
             2 * t * d * (3 * 4096 + 2 * 512),
             2 * flops.pairs(t) * 16 * 512, 2 * t * d * 512,
             2 * t * d * (3 * 512 + 1), 2 * (t * 10 * 16 // 512) * 3 * d * 512,
             2 * t * d * 18992]
    assert [round(x / 1e9, 1) for x in parts] == [
        551.9, 25.8, 446.7, 549.8, 17.2, 51.6, 16.1, 637.3]
    products, rule, attention, pairs, router, shared, held, head = parts
    assert flops.forward_flops(SIZES) == (
        3 * (products + rule) + attention + pairs
        + 4 * (router + shared + held) + head)
    assert flops.rule_flops(SIZES) == 3 * rule
    assert flops.forward_flops(SIZES) / t / 1e6 == pytest.approx(452.4,
                                                                 abs=0.1)
    assert flops.train_flops_per_sample(SIZES) / 1e12 == pytest.approx(
        11.12, abs=0.01)
    # what the padded grouped products WALK against what is live
    rows = t * SIZES["num_experts_per_tok"]
    assert (rows, t * 10 * 16 // 512) == (81_920, 2_560)


# -- the cell through the one command -------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    lines = run_tool("run.py", "--seed", "2147489999", "--seconds", "1",
                     "--trace", "1")
    return lines, json.loads(lines[-1])


def test_rehearsal_is_correct_and_prints_the_cut(rehearsal):
    lines, last = rehearsal
    dep = SIZES["deployment"]
    assert lines[0] == (
        f"cut: num_hidden_layers 4 of 48 (depth), num_experts "
        f"{SIZES['num_experts']} of 512 (experts_held), vocab_size 18992 of "
        f"151936 (vocabulary); one of {dep['chips_sharing_a_layer']} chips "
        f"that share a layer: " + dep["how"])
    assert last["correct"] is True, lines
    assert last["failed"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["dispatches_per_step"]["value"] == 1
    assert last["metrics"]["ops_without_scope"]["value"] == 0


def test_rehearsal_says_which_mixer_filter_rotary_and_path_ran(rehearsal):
    """Three delta blocks and one attention block a program built; a
    program lowered has three rules in chunks of 16, three ungated four-tap
    filters, one attention call on the Pallas path and two rotaries on 4 of
    the head's 16 columns; softmax routers (no ``score`` label); nothing
    declined."""
    lines, last = rehearsal
    said = next(l for l in lines if l.startswith("counters: models."))

    def count(name):        # the labels hold commas: by the whole name
        return int(said[said.index(name + " = ") + len(name) + 3:]
                   .split(",", 1)[0])

    delta = count('models.decoder.blocks{mixer="delta",'
                  'residual="sequential",where="trunk"}')
    plain = count('models.decoder.blocks{mixer="attention",'
                  'residual="sequential",where="trunk"}')
    assert delta == 3 * plain and plain > 0
    assert last["metrics"]["delta_mixer_blocks"]["value"] == delta
    attention = count('ops.sparse_attention.calls{path="pallas",seq="64",'
                      'topk="0"}')
    rules = count('ops.delta_rule.calls{chunk="16",dim="8",key_heads="2",'
                  'path="xla",value_heads="4"}')
    filters = count('ops.short_conv.calls{channels="64",gated="0",'
                    'path="xla",taps="4"}')
    assert rules == filters == 3 * attention
    assert last["metrics"]["short_conv_calls"]["value"] == filters
    assert count('ops.rotary.calls{dims="4",pairing="half",scaled="0"}') \
        == 2 * attention
    assert count('ops.moe.calls{held="4",path="ragged_dot",routed="8"}') \
        == 2 * 4 * attention
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

Q = ((16, 8192, 256), "bf16")
KV = ((2, 8192, 256), "bf16")
ROW = ((16, 8192, 1), "f32")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_flash_family_counts_a_group_of_eight_at_width_256(family):
    """From the declared shapes of the cell's one attention call, 16 query
    heads over 2 key-value heads of 256 over 8,192 tokens: the causal half
    at the head's width; compute-bound on the v5e, so the least time is
    the FLOPs'."""
    mod = plugins.load("kernels", family)
    assert mod.KERNEL == family
    lse = [ROW, ROW] if family != "sparse_flash_fwd" else []
    operands = (Q, KV, KV) + ((Q,) + tuple(lse) if lse else ())
    results = (Q, ROW) if family == "sparse_flash_fwd" else (
        (Q,) if family == "sparse_flash_dq" else (KV, KV))
    want = 2.0 * FAMILIES[family] * 16 * 8192 * 8192 * 256 / 2
    assert mod.flops(operands, results) == want
    pk = peaks.peaks_for("TPU v5 lite")
    call = hlo.CustomCall(family, operands, results)
    assert peaks.least_seconds(want, hlo.declared_bytes(call), pk) == \
        pytest.approx(want / 197e12)


def test_lowered_calls_at_a_width_of_256_are_the_three_families(monkeypatch):
    """The kernels' names from a lowering with heads 256 wide in groups of
    8 (interpret mode has no ``tpu_custom_call``, so the names are read off
    the jaxpr); the operands are not declined."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_sparse_flash as psf

    monkeypatch.setattr(psf, "BLOCK", 16)
    q = jnp.ones((1, 16, 64, 256), jnp.float32)
    k = jnp.ones((1, 2, 64, 256), jnp.float32)
    assert psf.supported(q, k, None, 0) == ""
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: psf.sparse_flash_attention(
            q, k, v, None, 256 ** -0.5, True).sum(), (0, 1, 2)))(q, k, k))
    for family in FAMILIES:
        assert family in jaxpr, family
    assert "window_flash" not in jaxpr


def scoped_run(by, labels=None):
    """A traced run as the readers see it: time by (op type, path), and by
    the first level's label."""
    from chipbench import scope_time

    return {"scope_time": scope_time.Table(by, {}),
            "labelled_busy_s": sum(by.values()), "workload": CELL,
            "time_by_label": labels or {}, "device_kind": "TPU v5 lite",
            "samples_per_step": 1, "steps_traced": 4, "chips": 1}


def test_time_shares_read_the_rules_labels_and_the_delta_paths():
    run = scoped_run({
        ("mul", "layer0.mixer"): 4.0,
        ("gated_delta_rule", "layer0.mixer.delta"): 2.0,
        ("gated_delta_rule_grad", "layer1.mixer.delta"): 3.0,
        ("short_conv", "layer2.mixer.delta"): 0.5,
        ("rms_norm_grad", "layer2.mixer.delta"): 0.5,
        ("sparse_attention", "layer3.mixer"): 2.0,
        ("moe_experts", "layer1.ffn"): 4.0, ("mul", "head"): 4.0},
        {"op:gated_delta_rule": 2.0, "op:gated_delta_rule_grad": 3.0,
         "op:short_conv": 0.25, "op:short_conv_grad": 0.25, "op:mul": 8.0,
         "kernel:sparse_flash_fwd": 2.0, "op:moe_experts": 4.0,
         "op:rms_norm_grad": 0.5})
    value = {n: plugins.load("layer_metrics", n).value(run) for n in (
        "delta_rule_time_pct", "delta_mixer_time_pct", "mixer_time_pct",
        "short_conv_time_pct", "ffn_time_pct", "head_time_pct")}
    assert value == {"delta_rule_time_pct": pytest.approx(25.0),
                     "delta_mixer_time_pct": pytest.approx(30.0),
                     "mixer_time_pct": pytest.approx(60.0),
                     "short_conv_time_pct": pytest.approx(2.5),
                     "ffn_time_pct": pytest.approx(20.0),
                     "head_time_pct": pytest.approx(20.0)}


def test_the_rules_share_of_the_peak_is_needed_flops_over_the_ops_time():
    """3 x ``rule_flops`` a step x 4 steps over the 5 s the two labels
    hold, against 197 TFLOP/s; the chunked form's own products are in the
    time and not in the FLOPs, so the share stays far under 100."""
    flops = plugins.load(CONFIG, "flops")
    reader = plugins.load("layer_metrics", "delta_rule_mfu_pct")
    assert reader.needed(CELL) == 3 * flops.rule_flops(SIZES) \
        == 3 * 2 * 3 * 8192 * 32 * 3 * 128 * 128
    run = scoped_run({("mul", "head"): 10.0}, {
        "op:gated_delta_rule": 2.0, "op:gated_delta_rule_grad": 3.0,
        "op:mul": 5.0})
    want = 100.0 * 3 * flops.rule_flops(SIZES) * 4 / (5.0 * 197e12)
    assert reader.value(run) == pytest.approx(want)
    assert 0 < want < 100
    # another cell's configuration states no rule_flops: nothing to read
    assert reader.needed("keye_vl_2_0_30b_a3b.resident") is None
    assert reader.needed("no_such_cell") is None


def test_delta_mixer_blocks_reads_the_counter_and_prints_the_others(capsys):
    from paddle_tpu import observe

    observe.reset()
    reg = observe.registry()
    for _ in range(3):
        reg.inc("models.decoder.blocks", labels={
            "mixer": "delta", "residual": "sequential", "where": "trunk"})
    reg.inc("models.decoder.blocks", labels={
        "mixer": "attention", "residual": "sequential", "where": "trunk"})
    reg.inc("ops.delta_rule.calls", labels={
        "key_heads": "16", "value_heads": "32", "dim": "128", "chunk": "64",
        "path": "xla"})
    reg.inc("ops.short_conv.calls", labels={
        "channels": "8192", "taps": "4", "path": "xla", "gated": "0"})
    reg.inc("ops.rotary.calls", labels={
        "dims": "64", "pairing": "half", "scaled": "0"})
    reg.inc("ops.sparse_attention.calls", labels={
        "path": "pallas", "seq": "8192", "topk": "0"})
    reg.inc("ops.moe.calls", labels={
        "held": "16", "routed": "512", "path": "pallas"})
    assert plugins.load("layer_metrics", "delta_mixer_blocks").value({}) == 3
    said = capsys.readouterr().out
    assert said.startswith("counters: models.decoder.blocks{")
    for part in ('ops.delta_rule.calls{chunk="64",dim="128",key_heads="16",'
                 'path="xla",value_heads="32"} = 1',
                 'ops.short_conv.calls{channels="8192",gated="0"',
                 'ops.rotary.calls{dims="64",pairing="half",scaled="0"} = 1',
                 'ops.sparse_attention.calls{path="pallas"',
                 'ops.moe.calls{held="16"'):
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
              "grouped_matmul_roofline", "grouped_matmul_t_roofline",
              "grouped_matmul_time_pct", "short_conv_time_pct",
              "short_conv_calls", "mixer_time_pct", "ffn_time_pct",
              "head_time_pct", "head_mfu_pct", "scoped_time_pct",
              "ops_without_scope", "mfu_pct", "step_ms_p95", "peak_hbm_gib",
              "setup_s"}
    assert set(NEW) | shared <= listed
    assert not {"index_select_time_pct", "sparse_attention_pallas_calls",
                "window_attention_time_pct", "window_flash_fwd_roofline",
                "window_attention_pallas_calls", "images_per_s_per_chip",
                "flash_fwd_roofline", "latent_proj_time_pct",
                "latent_mixer_blocks", "mtp_time_pct"} & listed
    for name in NEW:
        m = entry_of("per_layer", name)
        assert CELL in m["workloads"] and m["layer"] == "token mixers"
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


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_delta_mixers(name):
    """The parent's traced run, or another cell's: no such op, path or
    counter; the reader returns nothing and does not raise."""
    from paddle_tpu import observe

    observe.reset()
    observe.registry().inc("ops.short_conv.calls", labels={
        "channels": "2048", "taps": "3", "path": "xla"})
    observe.registry().inc("models.decoder.blocks", labels={
        "mixer": "conv", "residual": "sequential", "where": "trunk"})
    reader = plugins.load("layer_metrics", name)
    run = scoped_run({("mul", "layer0.mixer"): 2.0, ("mul", "head"): 1.0},
                     {"op:mul": 3.0, "op:short_conv": 0.1})
    assert reader.value({**run, "workload": "lfm2_8b_a1b.resident"}) is None
    assert reader.value({"scope_time": None, "workload": "x"}) is None
    assert reader.value({"steps": 3}) is None
    observe.reset()
