"""``lfm2_8b_a1b.resident``: the cell rehearsed through the one command,
the control of its comparison at the rehearsal's size, the FLOPs its
configuration states, the flash families at head width 64 and the readers
of its per-layer metrics.  CPU only."""

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

CELL = "lfm2_8b_a1b.resident"
CONFIG = "configs/lfm2_8b_a1b"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SIZES = json.load(open(os.path.join(ROOT, "chipbench", CONFIG,
                                    "config.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FAMILIES = {"sparse_flash_fwd": 2, "sparse_flash_dq": 3,
            "sparse_flash_dkv": 4}
NEW = {"short_conv_time_pct", "short_conv_calls"}


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
    entry = [c for c in BENCH["configs"] if c["name"] == "lfm2_8b_a1b"][0]
    assert cuts.problems(SIZES, entry) == []
    assert SIZES["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert [SIZES[k] for k in SIZES["reduced"]] == [5, 8, 16384]
    assert SIZES["published"] == {"num_hidden_layers": 24,
                                  "num_experts": 32, "vocab_size": 65536}
    assert SIZES["deployment"]["chips_sharing_a_layer"] == 4
    assert SIZES["vocab_size"] * 4 == SIZES["published"]["vocab_size"]
    assert SIZES["num_experts"] * 4 == SIZES["published"]["num_experts"]
    widths = {"hidden_size": 2048, "num_attention_heads": 32,
              "num_key_value_heads": 8, "intermediate_size": 7168,
              "moe_intermediate_size": 1792, "num_experts_per_tok": 4,
              "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 2,
              "norm_topk_prob": True, "use_expert_bias": True,
              "routed_scaling_factor": 1, "rope_theta": 1000000,
              "norm_eps": 1e-05}
    assert {k: SIZES[k] for k in widths} == widths
    cfg = plugins.load(CONFIG, "build").config_of(SIZES)
    assert (cfg.head_dim, cfg.num_routed, cfg.experts_held,
            cfg.experts_per_token) == (64, 32, 8, 4)
    tiny = SIZES["tiny"]
    assert tiny["num_experts"] < tiny["published"]["num_experts"]
    assert tiny["hidden_size"] // tiny["num_attention_heads"] == 16
    n = {name: math.prod(shape) for name, shape, _ in
         plugins.load(CONFIG, "reference").param_spec(SIZES)}
    assert sum(n.values()) == 507_820_160   # 6.09 GB resident at 12 B each
    conv = sum(v for k, v in n.items() if k.startswith("l0_conv_")
               and k != "l0_conv_norm")
    attn = sum(v for k, v in n.items() if k.startswith("l1_")
               and k.split("_", 1)[1] in ("q_w", "q_norm", "k_w", "k_norm",
                                          "v_w", "o_w"))
    assert (conv, attn) == (16_783_360, 10_485_888)
    assert n["tok_emb"] == 33_554_432 and "lm_head_w" not in n
    assert n["l0_mlp_w1"] * 3 == 44_040_192
    assert n["l1_w1"] * 3 == 88_080_384 and n["l1_router_w"] == 65_536


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file_as_published():
    row = next(json.loads(l) for l in open(CATALOG)
               if '"name": "LFM2-8B-A1B"' in l)
    assert SIZES["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if SIZES.get(k, "absent") != v)
    assert differs == sorted(SIZES["reduced"])
    assert {k: row["config"][k] for k in differs} == SIZES["published"]


def test_layers_held_are_the_sources_entries_one_to_five():
    build = plugins.load(CONFIG, "build")
    flops = plugins.load(CONFIG, "flops")
    cfg = build.config_of(SIZES)
    assert SIZES["layer_offset"] == 1 and cfg.num_layers == 5
    held = SIZES["layer_types"][1:6]
    assert held == ["conv", "full_attention", "conv", "conv", "conv"]
    assert [cfg.layer_mixer(i) for i in range(5)] == [
        build.MIXERS[k] for k in held]
    assert [cfg.layer_is_dense(i) for i in range(5)] == [True] + [False] * 4
    assert flops.layer_kinds(SIZES) == [
        (k, cfg.layer_is_dense(i)) for i, k in enumerate(held)]
    assert len(SIZES["layer_types"]) == 24
    assert SIZES["layer_types"].count("full_attention") == 6


def test_stated_flops_by_hand():
    """Per sequence of 8,192 tokens, forward, in GFLOP.  A conv mixer's two
    projections 275 (and 0.1 for the filter's three taps); the attention
    layer's projections 172 and its causal pairs 275; the dense
    feed-forward 722; the router 1, the experts held 180 (8,192 expected
    assignments); the tied head 550; three times the sum for a step: 10.63
    TFLOP."""
    flops = plugins.load(CONFIG, "flops")
    t, d = SIZES["seq_len"], 2048
    assert t == 8192 and flops.pairs(t) == 33_558_528
    parts = [2 * t * d * 4 * d, 2 * t * d * 3, 2 * t * d * (2 * 2048 + 1024),
             4 * flops.pairs(t) * 2048, 2 * 3 * t * d * 7168,
             2 * t * d * 32, 2 * (t * 4 * 8 // 32) * 3 * d * 1792,
             2 * t * d * 16384]
    assert [round(x / 1e9, 1) for x in parts] == [
        274.9, 0.1, 171.8, 274.9, 721.6, 1.1, 180.4, 549.8]
    mix, taps, proj, scores, dense, router, held, head = parts
    assert flops.forward_flops(SIZES) == (
        4 * (mix + taps) + proj + scores + dense + 4 * (router + held)
        + head)
    assert flops.train_flops_per_sample(SIZES) == 3 * flops.forward_flops(
        SIZES)
    assert flops.train_flops_per_sample(SIZES) / 1e12 == pytest.approx(
        10.63, abs=0.01)


# -- the cell through the one command -------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    lines = run_tool("run.py", "--seed", "2147489999", "--seconds", "1",
                     "--trace", "1")
    return lines, json.loads(lines[-1])


def test_rehearsal_is_correct_and_prints_the_cut(rehearsal):
    lines, last = rehearsal
    assert lines[0] == (
        "cut: num_hidden_layers 5 of 24 (depth), num_experts 8 of 32 "
        "(experts_held), vocab_size 16384 of 65536 (vocabulary); one of 4 "
        "chips that share a layer: " + SIZES["deployment"]["how"])
    assert last["correct"] is True, lines
    assert last["failed"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["dispatches_per_step"]["value"] == 1


def test_rehearsal_says_which_path_each_layer_took(rehearsal):
    """``short_conv`` on 4 layers of each program lowered and attention on
    1; the sigmoid router's calls (the vjp traces the forward again) and
    one bias update a routed layer."""
    lines, last = rehearsal
    said = next(l for l in lines if l.startswith("counters: "))
    found = dict(kv.rsplit(" = ", 1) for kv in said[len("counters: "):]
                 .split(", ops."))
    found = {("" if k.startswith("ops.") else "ops.") + k: int(v)
             for k, v in found.items()}
    conv = found['ops.short_conv.calls{channels="64",path="xla",taps="3"}']
    attention = found['ops.sparse_attention.calls{path="pallas",seq="64",'
                      'topk="0"}']
    assert conv == 4 * attention and attention > 0
    assert last["metrics"]["short_conv_calls"]["value"] == conv
    assert found['ops.moe.calls{held="4",path="ragged_dot",routed="8",'
                 'score="sigmoid"}'] == 2 * conv
    assert found["ops.moe.bias_updates"] == conv
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

Q = ((32, 8192, 64), "bf16")
KV = ((8, 8192, 64), "bf16")
ROW = ((32, 8192, 1), "f32")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_flash_family_reads_the_width_as_64(family):
    """From the declared shapes of the cell's one attention layer, 32 query
    heads of 64 over 8,192 tokens: the causal half, half of what the same
    heads count at width 128; compute-bound on the v5e, so the least time
    is the FLOPs'."""
    mod = plugins.load("kernels", family)
    assert mod.KERNEL == family
    lse = [ROW, ROW] if family != "sparse_flash_fwd" else []
    operands = (Q, KV, KV) + ((Q,) + tuple(lse) if lse else ())
    results = (Q, ROW) if family == "sparse_flash_fwd" else (
        (Q,) if family == "sparse_flash_dq" else (KV, KV))
    want = 2.0 * FAMILIES[family] * 32 * 8192 * 8192 * 64 / 2
    assert mod.flops(operands, results) == want
    wide = (((32, 8192, 128), "bf16"),) + operands[1:]
    assert mod.flops(wide, results) == 2 * want
    pk = peaks.peaks_for("TPU v5 lite")
    call = hlo.CustomCall(family, operands, results)
    assert peaks.least_seconds(want, hlo.declared_bytes(call), pk) == \
        pytest.approx(want / 197e12)


def test_lowered_calls_at_width_64_are_the_families(monkeypatch):
    """The kernels' names from a lowering at a small size with heads of 64
    and a group of four (interpret mode has no ``tpu_custom_call``, so the
    names are read off the jaxpr); no window family among them."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_sparse_flash as psf

    monkeypatch.setattr(psf, "BLOCK", 16)
    q = jnp.ones((1, 8, 64, 64), jnp.float32)
    k = jnp.ones((1, 2, 64, 64), jnp.float32)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: psf.sparse_flash_attention(
            q, k, v, None, None, True).sum(), (0, 1, 2)))(q, k, k))
    for family in FAMILIES:
        assert family in jaxpr, family
    assert "window_flash" not in jaxpr


def test_short_conv_time_share_reads_the_ops_two_labels():
    run = {"time_by_label": {
        "op:short_conv": 1.0, "op:short_conv_grad": 2.0, "op:mul": 10.0,
        "op:sequence_conv": 5.0, "kernel:sparse_flash_fwd": 2.0},
        "labelled_busy_s": 20.0, "workload": "no_such_cell"}
    value = {n: plugins.load("layer_metrics", n).value(run) for n in (
        "short_conv_time_pct", "sparse_attention_time_pct")}
    assert value == {"short_conv_time_pct": pytest.approx(15.0),
                     "sparse_attention_time_pct": pytest.approx(10.0)}


def test_short_conv_calls_reads_the_counter_and_prints_the_others(capsys):
    from paddle_tpu import observe

    observe.reset()
    reg = observe.registry()
    for _ in range(4):
        reg.inc("ops.short_conv.calls", labels={
            "channels": "2048", "taps": "3", "path": "xla"})
    reg.inc("ops.sparse_attention.calls", labels={
        "path": "pallas", "seq": "8192", "topk": "0"})
    reg.inc("ops.moe.calls", labels={
        "held": "8", "routed": "32", "path": "ragged_dot",
        "score": "sigmoid"})
    assert plugins.load("layer_metrics", "short_conv_calls").value({}) == 4
    said = capsys.readouterr().out
    assert said.startswith("counters: ops.moe.calls{")
    assert 'ops.short_conv.calls{channels="2048",path="xla",taps="3"} = 4' \
        in said and 'ops.sparse_attention.calls{path="pallas"' in said
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
              "mfu_pct", "step_ms_p95", "peak_hbm_gib", "setup_s"}
    assert NEW | shared <= listed
    assert not {"index_select_time_pct", "sparse_attention_pallas_calls",
                "window_attention_time_pct", "window_flash_fwd_roofline",
                "window_attention_pallas_calls", "images_per_s_per_chip",
                "flash_fwd_roofline"} & listed
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["layer"] == "token mixers"
            assert plugins.load("layer_metrics", m["name"]) is not None
    cell = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1 \
        and cell[0]["traffic"] == "resident" \
        and cell[0]["config"] == "lfm2_8b_a1b"
    assert [c["name"] for c in BENCH["configs"]].count("lfm2_8b_a1b") == 1


@pytest.mark.parametrize("name", sorted(NEW))
def test_readers_find_nothing_in_a_program_without_a_convolution(name):
    """The parent's traced run, or another cell's: no such label, no such
    counter; the reader returns nothing and does not raise."""
    from paddle_tpu import observe

    observe.reset()
    observe.registry().inc("ops.sparse_attention.calls", labels={
        "path": "pallas", "seq": "8192", "topk": "2048"})
    run = {"time_by_label": {"op:mul": 2.0, "op:sequence_conv": 1.0},
           "labelled_busy_s": 3.0, "workload": "another_cell"}
    assert plugins.load("layer_metrics", name).value(run) is None
    assert plugins.load("layer_metrics", name).value({"steps": 3}) is None
    observe.reset()
