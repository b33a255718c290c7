"""``instella_moe_16b_a3b.resident``: the cell rehearsed through the one
command, the control of its comparison at the rehearsal's size, the FLOPs
its configuration states, the flash families at a group of one and the
readers of its per-layer metrics.  CPU only."""

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

CELL = "instella_moe_16b_a3b.resident"
NAME = "instella_moe_16b_a3b"
CONFIG = "configs/" + NAME
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SIZES = json.load(open(os.path.join(ROOT, "chipbench", CONFIG,
                                    "config.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FAMILIES = {"sparse_flash_fwd": 2, "sparse_flash_dq": 3,
            "sparse_flash_dkv": 4}
NEW = {"latent_proj_time_pct": "token mixers",
       "mtp_time_pct": "model blocks",
       "latent_mixer_blocks": "token mixers"}


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
    entry = [c for c in BENCH["configs"] if c["name"] == NAME][0]
    assert cuts.problems(SIZES, entry) == []
    assert SIZES["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert [SIZES[k] for k in SIZES["reduced"]] == [4, 8, 16112]
    assert SIZES["published"] == {"num_hidden_layers": 27,
                                  "n_routed_experts": 64,
                                  "vocab_size": 128896}
    assert SIZES["deployment"]["chips_sharing_a_layer"] == 8
    assert SIZES["vocab_size"] * 8 == SIZES["published"]["vocab_size"]
    assert SIZES["n_routed_experts"] * 8 == \
        SIZES["published"]["n_routed_experts"]
    widths = {"hidden_size": 2048, "num_attention_heads": 16,
              "num_key_value_heads": 16, "kv_lora_rank": 512,
              "qk_nope_head_dim": 96, "qk_rope_head_dim": 32,
              "qk_head_dim": 128, "v_head_dim": 128, "q_lora_rank": None,
              "intermediate_size": 10944, "moe_intermediate_size": 1408,
              "num_experts_per_tok": 6, "n_shared_experts": 2,
              "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
              "routed_scaling_factor": 2.5, "rope_theta": 8000000,
              "rms_norm_eps": 1e-06, "farskip": True,
              "gated_attention": True, "qk_layernorm": True,
              "rope_interleave": True, "scoring_func": "sigmoid"}
    assert {k: SIZES[k] for k in widths} == widths
    assert SIZES["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    build = plugins.load(CONFIG, "build")
    cfg = build.config_of(SIZES)
    assert (cfg.head_dim, cfg.num_heads, cfg.num_kv_heads, cfg.num_routed,
            cfg.experts_held, cfg.experts_per_token, cfg.shared_width,
            cfg.dense_width, cfg.residual, cfg.mtp_depth, cfg.mtp_weight) \
        == (128, 16, 16, 64, 8, 6, 2816, 10944, "farskip", 1, 0.3)
    lat = cfg.latent
    assert (lat.rank, lat.nope, lat.rope, lat.value, lat.interleaved) == (
        512, 96, 32, 128, True) and len(lat.inv_freq) == 16
    assert lat.scale == pytest.approx(
        128 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert [cfg.layer_mixer(i) for i in range(4)] == ["latent"] * 4
    assert [cfg.layer_is_dense(i) for i in range(4)] == [True] + [False] * 3
    with pytest.raises(ValueError, match="nothing else"):
        build.config_of({**SIZES, "q_lora_rank": 1536})
    tiny = {**SIZES, **SIZES["tiny"]}
    assert tiny["n_routed_experts"] < tiny["published"]["n_routed_experts"]
    assert tiny["qk_nope_head_dim"] + tiny["qk_rope_head_dim"] \
        == tiny["qk_head_dim"] == tiny["v_head_dim"] == 16
    ramp = [f * 100 ** (i / 4) for i, f in
            enumerate(build.config_of(tiny).latent.inv_freq)]
    assert ramp[0] == pytest.approx(1) and ramp[-1] == pytest.approx(0.25)
    assert all(0.25 < r < 1 for r in ramp[1:-1])


def test_parameters_as_run_add_up_to_the_count_the_equations_give():
    n = {name: math.prod(shape) for name, shape, _ in
         plugins.load(CONFIG, "reference").param_spec(SIZES)}
    assert sum(n.values()) == 565_870_336   # 6.79 GB resident at 12 B each

    def under(p, keys):
        return sum(n[f"{p}_{k}"] for k in keys)

    mixer = ("attn_norm", "q_w", "q_norm", "kva_w", "kv_norm", "kvb_w",
             "k_norm", "gate_w", "o_w")
    assert [n[f"l1_{k}"] for k in ("q_w", "kva_w", "kvb_w", "gate_w",
                                   "o_w")] == [
        4_194_304, 1_114_112, 1_835_008, 4_194_304, 4_194_304]
    assert under("l1", ("attn_norm", "q_norm", "kv_norm", "k_norm")) == 2_816
    routed = ("moe_norm", "shared_w1", "shared_w3", "shared_w2", "router_w",
              "w1", "w3", "w2")
    for p in ("l0", "l1", "l2", "l3", "mtp"):
        assert under(p, mixer) == 15_534_848
    assert under("l0", ("mlp_norm", "mlp_w1", "mlp_w3", "mlp_w2")) \
        == 67_241_984
    for p in ("l1", "l2", "l3", "mtp"):
        assert under(p, routed) == 86_640_640
        assert under(p, ("w1", "w3", "w2")) == 69_206_016
        assert under(p, ("shared_w1", "shared_w3", "shared_w2")) \
            == 17_301_504
        assert n[f"{p}_router_w"] == 131_072
    assert n["tok_emb"] == n["lm_head_w"] == 32_997_376
    assert n["mtp_h_norm"] + n["mtp_e_norm"] + n["mtp_merge_w"] \
        + n["mtp_norm"] == 8_394_752
    assert n["final_norm"] == 2_048
    assert 82_776_832 + 4 * 102_175_488 + 2 * 32_997_376 + 8_394_752 \
        + 2_048 == 565_870_336


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file_as_published():
    row = next(json.loads(l) for l in open(CATALOG)
               if '"name": "Instella-MoE-16B-A3B-Base"' in l)
    assert SIZES["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if SIZES.get(k, "absent") != v)
    assert differs == sorted(SIZES["reduced"])
    assert {k: row["config"][k] for k in differs} == SIZES["published"]


def test_stated_flops_by_hand():
    """Per sequence of 8,192 tokens, forward, in GFLOP.  A latent mixer:
    query, gate and output 206.2, the latent with the shared key 18.3, keys
    and values from the latent 30.1, the causal pairs at 128 + 128 wide
    274.9; the dense feed-forward 1,101.7; a routed one: router 2.1, the
    two shared experts 283.5, the experts held 106.3 (6,144 expected
    assignments); the merge 137.4; the head 540.6, TWICE; five mixers, one
    dense and four routed feed-forwards; three times the sum for a step:
    19.60 TFLOP."""
    flops = plugins.load(CONFIG, "flops")
    t, d, h = SIZES["seq_len"], 2048, 16
    assert t == 8192 and flops.pairs(t) == 33_558_528
    parts = [2 * t * d * h * 3 * 128, 2 * t * d * (512 + 32),
             2 * t * 512 * h * (96 + 128), 2 * flops.pairs(t) * h * 256,
             2 * 3 * t * d * 10944, 2 * t * d * 64, 2 * 3 * t * d * 2816,
             2 * (t * 6 * 8 // 64) * 3 * d * 1408, 2 * t * 2 * d * d,
             2 * t * d * 16112]
    assert [round(x / 1e9, 1) for x in parts] == [
        206.2, 18.3, 30.1, 274.9, 1101.7, 2.1, 283.5, 106.3, 137.4, 540.6]
    qgo, kva, kvb, pairs, dense, router, shared, held, merge, head = parts
    assert flops.forward_flops(SIZES) == (
        5 * (qgo + kva + kvb + pairs) + dense
        + 4 * (router + shared + held) + merge + 2 * head)
    assert flops.train_flops_per_sample(SIZES) == 3 * flops.forward_flops(
        SIZES)
    assert flops.train_flops_per_sample(SIZES) / 1e12 == pytest.approx(
        19.60, abs=0.01)


# -- the cell through the one command -------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    lines = run_tool("run.py", "--seed", "2147489999", "--seconds", "1",
                     "--trace", "1")
    return lines, json.loads(lines[-1])


def test_rehearsal_is_correct_and_prints_the_cut(rehearsal):
    lines, last = rehearsal
    assert lines[0] == (
        "cut: num_hidden_layers 4 of 27 (depth), n_routed_experts 8 of 64 "
        "(experts_held), vocab_size 16112 of 128896 (vocabulary); one of 8 "
        "chips that share a layer: " + SIZES["deployment"]["how"])
    assert last["correct"] is True, lines
    assert last["failed"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["dispatches_per_step"]["value"] == 1
    assert last["metrics"]["ops_without_scope"]["value"] == 0


def test_rehearsal_says_which_mixer_residual_rotary_and_path_ran(rehearsal):
    """Five latent blocks a program built (four of the trunk, the
    module's), each with one attention call on the Pallas path and two
    partial interleaved YaRN rotaries a program lowered; four sigmoid
    routers (the vjp traces the forward again) and their bias updates;
    nothing declined."""
    lines, last = rehearsal
    said = next(l for l in lines if l.startswith("counters: "))

    def count(name):        # the labels hold commas: by the whole name
        return int(said[said.index(name + " = ") + len(name) + 3:]
                   .split(",", 1)[0])

    trunk = count('models.decoder.blocks{mixer="latent",residual="farskip",'
                  'where="trunk"}')
    module = count('models.decoder.blocks{mixer="latent",residual="farskip",'
                   'where="mtp"}')
    assert trunk == 4 * module and module > 0
    assert last["metrics"]["latent_mixer_blocks"]["value"] == trunk + module
    attention = count('ops.sparse_attention.calls{path="pallas",seq="64",'
                      'topk="0"}')
    rotary = count('ops.rotary.calls{dims="8",pairing="interleaved",'
                   'scaled="1"}')
    assert attention % 5 == 0 and rotary == 2 * attention
    assert count('ops.moe.calls{held="4",path="ragged_dot",routed="8",'
                 'score="sigmoid"}') == 2 * 4 * attention // 5
    assert count("ops.moe.bias_updates") == 4 * attention // 5
    assert "declined" not in said and 'path="xla"' not in said
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

Q = ((16, 8192, 128), "bf16")
ROW = ((16, 8192, 1), "f32")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_flash_family_counts_a_group_of_one_at_width_128(family):
    """From the declared shapes of one of the cell's five attention calls,
    16 query heads each over its own key-value head of 128 (96 unrotated +
    32 rotated for q and k, 128 for v) over 8,192 tokens: the causal half;
    compute-bound on the v5e, so the least time is the FLOPs'."""
    mod = plugins.load("kernels", family)
    assert mod.KERNEL == family
    lse = [ROW, ROW] if family != "sparse_flash_fwd" else []
    operands = (Q, Q, Q) + ((Q,) + tuple(lse) if lse else ())
    results = (Q, ROW) if family == "sparse_flash_fwd" else (
        (Q,) if family == "sparse_flash_dq" else (Q, Q))
    want = 2.0 * FAMILIES[family] * 16 * 8192 * 8192 * 128 / 2
    assert mod.flops(operands, results) == want
    pk = peaks.peaks_for("TPU v5 lite")
    call = hlo.CustomCall(family, operands, results)
    assert peaks.least_seconds(want, hlo.declared_bytes(call), pk) == \
        pytest.approx(want / 197e12)


def test_lowered_calls_of_a_group_of_one_are_the_families(monkeypatch):
    """The kernels' names from a lowering at a small size with as many
    key-value heads as query heads (interpret mode has no
    ``tpu_custom_call``, so the names are read off the jaxpr); no window
    family among them, and the operands are not declined."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_sparse_flash as psf

    monkeypatch.setattr(psf, "BLOCK", 16)
    q = jnp.ones((1, 4, 64, 128), jnp.float32)
    assert psf.supported(q, q, None, 0) == ""
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: psf.sparse_flash_attention(
            q, k, v, None, q.shape[-1] ** -0.5 * 1.87, True).sum(),
        (0, 1, 2)))(q, q, q))
    for family in FAMILIES:
        assert family in jaxpr, family
    assert "window_flash" not in jaxpr


def scoped_run(by):
    """A traced run as the readers see it: time by (op type, path)."""
    from chipbench import scope_time

    return {"scope_time": scope_time.Table(by, {}),
            "labelled_busy_s": sum(by.values()), "workload": "no_such_cell"}


def test_time_shares_read_the_latents_and_the_modules_paths():
    run = scoped_run({
        ("mul", "layer0.mixer"): 4.0, ("mul", "layer0.mixer.latent"): 1.0,
        ("rotary_embedding", "layer3.mixer.latent"): 0.5,
        ("concat_grad", "mtp.mixer.latent"): 0.5,
        ("mul", "mtp.merge"): 1.0, ("moe_experts", "mtp.ffn"): 2.0,
        ("mul", "mtp.mixer"): 1.0, ("mul_grad", "mtp.head"): 3.0,
        ("mul", "head"): 3.0, ("moe_experts", "layer1.ffn"): 4.0})
    value = {n: plugins.load("layer_metrics", n).value(run) for n in (
        "latent_proj_time_pct", "mtp_time_pct", "mixer_time_pct",
        "ffn_time_pct", "head_time_pct")}
    assert value == {"latent_proj_time_pct": pytest.approx(10.0),
                     "mtp_time_pct": pytest.approx(37.5),
                     # the trunk's own: the module's block is under ``mtp``
                     "mixer_time_pct": pytest.approx(27.5),
                     "ffn_time_pct": pytest.approx(20.0),
                     "head_time_pct": pytest.approx(15.0)}


def test_latent_mixer_blocks_reads_the_counter_and_prints_the_others(capsys):
    from paddle_tpu import observe

    observe.reset()
    reg = observe.registry()
    for where, n in (("trunk", 4), ("mtp", 1)):
        for _ in range(n):
            reg.inc("models.decoder.blocks", labels={
                "mixer": "latent", "residual": "farskip", "where": where})
    reg.inc("models.decoder.blocks", labels={
        "mixer": "attention", "residual": "sequential", "where": "trunk"})
    reg.inc("ops.rotary.calls", labels={
        "dims": "32", "pairing": "interleaved", "scaled": "1"})
    reg.inc("ops.sparse_attention.calls", labels={
        "path": "pallas", "seq": "8192", "topk": "0"})
    reg.inc("ops.moe.calls", labels={
        "held": "8", "routed": "64", "path": "pallas", "score": "sigmoid"})
    assert plugins.load("layer_metrics", "latent_mixer_blocks").value({}) == 5
    said = capsys.readouterr().out
    assert said.startswith("counters: models.decoder.blocks{")
    assert 'ops.rotary.calls{dims="32",pairing="interleaved",scaled="1"} ' \
        '= 1' in said and 'ops.sparse_attention.calls{path="pallas"' in said \
        and 'where="mtp"} = 1' in said and "ops.moe.calls{" in said
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
              "grouped_matmul_time_pct", "mixer_time_pct", "ffn_time_pct",
              "head_time_pct", "head_mfu_pct", "scoped_time_pct",
              "ops_without_scope", "mfu_pct", "step_ms_p95", "peak_hbm_gib",
              "setup_s"}
    assert set(NEW) | shared <= listed
    assert not {"index_select_time_pct", "sparse_attention_pallas_calls",
                "window_attention_time_pct", "window_flash_fwd_roofline",
                "window_attention_pallas_calls", "images_per_s_per_chip",
                "flash_fwd_roofline", "short_conv_time_pct",
                "short_conv_calls"} & listed
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["layer"] == NEW[m["name"]]
            assert m["moves"] == "step_ms_p95"
            assert plugins.load("layer_metrics", m["name"]) is not None
    assert set(NEW) <= {m["name"] for m in BENCH["per_layer"]}
    cell = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1 \
        and cell[0]["traffic"] == "resident" and cell[0]["config"] == NAME
    # found by name, never by place: later PRs append theirs
    assert [c["name"] for c in BENCH["configs"]].count(NAME) == 1


@pytest.mark.parametrize("name", sorted(NEW))
def test_readers_find_nothing_in_a_program_without_latent_mixers(name):
    """The parent's traced run, or another cell's: no such path, no such
    counter; the reader returns nothing and does not raise."""
    from paddle_tpu import observe

    observe.reset()
    observe.registry().inc("ops.sparse_attention.calls", labels={
        "path": "pallas", "seq": "8192", "topk": "2048"})
    observe.registry().inc("models.decoder.blocks", labels={
        "mixer": "attention", "residual": "sequential", "where": "trunk"})
    reader = plugins.load("layer_metrics", name)
    assert reader.value(scoped_run({("mul", "layer0.mixer"): 2.0,
                                    ("mul", "head"): 1.0})) is None
    assert reader.value({"scope_time": None, "workload": "x"}) is None
    assert reader.value({"steps": 3}) is None
    observe.reset()
