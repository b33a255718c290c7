"""``chipbench/scope_time.py`` and the per-layer metrics that read it: the
join of a hand-made compiled step to hand-made device events, the needed
FLOPs of ResNet-50's stages to the FLOP, every new metric's entry and
reader, and ``ops_without_scope`` at 0 in every cell.  CPU only."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops, plugins, scope_time  # noqa: E402
from chipbench.trace_reduce import Event  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
RESNET = "resnet50_imagenet.resident"
TRANSFORMER = "transformer_base_wmt.resident"
DECODERS = ["keye_vl_2_0_30b_a3b.resident", "trinity_mini.resident",
            "lfm2_8b_a1b.resident"]
CELLS = [TRANSFORMER, RESNET] + DECODERS

HLO = """\
HloModule jit_fn
ENTRY %main {
  %copy.1 = f32[8]{0} copy(%p), metadata={op_name="mut_state['w']"}
  %fusion.1 = bf16[4,8]{1,0} fusion(%a), kind=kOutput, metadata={op_name="jit(fn)/conv2d/~stem/conv_general_dilated"}
  %fusion.2 = bf16[4,8]{1,0} fusion(%b), kind=kOutput, metadata={op_name="jit(fn)/conv2d_grad/~stage1.block1/transpose(jvp(conv_general_dilated))"}
  %fusion.3 = (f32[8]{0}, f32[8]{0}) fusion(%c), kind=kLoop, metadata={op_name="jit(fn)/momentum/~stage1.block2/mul"}
  %fusion.4 = f32[4]{0} fusion(%d), kind=kLoop, metadata={op_name="jit(fn)/mean/~head/reduce_sum"}
  %fusion.5 = f32[4]{0} fusion(%e), kind=kLoop, metadata={op_name="jit(fn)/relu/max"}
  %fusion.6 = f32[4]{0} fusion(%f), kind=kLoop, metadata={op_name="jit(fn)/conv2d/~stage10.block1/conv_general_dilated"}
  %bare.7 = f32[4]{0} add(%g, %h)
}
"""


def events():
    """Two steps; times in ns.  ``fusion.2`` holds a child event."""
    def ev(inst, text, start, dur):
        return Event(f"%{inst} = {text}", float(start), float(dur))

    one = [("copy.1", "f32[8]{0} copy(%p)", 0, 10),
           ("fusion.1", "bf16[4,8]{1,0} fusion(%a)", 10, 100),
           ("fusion.2", "bf16[4,8]{1,0} fusion(%b)", 110, 300),
           ("fusion.6", "f32[4]{0} fusion(%f)", 150, 50),   # inside 2
           ("fusion.3", "(f32[8]{0}, f32[8]{0}) fusion(%c)", 410, 40),
           ("fusion.4", "f32[4]{0} fusion(%d)", 450, 20),
           ("fusion.5", "f32[4]{0} fusion(%e)", 470, 30),
           ("bare.7", "f32[4]{0} add(%g, %h)", 500, 5),
           ("ghost.9", "f32[4]{0} add(%g, %h)", 505, 5)]
    return [ev(i, t, s + 1000 * k, d) for k in (0, 1) for i, t, s, d in one]


BUSY_S = 2 * 510e-9


def a_run(**more):
    run = {"workload": "none", "labelled_busy_s": BUSY_S, "steps_traced": 2,
           "samples_per_step": 4, "chips": 1, "device_kind": "TPU v5e",
           "scope_time": scope_time.reduce(HLO, events())}
    run.update(more)
    return run


def test_instruction_to_op_type_and_path():
    got = scope_time.paths_of(HLO)
    assert got["fusion.2"] == ("conv2d_grad", "stage1.block1")
    assert got["fusion.5"] == ("relu", "")
    assert got["copy.1"] == ("mut_state", "")
    assert "bare.7" not in got
    # the first level stays what hlo.py reads: op types, no marked segment
    from chipbench import hlo

    assert set(hlo.instruction_scopes(HLO).values()) == {
        "mut_state", "conv2d", "conv2d_grad", "momentum", "mean", "relu"}


def test_table_is_self_time_by_op_type_and_path():
    by = a_run()["scope_time"].by
    ns = {k: round(v * 1e9) for k, v in by.items()}
    assert ns == {("mut_state", ""): 20, ("conv2d", "stem"): 200,
                  ("conv2d_grad", "stage1.block1"): 500,   # less its child
                  ("conv2d", "stage10.block1"): 100,
                  ("momentum", "stage1.block2"): 80, ("mean", "head"): 40,
                  ("relu", ""): 60, ("unjoined", ""): 20}
    inst = a_run()["scope_time"].instructions
    assert inst["fusion.3"][:2] == ("momentum", "stage1.block2")
    assert inst["fusion.3"][3] == "(f32[8]{0}, f32[8]{0})"
    assert inst["fusion.1"][3] == "bf16[4,8]{1,0}"
    assert scope_time.result_shape(
        "%f.1 = (f32[64]{0:T(128)}, bf16[8,8]{1,0:T(8,128)(2,1)}) fusion(%a)"
    ) == "(f32[64]{0:T(128)}, bf16[8,8]{1,0:T(8,128)(2,1)})"


def test_share_takes_whole_segments_and_patterns():
    run = a_run()
    assert scope_time.share(run, ("stage1",)) == \
        pytest.approx(580e-9 / BUSY_S)               # not stage10's
    assert scope_time.share(run, ("stage*",)) == \
        pytest.approx(680e-9 / BUSY_S)
    assert scope_time.share(run, ("stage*.block2",)) == \
        pytest.approx(80e-9 / BUSY_S)
    assert scope_time.share(run, ("stem", "head")) == \
        pytest.approx(240e-9 / BUSY_S)
    assert scope_time.share(run, ("stage2",)) is None
    assert scope_time.scoped_share(run) == pytest.approx(920e-9 / BUSY_S)
    assert scope_time.pct(None) is None and scope_time.pct(0.5) == 50.0


def test_none_without_a_trace_or_without_paths():
    assert scope_time.share({"steps": 3}, ("stem",)) is None
    assert scope_time.mfu({"steps": 3}, ("stem",)) is None
    # a rehearsal: a lowered step and no device plane
    assert scope_time.share({"optimized_hlo": HLO, "trace": None,
                             "workload": "none"}, ("stem",)) is None
    # the parent: a trace, and no instruction with a marked segment
    bare = "\n".join(l for l in HLO.splitlines() if "~" not in l)
    assert scope_time.reduce(bare, events()) is None
    run = a_run(scope_time=None)
    for name in ("stem_time_pct", "stage3_mfu_pct", "head_mfu_pct",
                 "mixer_time_pct", "scoped_time_pct"):
        assert plugins.load("layer_metrics", name).value(run) is None
        assert plugins.load("layer_metrics", name).value({"steps": 1}) is None


@pytest.fixture
def resnet50():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        resnet.build(class_dim=1000, depth=50, image_shape=(3, 224, 224))
    return main


def test_needed_flops_of_resnet50_by_stage_to_the_flop(resnet50):
    forward = {"stem": 236_027_904, "stage1": 1_335_885_824,
               "stage2": 1_901_068_288, "stage3": 2_774_532_096,
               "stage4": 1_464_336_384, "head": 4_096_000}
    for path, want in forward.items():
        assert scope_time.needed_flops((path,), resnet50) == 3 * want, path
    assert sum(forward.values()) == 7_715_946_496 \
        == flops.forward_flops(resnet50)
    assert scope_time.needed_flops(("*",), resnet50) \
        == flops.train_flops_per_sample(resnet50)
    assert scope_time.needed_flops(("stage1.block1",), resnet50) \
        > scope_time.needed_flops(("stage1.block2",), resnet50) > 0
    assert scope_time.needed_flops(("stage5",), resnet50) is None
    assert scope_time.uncounted_under(("*",), resnet50) == []
    assert scope_time.ops_without_scope(resnet50) == 0


def test_mfu_is_needed_work_over_the_time_under_the_name(resnet50):
    run = a_run()
    got = scope_time.mfu(run, ("stage1",), resnet50)
    # 3 x 1.336 GFLOP an image, 4 images a step, 2 steps, in 580 ns
    assert got == pytest.approx(
        3 * 1_335_885_824 * 4 * 2 / (580e-9 * 197e12))
    assert scope_time.mfu(run, ("stage2",), resnet50) is None   # no time
    four = scope_time.mfu(dict(run, chips=4), ("stage1",), resnet50)
    assert four == pytest.approx(got / 4)


def test_printed_tables(resnet50):
    run = a_run()
    said = scope_time.report(run, run["scope_time"], resnet50)
    lines = said.splitlines()
    assert lines[0].startswith("scopes: (2 steps traced")
    first = [l.split()[0] for l in lines[1:6]]
    assert first == ["stage1", "stem", "stage10", "head", "(no"]
    assert "MFU" in lines[1] and "MFU" in lines[2] and "MFU" not in lines[3]
    assert "relu 0.000" in lines[5] and "unjoined" in lines[5]
    at = lines.index("scopes x op types (ms a step):")
    assert lines[at + 1].split()[:2] == ["stage1", "conv2d_grad"]
    assert "momentum" in lines[at + 1]
    at = lines.index("longest instructions of the three heaviest paths "
                     "(ms a step):")
    assert lines[at + 1].split()[0] == "stage1.block1"
    assert "fusion.2" in lines[at + 2] and "bf16[4,8]{1,0}" in lines[at + 2]


# -- the entries and their readers, found by name --------------------------

BY_MODEL = {
    "resnet": ({RESNET}, ["stem_time_pct"]
               + [f"stage{k}_{w}_pct" for k in range(1, 5)
                  for w in ("time", "mfu")]),
    "transformer": ({TRANSFORMER}, ["encoder_time_pct", "decoder_time_pct",
                                    "embed_time_pct"]),
    "decoder_lm": (set(DECODERS), ["mixer_time_pct", "ffn_time_pct"]),
}


def entry(name):
    found = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert len(found) == 1, name
    return found[0]


@pytest.mark.parametrize("model", sorted(BY_MODEL))
def test_every_metric_of_the_models_blocks_has_its_reader_and_cells(model):
    cells, names = BY_MODEL[model]
    everywhere = {"head_time_pct": set(CELLS), "scoped_time_pct": set(CELLS),
                  "ops_without_scope": set(CELLS),
                  "head_mfu_pct": set(CELLS) - {RESNET}}
    for name in names + sorted(everywhere):
        m = entry(name)
        want = everywhere.get(name, cells)
        # of the cells this file knows by name, exactly those; cells that
        # later PRs add may be listed beside them
        assert set(m["workloads"]) & set(CELLS) == want
        assert cells <= want | {RESNET}
        assert m["layer"] == "model blocks" and m["moves"] == "step_ms_p95"
        counter = name == "ops_without_scope"
        assert m["source"] == ("program_counter" if counter
                               else "device_trace")
        assert m["unit"] == ("count" if counter else "%")
        assert m["better"] == ("higher" if "mfu" in name
                               or name == "scoped_time_pct" else "lower")
        assert plugins.load("layer_metrics", name) is not None


def test_the_new_entries_are_the_files_last_ones():
    new = [n for names in (BY_MODEL["resnet"][1], BY_MODEL["transformer"][1],
                           BY_MODEL["decoder_lm"][1]) for n in names]
    new += ["head_time_pct", "head_mfu_pct", "scoped_time_pct",
            "ops_without_scope"]
    # one run of the file, each once, wherever later PRs' entries put it
    names = [m["name"] for m in BENCH["per_layer"]]
    at = sorted(names.index(n) for n in new)
    assert all(names.count(n) == 1 for n in new)
    assert at == list(range(at[0], at[0] + len(new)))


def test_readers_take_the_paths_the_models_give():
    run = a_run()
    value = {n: plugins.load("layer_metrics", n).value(run)
             for n in ("stem_time_pct", "stage1_time_pct", "head_time_pct",
                       "scoped_time_pct", "encoder_time_pct")}
    assert value["stem_time_pct"] == pytest.approx(100 * 200 / 1020)
    assert value["stage1_time_pct"] == pytest.approx(100 * 580 / 1020)
    assert value["head_time_pct"] == pytest.approx(100 * 40 / 1020)
    assert value["scoped_time_pct"] == pytest.approx(100 * 920 / 1020)
    assert value["encoder_time_pct"] is None


# -- every cell's program carries a path on every op -----------------------

@pytest.mark.parametrize("cell", CELLS)
def test_no_op_of_the_cells_program_is_without_a_path(cell, capsys):
    import paddle_tpu.fluid as fluid

    config = [c for c in BENCH["configs"] if c["name"] ==
              [w for w in BENCH["workloads"] if w["name"] == cell][0]
              ["config"]][0]
    sizes = json.load(open(os.path.join(ROOT, config["file"])))
    sizes = {**sizes, **sizes["tiny"]}
    rel = os.path.relpath(os.path.dirname(config["file"]), "chipbench")
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        plugins.load(rel, "build").build(fluid, sizes)
        assert fluid.default_main_program() is main
        n = plugins.load("layer_metrics", "ops_without_scope").value({})
    assert n == 0
    assert "ops_without_scope 0 of the main program's" \
        in capsys.readouterr().out
    tops = {op.attr(scope_time.ATTR).partition(".")[0]
            for op in main.global_block().ops}
    first = {"resnet50_imagenet": {"stem", "stage1", "stage4", "head"},
             "transformer_base_wmt": {"embed", "encoder", "decoder", "head"}}
    assert first.get(config["name"], {"embed", "layer0", "head"}) <= tops


def test_a_program_without_name_scopes_reports_nothing():
    import paddle_tpu.fluid as fluid

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        fluid.layers.fc(input=x, size=2)
    assert scope_time.ops_without_scope(main) is None


def test_a_rehearsal_prints_the_count_and_reports_it():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", RESNET, "--seed", "2147489999", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert any(l.startswith("ops_without_scope 0 of") for l in lines)
    last = json.loads(lines[-1])
    assert last["metrics"]["ops_without_scope"] == {"value": 0,
                                                    "unit": "count"}
    assert all(m["unit"] == "count" for m in last["metrics"].values())
