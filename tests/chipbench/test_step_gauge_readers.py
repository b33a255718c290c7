"""``chipbench/step_gauges.py`` and the three per-layer metrics that read the
program's step gauges (``paddle_tpu.observe.step_gauges``, PR 54): each reader
on a hand-made ring, None where the program has no such reader, the entries
in ``BENCHMARK.json`` found by name, and ``moe_gauged_layers`` through one
cell's rehearsal against the program's own count of ``moe_experts`` ops.  CPU
only; nothing here counts the benchmark's cells or metrics or looks at the end
of a list: later PRs append theirs."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import plugins, step_gauges  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW = {"moe_live_rows_pct": ("%", "higher"),
       "moe_live_rows_range_pct": ("%", "lower"),
       "moe_gauged_layers": ("count", "higher")}
CELL = "lfm2_8b_a1b.resident"


def entry_of(group, name):
    found = [e for e in BENCH[group] if e["name"] == name]
    assert len(found) == 1, (group, name)
    return found[0]


def values(live, fullest, rows=100.0):
    """One step's gauges as the program renders them: ``live`` a scope."""
    out = {'executor.other{scope="layer1.ffn"}': 7.0}
    for scope, n in live.items():
        out[f'ops.moe.live_rows{{scope="{scope}"}}'] = float(n)
        out[f'ops.moe.rows{{scope="{scope}"}}'] = rows
        out[f'ops.moe.fullest_group{{scope="{scope}"}}'] = float(fullest)
    return out


# steps called at t = 0 .. 5; the window is [1, 4]
RING = [(i, f"span{i}", float(i),
         values({"layer1.ffn": 20 + 10 * i, "mtp.ffn": 30}, fullest=9 + i))
        for i in range(6)]
RUN = {"stamps": [1.0, 2.5, 4.0]}


@pytest.fixture
def ring(monkeypatch):
    asked = []

    def entries(since=None):
        asked.append(since)
        return [e for e in RING if since is None or e[2] >= since]

    monkeypatch.setattr(step_gauges, "entries", entries)
    return asked


def test_split_reads_a_rendered_name_back():
    assert step_gauges.split('ops.moe.rows{call="2",scope="mtp.ffn"}') == (
        "ops.moe.rows", {"call": "2", "scope": "mtp.ffn"})
    assert step_gauges.split("executor.dispatches") == (
        "executor.dispatches", {})


def test_window_of_takes_the_steps_called_inside_the_window():
    got = step_gauges.window_of(RING, 1.0, 4.0)
    assert got["steps"] == 4
    # (30 + 30) / 200 at step 1 .. (60 + 30) / 200 at step 4
    assert got["shares"] == pytest.approx([0.30, 0.35, 0.40, 0.45])
    assert got["scopes"] == {
        "layer1.ffn": {"first": 30.0, "median": 45.0, "last": 60.0,
                       "rows": 100.0, "fullest": 12.0},
        "mtp.ffn": {"first": 30.0, "median": 30.0, "last": 30.0,
                    "rows": 100.0, "fullest": 12.0}}
    assert step_gauges.window_of(RING, 10.0, 11.0) is None
    assert step_gauges.window_of(
        [(0, "s", 1.0, {"executor.other": 1.0})], 0.0, 2.0) is None


@pytest.mark.parametrize("name,want", [
    ("moe_live_rows_pct", 37.5), ("moe_live_rows_range_pct", 15.0),
    ("moe_gauged_layers", 2.0)])
def test_layer_metric_on_a_hand_made_ring(name, want, ring, capsys):
    run = dict(RUN)
    mod = plugins.load("layer_metrics", name)
    assert mod.value(run) == pytest.approx(want)
    assert mod.value(run) == pytest.approx(want)
    assert ring == [1.0]                # one call a run, kept in its record
    line, = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("expert layer load over 4 steps")]
    assert "layer1.ffn 30/45/60 of 100, fullest 12" in line
    assert "mtp.ffn 30/30/30 of 100, fullest 12" in line
    assert "step_gauges(wait=True) took" in line


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("found", [None, []])
def test_a_program_without_step_gauges_leaves_the_metric_out(
        name, found, monkeypatch, capsys):
    monkeypatch.setattr(step_gauges, "entries", lambda since=None: found)
    assert plugins.load("layer_metrics", name).value(dict(RUN)) is None
    assert "expert layer load" not in capsys.readouterr().out


def test_entries_is_none_where_the_program_has_no_reader(monkeypatch):
    from paddle_tpu import observe

    assert step_gauges.entries() == []          # the program's own, empty
    monkeypatch.delattr(observe, "step_gauges")
    assert step_gauges.entries() is None


def test_the_three_entries_list_the_cells_of_moe_time_pct_with_a_reader():
    cells = entry_of("per_layer", "moe_time_pct")["workloads"]
    assert len(cells) >= 7
    for name, (unit, better) in NEW.items():
        e = entry_of("per_layer", name)
        assert (e["unit"], e["better"], e["source"], e["layer"],
                e["moves"]) == (unit, better, "program_counter",
                                "expert layer", "step_ms_p95")
        assert set(cells) <= set(e["workloads"])
        assert hasattr(plugins.load("layer_metrics", name), "value")
    names = {w["name"] for w in BENCH["workloads"]}
    assert set(entry_of("per_layer", "moe_gauged_layers")["workloads"]) \
        <= names


def test_rehearsal_counts_the_routed_layers_the_program_builds():
    """One cell's whole path on the CPU: ``moe_gauged_layers`` is the
    program's own count of ``moe_experts`` ops at the ``tiny`` size, and the
    line of the expert layer's load is printed."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import unique_name

    config = entry_of("configs", entry_of("workloads", CELL)["config"])
    sizes = json.load(open(os.path.join(ROOT, config["file"])))
    sizes = {**sizes, **sizes["tiny"]}
    rel = os.path.relpath(os.path.dirname(config["file"]), "chipbench")
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), unique_name.guard():
        plugins.load(rel, "build").build(fluid, sizes)
    routed = sum(op.type == "moe_experts" for op in main.global_block().ops)
    assert routed >= 1
    assert last["metrics"]["moe_gauged_layers"] == {
        "value": float(routed), "unit": "count"}
    line, = [l for l in lines if l.startswith("expert layer load over")]
    assert line.count(" of ") >= routed
