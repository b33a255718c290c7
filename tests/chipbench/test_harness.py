"""The harness against its contract: ``BENCHMARK.json`` is well formed, a
rehearsal run ends in the contract's one JSON object, and a configuration,
a traffic mix, a layer metric and a cell added AS NEW FILES ONLY are found
and run.  CPU only; no TPU topology is described here."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(root, workload, trace, seed=2147489999):
    """Run the one command in rehearsal mode; returns (lines, last)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace), "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    return lines, json.loads(lines[-1])


# -- BENCHMARK.json ----------------------------------------------------

def all_metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["chipbench", "tests/chipbench"]
    assert len(json.dumps(BENCH)) < 64 * 1024
    n = len(BENCH["workloads"])
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200, "run_seconds must fit a check of the full 24 cells"
    assert 1 <= n <= 24


@pytest.mark.parametrize("entry", all_metrics() + BENCH["workloads"]
                         + BENCH["configs"], ids=lambda e: e["name"])
def test_names_are_allowed(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in ("why", "layer", "source"):
        if key in entry and key != "source" or \
                (key == "source" and "file" in entry):
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]


@pytest.mark.parametrize("m", all_metrics(), ids=lambda m: m["name"])
def test_metric_entries(m):
    assert UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    e2e = m in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | \
        ({"bound"} if e2e else {"layer", "moves"})
    assert set(m) <= allowed and set(m) >= allowed - {"workloads"}
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    kind = "metrics" if e2e else "layer_metrics"
    assert os.path.isfile(os.path.join(ROOT, "chipbench", kind,
                                       m["name"] + ".py"))


def cells_of(metric):
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric_each_of_its_cells_reports(m):
    target = [e for e in BENCH["end_to_end"] if e["name"] == m["moves"]]
    assert len(target) == 1
    assert set(cells_of(m)) <= set(cells_of(target[0]))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cells(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4)
    assert w["config"] in [c["name"] for c in BENCH["configs"]]
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "traffic",
                                       w["traffic"] + ".json"))
    mine = [e["name"] for e in BENCH["end_to_end"] if w["name"] in cells_of(e)]
    assert "setup_s" in mine and len(mine) >= 2
    assert any(w["name"] in cells_of(m) for m in BENCH["per_layer"])


def test_at_most_one_cell_asks_for_four_chips():
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_configs(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    sizes = json.load(open(os.path.join(ROOT, c["file"])))
    assert sizes["reduced"] == c["reduced"] == []
    assert sizes["source"] == c["source"]
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    d = os.path.dirname(os.path.join(ROOT, c["file"]))
    for f in ("build.py", "reference.py"):
        assert os.path.isfile(os.path.join(d, f))
    lim = sizes["limits"]
    assert set(lim) == {"grad_rel", "update_rel"}
    assert all(0 < v < 1 for v in lim.values())


def test_files_under_paths_are_named_from_allowed_characters():
    for base in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


# -- the one command, rehearsed ----------------------------------------

@pytest.fixture(scope="module")
def transformer_rehearsal():
    return rehearse(ROOT, "transformer_base_wmt.resident", trace=1)


def test_last_line_is_the_contracts_object(transformer_rehearsal):
    lines, last = transformer_rehearsal
    assert set(last) == RESULT_KEYS
    assert last["attempted"] > 0 and last["failed"] == 0
    assert isinstance(last["correct"], bool)
    assert last["device"]["platform"] == "cpu"
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_rehearsal_prints_counts_only(transformer_rehearsal):
    lines, last = transformer_rehearsal
    units = {m["name"]: m["unit"] for m in BENCH["per_layer"]}
    assert last["metrics"], "the traced rehearsal reports its counts"
    for name, m in last["metrics"].items():
        assert m["unit"] == units[name] == "count"
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["dispatches_per_step"]["value"] == 1


def test_rehearsal_prints_each_compared_number_beside_its_limit(
        transformer_rehearsal):
    lines, _ = transformer_rehearsal
    for key in ("grad_rel", "update_rel"):
        assert any(l.startswith(f"compare {key}:") and " limit " in l
                   for l in lines)
    counts = json.loads([l for l in lines if l.startswith(
        "rehearsal counts: ")][0].split(": ", 1)[1])
    assert counts["trace"]["host_spans"] > 0
    assert counts["trace"]["steps_traced"] >= 5


def test_no_accelerator_is_a_failure_not_a_fallback():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "transformer_base_wmt.resident", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    assert "no TPU" in p.stderr


# -- the path across chips, on four virtual devices ---------------------

def temporary_checkout(tmp_path):
    """A copy of the benchmark's files beside the program, to add to."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"),
               os.path.join(root, "paddle_tpu"))
    return root


def test_dp4_traffic_rehearses_through_parallel_executor(tmp_path):
    """``traffic/dp4.json`` is no cell yet (PERF.md, Open questions): this
    drives what it needs (the ``parallel_executor`` entry of ``loop.py``
    with its re-lowered step, a feed handed over from the host, the
    comparison with the reference under a mesh) with entries alone, as the
    PR that adds the cell will."""
    root = temporary_checkout(tmp_path)
    bench = json.loads(json.dumps(BENCH))
    cell = "transformer_base_wmt.dp4"
    bench["workloads"].append({
        "name": cell, "config": "transformer_base_wmt", "traffic": "dp4",
        "chips": 4, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "transformer_base_wmt.resident" in m.get("workloads", []) \
                and m["name"] != "dispatches_per_step":
            m["workloads"].append(cell)
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    lines, last = rehearse(root, cell, trace=1, seed=2147483659)
    assert set(last) == RESULT_KEYS
    assert last["correct"] is True, lines
    assert last["device"]["count"] >= 4 and last["failed"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert "dispatches_per_step" not in last["metrics"]
    counts = json.loads([l for l in lines if l.startswith(
        "rehearsal counts: ")][0].split(": ", 1)[1])
    assert counts["steps"] >= 1 and counts["trace"]["steps_traced"] >= 5
    assert [l for l in lines if l.startswith("memory 3:")]


# -- adding as files only ----------------------------------------------

TOY_CONFIG = {
    "name": "toy_mlp", "source": "a test", "task": "train", "unit": "rows",
    "precision": "bfloat16", "reduced": [], "width": 32, "classes": 4,
    "batch_per_chip": 8, "check_batch": 8,
    "optimizer": {"type": "sgd", "lr": 0.1},
    "limits": {"grad_rel": 0.2, "update_rel": 0.01},
    "tiny": {},
}
TOY_BUILD = '''
import numpy as np


def build(fluid, sizes, deterministic=False):
    x = fluid.layers.data(name="x", shape=[sizes["width"]], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=x, size=sizes["width"], act="relu")
    p = fluid.layers.fc(input=h, size=sizes["classes"], act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(input=p, label=y))
    fluid.optimizer.SGD(learning_rate=sizes["optimizer"]["lr"]).minimize(loss)
    return {"loss": loss, "units_per_sample": 1}


def make_feed(sizes, batch, rng):
    return {"x": rng.normal(size=(batch, sizes["width"])).astype(np.float32),
            "y": rng.randint(0, sizes["classes"],
                             size=(batch, 1)).astype(np.int64)}


def trainable_names(program):
    return [p.name for p in program.global_block().all_parameters()]
'''
TOY_REFERENCE = '''
import jax
import jax.numpy as jnp
import numpy as np


def param_spec(s):
    w, c = s["width"], s["classes"]
    return [("w1", (w, w), None), ("b1", (w,), None), ("w2", (w, c), None),
            ("b2", (c,), None)]


def init_params(seed, s):
    key = jax.random.PRNGKey(np.uint32(seed % 2 ** 32))
    return [0.3 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
            for i, (_, shape, _) in enumerate(param_spec(s))]


def loss_fn(params, feed, s, matmul_dtype=None):
    def q(a):
        return a if matmul_dtype is None else \\
            a.astype(matmul_dtype).astype(jnp.float32)
    w1, b1, w2, b2 = params
    h = jax.nn.relu(q(feed["x"]) @ q(w1) + b1)
    logp = jax.nn.log_softmax(q(h) @ q(w2) + b2)
    return -jnp.take_along_axis(logp, feed["y"], axis=-1).mean()


def loss_and_grads(params, feed, s, matmul_dtype=None):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(list(params), feed, s,
                                           matmul_dtype)


def optimizer_step(param, grad, s):
    return param - s["optimizer"]["lr"] * grad


def step_size(s):
    return s["optimizer"]["lr"]
'''
TOY_METRIC = '''
"""Steps completed in the window: a count a CPU can give."""


def value(run):
    return float(run["steps"])
'''


def test_config_traffic_metric_and_cell_added_as_new_files_only(tmp_path):
    root = temporary_checkout(tmp_path)
    before = {}
    for d, _, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            path = os.path.join(d, f)
            before[path] = open(path, "rb").read()

    cfg = os.path.join(root, "chipbench", "configs", "toy_mlp")
    os.makedirs(cfg)
    json.dump(TOY_CONFIG, open(os.path.join(cfg, "config.json"), "w"))
    open(os.path.join(cfg, "build.py"), "w").write(TOY_BUILD)
    open(os.path.join(cfg, "reference.py"), "w").write(TOY_REFERENCE)
    traffic = json.load(open(os.path.join(
        root, "chipbench", "traffic", "resident.json")))
    traffic.update(name="resident_x2", batch_per_chip_scale=2)
    json.dump(traffic, open(os.path.join(
        root, "chipbench", "traffic", "resident_x2.json"), "w"))
    open(os.path.join(root, "chipbench", "layer_metrics",
                      "steps_in_window.py"), "w").write(TOY_METRIC)

    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "toy_mlp", "source": "a test", "reduced": [], "why": "test",
        "file": "chipbench/configs/toy_mlp/config.json"})
    bench["workloads"].append({
        "name": "toy_mlp.resident_x2", "config": "toy_mlp",
        "traffic": "resident_x2", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "executor dispatch",
        "moves": "step_ms_p95", "workloads": ["toy_mlp.resident_x2"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    lines, last = rehearse(root, "toy_mlp.resident_x2", trace=1, seed=7)
    assert set(last) == RESULT_KEYS
    assert last["correct"] is True, lines
    assert last["metrics"]["steps_in_window"]["value"] >= 1
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    for path, content in before.items():
        assert open(path, "rb").read() == content, path + " was edited"
