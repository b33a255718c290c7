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
sys.path.insert(0, ROOT)

from chipbench import cuts  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


def own_environment():
    """This process's, less what would pin a child's devices."""
    return {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}


def rehearsal(root, workload, trace, seed):
    """The one command in rehearsal mode, run to its end with exit code 0."""
    p = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace), "--rehearse"],
        cwd=root, env=own_environment(), capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return p


def rehearse(root, workload, trace, seed=2147489999):
    """Run the one command in rehearsal mode; returns (lines, last)."""
    lines = rehearsal(root, workload, trace, seed).stdout.strip().splitlines()
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
    assert cuts.problems(sizes, c) == []
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
    "precision": "bfloat16", "reduced": [], "width": 32,
    "num_hidden_layers": 1, "vocab_size": 4,
    "batch_per_chip": 8, "check_batch": 8,
    "optimizer": {"type": "sgd", "lr": 0.1},
    "limits": {"grad_rel": 0.2, "update_rel": 0.01},
    "tiny": {},
}
TOY_BUILD = '''
import numpy as np


def LR(sizes):
    return sizes["optimizer"]["lr"]


def build(fluid, sizes, deterministic=False):
    x = fluid.layers.data(name="x", shape=[sizes["width"]], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    h = x
    for _ in range(sizes["num_hidden_layers"]):
        h = fluid.layers.fc(input=h, size=sizes["width"], act="relu")
    p = fluid.layers.fc(input=h, size=sizes["vocab_size"], act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(input=p, label=y))
    fluid.optimizer.SGD(learning_rate=LR(sizes)).minimize(loss)
    return {"loss": loss, "units_per_sample": 1}


def make_feed(sizes, batch, rng):
    return {"x": rng.normal(size=(batch, sizes["width"])).astype(np.float32),
            "y": rng.randint(0, sizes["vocab_size"],
                             size=(batch, 1)).astype(np.int64)}


def trainable_names(program):
    return [p.name for p in program.global_block().all_parameters()]
'''
TOY_REFERENCE = '''
import jax
import jax.numpy as jnp
import numpy as np


def param_spec(s):
    w, c = s["width"], s["vocab_size"]
    hidden = [(n + str(i), shape, None) for i in range(s["num_hidden_layers"])
              for n, shape in (("w", (w, w)), ("b", (w,)))]
    return hidden + [("w_out", (w, c), None), ("b_out", (c,), None)]


def init_params(seed, s):
    key = jax.random.PRNGKey(np.uint32(seed % 2 ** 32))
    return [0.3 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
            for i, (_, shape, _) in enumerate(param_spec(s))]


def loss_fn(params, feed, s, matmul_dtype=None):
    def q(a):
        return a if matmul_dtype is None else \\
            a.astype(matmul_dtype).astype(jnp.float32)
    *hidden, w_out, b_out = params
    h = feed["x"]
    for w, b in zip(hidden[::2], hidden[1::2]):
        h = jax.nn.relu(q(h) @ q(w) + b)
    logp = jax.nn.log_softmax(q(h) @ q(w_out) + b_out)
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


def files_of(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            path = os.path.join(d, f)
            out[path] = open(path, "rb").read()
    return out


def add_toy(root, config, build=TOY_BUILD, flops=None):
    """The toy configuration ``config`` as files of its own, and a bench
    (not yet written) with its entry and one cell ``<name>.resident``."""
    name = config["name"]
    cfg = os.path.join(root, "chipbench", "configs", name)
    os.makedirs(cfg)
    json.dump(config, open(os.path.join(cfg, "config.json"), "w"))
    open(os.path.join(cfg, "build.py"), "w").write(build)
    open(os.path.join(cfg, "reference.py"), "w").write(TOY_REFERENCE)
    if flops:
        open(os.path.join(cfg, "flops.py"), "w").write(flops)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": name, "source": config["source"], "why": "test",
        "reduced": config["reduced"],
        "file": f"chipbench/configs/{name}/config.json"})
    bench["workloads"].append({
        "name": name + ".resident", "config": name, "traffic": "resident",
        "chips": 1, "why": "test"})
    return bench


def test_config_traffic_metric_and_cell_added_as_new_files_only(tmp_path):
    root = temporary_checkout(tmp_path)
    before = files_of(root)

    bench = add_toy(root, TOY_CONFIG)
    traffic = json.load(open(os.path.join(
        root, "chipbench", "traffic", "resident.json")))
    traffic.update(name="resident_x2", batch_per_chip_scale=2)
    json.dump(traffic, open(os.path.join(
        root, "chipbench", "traffic", "resident_x2.json"), "w"))
    open(os.path.join(root, "chipbench", "layer_metrics",
                      "steps_in_window.py"), "w").write(TOY_METRIC)
    bench["workloads"][-1].update(name="toy_mlp.resident_x2",
                                  traffic="resident_x2")
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
    assert lines[0] == "cut: none"
    # its optimizer op is none the walk of chipbench/flops.py knows, and it
    # states no FLOPs of its own: no reading, and the record says why
    assert [l for l in lines if l.startswith("flops per sample: withheld")
            and "['sgd']" in l], lines
    for path, content in before.items():
        assert open(path, "rb").read() == content, path + " was edited"


# -- a configuration that is one chip's share of a deployment -----------

TOY_CUT = {
    **TOY_CONFIG, "name": "toy_cut", "num_hidden_layers": 4,
    "vocab_size": 16, "num_experts": 8,
    "reduced": ["num_hidden_layers", "vocab_size", "num_experts"],
    "published": {"num_hidden_layers": 12, "vocab_size": 128,
                  "num_experts": 64},
    "deployment": {
        "chips_sharing_a_layer": 8,
        "how": "experts and vocabulary rows of a layer lie on 8 chips",
        "cuts": {
            "num_hidden_layers": {"kind": "depth", "why": "the layers left "
                                  "out lie on further pipeline stages"},
            "vocab_size": {"kind": "vocabulary", "why": "an eighth of the "
                           "rows; ids and loss are over the slice"},
            "num_experts": {"kind": "experts_held", "why": "an eighth of a "
                            "layer's routed experts (the toy routes none)"}}},
}
TOY_FLOPS = '''
"""Two operations per multiply-accumulate of every fc, forward and twice
that backward, from the sizes as run."""


def train_flops_per_sample(sizes):
    w = sizes["width"]
    return 3 * 2 * (sizes["num_hidden_layers"] * w * w
                    + w * sizes["vocab_size"])
'''


def test_cut_configuration_with_its_own_flops_added_as_new_files_only(
        tmp_path):
    root = temporary_checkout(tmp_path)
    before = files_of(root)
    bench = add_toy(root, TOY_CUT, flops=TOY_FLOPS)
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    assert cuts.problems(TOY_CUT, bench["configs"][-1]) == []

    lines, last = rehearse(root, "toy_cut.resident", trace=1, seed=11)
    assert last["correct"] is True, lines
    assert lines[0] == (
        "cut: num_hidden_layers 4 of 12 (depth), vocab_size 16 of 128 "
        "(vocabulary), num_experts 8 of 64 (experts_held); one of 8 chips "
        "that share a layer: experts and vocabulary rows of a layer lie on "
        "8 chips")
    want = 3 * 2 * (4 * 32 * 32 + 32 * 16)
    assert (f"flops per sample: {want}, stated by "
            "chipbench/configs/toy_cut/flops.py") in lines
    for path, content in before.items():
        assert open(path, "rb").read() == content, path + " was edited"


def changed(config, **top):
    """A deep copy of ``config`` with keys replaced; ``a__b`` reaches into
    ``config["a"]["b"]``, and None deletes the key."""
    out = json.loads(json.dumps(config))
    for key, v in top.items():
        where = out
        *path, last = key.split("__")
        for part in path:
            where = where[part]
        if v is None:
            del where[last]
        else:
            where[last] = v
    return out


@pytest.mark.parametrize("config, entry_reduced, reason", [
    (TOY_CUT, ["num_hidden_layers", "vocab_size"], "reduced differs"),
    (changed(TOY_CUT, published__vocab_size=None),
     None, "vocab_size: missing from `published`"),
    (changed(TOY_CUT, num_experts=7),
     None, "num_experts: 7 as run is under the floor of 8"),
    (changed(TOY_CUT, num_hidden_layers=3),
     None, "num_hidden_layers: 3 as run is under the floor of 4"),
    (changed(TOY_CUT, vocab_size=15),
     None, "vocab_size: 15 as run is under the floor of 16"),
    (changed(TOY_CUT, published__num_experts=8),
     None, "num_experts: published 8 is not larger"),
    (changed(TOY_CUT, deployment=None), None, "states `deployment`"),
    (changed(TOY_CUT, deployment__chips_sharing_a_layer=0),
     None, "chips_sharing_a_layer is a whole number"),
    (changed(TOY_CUT, reduced=TOY_CUT["reduced"] + ["width"],
             published__width=64,
             deployment__cuts__width={"kind": "depth", "why": "narrower"}),
     TOY_CUT["reduced"] + ["width"], "width: a cut of a width"),
    (changed(TOY_CUT, reduced=["num_hidden_layers: 4 of 12"]),
     ["num_hidden_layers: 4 of 12"], "each a name"),
], ids=["differs_between_the_files", "missing_from_published",
        "experts_under_8", "depth_under_4", "vocabulary_under_an_eighth",
        "published_not_larger", "no_deployment", "no_chips", "a_width",
        "not_a_name"])
def test_cut_configuration_is_refused(config, entry_reduced, reason):
    entry = {"name": config["name"], "reduced":
             config["reduced"] if entry_reduced is None else entry_reduced}
    wrong = cuts.problems(config, entry)
    assert any(reason in w for w in wrong), wrong
    assert len(wrong) == 1, wrong


def test_run_refuses_a_cut_that_breaks_the_rules(tmp_path):
    root = temporary_checkout(tmp_path)
    bench = add_toy(root, changed(TOY_CUT, num_experts=7))
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", "toy_cut.resident", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()
    assert "under the floor of 8" in p.stderr


# -- the timed path broken underneath ------------------------------------

def test_a_step_that_leaves_its_state_unchanged_is_not_correct(tmp_path):
    """The harness's whole run but for its look for a chip, over a program
    whose optimizer moves nothing (the configuration states lr 0.1)."""
    root = temporary_checkout(tmp_path)
    broken = TOY_BUILD.replace('return sizes["optimizer"]["lr"]',
                               "return 0.0")
    assert broken != TOY_BUILD
    bench = add_toy(root, TOY_CONFIG, build=broken)
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    lines, last = rehearse(root, "toy_mlp.resident", trace=0, seed=13)
    assert last["correct"] is False and last["failed"] == 0
    assert [l for l in lines if l.startswith("compare update_rel:")
            and l.endswith("NOT WITHIN")], lines
    assert [l for l in lines if l.startswith("compare grad_rel:")
            and l.endswith(" ok")], lines


# -- the comparison holds no copy it does not need ------------------------

def test_comparison_takes_device_arrays_as_they_are(monkeypatch):
    """Live array bytes while the comparison runs: the reference's weights,
    the fetched gradients and the parameters after the step, 3 copies of
    the parameters, with a quarter of one for the feed and the loss.  With a
    round trip through the host there is a fourth: the gradients again."""
    import types

    import jax
    import numpy as np

    from chipbench import check

    ref = types.ModuleType("toy_reference")
    exec(TOY_REFERENCE, ref.__dict__)
    sizes = {**TOY_CONFIG, "width": 256, "num_hidden_layers": 4}
    rng = np.random.RandomState(5)
    feed = {"x": rng.normal(size=(8, 256)).astype(np.float32),
            "y": rng.randint(0, 4, size=(8, 1)).astype(np.int64)}

    def live_bytes():
        return sum(a.nbytes for a in jax.live_arrays())

    start = live_bytes()
    weights = ref.init_params(3, sizes)
    one_copy = sum(w.nbytes for w in weights)
    loss, grads = ref.loss_and_grads(weights, feed, sizes)
    after = [ref.optimizer_step(w, g, sizes) for w, g in zip(weights, grads)]
    seen = {}
    jitted = check._jitted

    def watched(*key):
        fn = jitted(*key)

        def call(*args):
            host = [a for a in jax.tree_util.tree_leaves(args)
                    if not isinstance(a, jax.Array)]
            seen["bytes"] = live_bytes() - start \
                + sum(np.asarray(a).nbytes for a in host)
            seen["args"] = args
            return fn(*args)
        return call

    monkeypatch.setattr(check, "_jitted", watched)
    numbers = check.program(ref, sizes, weights, feed, loss, grads, after)
    assert numbers["grad_rel"] == 0.0 and numbers["update_rel"] < 1e-3
    assert all(a is b for a, b in zip(seen["args"][3], grads))
    assert all(a is b for a, b in zip(seen["args"][4], after))
    assert seen["args"][2] is loss
    assert 3 * one_copy <= seen["bytes"] <= 3.25 * one_copy, \
        (seen["bytes"], one_copy)


# -- the run's order: seed, warm up, window, reading, comparison last ------

def at(lines, start):
    found = [i for i, l in enumerate(lines) if l.startswith(start)]
    assert len(found) == 1, (start, found)
    return found[0]


def test_phases_come_in_the_order_weights_warm_up_window_comparison(
        transformer_rehearsal):
    lines, _ = transformer_rehearsal
    order = [at(lines, s) for s in (
        "memory after weights:", "memory after warm-up:",
        "memory after window:", "memory 0:", "compare grad_rel:",
        "compare update_rel:", "memory after comparison:", "set-up:")]
    assert order == sorted(order), order
    # the traced stretch lies between the reading and the comparison
    assert order[3] < at(lines, "flops per sample:") < order[4]


def test_setup_ends_before_the_comparison_begins(transformer_rehearsal):
    lines, _ = transformer_rehearsal
    said = lines[at(lines, "comparison: began ")]
    began, opened = (float(x) for x in re.findall(r"(\d+\.\d+) s", said))
    assert opened + 1.0 <= began, said     # the window's second lies between
    setup, after = lines[at(lines, "set-up:")].split(
        "; after the window, in no metric: ")
    assert "reference_check_s" not in setup and "reseed_s" not in setup
    assert "first_call_s" in setup and "weights_s" in setup
    assert "reference_check_s" in after and "reseed_s" in after
    parts = dict(p.rsplit(" ", 1) for p in setup[len("set-up: "):].split(", "))
    assert sum(float(v) for v in parts.values()) <= opened


def test_a_comparison_that_fails_ends_not_correct_with_its_numbers_last(
        tmp_path):
    """Limits of 0 in a temporary copy: the window has run and the reading
    is taken when the comparison fails, and the run still ends ``correct:
    false``, exit code 0, the compared numbers its last lines of stderr."""
    root = temporary_checkout(tmp_path)
    config = changed(TOY_CONFIG, limits__grad_rel=0.0, limits__update_rel=0.0)
    json.dump(add_toy(root, config),
              open(os.path.join(root, "BENCHMARK.json"), "w"))
    p = rehearsal(root, "toy_mlp.resident", trace=0, seed=2147483900)
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert last["attempted"] > 0
    said = p.stderr.strip().splitlines()[-3:]
    assert said[0].startswith("compare grad_rel:") \
        and said[0].endswith("limit 0.0 NOT WITHIN"), said
    assert said[1].startswith("compare update_rel:") and " limit 0.0 " \
        in said[1]
    assert said[2].startswith("not compared: loss_rel")
    assert said == [l for l in p.stdout.splitlines()
                    if l.startswith(("compare ", "not compared:"))]
    # and in the result's line, under a key of its own that comes last
    assert list(last)[-1] == "compared"
    assert set(last["compared"]) == {"grad_rel", "update_rel"}
    for k, line in zip(("grad_rel", "update_rel"), said):
        assert last["compared"][k]["limit"] == 0.0
        assert repr(last["compared"][k]["value"]) in line
