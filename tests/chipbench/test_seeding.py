"""Seeding, as ``chipbench/run.py`` does it since the comparison runs last:
the reference makes the same weights from one seed every time (the scope
gets its arrays, the step donates them, and ``Cell.check`` makes them
again), and ``Cell.seed_state`` keeps no second copy of the parameters
alive, the first time or when it seeds over a state that is there.  CPU
only, the configurations' ``tiny`` sizes.  A file of its own so that the
test workers can run it beside ``test_harness.py``."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# this process's environment, less what would pin a child's devices
ENV = {k: v for k, v in os.environ.items()
       if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_reference_makes_the_same_weights_from_the_seed_twice(c):
    """``seed_state`` hands the reference's arrays to the scope, which the
    step donates, and ``check`` makes them again: bit for bit the same."""
    import numpy as np

    from chipbench import plugins

    sizes = json.load(open(os.path.join(ROOT, c["file"])))
    sizes = {**sizes, **sizes["tiny"]}
    rel = os.path.relpath(os.path.dirname(os.path.join(ROOT, c["file"])),
                          os.path.join(ROOT, "chipbench"))
    ref = plugins.load(rel, "reference")
    seed = 2147489999
    first = [np.asarray(w) for w in ref.init_params(seed, sizes)]
    again = [np.asarray(w) for w in ref.init_params(seed, sizes)]
    spec = ref.param_spec(sizes)
    assert len(first) == len(again) == len(spec)
    for (name, shape, _), a, b in zip(spec, first, again):
        assert a.shape == tuple(shape) and a.dtype == np.float32, name
        assert a.tobytes() == b.tobytes(), name


SEEDING = '''
import json, sys
sys.path.insert(0, {root!r})
from chipbench import run as R

bench = R.read_json(R.ROOT, "BENCHMARK.json")
cell = R.find(bench["workloads"], "transformer_base_wmt.resident", "workload")
entry = R.find(bench["configs"], cell["config"], "config")
traffic = R.read_json(R.HERE, "traffic", cell["traffic"] + ".json")
sizes = R.cell_sizes(entry, True)
R.prepare_environment(True, 1)
import jax
import numpy as np

built = R.Cell(cell, entry, traffic, sizes, True)
scope = built.fluid.global_scope()


def live():
    return sum(a.nbytes for a in jax.live_arrays())


seen = {{}}
make = built.reference.init_params


def watched(seed, sizes):
    # what is alive as the reference starts on its weights
    seen["live_as_the_reference_starts"] = live()
    seen["parameters_in_the_scope_then"] = sum(
        scope.get(n) is not None for n in built.names)
    return make(seed, sizes)


built.reference.init_params = watched
out = {{}}
for seeding in ("first", "again"):
    built.seed_state(2147489999)
    held = [scope.get(n) for n in scope.keys()]
    out[seeding] = dict(
        seen, live=live(),
        in_scope=sum(a.nbytes for a in held if isinstance(a, jax.Array)),
        parameters=sum(scope.get(n).nbytes for n in built.names),
        attributes_holding_arrays=sorted(
            k for k, v in vars(built).items()
            if any(isinstance(x, jax.Array)
                   for x in jax.tree_util.tree_leaves(v))),
        equal_to_the_reference=all(
            np.asarray(scope.get(n)).tobytes() == np.asarray(w).tobytes()
            for n, w in zip(built.names, make(2147489999, sizes))))
    del held                # or this script is what keeps the state alive
print(json.dumps(out))
'''


def test_seeding_keeps_no_second_copy_and_the_cell_holds_no_array():
    p = subprocess.run([sys.executable, "-c", SEEDING.format(root=ROOT)],
                       cwd=ROOT, env=ENV, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    for seeding in ("first", "again"):
        got = out[seeding]
        assert got["attributes_holding_arrays"] == [], got
        assert got["equal_to_the_reference"] is True
        assert got["parameters_in_the_scope_then"] == 0
        # the state with its parameters let go, and nothing of an earlier
        # state beside it: the second seeding starts from a full scope
        assert got["live_as_the_reference_starts"] \
            <= got["in_scope"] - got["parameters"] + 4096, got
        # afterwards the scope's arrays are all there is: no list kept
        assert got["in_scope"] <= got["live"] <= got["in_scope"] + 4096, got
        assert 3 * got["parameters"] <= got["in_scope"] \
            <= 3.05 * got["parameters"], got
