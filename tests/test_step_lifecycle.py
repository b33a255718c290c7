"""The one training-step lifecycle behind the executors (``fluid/step.py``).

Four entry points walk it: ``Executor.run``, ``Executor.run_steps``,
``ParallelExecutor.run`` and ``ParallelExecutor.run_steps``.  What a
compiled step is specialised on is decided in ONE function,
``step.signature``; these tests hold every path to it:

 (a) each execution-mode toggle makes every path build a fresh entry, and
     toggling back hits the old one;
 (b) the ``extra`` each kind hands the persistent compile cache has exactly
     the keys and values the four hand-written copies had before they were
     merged (literals written from that code), so no stored fingerprint
     moved;
 (c) the key is the program's serial, not its ``id()``.
"""

import gc
import json

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import compile_cache
from paddle_tpu.fluid import amp, fault, framework, guardian
from paddle_tpu.fluid import step as step_mod
from paddle_tpu.observe import trace


@pytest.fixture(autouse=True)
def clean_slate():
    fault.clear()
    guardian.disable()
    amp.disable()
    yield
    fault.clear()
    guardian.disable()
    amp.disable()


def _build(seed=7):
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    img = fluid.layers.data(name="img", shape=[16], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    pred = fluid.layers.fc(input=img, size=10, act="softmax")
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=pred, label=label))
    fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    return loss


def _feed():
    rng = np.random.RandomState(0)
    return {"img": rng.normal(size=(8, 16)).astype(np.float32),
            "label": rng.randint(0, 10, size=(8, 1)).astype(np.int64)}


class _Path:
    """One entry point: ``go()`` dispatches once, ``entries()`` counts what
    its in-process cache holds."""

    def __init__(self, name, loss):
        prog = fluid.default_main_program()
        if name.startswith("pe_"):
            pe = fluid.ParallelExecutor(loss_name=loss.name,
                                        main_program=prog, mesh="dp2")
            assert pe.device_count == 2
            cache = pe._cache if name == "pe_run" else pe._window_cache
            if name == "pe_run":
                self.go = lambda: pe.run([loss], feed=_feed())
            else:
                self.go = lambda: pe.run_steps([loss], feed=_feed(),
                                               n_steps=2)
        else:
            exe = fluid.Executor(fluid.CPUPlace())
            cache = exe._cache
            if name == "run":
                self.go = lambda: exe.run(prog, feed=_feed(),
                                          fetch_list=[loss])
            else:
                self.go = lambda: exe.run_steps(prog, feed=_feed(),
                                                fetch_list=[loss], n_steps=2)
        self.entries = lambda: len(cache)


def _toggle(name, monkeypatch, on):
    if name == "amp":
        amp.enable("bfloat16") if on else amp.disable()
    elif name == "guardian":
        guardian.enable("skip") if on else guardian.disable()
    elif on:
        monkeypatch.setenv(name, "0")
    else:
        monkeypatch.delenv(name)


# the per-step sharded path has no guarded wrapper (ROADMAP D15): the
# guardian does not change what it compiles, so it is no toggle of its key
CASES = [(p, t) for p in ("run", "run_steps", "pe_run", "pe_run_steps")
         for t in ("amp", "PADDLE_TPU_FLASH", "PADDLE_TPU_FUSED", "guardian")
         if (p, t) != ("pe_run", "guardian")]


@pytest.mark.parametrize("path,toggle", CASES)
def test_a_toggle_builds_a_fresh_entry_and_toggling_back_hits(
        path, toggle, monkeypatch):
    loss = _build()
    p = _Path(path, loss)

    def fresh_builds():
        """Builds seen by the spans: `fluid.run.build` (only a `fluid.run`
        root that says fresh has one) or `executor.trace`."""
        return sum(1 for r in trace.recorded()
                   if r.name in ("fluid.run.build", "executor.trace"))

    p.go()
    assert p.entries() == 1
    builds = fresh_builds()
    assert builds >= 1
    p.go()
    assert (p.entries(), fresh_builds()) == (1, builds)

    _toggle(toggle, monkeypatch, True)
    p.go()
    assert p.entries() == 2, f"{path}: {toggle} served a stale executable"
    assert fresh_builds() == builds + 1

    _toggle(toggle, monkeypatch, False)
    p.go()
    assert (p.entries(), fresh_builds()) == (2, builds + 1)


# written from the four copies as they stood before fluid/step.py
# (executor.py `_build_entry` / `run_steps`, spmd.py `cache_extra` and its
# two callers); `None` for amp is "AMP off", the CPU place is "cpu"
_TOGGLES = {"amp": None, "flash": "", "fused": ""}
_SPMD = {"platform": "spmd", "mesh": [["dp", 2]], "multihost": False,
         "zero1": False}
PARENT_EXTRA = {
    "run": {"kind": "run", "feed_lods": [], "state_lods": [],
            "platform": "cpu", "guard": None, **_TOGGLES},
    "run_steps": {"kind": "run_steps", "n_steps": 2, "feed_per_step": False,
                  "platform": "cpu", "guard": None, **_TOGGLES},
    "pe_run": {"kind": "sharded_step", "donate": False, **_SPMD,
               **_TOGGLES},
    "pe_run_steps": {"kind": "sharded_window", "n_steps": 2,
                     "feed_per_step": False, "donate": True, "guard": None,
                     **_SPMD, **_TOGGLES},
}


@pytest.mark.parametrize("path", sorted(PARENT_EXTRA))
def test_the_extra_of_each_kind_is_what_its_copy_wrote(path, monkeypatch):
    loss = _build()
    seen = []

    def probe(program, feed_arrays=None, fetch_names=None, extra=None,
              spec_table=None):
        seen.append(extra)
        return None

    monkeypatch.setattr(compile_cache, "executor_probe", probe)
    monkeypatch.delenv("PADDLE_TPU_FLASH", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FUSED", raising=False)
    _Path(path, loss).go()
    assert len(seen) == 1
    # as the fingerprint sees it: json, where a tuple is a list
    assert json.loads(json.dumps(seen[0])) == PARENT_EXTRA[path]


def test_two_programs_built_and_dropped_in_turn_share_no_entry():
    """`id(program)` of a dropped program is recycled; its serial is not."""
    keys = set()
    for _ in range(3):
        framework.fresh_session()
        loss = _build()
        prog = fluid.default_main_program()
        pe = fluid.ParallelExecutor(loss_name=loss.name, main_program=prog,
                                    mesh="dp2")
        pe.run([loss], feed=_feed())
        (key,) = pe._cache
        assert prog._cache_token in key and id(prog) not in key
        keys.add(key)
        del pe, prog, loss
        gc.collect()
    assert len(keys) == 3
    feed = _feed()
    assert step_mod.signature("run", fluid.default_main_program(), [],
                              feed)[0] == \
        step_mod.signature("run", fluid.default_main_program(), [], feed)[0]
