"""Fused-kernel smoke (CPU interpret mode, < 5 s).

The CI oracle for the Pallas fused-kernel layer (ISSUE 12): a GUARDED
16-step training window through the streaming softmax-cross-entropy and
the fused adam sweep must

 - train all 16 steps with ``PADDLE_TPU_FUSED=1`` (interpret mode on the
   CPU mesh) and finish with losses matching the unfused XLA lowering
   within 1e-6,
 - leave nonzero ``ops.fused.softmax_xent`` / ``ops.fused.adam`` dispatch
   counters in the always-on registry, and
 - with the ``PADDLE_TPU_FUSED=0`` kill-switch, restore the EXACT unfused
   lowering: the kill-switch run's losses are bit-identical to the
   baseline unfused run.

Run directly (``python tools/fused_smoke.py``) or from tier-1 via
``tests/test_pallas_fused.py::test_fused_smoke_tool``.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_STEPS = 16


def _one_run(fused: str, feeds):
    """Fresh program/scope/executor per config (the jit + trace caches key
    on the env knob, but a fresh session keeps the oracle airtight)."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    import paddle_tpu.fluid.executor as _executor
    from paddle_tpu.fluid import framework, guardian, unique_name

    os.environ["PADDLE_TPU_FUSED"] = fused
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    _executor._global_scope = _executor.Scope()
    fluid.default_main_program().random_seed = 11
    fluid.default_startup_program().random_seed = 11

    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=x, size=16, act="relu")
    logits = fluid.layers.fc(input=h, size=10, act=None)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)

    guardian.install(guardian.GuardianConfig(policy="skip"))
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        (lv,) = exe.run_steps(fluid.default_main_program(), feed=feeds,
                              fetch_list=[loss], n_steps=N_STEPS,
                              feed_per_step=True)
        guardian.flush()
        gm = guardian.metrics()
    finally:
        guardian.disable()
    return float(np.asarray(lv).reshape(-1)[0]), gm


def main() -> dict:
    import numpy as np

    import paddle_tpu.fluid as fluid

    t0 = time.perf_counter()
    prev = os.environ.get("PADDLE_TPU_FUSED")
    rng = np.random.RandomState(3)
    feeds = {"x": rng.normal(size=(N_STEPS, 8, 16)).astype(np.float32),
             "label": rng.randint(0, 10, size=(N_STEPS, 8, 1))
             .astype(np.int64)}
    try:
        c0 = dict(fluid.profiler.counters())
        base, gm_base = _one_run("0", feeds)     # unfused baseline
        fused, gm_fused = _one_run("1", feeds)   # fused kernels
        kill, _ = _one_run("0", feeds)           # kill-switch restore
        c1 = fluid.profiler.counters()
    finally:
        # restore env for in-process callers (the tier-1 test imports us)
        if prev is None:
            os.environ.pop("PADDLE_TPU_FUSED", None)
        else:
            os.environ["PADDLE_TPU_FUSED"] = prev

    def delta(name):
        return c1.get(name, 0) - c0.get(name, 0)

    xent = delta('ops.fused.softmax_xent{target="hard"}')
    adam = delta("ops.fused.adam")
    report = {
        "ok": bool(
            np.isfinite(base) and np.isfinite(fused)
            and abs(fused - base) < 1e-6       # fused ≡ unfused semantics
            and kill == base                   # kill-switch is EXACT
            and xent > 0 and adam > 0
            and gm_base.get("steps") == N_STEPS
            and gm_fused.get("steps") == N_STEPS
            and gm_fused.get("trips", 0) == 0),
        "loss_unfused": base,
        "loss_fused": fused,
        "loss_killswitch": kill,
        "fused_vs_unfused_diff": abs(fused - base),
        "killswitch_bitwise": kill == base,
        "ops_fused_softmax_xent": int(xent),
        "ops_fused_adam": int(adam),
        "guardian_steps": gm_fused.get("steps"),
        "elapsed_s": round(time.perf_counter() - t0, 2),
    }
    print(json.dumps(report, indent=1))
    return report


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
