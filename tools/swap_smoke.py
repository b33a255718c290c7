#!/usr/bin/env python
"""Hot model swap smoke (CPU, < 10 s) — the ISSUE 16 CI oracle.

One decode engine, end to end through the registry lifecycle:

 1. serve baseline traffic on serial 0;
 2. commit serial 1 under the ``_SUCCESS`` protocol and hot-swap it
    while a stream is MID-GENERATION (immediate policy): the stream
    finishes its full budget — zero shed — and fresh traffic serves
    the new weights;
 3. commit serial 2 NaN-poisoned via ``PADDLE_FAULT_CKPT_POISON_SERIAL``
    (structurally valid, numerically garbage): the canary sentinel
    trips on its first probation tick and auto-rolls back to serial 1,
    vetoing serial 2 forever — with traffic still served throughout;
 4. the compile counter stays FLAT across both swaps AND the rollback
    (fixed-executable-set invariant), and post-rollback traffic is
    bitwise the pre-poison engine (K/V scrub).

Run directly (``python tools/swap_smoke.py``) or from tier-1 via
``tests/test_model_swap.py::test_swap_smoke_tool_runs_clean``.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> dict:
    import numpy as np

    from paddle_tpu.fluid import fault as _fault
    from paddle_tpu.models import transformer
    from paddle_tpu.serving import (DecodeEngine, ModelRegistry,
                                    write_weights_serial)

    t_start = time.perf_counter()
    report = {"ok": False}
    eng = None
    try:
        model = transformer.DecodeModel(cfg=transformer.decode_lm_config(),
                                        max_slots=4, max_len=64,
                                        prefill_buckets=[4, 8])
        eng = DecodeEngine(model)
        report["executables_after_warmup"] = eng.warmup()
        m0 = eng.metrics.snapshot()

        rng = np.random.RandomState(11)
        prompts = [[int(t) for t in rng.randint(2, model.vocab_size - 1,
                                                size=3)]
                   for _ in range(3)]
        names = model.weight_names()
        w0 = eng.snapshot_weights(names)

        def perturbed(seed):
            prng = np.random.RandomState(seed)
            out = {}
            for n in sorted(w0):
                a = np.asarray(w0[n])
                out[n] = (a + 0.05 * prng.normal(size=a.shape)
                          ).astype(a.dtype) \
                    if np.issubdtype(a.dtype, np.floating) \
                    else np.array(a, copy=True)
            return out

        ckpt_root = tempfile.mkdtemp(prefix="swap_smoke_")
        reg = ModelRegistry(eng, ckpt_root, policy="immediate",
                            canary_requests=2, serial=0)

        # -- 1. baseline traffic on serial 0
        base = [eng.generate(p, 6) for p in prompts]

        # -- 2. commit serial 1, swap it in mid-generation, promote
        write_weights_serial(ckpt_root, 1, perturbed(seed=3))
        fut = eng.submit(prompts[0], 24)
        deadline = time.perf_counter() + 5
        while not eng._n_active and time.perf_counter() < deadline:
            time.sleep(0.002)
        report["swap_serial"] = reg.poll_once()
        report["midflight_tokens"] = len(fut.result(timeout=60))
        # probation traffic (2 completions incl. the mid-flight one)
        after_swap = eng.generate(prompts[1], 6)
        reg.poll_once()  # settles the promotion off-tick if needed
        # where the mid-flight request finished before the swap took its
        # baseline (a slow host), probation is one completion short: serve
        # more until it settles, or stage 3's poll finds it still running
        deadline = time.perf_counter() + 20
        while reg.canary_active() and time.perf_counter() < deadline:
            eng.generate(prompts[1], 6)
            reg.poll_once()
        report["serial_after_swap"] = reg.serial
        report["new_weights_serving"] = after_swap != base[1]

        # -- 3. commit serial 2 POISONED: canary must auto-rollback
        _fault.install(_fault.FaultPlan(ckpt_poison_serial=2))
        try:
            write_weights_serial(ckpt_root, 2, perturbed(seed=4))
        finally:
            _fault.clear()
        report["poison_swap_serial"] = reg.poll_once()
        served = eng.generate(prompts[2], 6)  # trips the sentinel
        report["served_during_canary"] = len(served)
        deadline = time.perf_counter() + 5
        while reg.serial != 1 and time.perf_counter() < deadline:
            time.sleep(0.002)
        report["serial_after_rollback"] = reg.serial
        report["vetoed"] = reg.vetoed()
        report["repoll_after_veto"] = reg.poll_once()

        # -- 4. invariants across the whole lifecycle
        with eng._dispatch_lock:  # back to serial 0 for the bitwise check
            eng._rebind_weights(w0)
            eng._scrub_caches()
        report["post_rollback_bitwise"] = \
            [eng.generate(p, 6) for p in prompts] == base
        snap = eng.metrics.snapshot()
        report["compiles_delta"] = \
            snap["bucket_compiles"] - m0["bucket_compiles"]
        report["shed_delta"] = snap["shed"] - m0["shed"]
        report["swaps"] = snap["model_swaps"]
        report["rollbacks"] = snap["model_rollbacks"]
        report["elapsed_s"] = round(time.perf_counter() - t_start, 2)
        report["ok"] = bool(
            report["swap_serial"] == 1
            and report["midflight_tokens"] == 24
            and report["serial_after_swap"] == 1
            and report["new_weights_serving"]
            and report["poison_swap_serial"] == 2
            and report["served_during_canary"] == 6
            and report["serial_after_rollback"] == 1
            and report["vetoed"] == [2]
            and report["repoll_after_veto"] is None
            and report["post_rollback_bitwise"]
            and report["compiles_delta"] == 0
            and report["shed_delta"] == 0
            and report["swaps"] == 2
            and report["rollbacks"] == 1)
    except Exception as exc:  # a broken smoke must still print its JSON
        import traceback

        report["error"] = f"{type(exc).__name__}: {exc}"
        report["trace"] = traceback.format_exc(limit=5)
    finally:
        if eng is not None:
            try:
                eng.shutdown(timeout_s=10)
            except Exception:
                pass
    print(json.dumps(report, indent=1))
    return report


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
