#!/usr/bin/env python3
"""Read the comparison's numbers over many seeds in ONE process.

    python3 chipbench/check_seeds.py --workload <name> --seeds 1,2,3 \
        --control-seeds 4,5,6

For every seed the program's deterministic step is compared with the
float32 reference (one line each); for every control seed the reference
with float8 (e4m3) contraction inputs stands in the program's place.  The
limits in a configuration's ``config.json`` are set from these two
readings: above the program's largest, below the control's smallest.  Not
part of a benchmark run; set-up is paid once.  Each seed lets the state of
the one before go, seeds again and compares, as a run does after its window.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from chipbench import run as R

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bench = R.read_json(ROOT, "BENCHMARK.json")
    cell = R.find(bench["workloads"], args.workload, "workload")
    config_entry = R.find(bench["configs"], cell["config"], "config")
    traffic = R.read_json(R.HERE, "traffic", cell["traffic"] + ".json")
    sizes = R.cell_sizes(config_entry, args.rehearse)
    R.prepare_environment(args.rehearse, cell["chips"])

    import jax
    import jax.numpy as jnp

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("check_seeds: no TPU", file=sys.stderr)
        return 3
    built = R.Cell(cell, config_entry, traffic, sizes, args.rehearse)
    dispatch = None
    for kind, seeds, dtype in (("program", args.seeds, None),
                               ("control_fp8", args.control_seeds,
                                jnp.float8_e4m3fn)):
        for seed in [int(s) for s in seeds.split(",") if s]:
            built.seed_state(seed)
            if dispatch is None:
                small = built.feed(seed, sizes["check_batch"]
                                   * (1 if traffic["entry"] == "executor"
                                      else cell["chips"]), stream=1)
                dispatch, _, _ = built.make_step(small)
            numbers = built.check(seed, dispatch, matmul_dtype=dtype)
            print(json.dumps({"kind": kind, "seed": seed, **numbers}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
