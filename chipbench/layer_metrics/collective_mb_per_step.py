"""Bytes the step's collectives move, from ``spmd.collective_stats`` on the
optimized HLO of the step (a count, not a time)."""


def value(run):
    if run["chips"] < 2 or not run.get("optimized_hlo"):
        return None
    from paddle_tpu.parallel import spmd

    return spmd.collective_stats(run["optimized_hlo"])["bytes"] / 1e6
