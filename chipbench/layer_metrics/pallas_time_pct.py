"""Device time in events of Pallas kernels over device busy time."""


def value(run):
    return run.get("pallas_time_pct")
