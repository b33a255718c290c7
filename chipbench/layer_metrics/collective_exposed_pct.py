"""Collective time during which no compute runs on that device, over the
traced window, worst device."""


def value(run):
    if run["chips"] < 2:
        return None
    return run.get("collective_exposed_pct")
