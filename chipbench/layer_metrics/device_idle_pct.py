"""1 - union of op intervals over the traced window, worst device."""


def value(run):
    t = run.get("trace")
    return None if not t else t["idle_pct_worst"]
