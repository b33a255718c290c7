"""Sum of least times of the matched Pallas events over the sum of the same
events' durations; withheld (None) when no family's events equal its calls."""


def value(run):
    roof = run.get("roofline")
    return None if not roof else roof["pct"]
