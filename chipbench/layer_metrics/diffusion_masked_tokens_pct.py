"""Median over the window's steps of ``ops.weighted_mean.live_rows`` over
``ops.weighted_mean.rows``: the share of a step's tokens that bear loss,
which under diffusion over blocks is the share the noise masked (a level a
block uniform on [1e-3, 1]: about half).  Read from the program's step
gauges (``chipbench/step_gauges.py`` ``entries``): the op that weighs the
loss publishes both from the weights it was fed.  None where the program
has no such gauge: a model trained by next-token loss, or the parent of the
PR that added the op."""

import statistics

from chipbench import step_gauges

LIVE, ROWS = "ops.weighted_mean.live_rows", "ops.weighted_mean.rows"


def value(run):
    found = step_gauges.entries(since=run["stamps"][0])
    shares = []
    for entry in found or ():
        if not run["stamps"][0] <= entry[2] <= run["stamps"][-1]:
            continue
        sums = {LIVE: 0.0, ROWS: 0.0}
        for rendered, v in entry[3].items():
            name = step_gauges.split(rendered)[0]
            if name in sums:
                sums[name] += v
        if sums[ROWS]:
            shares.append(sums[LIVE] / sums[ROWS])
    if not shares:
        return None
    print(f"loss-bearing tokens over {len(shares)} steps of the window: "
          f"median {100 * statistics.median(shares):.3f}% (min "
          f"{100 * min(shares):.3f}% max {100 * max(shares):.3f}%)",
          flush=True)
    return 100.0 * statistics.median(shares)
