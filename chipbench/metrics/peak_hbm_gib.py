"""Peak HBM set aside on the fullest of the cell's devices, in GiB, read as
the window closes: the arrays at their high-water mark (state, feed,
fetches in flight) plus the temporaries of the step (``run.py``
``device_record`` says why the two are added).  The comparison that decides
``correct`` runs after the reading, so nothing of the harness's is in it."""


def value(run):
    return run["memory_peak_bytes"] / 2 ** 30
