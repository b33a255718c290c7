"""Peak HBM set aside on the fullest of the cell's devices, in GiB: arrays
at their high-water mark plus the temporaries of the largest program that
ran (``run.py`` ``device_record`` says why the two are added)."""


def value(run):
    return run["memory_peak_bytes"] / 2 ** 30
