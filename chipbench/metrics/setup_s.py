"""Process start to window open: import, device init, build, startup,
weights, reference check, first call and warm-up."""


def value(run):
    return run["setup_s"]
