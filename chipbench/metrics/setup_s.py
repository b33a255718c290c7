"""Process start to window open: import, device init, build, startup,
weights, first call and warm-up: what a trainer pays after every restart.
The comparison that decides ``correct`` runs after the window."""


def value(run):
    return run["setup_s"]
