"""jax compilations (or cache loads) plus the program's ``compile_cache.miss``
counter, over the measured window.  Must be 0."""


def value(run):
    return float(run["compiles_in_window"])
