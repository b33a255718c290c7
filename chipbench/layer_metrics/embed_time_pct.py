"""Both embeddings, the position encodings and the padding biases, forward,
backward and update: share of the device's busy time under the name scope
``embed`` (``chipbench/scope_time.py``).  None where nothing carries it."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("embed",)))
