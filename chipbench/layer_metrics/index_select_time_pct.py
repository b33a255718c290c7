"""The indexer and its top-k: the share of the device's busy time under
the op type ``sparse_indexer`` (index projections, rotation, index scores
in query tiles, the bisection that selects each query's keys), from the
labels of the traced stretch.  It has no backward: its gradient is zero."""

from chipbench import op_time


def value(run):
    s = op_time.share(run, ("op:sparse_indexer",))
    return None if s is None else 100.0 * s
