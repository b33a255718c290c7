"""The model's head (ResNet: pool, fc, loss, accuracy; the Transformer: the
projection to the vocabulary and the loss; a decoder: final norm, head
product, loss), forward, backward and update: share of the device's busy
time under the name scope ``head`` (``chipbench/scope_time.py``).  None
where nothing carries the path."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("head",)))
