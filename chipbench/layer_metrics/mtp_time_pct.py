"""The multi-token module after the trunk (the merge of the trunk's last
stream with the next token's embedding, one more routed block, the second
pass through the head and the second loss), forward, backward and update:
share of the device's busy time under ``mtp`` (``chipbench/scope_time.py``:
``mtp.merge``, ``mtp.mixer``, ``mtp.ffn``, ``mtp.head``).  None where
nothing carries the path: a model without the module."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(run, ("mtp",)))
