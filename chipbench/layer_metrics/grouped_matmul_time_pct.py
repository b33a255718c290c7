"""The expert layer's grouped products, forward and backward: the share of
the device's busy time inside the two Pallas families
``kernel:grouped_matmul`` and ``kernel:grouped_matmul_t``.  What surrounds
the products (router, sort, gathers, selects, combine) is under
``op:moe_experts*`` and is counted by ``moe_time_pct``, whose own search
for products finds XLA's ``ragged-dot*`` calls only: where these kernels
run, the layer's share is that metric plus this one.  None where the step
calls no such kernel."""

from chipbench import op_time


def value(run):
    s = op_time.share(run, ("kernel:grouped_matmul",))
    return None if s is None else 100.0 * s
