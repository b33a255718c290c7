"""The routed expert layer, forward and backward: router, sort, grouped
products and combine.  The share of the device's busy time under the op
type ``moe_experts`` (``op:moe_experts``, ``op:moe_experts_grad``) plus the
grouped products themselves.  On a TPU XLA lowers ``lax.ragged_dot`` to
custom calls of its own (instructions ``ragged-dot-*``), whose metadata
carries no scope and which the lowered step does not name, so ``tracing.py``
books them under the catch-all ``kernel:unknown``.  This reader does not
take that label: it reads the traced stretch again and counts the custom
calls whose instruction XLA named ``ragged-dot``, and nothing else.  (Were
another op to call ``lax.ragged_dot``, its products would be counted here
too; ``pallas_time_pct`` counts them as Pallas, which they are not.)  None
where no label starts with ``op:moe_experts``: a program without the op."""

from chipbench import hlo, op_time, program_spans, trace_reduce

XLA_GROUPED_PRODUCT = "ragged-dot"


def grouped_product_s(events):
    """Self time in seconds, and the number, of XLA's own grouped-product
    custom calls among one device's events."""
    def mine(ev):
        return hlo.event_call(ev.name) is not None and \
            hlo.instruction_name(ev.name).startswith(XLA_GROUPED_PRODUCT)

    seconds = trace_reduce.time_by_label(events, mine).get(True, 0.0)
    return seconds, sum(1 for ev in events if mine(ev))


def value(run):
    under_op = op_time.share(run, ("op:moe_experts",))
    if under_op is None:
        return None
    seconds, n = 0.0, 0
    pb = program_spans.newest_trace(run["workload"])
    if pb is not None:
        devices = trace_reduce.read(pb).devices
        seconds, n = grouped_product_s(devices[sorted(devices)[0]])
    products = seconds / run["labelled_busy_s"]
    print(f"moe_time_pct: {100.0 * under_op:.3f} under op:moe_experts* + "
          f"{100.0 * products:.3f} in {n} events of XLA's "
          f"{XLA_GROUPED_PRODUCT}-* custom calls", flush=True)
    return 100.0 * (under_op + products)
