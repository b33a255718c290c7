"""How many lowerings of ``gated_delta_rule`` and of its grad op took the
Pallas kernels in a program whose rule decays by a VECTOR along the key
(counters ``ops.delta_rule.calls{path="pallas"}`` and
``ops.delta_rule.grad_calls{path="pallas"}``, the sum
``delta_rule_pallas_calls`` makes, read where
``ops.delta_rule.channel_calls`` says such a rule was lowered: once a layer
and pass in each program lowered, so a step of four such layers reads eight
for each of its lowerings).  0 where every such layer ran the XLA path;
None where the program has no channel-decay rule or no such counter."""

COUNTED = ("ops.delta_rule.calls", "ops.delta_rule.grad_calls")
CHANNEL = "ops.delta_rule.channel_calls"


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = profiler.counters()
    except Exception:
        return None
    if not any(k.startswith(CHANNEL) for k in found):
        return None
    return sum(v for k, v in found.items()
               if k.startswith(COUNTED) and 'path="pallas"' in k)
