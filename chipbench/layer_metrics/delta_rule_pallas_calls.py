"""How many lowerings of ``gated_delta_rule`` and of its grad op took the
Pallas kernels (counters ``ops.delta_rule.calls{path="pallas"}`` and
``ops.delta_rule.grad_calls{path="pallas"}``: once a layer and pass in each
program lowered, so a step of three delta layers reads six: three forward
walks, and three backward passes of two kernels each).  The reader prints
every ``ops.delta_rule.*`` counter with its labels (``declined{why}`` among
them), so that a run's record says which path each layer took.  None where
the program has no such counter."""

PRINTED = ("ops.delta_rule.",)
COUNTED = ("ops.delta_rule.calls", "ops.delta_rule.grad_calls")


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = {k: v for k, v in profiler.counters().items()
                 if k.startswith(PRINTED)}
    except Exception:
        return None
    if not found:
        return None
    print("counters: " + ", ".join(f"{k} = {v}"
                                   for k, v in sorted(found.items())),
          flush=True)
    return sum(v for k, v in found.items()
               if k.startswith(COUNTED) and 'path="pallas"' in k)
