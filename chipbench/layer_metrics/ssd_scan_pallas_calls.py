"""How many lowerings of ``ssd_scan`` and of its grad op took the Pallas
kernels (counters ``ops.ssd.scans{path="pallas"}`` and
``ops.ssd.grad_scans{path="pallas"}``: once a layer and pass in each program
lowered, so a step of three scan layers reads six: three forward walks, and
three backward passes of two kernels each; 18 in a run of three lowerings).
The reader prints every ``ops.ssd.*`` counter with its labels
(``declined{why}`` among them), so that a run's record says which path each
layer took.  0 where every scan ran the XLA path; None where the program
has no such counter."""

PRINTED = ("ops.ssd.",)
COUNTED = ("ops.ssd.scans", "ops.ssd.grad_scans")


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
