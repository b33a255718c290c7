"""How many lowerings of ``sparse_attention`` took the Pallas kernels
(counter ``ops.sparse_attention.calls{path="pallas"}``: once a layer in
each program lowered; a layer on the XLA path counts twice, because the
generic vjp traces its forward again).  The reader prints every counter of
the decoder's ops, ``ops.sparse_attention.*`` and ``ops.moe.*`` with their
labels (``declined{why}`` among them), so that a run's record says which
path each layer took.  None where the program has no such counter."""

PRINTED = ("ops.sparse_attention.", "ops.moe.")


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
               if k.startswith("ops.sparse_attention.calls")
               and 'path="pallas"' in k)
