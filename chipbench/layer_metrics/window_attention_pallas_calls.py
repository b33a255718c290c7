"""How many lowerings of ``sparse_attention`` under a causal window took
the Pallas kernels (counter ``ops.sparse_attention.calls{path="pallas",
window="<w>"}``: once a window layer in each program lowered; a layer on
the XLA path counts twice under ``path="xla"``, because the generic vjp
traces its forward again).  The reader prints every counter of the
decoder's ops, ``ops.sparse_attention.*`` and ``ops.moe.*`` with their
labels (``declined{why}``, ``calls{score}`` and ``bias_updates`` among
them), so that a run's record says which path each layer took and that the
routers' balancing rule was lowered.  None where the program has no call
with a ``window`` label: the parent of the PR that added it, or a model
without window layers."""

PRINTED = ("ops.sparse_attention.", "ops.moe.")


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = {k: v for k, v in profiler.counters().items()
                 if k.startswith(PRINTED)}
    except Exception:
        return None
    windowed = {k: v for k, v in found.items()
                if k.startswith("ops.sparse_attention.calls")
                and 'window="' in k}
    if not windowed:
        return None
    print("counters: " + ", ".join(f"{k} = {v}"
                                   for k, v in sorted(found.items())),
          flush=True)
    return sum(v for k, v in windowed.items() if 'path="pallas"' in k)
