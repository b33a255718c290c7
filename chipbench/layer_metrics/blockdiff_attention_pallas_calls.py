"""How many lowerings of ``sparse_attention`` under the block rule of
diffusion over blocks took the Pallas kernels (counter
``ops.sparse_attention.calls{path="pallas",block="<n>"}``: once a layer in
each program lowered, as ``sparse_attention_pallas_calls`` counts its own;
a layer on the XLA path counts twice under ``path="xla"``, because the
generic vjp traces its forward again).  The reader prints every counter of
the decoder's ops, ``ops.sparse_attention.*`` and ``ops.moe.*`` with their
labels (``declined{why}`` and ``tiles{kernel,kind}`` among them), so that a
run's record says which path each layer took.  None where the program has
no call with a ``block`` label: the parent of the PR that added it, or a
model that is not trained by diffusion over blocks."""

PRINTED = ("ops.sparse_attention.", "ops.moe.")


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = {k: v for k, v in profiler.counters().items()
                 if k.startswith(PRINTED)}
    except Exception:
        return None
    ruled = {k: v for k, v in found.items()
             if k.startswith("ops.sparse_attention.calls")
             and 'block="' in k}
    if not ruled:
        return None
    print("counters: " + ", ".join(f"{k} = {v}"
                                   for k, v in sorted(found.items())),
          flush=True)
    return sum(v for k, v in ruled.items() if 'path="pallas"' in k)
