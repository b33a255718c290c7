"""How many ``short_conv`` ops were lowered (counter
``ops.short_conv.calls{channels,taps,path}``: once a conv layer in each
program lowered; the backward is not counted).  The reader prints every
counter of the decoder's ops, ``ops.short_conv.*``,
``ops.sparse_attention.*`` and ``ops.moe.*`` with their labels
(``calls{path}``, ``declined{why}``, ``calls{held,routed,score}`` and
``bias_updates`` among them), so that a run's record says which path each
layer took.  None where the program has no such counter: the parent of the
PR that added it, or a model without conv layers."""

PRINTED = ("ops.short_conv.", "ops.sparse_attention.", "ops.moe.")


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = {k: v for k, v in profiler.counters().items()
                 if k.startswith(PRINTED)}
    except Exception:
        return None
    calls = [v for k, v in found.items()
             if k.startswith("ops.short_conv.calls")]
    if not calls:
        return None
    print("counters: " + ", ".join(f"{k} = {v}"
                                   for k, v in sorted(found.items())),
          flush=True)
    return sum(calls)
