"""How many global attention layers were built with a rotary table of
their own (counter ``models.decoder.rotary{kind="global",scope="layer<i>",
table="given"}``: one key such a layer, counted once in each program built,
so the layers are the KEYS).  The reader prints every counter
of the decoder's builder and ops, ``models.decoder.*``, ``ops.rotary.*``,
``ops.sparse_attention.*`` and ``ops.moe.*`` with their labels
(``calls{dims,pairing,scaled}``, ``calls{path,window}``, ``tiles{kernel,
kind}``, ``column_tiles{kernel,width,tile}``, ``declined{why}`` among
them), so that a run's record says which rotary table, attention path,
band and product tile ran.  None where the program has no such counter:
the parent of the PR that added it, or a model whose global layers share
the window layers' table."""

PRINTED = ("models.decoder.", "ops.rotary.", "ops.sparse_attention.",
           "ops.moe.")


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = {k: v for k, v in profiler.counters().items()
                 if k.startswith(PRINTED)}
    except Exception:
        return None
    layers = [k for k in found if k.startswith("models.decoder.rotary")
              and 'kind="global"' in k]
    if not layers:
        return None
    print("counters: " + ", ".join(f"{k} = {v}"
                                   for k, v in sorted(found.items())),
          flush=True)
    return len(layers)
