"""How many blocks with a delta mixer were built (counter
``models.decoder.blocks{mixer,residual,where}``, summed over
``mixer="delta"``: once a block in each program built).  The reader prints
every counter of the decoder's builder and ops, ``models.decoder.*``,
``ops.delta_rule.*``, ``ops.short_conv.*``, ``ops.rotary.*``,
``ops.sparse_attention.*`` and ``ops.moe.*`` with their labels
(``calls{key_heads,value_heads,dim,chunk,path}``, ``calls{gated}``,
``calls{dims}``, ``calls{path}``, ``declined{why}`` among them), so that a
run's record says which mixer, filter, rotary and attention path ran.  None
where the program has no such counter: the parent of the PR that added it,
or a model without delta mixers."""

PRINTED = ("models.decoder.", "ops.delta_rule.", "ops.short_conv.",
           "ops.rotary.", "ops.sparse_attention.", "ops.moe.")


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = {k: v for k, v in profiler.counters().items()
                 if k.startswith(PRINTED)}
    except Exception:
        return None
    blocks = [v for k, v in found.items()
              if k.startswith("models.decoder.blocks")
              and 'mixer="delta"' in k]
    if not blocks:
        return None
    print("counters: " + ", ".join(f"{k} = {v}"
                                   for k, v in sorted(found.items())),
          flush=True)
    return sum(blocks)
