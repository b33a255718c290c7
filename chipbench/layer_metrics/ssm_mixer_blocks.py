"""How many blocks with a state-space mixer were built (counter
``models.decoder.blocks{mixer,residual,where,parts}``, summed over
``mixer="ssm"``: once a block in each program built; it falls if a step
takes another path).  The reader prints every counter of the decoder's
builder and of the ops such a model lowers, ``models.decoder.*``,
``ops.ssd.*``, ``ops.short_conv.*``, ``ops.sparse_attention.*`` and
``ops.moe.*`` with their labels (``scans{heads,dim,groups,state,chunk,
path}``, ``grad_scans{chunk,path}``, ``calls{bias}``, ``declined{why}``,
``ungated_layers`` among them), so that a run's record says which mixer,
filter, attention path and expert form ran.  None where the program has no
such counter: the parent of the PR that added it, or a model without
state-space mixers."""

PRINTED = ("models.decoder.", "ops.ssd.", "ops.short_conv.",
           "ops.sparse_attention.", "ops.moe.")


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = {k: v for k, v in profiler.counters().items()
                 if k.startswith(PRINTED)}
    except Exception:
        return None
    blocks = [v for k, v in found.items()
              if k.startswith("models.decoder.blocks")
              and 'mixer="ssm"' in k]
    if not blocks:
        return None
    print("counters: " + ", ".join(f"{k} = {v}"
                                   for k, v in sorted(found.items())),
          flush=True)
    return sum(blocks)
