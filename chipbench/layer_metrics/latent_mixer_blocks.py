"""How many blocks with a latent mixer were built (counter
``models.decoder.blocks{mixer,residual,where}``, summed over
``mixer="latent"``: once a block in each program built, the trunk's and
the multi-token module's).  The reader prints every counter of the
decoder's builder and ops, ``models.decoder.*``, ``ops.rotary.*``,
``ops.sparse_attention.*`` and ``ops.moe.*`` with their labels
(``blocks{residual,where}``, ``calls{dims,pairing,scaled}``,
``calls{path}``, ``declined{why}`` among them), so that a run's record
says which mixer, residual rule, rotary and attention path ran.  None where
the program has no such counter: the parent of the PR that added it, or a
model without latent mixers."""

PRINTED = ("models.decoder.", "ops.rotary.", "ops.sparse_attention.",
           "ops.moe.")


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = {k: v for k, v in profiler.counters().items()
                 if k.startswith(PRINTED)}
    except Exception:
        return None
    blocks = [v for k, v in found.items()
              if k.startswith("models.decoder.blocks")
              and 'mixer="latent"' in k]
    if not blocks:
        return None
    print("counters: " + ", ".join(f"{k} = {v}"
                                   for k, v in sorted(found.items())),
          flush=True)
    return sum(blocks)
