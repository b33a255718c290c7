"""How many blocks were built whose delta mixer decays by a VECTOR along
the key (counter ``models.decoder.delta{decay,gate}``, summed over
``decay="channel"``: once a block in each program built).  The reader
prints every counter of the decoder's builder and ops,
``models.decoder.*``, ``ops.delta_rule.*``, ``ops.short_conv.*``,
``ops.sparse_attention.*`` and ``ops.moe.*`` with their labels
(``delta{decay,gate}``, ``latent{rotary,head_norm,value}``,
``channel_calls{key_heads,dim,chunk,sub}``, ``grad_calls{chunk,path}``,
``calls{path}``, ``declined{why}`` among them), so that a run's record says
which decay, gate, latent layer and attention path ran.  None where the
program has no such counter: the parent of the PR that added it, or a
model whose delta mixers decay by one number a head."""

PRINTED = ("models.decoder.", "ops.delta_rule.", "ops.short_conv.",
           "ops.sparse_attention.", "ops.moe.")


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        found = {k: v for k, v in profiler.counters().items()
                 if k.startswith(PRINTED)}
    except Exception:
        return None
    blocks = [v for k, v in found.items()
              if k.startswith("models.decoder.delta{")
              and 'decay="channel"' in k]
    if not blocks:
        return None
    print("counters: " + ", ".join(f"{k} = {v}"
                                   for k, v in sorted(found.items())),
          flush=True)
    return sum(blocks)
