"""Of the live tiles that the block-rule kernels walk, the share that takes
the body without a mask (counter ``ops.sparse_attention.tiles{kernel,kind}``,
which ``ops/pallas_sparse_flash.py`` counts once a kernel call it traces:
the tiles one head walks there; summed over ``kernel="blockdiff_flash_*"``,
``kind="interior"`` over both kinds).  Over two copies of ``n`` tiles each
``n (n - 1)`` tiles are interior (the clean tiles before a query tile's own,
for the clean and for the noised query tiles) and ``3 n`` on an edge (a
clean tile's own, and for a noised tile the clean tile at its place and its
own): 56 of 80, 70%, at 4,096 tokens a copy in tiles of 512.  None where the
program has no such counter: a model that is not trained by diffusion over
blocks, the XLA path, or the parent of the PR that added the kernels."""

PREFIX = 'ops.sparse_attention.tiles{kernel="blockdiff_flash_'


def value(run):
    try:
        from paddle_tpu.fluid import profiler

        tiles = {k: v for k, v in profiler.counters().items()
                 if k.startswith(PREFIX)}
    except Exception:
        return None
    if not sum(tiles.values()):
        return None
    print("counters: " + ", ".join(f"{k} = {v}"
                                   for k, v in sorted(tiles.items())),
          flush=True)
    return 100.0 * sum(v for k, v in tiles.items()
                       if 'kind="interior"' in k) / sum(tiles.values())
