"""Of the live tiles that the window kernels walk, the share that takes the
body without a positional mask (counter
``ops.sparse_attention.tiles{kernel,kind}``, which
``ops/pallas_sparse_flash.py`` counts once a kernel call it traces: the
tiles one head walks there; summed over ``kernel="window_flash_*"``,
``kind="interior"`` over both kinds).  A band of ``ceil((window - 1) /
block) + 1`` tiles a row has its first and its last on an edge: 1 of 3 at a
window of 1,024 and tiles of 512.  None where the program has no such
counter: a model without window layers, the XLA path, or the parent of the
PR that added the counter."""

PREFIX = 'ops.sparse_attention.tiles{kernel="window_flash_'


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
