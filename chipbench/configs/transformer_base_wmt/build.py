"""Transformer-base through the program's normal path.

``build`` writes the model, its loss and ``optimizer.minimize`` into the
current default programs with ``paddle_tpu.models.transformer`` exactly as a
user would, from the sizes in ``config.json``.  ``deterministic=True``
builds the same graph with dropout off, for the one step that is compared
with the reference; parameter and optimizer-state names are the same, so
both programs train the same scope.
"""

import numpy as np


def build(fluid, sizes, deterministic=False):
    from paddle_tpu.models import transformer

    if sizes["num_encoder_layers"] != sizes["num_decoder_layers"]:
        raise ValueError("models/transformer builds equal stacks")
    opt = sizes["optimizer"]
    cfg = transformer.Config(
        "base", src_vocab_size=sizes["vocab_size"],
        tgt_vocab_size=sizes["vocab_size"], d_model=sizes["d_model"],
        d_inner=sizes["d_ff"], n_head=sizes["num_heads"],
        n_layer=sizes["num_encoder_layers"],
        dropout=0.0 if deterministic else sizes["dropout"],
        label_smooth=sizes["label_smoothing"])
    # transformer.build fixes Adam(beta1 0.9, beta2 0.98, epsilon 1e-9),
    # the paper's; the file states the same numbers for the reference
    _, _, _, loss = transformer.build(cfg, src_len=sizes["src_len"],
                                      tgt_len=sizes["tgt_len"], lr=opt["lr"])
    return {"loss": loss, "units_per_sample": sizes["tgt_len"]}


def make_feed(sizes, batch, rng):
    """A batch of token ids from ``rng``; no padding (id 0), labels repeat
    the decoder input, as ``chip_smoke.py`` feeds it."""
    v = sizes["vocab_size"]
    src = rng.randint(1, v, size=(batch, sizes["src_len"]))
    tgt = rng.randint(1, v, size=(batch, sizes["tgt_len"]))
    return {"src_word": src.astype(np.int64), "tgt_word": tgt.astype(np.int64),
            "lbl_word": tgt[..., None].astype(np.int64)}


def trainable_names(program):
    """The program's trainable parameters in creation order: the order of
    ``reference.param_spec``."""
    return [p.name for p in program.global_block().all_parameters()
            if getattr(p, "trainable", True)]
