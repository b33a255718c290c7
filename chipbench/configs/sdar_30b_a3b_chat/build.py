"""SDAR-30B-A3B-Chat's decoder, one of 8 chips' share, trained by diffusion
over blocks, through the program's normal path:
``paddle_tpu.models.decoder_lm`` from the sizes in ``config.json`` (one
``BlockDiffusion`` record beside the layer's), its loss and
``optimizer.minimize``, exactly as a user would build it.  The model has no
dropout, so the deterministic build is the same graph; parameter and
optimizer-state names are the same in both.

The noise is the data pipeline's: ``make_feed`` draws a level a block and
the mask from the run's seed (``decoder_lm.noise``) and feeds the clean
tokens, the noised copy and the loss weights; the program and the reference
read the same three.
"""

import numpy as np


def config_of(sizes):
    from paddle_tpu.models import decoder_lm

    if sizes["mlp_only_layers"] or sizes["decoder_sparse_step"] != 1 \
            or sizes["use_sliding_window"] or sizes["rope_scaling"] \
            or sizes["tie_word_embeddings"] or sizes["attention_bias"]:
        raise ValueError("every layer is a routed one under plain global "
                         "attention, an untied head and no bias: that and "
                         "nothing else is built")
    rule = sizes["block_diffusion"]
    return decoder_lm.Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        expert_width=sizes["moe_intermediate_size"],
        # the router keeps its published width; the experts held are cut
        num_routed=sizes["published"]["num_experts"],
        experts_held=sizes["num_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_offset=sizes["expert_offset"],
        norm_topk=sizes["norm_topk_prob"],
        rms_eps=sizes["rms_norm_eps"], rope_theta=sizes["rope_theta"],
        block_diffusion=decoder_lm.BlockDiffusion(
            block=rule["block_length"], mask_id=rule["mask_token_id"]))


def build(fluid, sizes, deterministic=False):
    from paddle_tpu.models import decoder_lm

    opt = sizes["optimizer"]
    _, _, loss = decoder_lm.build(
        config_of(sizes), seq_len=sizes["seq_len"], lr=opt["lr"],
        beta1=opt["beta1"], beta2=opt["beta2"], epsilon=opt["epsilon"])
    # a trainer counts the data's tokens: the step walks twice as many
    # positions, the clean copy and the noised one
    return {"loss": loss, "units_per_sample": sizes["seq_len"]}


def make_feed(sizes, batch, rng):
    """One document per sequence, ids uniform over the slice without the
    mask id; then a level a block, uniform on [noise_floor, 1], masks the
    block's tokens, and a masked token weighs 1 / level."""
    from paddle_tpu.models import decoder_lm

    rule = sizes["block_diffusion"]
    if rule["mask_token_id"] != sizes["vocab_size"] - 1:
        raise ValueError("the mask is the slice's last id")
    tokens = rng.randint(0, sizes["vocab_size"] - 1,
                         size=(batch, sizes["seq_len"])).astype(np.int64)
    noised, weights = decoder_lm.noise(config_of(sizes), tokens, rng,
                                       floor=rule["noise_floor"])
    return {"tokens": tokens, "noised": noised, "weights": weights}


def trainable_names(program):
    """The program's trainable parameters in creation order: the order of
    ``reference.param_spec``."""
    return [p.name for p in program.global_block().all_parameters()
            if getattr(p, "trainable", True)]
