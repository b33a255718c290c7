"""Keye-VL-2.0-30B-A3B's decoder, one of 8 chips' share, through the
program's normal path: ``paddle_tpu.models.decoder_lm`` from the sizes in
``config.json``, its loss and ``optimizer.minimize``, exactly as a user
would build it.  The model has no dropout, so the deterministic build is
the same graph; parameter and optimizer-state names are the same in both.
"""

import numpy as np


def config_of(sizes):
    from paddle_tpu.models import decoder_lm

    sa = sizes["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer has one key head")
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
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"])


def build(fluid, sizes, deterministic=False):
    from paddle_tpu.models import decoder_lm

    opt = sizes["optimizer"]
    _, _, loss = decoder_lm.build(
        config_of(sizes), seq_len=sizes["seq_len"], lr=opt["lr"],
        beta1=opt["beta1"], beta2=opt["beta2"], epsilon=opt["epsilon"])
    return {"loss": loss, "units_per_sample": sizes["seq_len"]}


def make_feed(sizes, batch, rng):
    """One document per sequence: seq_len + 1 ids uniform over the slice;
    the labels are the tokens shifted by one."""
    ids = rng.randint(0, sizes["vocab_size"],
                      size=(batch, sizes["seq_len"] + 1)).astype(np.int64)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:, None]}


def trainable_names(program):
    """The program's trainable parameters in creation order: the order of
    ``reference.param_spec``."""
    return [p.name for p in program.global_block().all_parameters()
            if getattr(p, "trainable", True)]
