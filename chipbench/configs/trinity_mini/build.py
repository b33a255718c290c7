"""Trinity-Mini's decoder, one of 16 chips' share, through the program's
normal path: ``paddle_tpu.models.decoder_lm`` from the sizes in
``config.json``, its loss, ``optimizer.minimize`` and the routers' balancing
rule after it, exactly as a user would build it.  The model has no dropout,
so the deterministic build is the same graph; parameter and
optimizer-state names are the same in both.
"""

import math

import numpy as np

KINDS = {True: "sliding_attention", False: "full_attention"}


def config_of(sizes):
    from paddle_tpu.models import decoder_lm

    if sizes["n_group"] != 1 or sizes["topk_group"] != 1:
        raise ValueError("the router has no group limit")
    cfg = decoder_lm.Config(
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
        norm_topk=sizes["route_norm"], rms_eps=sizes["rms_norm_eps"],
        rope_theta=sizes["rope_theta"], window=sizes["sliding_window"],
        global_every=sizes["global_attn_every_n_layers"],
        rope_global=False, layer_offset=sizes["layer_offset"],
        attn_gate=True, post_norms=True,
        embed_scale=math.sqrt(sizes["hidden_size"])
        if sizes["mup_enabled"] else 1.0,
        dense_layers=sizes["num_dense_layers"],
        dense_width=sizes["intermediate_size"],
        shared_width=sizes["moe_intermediate_size"]
        * sizes["num_shared_experts"],
        router_score=sizes["score_func"], route_norm_eps=1e-20,
        route_scale=sizes["route_scale"],
        route_bias_coeff=sizes["load_balance_coeff"])
    # the layers held are the source's own: its list says the same
    held = sizes["layer_types"][cfg.layer_offset:
                                cfg.layer_offset + cfg.num_layers]
    built = [KINDS[bool(cfg.layer_window(i))] for i in range(cfg.num_layers)]
    if held != built:
        raise ValueError(f"layer_types {held} from layer {cfg.layer_offset} "
                         f"on, the builder's rule {built}")
    return cfg


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
    ``reference.param_spec``.  The routers' selection biases are no
    parameters: persistable state that a rule moves."""
    return [p.name for p in program.global_block().all_parameters()
            if getattr(p, "trainable", True)]
