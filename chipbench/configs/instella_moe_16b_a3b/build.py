"""Instella-MoE-16B-A3B-Base's decoder, one of 8 chips' share, through the
program's normal path: ``paddle_tpu.models.decoder_lm`` from the sizes in
``config.json``, its two losses, ``optimizer.minimize`` and the routers'
balancing rule after it, exactly as a user would build it.  The model has
no dropout, so the deterministic build is the same graph; parameter and
optimizer-state names are the same in both.
"""

import numpy as np

# what the file states and the builder has ONE way of building: anything
# else is refused, never approximated
ONLY = {"model_type": "deepseek_v3", "attention_bias": False,
        "hidden_act": "silu", "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "moe_layer_freq": 1, "q_lora_rank": None,
        "tie_word_embeddings": False, "norm_topk_prob": True,
        "qk_layernorm": True, "num_nextn_predict_layers": 1}


def latent_of(sizes):
    """The latent mixer's record: widths as the file has them, the
    rotary's YaRN table and the softmax scale that goes with it."""
    from paddle_tpu.models import decoder_lm

    yarn = sizes["rope_scaling"]
    if yarn["type"] != "yarn" or yarn["mscale"] != yarn["mscale_all_dim"]:
        raise ValueError("a YaRN table whose cos and sin keep their size "
                         "(mscale = mscale_all_dim): nothing else is built")
    rope = sizes["qk_rope_head_dim"]
    return decoder_lm.Latent(
        rank=sizes["kv_lora_rank"], nope=sizes["qk_nope_head_dim"],
        rope=rope, value=sizes["v_head_dim"],
        inv_freq=decoder_lm.yarn_inv_freq(
            rope, sizes["rope_theta"], yarn["factor"],
            yarn["original_max_position_embeddings"], yarn["beta_fast"],
            yarn["beta_slow"]),
        interleaved=sizes["rope_interleave"],
        scale=decoder_lm.yarn_softmax_scale(
            sizes["qk_head_dim"], yarn["factor"], yarn["mscale_all_dim"]))


def config_of(sizes):
    from paddle_tpu.models import decoder_lm

    wrong = {k: sizes[k] for k, v in ONLY.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"{wrong}: the builder makes {ONLY} and nothing "
                         "else")
    if sizes["qk_head_dim"] != sizes["qk_nope_head_dim"] \
            + sizes["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not its two parts' sum")
    assumed = sizes["assumed"]
    return decoder_lm.Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["qk_head_dim"],
        expert_width=sizes["moe_intermediate_size"],
        # the router keeps its published width; the experts held are cut
        num_routed=sizes["published"]["n_routed_experts"],
        experts_held=sizes["n_routed_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_offset=sizes["expert_offset"],
        norm_topk=sizes["norm_topk_prob"], rms_eps=sizes["rms_norm_eps"],
        rope_theta=sizes["rope_theta"], layer_offset=sizes["layer_offset"],
        attn_gate=sizes["gated_attention"],
        dense_layers=sizes["first_k_dense_replace"],
        dense_width=sizes["intermediate_size"],
        shared_width=sizes["moe_intermediate_size"]
        * sizes["n_shared_experts"],
        router_score=sizes["scoring_func"],
        route_norm_eps=assumed["route_norm_eps"],
        route_scale=sizes["routed_scaling_factor"],
        route_bias_coeff=assumed["bias_update_rate"],
        mixers=["latent"] * sizes["published"]["num_hidden_layers"],
        latent=latent_of(sizes),
        residual="farskip" if sizes["farskip"] else "sequential",
        mtp_depth=sizes["num_nextn_predict_layers"],
        mtp_weight=assumed["mtp_loss_weight"])


def build(fluid, sizes, deterministic=False):
    from paddle_tpu.models import decoder_lm

    opt = sizes["optimizer"]
    _, _, loss = decoder_lm.build(
        config_of(sizes), seq_len=sizes["seq_len"], lr=opt["lr"],
        beta1=opt["beta1"], beta2=opt["beta2"], epsilon=opt["epsilon"])
    return {"loss": loss, "units_per_sample": sizes["seq_len"]}


def make_feed(sizes, batch, rng):
    """One document per sequence: seq_len + 2 ids uniform over the slice;
    ``labels`` are the tokens shifted by one (and the ids the multi-token
    module embeds), ``labels2`` by two."""
    ids = rng.randint(0, sizes["vocab_size"],
                      size=(batch, sizes["seq_len"] + 2)).astype(np.int64)
    return {"tokens": ids[:, :-2], "labels": ids[:, 1:-1, None],
            "labels2": ids[:, 2:, None]}


def trainable_names(program):
    """The program's trainable parameters in creation order: the order of
    ``reference.param_spec``.  The routers' selection biases are no
    parameters: persistable state that a rule moves."""
    return [p.name for p in program.global_block().all_parameters()
            if getattr(p, "trainable", True)]
