"""Kimi-Linear-48B-A3B-Instruct's decoder, one of 32 chips' share, through
the program's normal path: ``paddle_tpu.models.decoder_lm`` from the sizes
in ``config.json``, its loss, ``optimizer.minimize`` and the routers'
balancing rule after it, exactly as a user would build it.  The model has
no dropout, so the deterministic build is the same graph; parameter and
optimizer-state names are the same in both.
"""

import numpy as np

# what the file states and the builder has ONE way of building: anything
# else is refused, never approximated
ONLY = {"model_type": "kimi_linear", "hidden_act": "silu",
        "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
        "moe_layer_freq": 1, "use_grouped_topk": True, "num_expert_group": 1,
        "topk_group": 1, "num_shared_experts": 1, "q_lora_rank": None,
        "rope_scaling": None, "mla_use_nope": True,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 0}


def mixers_of(sizes):
    """Every PUBLISHED layer's mixer, from the source's two lists, which
    count layers from 1: latent attention on ``full_attn_layers``, the
    delta rule on ``kda_layers``."""
    linear = sizes["linear_attn_config"]
    full, kda = set(linear["full_attn_layers"]), set(linear["kda_layers"])
    layers = range(1, sizes["published"]["num_hidden_layers"] + 1)
    if full & kda or full | kda != set(layers):
        raise ValueError("full_attn_layers and kda_layers do not divide the "
                         f"layers 1-{len(layers)} between them")
    return ["latent" if i in full else "delta" for i in layers]


def config_of(sizes):
    from paddle_tpu.models import decoder_lm

    wrong = {k: sizes[k] for k, v in ONLY.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"{wrong}: the builder makes {ONLY} and nothing "
                         "else")
    linear, assumed = sizes["linear_attn_config"], sizes["assumed"]
    if linear["num_heads"] != sizes["num_attention_heads"] \
            or sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise ValueError("both mixers have num_attention_heads heads, each "
                         "with a key and a value of its own")
    return decoder_lm.Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        # a latent head's own width; the file's head_dim (hidden / heads)
        # is read by nothing
        head_dim=sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        expert_width=sizes["moe_intermediate_size"],
        # the router keeps its published width; the experts held are cut
        num_routed=sizes["published"]["num_experts"],
        experts_held=sizes["num_experts"],
        experts_per_token=sizes["num_experts_per_token"],
        expert_offset=sizes["expert_offset"],
        norm_topk=sizes["moe_renormalize"], rms_eps=sizes["rms_norm_eps"],
        rope_theta=sizes["rope_theta"], layer_offset=sizes["layer_offset"],
        dense_layers=sizes["first_k_dense_replace"],
        dense_width=sizes["intermediate_size"],
        shared_width=sizes["moe_intermediate_size"]
        * sizes["num_shared_experts"],
        router_score=sizes["moe_router_activation_func"],
        route_norm_eps=assumed["route_norm_eps"],
        route_scale=sizes["routed_scaling_factor"],
        route_bias_coeff=assumed["bias_update_rate"],
        mixers=mixers_of(sizes),
        latent=decoder_lm.Latent(
            rank=sizes["kv_lora_rank"], nope=sizes["qk_nope_head_dim"],
            rope=sizes["qk_rope_head_dim"], value=sizes["v_head_dim"],
            rotary=not sizes["mla_use_nope"], head_norm=False),
        delta=decoder_lm.Delta(
            key_heads=linear["num_heads"], value_heads=linear["num_heads"],
            key_dim=linear["head_dim"], value_dim=linear["head_dim"],
            taps=linear["short_conv_kernel_size"],
            chunk=sizes["delta_chunk"]),
        delta_gates=decoder_lm.DeltaGates(
            decay_rank=linear["head_dim"], gate="sigmoid",
            gate_rank=linear["head_dim"]))


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
