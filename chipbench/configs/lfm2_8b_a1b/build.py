"""LFM2-8B-A1B's decoder, one of 4 chips' share, through the program's
normal path: ``paddle_tpu.models.decoder_lm`` from the sizes in
``config.json``, its loss, ``optimizer.minimize`` and the routers' balancing
rule after it, exactly as a user would build it.  The model has no dropout,
so the deterministic build is the same graph; parameter and
optimizer-state names are the same in both.
"""

import numpy as np

MIXERS = {"conv": "conv", "full_attention": "attention"}


def config_of(sizes):
    from paddle_tpu.models import decoder_lm

    if sizes["conv_bias"] or not sizes["use_expert_bias"]:
        raise ValueError("the convolution has no bias and the router has "
                         "one: nothing else is built")
    unknown = sorted(set(sizes["layer_types"]) - set(MIXERS))
    if unknown:
        raise ValueError(f"layer_types names {unknown}: no such mixer")
    assumed = sizes["assumed"]
    return decoder_lm.Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["hidden_size"] // sizes["num_attention_heads"],
        expert_width=sizes["moe_intermediate_size"],
        # the router keeps its published width; the experts held are cut
        num_routed=sizes["published"]["num_experts"],
        experts_held=sizes["num_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_offset=sizes["expert_offset"],
        norm_topk=sizes["norm_topk_prob"], rms_eps=sizes["norm_eps"],
        rope_theta=sizes["rope_theta"], layer_offset=sizes["layer_offset"],
        dense_layers=sizes["num_dense_layers"],
        dense_width=sizes["intermediate_size"], router_score="sigmoid",
        route_norm_eps=assumed["route_norm_eps"],
        route_scale=sizes["routed_scaling_factor"],
        route_bias_coeff=assumed["bias_update_rate"],
        # the source's own list, whole: the builder reads the layers held
        # at layer_offset + i
        mixers=[MIXERS[kind] for kind in sizes["layer_types"]],
        conv_taps=sizes["conv_L_cache"], tie_head=True)


def mixers_built(program, sizes):
    """The source's name for the mixer of every layer held, read off the
    parameters the program made: a conv layer has a filter and no query
    projection, an attention layer the reverse."""
    names = {p.name for p in program.global_block().all_parameters()}
    kinds = []
    for i in range(sizes["num_hidden_layers"]):
        conv, attn = f"l{i}_conv_w" in names, f"l{i}_q_w" in names
        if conv == attn:
            raise ValueError(f"layer {i} has {'both' if conv else 'no'} "
                             f"mixer's parameters")
        kinds.append("conv" if conv else "full_attention")
    return kinds


def build(fluid, sizes, deterministic=False):
    from paddle_tpu.models import decoder_lm

    opt = sizes["optimizer"]
    _, _, loss = decoder_lm.build(
        config_of(sizes), seq_len=sizes["seq_len"], lr=opt["lr"],
        beta1=opt["beta1"], beta2=opt["beta2"], epsilon=opt["epsilon"])
    first = sizes["layer_offset"]
    held = sizes["layer_types"][first:first + sizes["num_hidden_layers"]]
    built = mixers_built(loss.block.program, sizes)
    if held != built:
        raise ValueError(f"layer_types {held} from layer {first} on, the "
                         f"builder made {built}")
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
