"""Mellum2-12B-A2.5B-Instruct's decoder, one of 8 chips' share, through the
program's normal path: ``paddle_tpu.models.decoder_lm`` from the sizes in
``config.json``, its loss and ``optimizer.minimize``, exactly as a user
would build it.  The model has no dropout, so the deterministic build is
the same graph; parameter and optimizer-state names are the same in both.
"""

import numpy as np

# what the file states and the builder has ONE way of building: anything
# else is refused, never approximated
ONLY = {"model_type": "mellum", "hidden_act": "silu",
        "attention_bias": False, "norm_topk_prob": True,
        "tie_word_embeddings": False, "use_sliding_window": True}
KINDS = {True: "sliding_attention", False: "full_attention"}


def global_every(sizes):
    """The period of the published ``layer_types``: every n-th layer is a
    ``full_attention`` one, which is the rule the builder has."""
    kinds = sizes["layer_types"]
    every = kinds.index("full_attention") + 1
    if kinds != [KINDS[(i + 1) % every != 0] for i in range(len(kinds))]:
        raise ValueError(f"layer_types {kinds}: no full_attention layer "
                         f"every {every}")
    return every


def global_rotary(sizes):
    """The ``full_attention`` layers' rotary record, from
    ``rope_parameters``: the YaRN table and the temperature it brings."""
    from paddle_tpu.models import decoder_lm

    window, full = (sizes["rope_parameters"][k]
                    for k in ("sliding_attention", "full_attention"))
    if (window["rope_type"], full["rope_type"]) != ("default", "yarn") \
            or window["rope_theta"] != full["rope_theta"]:
        raise ValueError(f"rope_parameters {sizes['rope_parameters']}: a "
                         "plain table for the window layers and a YaRN "
                         "table from the same base for the full ones is "
                         "what is built")
    scale = decoder_lm.yarn_softmax_scale(sizes["head_dim"], full["factor"])
    stated = sizes["head_dim"] ** -0.5 * full["attention_factor"] ** 2
    if abs(scale - stated) > 1e-12 * stated:
        raise ValueError(
            f"attention_factor {full['attention_factor']} is not the "
            f"0.1 ln(factor) + 1 that factor {full['factor']} derives")
    return decoder_lm.Rotary(
        inv_freq=decoder_lm.yarn_inv_freq(
            sizes["head_dim"], full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"]),
        scale=scale)


def config_of(sizes):
    from paddle_tpu.models import decoder_lm

    wrong = {k: sizes[k] for k, v in ONLY.items() if sizes[k] != v}
    if wrong or set(sizes["mlp_layer_types"]) != {"sparse"}:
        raise ValueError(f"{wrong or sizes['mlp_layer_types']}: the "
                         f"builder makes {ONLY}, every feed-forward "
                         "sparse, and nothing else")
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
        norm_topk=sizes["norm_topk_prob"], rms_eps=sizes["rms_norm_eps"],
        rope_theta=sizes["rope_parameters"]["sliding_attention"]
        ["rope_theta"],
        window=sizes["sliding_window"], global_every=global_every(sizes),
        layer_offset=sizes["layer_offset"],
        global_rotary=global_rotary(sizes))
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
    ``reference.param_spec``."""
    return [p.name for p in program.global_block().all_parameters()
            if getattr(p, "trainable", True)]
