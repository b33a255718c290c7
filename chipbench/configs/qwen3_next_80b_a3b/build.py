"""Qwen3-Next-80B-A3B-Instruct's decoder, one of 32 chips' share, through
the program's normal path: ``paddle_tpu.models.decoder_lm`` from the sizes
in ``config.json``, its loss and ``optimizer.minimize``, exactly as a user
would build it.  The model has no dropout, so the deterministic build is
the same graph; parameter and optimizer-state names are the same in both.
"""

import numpy as np

# what the file states and the builder has ONE way of building: anything
# else is refused, never approximated
ONLY = {"model_type": "qwen3_next", "hidden_act": "silu",
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "norm_topk_prob": True, "tie_word_embeddings": False,
        "use_sliding_window": False, "rope_scaling": None}


def mixers_of(sizes):
    """Every PUBLISHED layer's mixer: softmax attention on every
    ``full_attention_interval``-th, the delta rule on the others."""
    every = sizes["full_attention_interval"]
    return ["attention" if (i + 1) % every == 0 else "delta"
            for i in range(sizes["published"]["num_hidden_layers"])]


def config_of(sizes):
    from paddle_tpu.models import decoder_lm

    wrong = {k: sizes[k] for k, v in ONLY.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"{wrong}: the builder makes {ONLY} and nothing "
                         "else")
    rotated = sizes["head_dim"] * sizes["partial_rotary_factor"]
    if rotated != int(rotated) or int(rotated) % 2:
        raise ValueError(f"partial_rotary_factor leaves {rotated} columns "
                         "to rotate: an even whole number is built")
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
        norm_topk=sizes["norm_topk_prob"], rms_eps=sizes["rms_norm_eps"],
        rope_theta=sizes["rope_theta"], layer_offset=sizes["layer_offset"],
        attn_gate=True,
        shared_width=sizes["shared_expert_intermediate_size"],
        shared_gate=True, mixers=mixers_of(sizes),
        delta=decoder_lm.Delta(
            key_heads=sizes["linear_num_key_heads"],
            value_heads=sizes["linear_num_value_heads"],
            key_dim=sizes["linear_key_head_dim"],
            value_dim=sizes["linear_value_head_dim"],
            taps=sizes["linear_conv_kernel_dim"],
            chunk=sizes["delta_chunk"]),
        rotary_dims=int(rotated))


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
