"""Nemotron-Labs-TwoTower-30B-A3B-Base's language tower, one of 16 chips'
share, through the program's normal path: ``paddle_tpu.models.decoder_lm``
from the sizes in ``config.json``, its loss, ``optimizer.minimize`` and the
routers' balancing rule after it, exactly as a user would build it.  The
model has no dropout, so the deterministic build is the same graph;
parameter and optimizer-state names are the same in both.  No second tower
and no diffusion loss: the config has no key for either (``config.json``,
``assumed.second_tower``).
"""

import numpy as np

# what the file states and the builder has ONE way of building: anything
# else is refused, never approximated
ONLY = {"model_type": "nemotron_h", "mlp_hidden_act": "relu2",
        "mamba_hidden_act": "silu", "attention_bias": False,
        "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
        "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
        "norm_topk_prob": True, "tie_word_embeddings": False,
        "sliding_window": None, "time_step_limit": [0, None]}
#: hybrid_override_pattern's letters: (the layer's mixer, what it is made of)
LETTERS = {"M": ("ssm", "mixer"), "*": ("attention", "mixer"),
           "E": (None, "ffn")}


def layers_of(sizes):
    """Every PUBLISHED layer as (mixer, sub-block) from the source's
    pattern, one letter a layer."""
    pattern = sizes["hybrid_override_pattern"]
    if len(pattern) != sizes["published"]["num_hidden_layers"] \
            or set(pattern) - set(LETTERS):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: one of "
                         f"{sorted(LETTERS)} for each published layer")
    return [LETTERS[letter] for letter in pattern]


def config_of(sizes):
    from paddle_tpu.models import decoder_lm

    wrong = {k: sizes[k] for k, v in ONLY.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"{wrong}: the builder makes {ONLY} and nothing "
                         "else")
    assumed = sizes["assumed"]
    mixers, sub_blocks = zip(*layers_of(sizes))
    return decoder_lm.Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        expert_width=sizes["moe_intermediate_size"],
        # the router keeps its published width; the experts held are cut
        num_routed=sizes["published"]["n_routed_experts"],
        experts_held=sizes["n_routed_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_offset=sizes["expert_offset"],
        norm_topk=sizes["norm_topk_prob"],
        rms_eps=sizes["layer_norm_epsilon"],
        # no layer takes positions (assumed.no_rotary)
        rope_theta=sizes["rope_theta"], rope_global=False, qk_norm=False,
        layer_offset=sizes["layer_offset"],
        shared_width=sizes["moe_shared_expert_intermediate_size"],
        router_score="sigmoid", route_norm_eps=assumed["route_norm_eps"],
        route_scale=sizes["routed_scaling_factor"],
        route_bias_coeff=assumed["bias_update_rate"],
        mixers=mixers, sub_blocks=sub_blocks, expert_gate=False,
        ssm=decoder_lm.Ssm(
            heads=sizes["mamba_num_heads"], head_dim=sizes["mamba_head_dim"],
            groups=sizes["n_groups"], state=sizes["ssm_state_size"],
            taps=sizes["conv_kernel"], chunk=sizes["chunk_size"],
            conv_bias=sizes["use_conv_bias"]))


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
