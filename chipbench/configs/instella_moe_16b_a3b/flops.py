"""FLOPs that one sequence's forward and backward NEED, from the sizes as
run: two operations per multiply-accumulate of every contraction, and the
backward twice the forward.

Needed work, not work done: attention counts the causal half of its pairs,
at the key's width (unrotated + rotated) for the scores and at the value's
for the values, so a kernel that multiplies whole tiles and masks shows as
lower MFU; the experts count the EXPECTED assignments that reach the
experts held (``T * per_token * held / routed``, the uniform router's
share), not the rows a padded grouped product walks.  A latent mixer is
five products (query, the latent with the shared rotated key, keys and
values from the latent, gate, output) and the pairs; the rotaries, norms
and the gate's multiplication are not counted.  The dense layer, the shared
experts and the router are counted whole (every chip computes them), the
multi-token module as its merge and one routed block, and the head TWICE:
the trunk's product and the module's, each over the vocabulary's slice.
"""


def pairs(t):
    """Query-key pairs that count: the causal half with the diagonal."""
    return t * (t + 1) // 2


def parts(sizes):
    """Multiply-accumulates of one sequence's forward, by part."""
    t, d = sizes["seq_len"], sizes["hidden_size"]
    h = sizes["num_attention_heads"]
    qk, dv = sizes["qk_head_dim"], sizes["v_head_dim"]
    rank, rope = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    routed = sizes["published"]["n_routed_experts"]
    held, per_tok = sizes["n_routed_experts"], sizes["num_experts_per_tok"]
    f = sizes["moe_intermediate_size"]
    return {
        # query, gate, output; the latent and the shared key; keys, values
        "mixer_products": t * d * h * (qk + 2 * dv) + t * d * (rank + rope)
        + t * rank * h * (sizes["qk_nope_head_dim"] + dv),
        "mixer_pairs": pairs(t) * h * (qk + dv),
        "dense": 3 * t * d * sizes["intermediate_size"],
        "router": t * d * routed,
        "shared": 3 * t * d * f * sizes["n_shared_experts"],
        "experts": (t * per_tok * held // routed) * 3 * d * f,
        "merge": t * 2 * d * d,
        "head": t * d * sizes["vocab_size"],
    }


def forward_flops(sizes):
    p = parts(sizes)
    mixer = p["mixer_products"] + p["mixer_pairs"]
    routed = p["router"] + p["shared"] + p["experts"]
    dense_layers = max(0, min(
        sizes["first_k_dense_replace"] - sizes["layer_offset"],
        sizes["num_hidden_layers"]))
    blocks = sizes["num_hidden_layers"] + sizes["num_nextn_predict_layers"]
    total = blocks * mixer + dense_layers * p["dense"] \
        + (blocks - dense_layers) * routed \
        + sizes["num_nextn_predict_layers"] * (p["merge"] + p["head"]) \
        + p["head"]
    return 2 * total


def train_flops_per_sample(sizes):
    return 3 * forward_flops(sizes)
