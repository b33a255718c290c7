"""FLOPs that one sequence's forward and backward NEED, from the sizes as
run: two operations per multiply-accumulate of every contraction, and the
backward twice the forward.

Needed work, not work done.  The delta rule counts what the RECURRENCE
states a token and head: the state read along the key, the rank-one write
and the state read along the query, ``3 * dk * dv`` (the decay of the
state's rows is no contraction); the chunked form's extra products (the
sub-blocks' key-key and query-key scores, the inverse, its two products)
are how the program gets there and are not counted, so the rule's share of
the peak is low by construction.  A delta mixer's products are the one in
(queries, keys, values), the step's column a head, both pairs through the
rank (decay and output gate) and the one out; the filter, norms and gates'
forms are not counted.  The latent layer counts its four products and the
causal half of its pairs, at the key's width (192) for the scores and at
the value's (128) for the values.  The experts count the EXPECTED
assignments that reach the experts held (``T * per_token * held /
routed``, the uniform router's share), not the rows a padded grouped
product walks.  Router, shared expert, dense layer and both mixers are
counted whole: every chip computes them.
"""


def pairs(t):
    """Query-key pairs that count: the causal half with the diagonal."""
    return t * (t + 1) // 2


def layer_kinds(sizes):
    """[(mixer, is the feed-forward dense)] of the layers held; the
    source's lists count layers from 1."""
    first = sizes["layer_offset"]
    full = set(sizes["linear_attn_config"]["full_attn_layers"])
    return [("latent" if i + 1 in full else "delta",
             i < sizes["first_k_dense_replace"])
            for i in range(first, first + sizes["num_hidden_layers"])]


def parts(sizes):
    """Multiply-accumulates of one sequence's forward, by part (a layer's,
    or the head's)."""
    t, d = sizes["seq_len"], sizes["hidden_size"]
    linear = sizes["linear_attn_config"]
    h, dk = linear["num_heads"], linear["head_dim"]
    rank = dk
    heads, dv = sizes["num_attention_heads"], sizes["v_head_dim"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    lat = sizes["kv_lora_rank"]
    routed = sizes["published"]["num_experts"]
    held, per_tok = sizes["num_experts"], sizes["num_experts_per_token"]
    f = sizes["moe_intermediate_size"]
    return {
        # [q | k | v], the step, two pairs through the rank, the output
        "delta_products": t * d * (3 * h * dk + h)
        + 2 * (t * d * rank + t * rank * h * dk) + t * h * dk * d,
        "delta_rule": t * h * 3 * dk * dk,
        # query; the latent and the shared key; keys and values; output
        "latent_products": t * d * heads * (nope + rope) + t * d * (lat + rope)
        + t * lat * heads * (nope + dv) + t * heads * dv * d,
        "latent_pairs": pairs(t) * heads * (nope + rope + dv),
        "dense": 3 * t * d * sizes["intermediate_size"],
        "router": t * d * routed,
        "shared": 3 * t * d * f * sizes["num_shared_experts"],
        "experts": (t * per_tok * held // routed) * 3 * d * f,
        "head": t * d * sizes["vocab_size"],
    }


def rule_flops(sizes):
    """The delta rule's own forward FLOPs of one sequence, every delta
    layer held: what ``delta_rule_mfu_pct`` sets against the op's time."""
    delta = sum(mixer == "delta" for mixer, _ in layer_kinds(sizes))
    return 2 * delta * parts(sizes)["delta_rule"]


def forward_flops(sizes):
    p = parts(sizes)
    total = p["head"]
    for mixer, dense in layer_kinds(sizes):
        total += p["delta_products"] + p["delta_rule"] if mixer == "delta" \
            else p["latent_products"] + p["latent_pairs"]
        total += p["dense"] if dense \
            else p["router"] + p["shared"] + p["experts"]
    return 2 * total


def train_flops_per_sample(sizes):
    return 3 * forward_flops(sizes)
