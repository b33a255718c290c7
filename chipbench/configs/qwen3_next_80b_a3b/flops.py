"""FLOPs that one sequence's forward and backward NEED, from the sizes as
run: two operations per multiply-accumulate of every contraction, and the
backward twice the forward.

Needed work, not work done.  The delta rule counts what the RECURRENCE
states a token and value head: the state read along the key, the rank-one
write and the state read along the query, ``3 * dk * dv``; the chunked
form's extra products (the chunk's key-key and query-key scores, the
inverse, its two products) are how the program gets there and are not
counted, so the rule's share of the peak is low by construction.  Attention
counts the causal half of its pairs at the head's width; the experts count
the EXPECTED assignments that reach the experts held (``T * per_token *
held / routed``, the uniform router's share), not the rows a padded
grouped product walks.  A delta mixer's projections are the one in
(queries, keys, values, the output's gate, both of the rule's gates) and
the one out; the filter, norms and gates are not counted.  Router, shared
expert (with its gate's one column) and both mixers are counted whole:
every chip computes them.
"""


def pairs(t):
    """Query-key pairs that count: the causal half with the diagonal."""
    return t * (t + 1) // 2


def delta_layers(sizes):
    """How many of the layers held mix tokens by the delta rule."""
    first, every = sizes["layer_offset"], sizes["full_attention_interval"]
    return sum((first + i + 1) % every != 0
               for i in range(sizes["num_hidden_layers"]))


def parts(sizes):
    """Multiply-accumulates of one sequence's forward, by part (a layer's,
    or the head's)."""
    t, d = sizes["seq_len"], sizes["hidden_size"]
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    h, hkv, dh = (sizes["num_attention_heads"],
                  sizes["num_key_value_heads"], sizes["head_dim"])
    routed = sizes["published"]["num_experts"]
    held, per_tok = sizes["num_experts"], sizes["num_experts_per_tok"]
    f = sizes["moe_intermediate_size"]
    return {
        "delta_products": t * d * (2 * hk * dk + 2 * hv * dv + 2 * hv)
        + t * hv * dv * d,
        "delta_rule": t * hv * 3 * dk * dv,
        # query, gate, output; keys and values
        "attention_products": t * d * (3 * h * dh + 2 * hkv * dh),
        "attention_pairs": pairs(t) * h * 2 * dh,
        "router": t * d * routed,
        "shared": t * d * (3 * sizes["shared_expert_intermediate_size"] + 1),
        "experts": (t * per_tok * held // routed) * 3 * d * f,
        "head": t * d * sizes["vocab_size"],
    }


def rule_flops(sizes):
    """The delta rule's own forward FLOPs of one sequence, every delta
    layer held: what ``delta_rule_mfu_pct`` sets against the op's time."""
    return 2 * delta_layers(sizes) * parts(sizes)["delta_rule"]


def forward_flops(sizes):
    p = parts(sizes)
    layers, delta = sizes["num_hidden_layers"], delta_layers(sizes)
    total = delta * (p["delta_products"] + p["delta_rule"]) \
        + (layers - delta) * (p["attention_products"] + p["attention_pairs"]) \
        + layers * (p["router"] + p["shared"] + p["experts"]) + p["head"]
    return 2 * total


def train_flops_per_sample(sizes):
    return 3 * forward_flops(sizes)
