"""FLOPs that one sequence's forward and backward NEED, from the sizes as
run: two operations per multiply-accumulate of every contraction, and the
backward twice the forward.

Needed work, not work done.  A state-space mixer counts its projection in
(gate, filter input and step side by side) and out, and the scan as the
RECURRENCE states it a token and head: the state decayed, the rank-one
write and the read along C, ``3 * P * N`` multiply-adds
(``paddle_tpu.ops.ssd.scan_flops``'s count); the chunked form's own
products (``C B^T``, the scores' product, the chunk's write and the state's
read at chunk width) are how the program gets there and are not counted, so
the scan's share of the peak is low by construction.  The filter, norms,
gates and ``D u`` are not counted.  The attention layer counts its four
products and the causal half of its pairs.  The experts count the EXPECTED
assignments that reach the experts held (``T * per_token * held / routed``,
the uniform router's share), not the rows a padded grouped product walks,
at TWO matrices an expert.  Router, shared expert, attention and the mixers
are counted whole: every chip computes them.
"""


def pairs(t):
    """Query-key pairs that count: the causal half with the diagonal."""
    return t * (t + 1) // 2


def layer_letters(sizes):
    """The pattern's letters of the layers held."""
    first = sizes["layer_offset"]
    return sizes["hybrid_override_pattern"][
        first:first + sizes["num_hidden_layers"]]


def parts(sizes):
    """Multiply-accumulates of one sequence's forward, by part (a layer's,
    or the head's)."""
    t, d = sizes["seq_len"], sizes["hidden_size"]
    h, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    g, n = sizes["n_groups"], sizes["ssm_state_size"]
    heads, kv, dh = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    routed = sizes["published"]["n_routed_experts"]
    held, per_tok = sizes["n_routed_experts"], sizes["num_experts_per_tok"]
    return {
        # [z | xBC | dt] in, the output
        "ssm_products": t * d * (2 * h * p + 2 * g * n + h) + t * h * p * d,
        "ssm_scan": t * h * 3 * p * n,
        "attn_products": t * d * (heads + 2 * kv) * dh + t * heads * dh * d,
        "attn_pairs": pairs(t) * heads * 2 * dh,
        "router": t * d * routed,
        "shared": 2 * t * d * sizes["moe_shared_expert_intermediate_size"],
        "experts": (t * per_tok * held // routed) * 2 * d
        * sizes["moe_intermediate_size"],
        "head": t * d * sizes["vocab_size"],
    }


def scan_flops(sizes):
    """The scan's own forward FLOPs of one sequence, every state-space
    layer held: what ``ssm_scan_mfu_pct`` sets against the op's time."""
    return 2 * layer_letters(sizes).count("M") * parts(sizes)["ssm_scan"]


def forward_flops(sizes):
    p = parts(sizes)
    by_letter = {"M": p["ssm_products"] + p["ssm_scan"],
                 "*": p["attn_products"] + p["attn_pairs"],
                 "E": p["router"] + p["shared"] + p["experts"]}
    return 2 * (p["head"] + sum(by_letter[c] for c in layer_letters(sizes)))


def train_flops_per_sample(sizes):
    return 3 * forward_flops(sizes)
