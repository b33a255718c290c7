"""FLOPs that one document's forward and backward NEED, from the sizes as
run: two operations per multiply-accumulate of every contraction, and the
backward twice the forward.

A step of diffusion over blocks walks TWO copies of the document, so every
row-wise contraction (projections, router, experts) counts ``2 * seq_len``
positions a document; the head reads the noised rows alone, ``seq_len``.
Needed work, not work done: attention counts the pairs the block rule
states (a query of block b, clean or noised, counts ``(b + 1) * block``
keys: ``2 * block^2 * n (n + 1) / 2`` a head over ``n = seq_len / block``
blocks), not the tiles a kernel walks; the experts count the EXPECTED
assignments that reach the experts held (``positions * per_token * held /
routed``, the uniform router's share), not the rows a padded grouped
product walks.
"""


def rule_pairs(sizes):
    """(query, key) pairs a head needs over both copies of one document."""
    block = sizes["block_diffusion"]["block_length"]
    n = sizes["seq_len"] // block
    return 2 * block * block * n * (n + 1) // 2


def forward_flops(sizes):
    t, d = sizes["seq_len"], sizes["hidden_size"]
    rows = 2 * t
    hq, hkv, dh = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    routed = sizes["published"]["num_experts"]
    held, per_tok = sizes["num_experts"], sizes["num_experts_per_tok"]
    f = sizes["moe_intermediate_size"]
    projections = rows * d * (2 * hq * dh + 2 * hkv * dh)
    attention = 2 * rule_pairs(sizes) * hq * dh
    router = rows * d * routed
    experts = (rows * per_tok * held // routed) * 3 * d * f
    layer = projections + attention + router + experts
    head = t * d * sizes["vocab_size"]
    return 2 * (sizes["num_hidden_layers"] * layer + head)


def train_flops_per_sample(sizes):
    return 3 * forward_flops(sizes)
