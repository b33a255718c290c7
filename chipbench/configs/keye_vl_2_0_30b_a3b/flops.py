"""FLOPs that one sequence's forward and backward NEED, from the sizes as
run: two operations per multiply-accumulate of every contraction, and the
backward twice the forward.

Needed work, not work done: attention counts the SELECTED pairs only
(``sum_t min(t + 1, topk)``), so a kernel that multiplies every causal tile
and masks shows as low MFU and a later gathering kernel as a gain; the
index scores count the causal pairs (every one has to be scored before any
can be left out); the experts count the EXPECTED assignments that reach the
experts held (``T * per_token * held / routed``, the uniform router's
share), not the rows a padded grouped product walks.
"""


def forward_flops(sizes):
    sa = sizes["sa_config"]
    t, d = sizes["seq_len"], sizes["hidden_size"]
    hq, hkv, dh = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    hi, di, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                    sa["topk"])
    routed = sizes["published"]["num_experts"]
    held, per_tok = sizes["num_experts"], sizes["num_experts_per_tok"]
    f = sizes["moe_intermediate_size"]
    selected = sum(min(s + 1, topk) for s in range(t))
    causal = t * (t + 1) // 2
    projections = t * d * (2 * hq * dh + 2 * hkv * dh)
    indexer = t * d * (hi * di + di + hi) + causal * hi * di
    attention = 2 * selected * hq * dh
    router = t * d * routed
    experts = (t * per_tok * held // routed) * 3 * d * f
    layer = projections + indexer + attention + router + experts
    head = t * d * sizes["vocab_size"]
    return 2 * (sizes["num_hidden_layers"] * layer + head)


def train_flops_per_sample(sizes):
    return 3 * forward_flops(sizes)
