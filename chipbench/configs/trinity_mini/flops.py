"""FLOPs that one sequence's forward and backward NEED, from the sizes as
run: two operations per multiply-accumulate of every contraction, and the
backward twice the forward.

Needed work, not work done: a window layer counts the pairs inside the band
(``sum_t min(t + 1, window)``), the global layer the causal half, so a
kernel that multiplies whole tiles and masks shows as lower MFU; the experts
count the EXPECTED assignments that reach the experts held (``T * per_token
* held / routed``, the uniform router's share), not the rows a padded
grouped product walks.  The output gate's projection, the shared expert and
the leading dense layer are counted whole: every chip computes them.
"""


def layer_kinds(sizes):
    """[(window or 0, dense?)] of the layers held, from the published
    index of each."""
    first = sizes["layer_offset"]
    every = sizes["global_attn_every_n_layers"]
    return [(0 if (i + 1) % every == 0 else sizes["sliding_window"],
             i < sizes["num_dense_layers"])
            for i in range(first, first + sizes["num_hidden_layers"])]


def pairs(t, window):
    """Query-key pairs that count: ``sum_t min(t + 1, window)``; the causal
    half where there is no window."""
    return sum(min(s + 1, window or t) for s in range(t))


def forward_flops(sizes):
    t, d = sizes["seq_len"], sizes["hidden_size"]
    hq, hkv, dh = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    routed = sizes["published"]["num_experts"]
    held, per_tok = sizes["num_experts"], sizes["num_experts_per_tok"]
    f = sizes["moe_intermediate_size"]
    # q, the gate and the output projection; k and v
    projections = t * d * (3 * hq * dh + 2 * hkv * dh)
    dense = 3 * t * d * sizes["intermediate_size"]
    experts = t * d * routed \
        + 3 * t * d * f * sizes["num_shared_experts"] \
        + (t * per_tok * held // routed) * 3 * d * f
    total = t * d * sizes["vocab_size"]
    for window, is_dense in layer_kinds(sizes):
        total += projections + 2 * pairs(t, window) * hq * dh \
            + (dense if is_dense else experts)
    return 2 * total


def train_flops_per_sample(sizes):
    return 3 * forward_flops(sizes)
