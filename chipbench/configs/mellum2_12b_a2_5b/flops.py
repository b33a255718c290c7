"""FLOPs that one sequence's forward and backward NEED, from the sizes as
run: two operations per multiply-accumulate of every contraction, and the
backward twice the forward.

Needed work, not work done: a window layer counts the pairs inside the band
(``sum_t min(t + 1, window)``), the full layer the causal half, so a kernel
that multiplies whole tiles and masks shows as lower MFU; the experts count
the EXPECTED assignments that reach the experts held (``T * per_token * held
/ routed``, the uniform router's share), not the rows a padded grouped
product walks.  There is no other feed-forward: ``intermediate_size`` has no
user while every ``mlp_layer_types`` entry is ``sparse``.
"""


def layer_windows(sizes):
    """The window of every layer held, 0 in a ``full_attention`` one."""
    first = sizes["layer_offset"]
    return [sizes["sliding_window"] if kind == "sliding_attention" else 0
            for kind in sizes["layer_types"][
                first:first + sizes["num_hidden_layers"]]]


def pairs(t, window):
    """Query-key pairs that count: ``sum_t min(t + 1, window)``; the causal
    half where there is no window."""
    w = min(window or t, t)
    return w * (w + 1) // 2 + (t - w) * w


def forward_flops(sizes):
    t, d = sizes["seq_len"], sizes["hidden_size"]
    hq, hkv, dh = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    routed = sizes["published"]["num_experts"]
    held, per_tok = sizes["num_experts"], sizes["num_experts_per_tok"]
    # q and the output projection; k and v
    projections = t * d * (2 * hq * dh + 2 * hkv * dh)
    experts = t * d * routed + (t * per_tok * held // routed) * 3 * d \
        * sizes["moe_intermediate_size"]
    total = t * d * sizes["vocab_size"]
    for window in layer_windows(sizes):
        total += projections + 2 * pairs(t, window) * hq * dh + experts
    return 2 * total


def train_flops_per_sample(sizes):
    return 3 * forward_flops(sizes)
