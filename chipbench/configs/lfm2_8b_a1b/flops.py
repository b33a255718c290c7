"""FLOPs that one sequence's forward and backward NEED, from the sizes as
run: two operations per multiply-accumulate of every contraction, and the
backward twice the forward.

Needed work, not work done: the attention layer counts the causal half of
its pairs, so a kernel that multiplies whole tiles and masks shows as lower
MFU; the experts count the EXPECTED assignments that reach the experts held
(``T * per_token * held / routed``, the uniform router's share), not the
rows a padded grouped product walks.  A conv layer's mixer is its two
projections (hidden to 3 * hidden, hidden to hidden) and the filter's
``taps`` multiply-accumulates a channel and token; the two gates are one
multiplication each and are not counted.  The dense layer is counted whole
(every chip computes it), and so is the head, which is the embedding's
slice used a second time.
"""


def layer_kinds(sizes):
    """[(the source's name of the mixer, dense?)] of the layers held, from
    the published index of each."""
    first = sizes["layer_offset"]
    return [(sizes["layer_types"][i], i < sizes["num_dense_layers"])
            for i in range(first, first + sizes["num_hidden_layers"])]


def pairs(t):
    """Query-key pairs that count: the causal half with the diagonal."""
    return t * (t + 1) // 2


def forward_flops(sizes):
    t, d = sizes["seq_len"], sizes["hidden_size"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    dh = d // hq
    routed = sizes["published"]["num_experts"]
    held, per_tok = sizes["num_experts"], sizes["num_experts_per_tok"]
    f = sizes["moe_intermediate_size"]
    conv = t * d * (3 * d + d) + t * d * sizes["conv_L_cache"]
    # q and the output projection; k and v; scores and values
    attention = t * d * (2 * hq * dh + 2 * hkv * dh) \
        + 2 * pairs(t) * hq * dh
    dense = 3 * t * d * sizes["intermediate_size"]
    experts = t * d * routed + (t * per_tok * held // routed) * 3 * d * f
    total = t * d * sizes["vocab_size"]
    for kind, is_dense in layer_kinds(sizes):
        total += (conv if kind == "conv" else attention) \
            + (dense if is_dense else experts)
    return 2 * total


def train_flops_per_sample(sizes):
    return 3 * forward_flops(sizes)
