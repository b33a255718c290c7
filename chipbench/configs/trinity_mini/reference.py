"""Trinity-Mini's decoder, one of 16 chips' share, in plain float32
``jax.numpy``: forward, next-token loss, gradients, one Adam step and the
routers' balancing rule.  Independent of ``paddle_tpu``: no ops, no
kernels, no AMP.

The layer (x: one sequence ``[T, 2048]``, layer i of the PUBLISHED model,
``layer_offset`` being the index of the first layer held; keys of the
source's config in backticks, the rest from the family's public ``afmoe``
implementation, listed in ``config.json`` under ``assumed``)::

    h0 = Emb[tokens] * sqrt(hidden)                       (`mup_enabled`)
    a  = RMSNorm_in(x);  q = a Wq [T,32,128], k = a Wk [T,4,128],
                         v = a Wv [T,4,128],  g = a Wg [T,4096]
    q  = RMSNorm_head(q), k = RMSNorm_head(k)   (per head; `rms_norm_eps`)
    `sliding_attention` layer: q, k = RoPE(q, k; `rope_theta`, rotate-half,
        all 128 dims); key s counts for query t iff
        0 <= t - s < `sliding_window`
    `full_attention` layer ((i+1) % `global_attn_every_n_layers` == 0): no
        positions; key s counts iff s <= t
    o  = softmax(q k^T / sqrt(128) over the keys that count) v
    y  = (o * sigmoid(g)) Wo
    x1 = x + RMSNorm_post_attn(y);   m = RMSNorm_pre_mlp(x1)
    i <  `num_dense_layers`: f = W2(silu(W1 m) * W3 m), `intermediate_size`
    i >= `num_dense_layers`: s = sigmoid(m Wr) in R^128
        E = top-8 of (s + b);  w_e = s_e / (sum_E s + 1e-20) * `route_scale`
        f = Shared(m) + sum_{e in E, held here} w_e Expert_e(m), SiLU-gated,
            width `moe_intermediate_size`
    x2 = x1 + RMSNorm_post_mlp(f)
    logits = RMSNorm_final(x_last) Whead; mean next-token cross-entropy
    after each step, per routed layer:
        b_e += `load_balance_coeff` * sign(mean_e'(n_e') - n_e)

``n_e`` is the step's assignments to expert e over all 128, held here or
not; b starts at 0 and gets no gradient.  What the absent experts would add
is left out; the shared expert, the router and the dense layer are whole.
No capacity, no drop, no auxiliary loss, no group limit.

Attention runs in query blocks under ``jax.checkpoint`` and every layer is
a checkpoint, so that the comparison at the timed sequence length fits
beside six float32 copies of the parameters.  ``matmul_dtype`` rounds the inputs of
every contraction to a narrower type: that is the CONTROL of the comparison,
never the reference.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
NORM_EPS = 1e-20        # in the router's renormalization


def _dims(s):
    first = s["layer_offset"]
    every = s["global_attn_every_n_layers"]
    return dict(
        d=s["hidden_size"], hq=s["num_attention_heads"],
        hkv=s["num_key_value_heads"], dh=s["head_dim"],
        routed=s["published"]["num_experts"], held=s["num_experts"],
        f=s["moe_intermediate_size"] * s["num_shared_experts"],
        fe=s["moe_intermediate_size"], fd=s["intermediate_size"],
        k=s["num_experts_per_tok"], v=s["vocab_size"],
        eps=s["rms_norm_eps"], theta=float(s["rope_theta"]),
        offset=s.get("expert_offset", 0), scale=s["route_scale"],
        embed=math.sqrt(s["hidden_size"]) if s["mup_enabled"] else 1.0,
        # per layer held: (its window, 0 in a global layer; dense?)
        kinds=[(0 if (i + 1) % every == 0 else s["sliding_window"],
                i < s["num_dense_layers"])
               for i in range(first, first + s["num_hidden_layers"])])


def layer_spec(p, c, dense):
    """One layer's [(name, shape, init)], in the program's order."""
    d, std, one = c["d"], ("normal", 0.02), ("near", 1.0)
    wide = c["hq"] * c["dh"]
    spec = [
        (f"{p}_attn_norm", (d,), one),
        (f"{p}_q_w", (d, wide), std), (f"{p}_q_norm", (c["dh"],), one),
        (f"{p}_k_w", (d, c["hkv"] * c["dh"]), std),
        (f"{p}_k_norm", (c["dh"],), one),
        (f"{p}_v_w", (d, c["hkv"] * c["dh"]), std),
        (f"{p}_gate_w", (d, wide), std), (f"{p}_o_w", (wide, d), std),
        (f"{p}_post_attn_norm", (d,), one)]
    if dense:
        spec += [(f"{p}_mlp_norm", (d,), one),
                 (f"{p}_mlp_w1", (d, c["fd"]), std),
                 (f"{p}_mlp_w3", (d, c["fd"]), std),
                 (f"{p}_mlp_w2", (c["fd"], d), std)]
    else:
        spec += [(f"{p}_moe_norm", (d,), one),
                 (f"{p}_shared_w1", (d, c["f"]), std),
                 (f"{p}_shared_w3", (d, c["f"]), std),
                 (f"{p}_shared_w2", (c["f"], d), std),
                 (f"{p}_router_w", (d, c["routed"]), std),
                 (f"{p}_w1", (c["held"], d, c["fe"]), std),
                 (f"{p}_w3", (c["held"], d, c["fe"]), std),
                 (f"{p}_w2", (c["held"], c["fe"], d), std)]
    return spec + [(f"{p}_post_mlp_norm", (d,), one)]


def param_spec(s):
    """[(name, shape, init)] in the order the program creates its trainable
    parameters.  init: ("normal", std) | ("near", centre)."""
    c = _dims(s)
    spec = [("tok_emb", (c["v"], c["d"]), ("normal", 0.02))]
    for i, (_, dense) in enumerate(c["kinds"]):
        spec += layer_spec(f"l{i}", c, dense)
    return spec + [("final_norm", (c["d"],), ("near", 1.0)),
                   ("lm_head_w", (c["d"], c["v"]), ("normal", 0.02))]


def init_params(seed, s):
    """All weights on the device in one jitted call, float32."""
    spec = param_spec(s)

    def make(key):
        out = []
        for i, (_, shape, init) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if init[0] == "normal":
                w = init[1] * jax.random.normal(k, shape, jnp.float32)
            else:
                w = init[1] + jax.random.uniform(k, shape, jnp.float32,
                                                 -0.05, 0.05)
            out.append(w)
        return out

    return jax.jit(make)(jax.random.PRNGKey(np.uint32(seed % (2 ** 32))))


def _rounder(matmul_dtype):
    if matmul_dtype is None:
        return lambda a: a
    return lambda a: a.astype(matmul_dtype).astype(jnp.float32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x: [T, H, d]; position t rotates pair (i, i + d/2) by t * theta^(-2i/d)
    (the rotate-half form of the family's public modelling code)."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(x, ws, c, window, rnd):
    """x: [T, hidden] (normed); ws: the layer's seven attention weights;
    ``window`` 0 in a global layer."""
    wq, gq, wk, gk, wv, wg, wo = ws
    t = x.shape[0]
    hq, hkv, dh = c["hq"], c["hkv"], c["dh"]

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    q = rms_norm(mm(x, wq).reshape(t, hq, dh), gq, c["eps"])
    k = rms_norm(mm(x, wk).reshape(t, hkv, dh), gk, c["eps"])
    if window:
        q, k = rope(q, c["theta"]), rope(k, c["theta"])
    v = mm(x, wv).reshape(t, hkv, dh)
    bq = min(Q_BLOCK, t)
    assert t % bq == 0
    qb = q.reshape(t // bq, bq, hkv, hq // hkv, dh)

    @jax.checkpoint
    def block(args):
        i, qblk = args
        back = (i * bq + jnp.arange(bq))[:, None] - jnp.arange(t)[None, :]
        counts = (back >= 0) & (back < (window or t))
        s = jnp.einsum("qgrd,sgd->grqs", rnd(qblk), rnd(k)) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(counts[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("grqs,sgd->qgrd", rnd(p), rnd(v))
        return o.reshape(bq, hq * dh)

    o = jax.lax.map(block, (jnp.arange(t // bq), qb)).reshape(t, hq * dh)
    return mm(o * jax.nn.sigmoid(mm(x, wg)), wo)


def feed_forward(x, w1, w3, w2, rnd=lambda a: a):
    h = jax.nn.silu(jnp.matmul(rnd(x), rnd(w1))) \
        * jnp.matmul(rnd(x), rnd(w3))
    return jnp.matmul(rnd(h), rnd(w2))


def route(x, wr, bias, top_k, scale, rnd=lambda a: a):
    """(weights [T, k], experts [T, k]): the bias chooses, the scores
    weigh."""
    s = jax.nn.sigmoid(jnp.matmul(rnd(x), rnd(wr)))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    vals = jnp.take_along_axis(s, idx, -1)
    return vals / (jnp.sum(vals, -1, keepdims=True) + NORM_EPS) * scale, idx


def routed(x, wr, bias, w1, w3, w2, top_k, scale, offset=0,
           rnd=lambda a: a):
    """(what the experts ``[offset, offset + w1.shape[0])`` give, the
    assignments to each of the router's experts [routed] int32).  x:
    [T, hidden]; wr: [hidden, routed]."""
    vals, idx = route(x, wr, bias, top_k, scale, rnd)
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        we = jnp.sum(jnp.where(idx == e + offset, vals, 0.0), -1)
        y = y + we[:, None] * feed_forward(x, w1[e], w3[e], w2[e], rnd)
    counts = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(wr.shape[-1]),
                     0).astype(jnp.int32)
    return y, counts


def _layers(params, c):
    """The parameter list cut into [tok_emb], one list a layer, [final
    norm, head]."""
    out, at = [], 1
    for _, dense in c["kinds"]:
        n = 14 if dense else 18
        out.append(params[at:at + n])
        at += n
    return out


def forward_one(params, tokens, s, matmul_dtype=None, biases=None):
    """(logits [T, V] of one sequence, [counts [routed] per routed
    layer]).  ``biases``: one [routed] per routed layer, zeros if None."""
    c = _dims(s)
    rnd = _rounder(matmul_dtype)
    h = params[0][tokens] * c["embed"]
    all_counts = []
    for (window, dense), ws in zip(c["kinds"], _layers(params, c)):
        bias = None
        if not dense:       # one bias and one count a routed layer so far
            bias = jnp.zeros((c["routed"],), jnp.float32) \
                if biases is None else biases[len(all_counts)]

        @jax.checkpoint
        def layer(h, ws, bias, window=window, dense=dense):
            y = attention(rms_norm(h, ws[0], c["eps"]), ws[1:8], c, window,
                          rnd)
            h = h + rms_norm(y, ws[8], c["eps"])
            m = rms_norm(h, ws[9], c["eps"])
            if dense:
                f, counts = feed_forward(m, *ws[10:13], rnd), None
            else:
                f, counts = routed(m, ws[13], bias, *ws[14:17], c["k"],
                                   c["scale"], c["offset"], rnd)
                f = f + feed_forward(m, *ws[10:13], rnd)
            return h + rms_norm(f, ws[-1], c["eps"]), counts

        h, counts = layer(h, ws, bias)
        if counts is not None:
            all_counts.append(counts)
    h = rms_norm(h, params[-2], c["eps"])
    return jnp.matmul(rnd(h), rnd(params[-1])), all_counts


def loss_and_counts(params, feed, s, matmul_dtype=None, biases=None):
    """(mean loss over the batch, [the batch's assignments per routed
    layer])."""
    tokens, labels = feed["tokens"], feed["labels"][..., 0]
    total, counts = 0.0, None
    for b in range(tokens.shape[0]):
        logits, cs = forward_one(params, tokens[b], s, matmul_dtype, biases)
        logp = jax.nn.log_softmax(logits, -1)
        total = total - jnp.mean(
            jnp.take_along_axis(logp, labels[b][:, None], -1))
        counts = cs if counts is None else [a + n
                                            for a, n in zip(counts, cs)]
    return total / tokens.shape[0], counts


def loss_fn(params, feed, s, matmul_dtype=None):
    return loss_and_counts(params, feed, s, matmul_dtype)[0]


def loss_and_grads(params, feed, s, matmul_dtype=None):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(list(params), feed, s,
                                           matmul_dtype)


def bias_step(bias, counts, s):
    """The balancing rule: up for an expert that got fewer assignments
    than the mean this step, down for one that got more."""
    n = counts.astype(jnp.float32)
    return bias + s["load_balance_coeff"] * jnp.sign(jnp.mean(n) - n)


def biases_after_step(params, feed, s, biases=None):
    """Every routed layer's bias after one step on ``feed`` (from zeros
    where ``biases`` is None)."""
    with jax.default_matmul_precision("highest"):
        _, counts = loss_and_counts(params, feed, s, None, biases)
    zeros = jnp.zeros((s["published"]["num_experts"],), jnp.float32)
    return [bias_step(zeros if biases is None else biases[i], n, s)
            for i, n in enumerate(counts)]


def optimizer_step(param, grad, s):
    """The FIRST Adam step from zero moments."""
    o = s["optimizer"]
    b1, b2 = o["beta1"], o["beta2"]
    m = (1 - b1) * grad
    v = (1 - b2) * grad * grad
    lr_t = o["lr"] * math.sqrt(1 - b2) / (1 - b1)
    return param - lr_t * m / (jnp.sqrt(v) + o["epsilon"])


def step_size(s):
    return s["optimizer"]["lr"]
