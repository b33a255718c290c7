"""LFM2-8B-A1B's decoder, one of 4 chips' share, in plain float32
``jax.numpy``: forward, next-token loss, gradients, one Adam step and the
routers' balancing rule.  Independent of ``paddle_tpu``: no ops, no
kernels, no AMP.

The layer (x: one sequence ``[T, 2048]``, layer i of the PUBLISHED model,
``layer_offset`` being the index of the first layer held; keys of the
source's config in backticks, the rest from the family's public
``lfm2_moe`` implementation, listed in ``config.json`` under ``assumed``)::

    h0 = Emb[tokens]                                          (no scaling)
    a  = RMSNorm_operator(x)                                   (`norm_eps`)
    `conv` layer:
        [B | C | u] = a Win      Win [2048, 6144], no bias (`conv_bias`)
        z    = B * u
        c[t] = sum_{j<L} w[:, j] * z[t - (L-1) + j],  z[s] = 0 for s < 0
               L = `conv_L_cache` 3; w [2048, 3]: one filter a channel
        y    = (C * c) Wout      Wout [2048, 2048]; no activation
    `full_attention` layer:
        q = a Wq [T,32,64], k = a Wk [T,8,64], v = a Wv [T,8,64]
        q = RMSNorm_head(q), k = RMSNorm_head(k)      (over the 64 dims)
        q, k = RoPE(q, k; `rope_theta`, rotate-half, all 64 dims)
        y = concat_h softmax_{s <= t}(q_h k_{h // 4}^T / 8) v_{h // 4}  Wo
    x1 = x + y;   m = RMSNorm_ffn(x1)
    i <  `num_dense_layers`: f = W2(silu(W1 m) * W3 m), `intermediate_size`
    i >= `num_dense_layers`: s = sigmoid(m Wr) in R^32
        E = top-4 of (s + b)                          (`use_expert_bias`)
        w_e = s_e / (sum_E s + 1e-6) * `routed_scaling_factor`
        f = sum_{e in E, held here} w_e W2_e(silu(W1_e m) * W3_e m),
            width `moe_intermediate_size`; no shared expert
    x2 = x1 + f
    logits = RMSNorm_final(x_last) Emb^T  (tied); mean next-token
    cross-entropy
    after each step, per routed layer:
        b_e += 1e-3 * sign(mean_e'(n_e') - n_e)

``n_e`` is the step's assignments to expert e over all 32, held here or
not; b starts at 0 and gets no gradient.  What the absent experts would add
is left out; the mixers, the router and the dense layer are whole.  No
capacity, no drop, no auxiliary loss.  ``Emb`` is ONE parameter: its
gradient is the lookup's rows plus the head product's.

Attention runs in query blocks under ``jax.checkpoint``, every layer and
every expert's feed-forward is a checkpoint, so that the comparison at the
timed sequence length fits beside six float32 copies of the parameters.
``matmul_dtype`` rounds the inputs of every contraction to a narrower type:
that is the CONTROL of the comparison, never the reference.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
KINDS = ("conv", "full_attention")


def _dims(s):
    first = s["layer_offset"]
    held = range(first, first + s["num_hidden_layers"])
    assert set(s["layer_types"]) <= set(KINDS), s["layer_types"]
    return dict(
        d=s["hidden_size"], hq=s["num_attention_heads"],
        hkv=s["num_key_value_heads"],
        dh=s["hidden_size"] // s["num_attention_heads"],
        taps=s["conv_L_cache"],
        routed=s["published"]["num_experts"], held=s["num_experts"],
        fe=s["moe_intermediate_size"], fd=s["intermediate_size"],
        k=s["num_experts_per_tok"], v=s["vocab_size"],
        eps=s["norm_eps"], theta=float(s["rope_theta"]),
        offset=s.get("expert_offset", 0),
        scale=s["routed_scaling_factor"],
        route_eps=s["assumed"]["route_norm_eps"],
        # per layer held: (the source's name of its mixer, dense?)
        kinds=[(s["layer_types"][i], i < s["num_dense_layers"])
               for i in held])


def layer_spec(p, c, kind, dense):
    """One layer's [(name, shape, init)], in the program's order."""
    d, std, one = c["d"], ("normal", 0.02), ("near", 1.0)
    if kind == "conv":
        spec = [(f"{p}_conv_norm", (d,), one),
                (f"{p}_conv_in_w", (d, 3 * d), std),
                (f"{p}_conv_w", (d, c["taps"]), std),
                (f"{p}_conv_out_w", (d, d), std)]
    else:
        wide = c["hq"] * c["dh"]
        spec = [(f"{p}_attn_norm", (d,), one),
                (f"{p}_q_w", (d, wide), std),
                (f"{p}_q_norm", (c["dh"],), one),
                (f"{p}_k_w", (d, c["hkv"] * c["dh"]), std),
                (f"{p}_k_norm", (c["dh"],), one),
                (f"{p}_v_w", (d, c["hkv"] * c["dh"]), std),
                (f"{p}_o_w", (wide, d), std)]
    if dense:
        return spec + [(f"{p}_mlp_norm", (d,), one),
                       (f"{p}_mlp_w1", (d, c["fd"]), std),
                       (f"{p}_mlp_w3", (d, c["fd"]), std),
                       (f"{p}_mlp_w2", (c["fd"], d), std)]
    return spec + [(f"{p}_moe_norm", (d,), one),
                   (f"{p}_router_w", (d, c["routed"]), std),
                   (f"{p}_w1", (c["held"], d, c["fe"]), std),
                   (f"{p}_w3", (c["held"], d, c["fe"]), std),
                   (f"{p}_w2", (c["held"], c["fe"], d), std)]


def param_spec(s):
    """[(name, shape, init)] in the order the program creates its trainable
    parameters; no head: the embedding is the head.  init: ("normal", std)
    | ("near", centre)."""
    c = _dims(s)
    spec = [("tok_emb", (c["v"], c["d"]), ("normal", 0.02))]
    for i, (kind, dense) in enumerate(c["kinds"]):
        spec += layer_spec(f"l{i}", c, kind, dense)
    return spec + [("final_norm", (c["d"],), ("near", 1.0))]


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


def causal_filter(z, w):
    """z: [T, C]; w: [C, L].  ``c[t] = sum_j w[:, j] z[t - (L-1) + j]`` as
    the sum over L shifted copies of z, each zero before the sequence's
    first token."""
    t, taps = z.shape[0], w.shape[1]
    out = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j             # how far back this tap looks
        shifted = jnp.concatenate([jnp.zeros_like(z[:back]), z[:t - back]]) \
            if back else z
        out = out + w[:, j] * shifted
    return out


def short_conv(x, ws, rnd=lambda a: a):
    """x: [T, hidden] (normed); ws: in-projection, filter, out-projection."""
    win, w, wout = ws
    d = w.shape[0]
    bcu = jnp.matmul(rnd(x), rnd(win))
    gate_b, gate_c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    return jnp.matmul(rnd(gate_c * causal_filter(gate_b * u, w)), rnd(wout))


def attention(x, ws, c, rnd):
    """x: [T, hidden] (normed); ws: the layer's six attention weights."""
    wq, gq, wk, gk, wv, wo = ws
    t = x.shape[0]
    hq, hkv, dh = c["hq"], c["hkv"], c["dh"]

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    q = rope(rms_norm(mm(x, wq).reshape(t, hq, dh), gq, c["eps"]),
             c["theta"])
    k = rope(rms_norm(mm(x, wk).reshape(t, hkv, dh), gk, c["eps"]),
             c["theta"])
    v = mm(x, wv).reshape(t, hkv, dh)
    bq = min(Q_BLOCK, t)
    assert t % bq == 0
    qb = q.reshape(t // bq, bq, hkv, hq // hkv, dh)

    @jax.checkpoint
    def block(args):
        i, qblk = args
        counts = (i * bq + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.einsum("qgrd,sgd->grqs", rnd(qblk), rnd(k)) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(counts[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("grqs,sgd->qgrd", rnd(p), rnd(v))
        return o.reshape(bq, hq * dh)

    o = jax.lax.map(block, (jnp.arange(t // bq), qb)).reshape(t, hq * dh)
    return mm(o, wo)


def feed_forward(x, w1, w3, w2, rnd=lambda a: a):
    h = jax.nn.silu(jnp.matmul(rnd(x), rnd(w1))) \
        * jnp.matmul(rnd(x), rnd(w3))
    return jnp.matmul(rnd(h), rnd(w2))


def route(x, wr, bias, top_k, scale, eps, rnd=lambda a: a):
    """(weights [T, k], experts [T, k]): the bias chooses, the scores
    weigh."""
    s = jax.nn.sigmoid(jnp.matmul(rnd(x), rnd(wr)))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    vals = jnp.take_along_axis(s, idx, -1)
    return vals / (jnp.sum(vals, -1, keepdims=True) + eps) * scale, idx


def routed(x, wr, bias, w1, w3, w2, top_k, scale, eps, offset=0,
           rnd=lambda a: a):
    """(what the experts ``[offset, offset + w1.shape[0])`` give, the
    assignments to each of the router's experts [routed] int32).  x:
    [T, hidden]; wr: [hidden, routed]."""
    vals, idx = route(x, wr, bias, top_k, scale, eps, rnd)
    expert = jax.checkpoint(lambda x, a, b, c: feed_forward(x, a, b, c, rnd))
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        we = jnp.sum(jnp.where(idx == e + offset, vals, 0.0), -1)
        y = y + we[:, None] * expert(x, w1[e], w3[e], w2[e])
    counts = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(wr.shape[-1]),
                     0).astype(jnp.int32)
    return y, counts


def _layers(params, c):
    """The parameter list without the embedding and the final norm, cut
    into one list a layer."""
    out, at = [], 1
    for kind, dense in c["kinds"]:
        n = (4 if kind == "conv" else 7) + (4 if dense else 5)
        out.append(params[at:at + n])
        at += n
    assert at == len(params) - 1
    return out


def forward_one(params, tokens, s, matmul_dtype=None, biases=None,
                head=None):
    """(logits [T, V] of one sequence, [counts [routed] per routed
    layer]).  ``biases``: one [routed] per routed layer, zeros if None.
    ``head``: the matrix whose transpose makes the logits; the embedding
    (None) in the model, another array only to tell its two uses apart."""
    c = _dims(s)
    rnd = _rounder(matmul_dtype)
    h = params[0][tokens]
    all_counts = []
    for (kind, dense), ws in zip(c["kinds"], _layers(params, c)):
        bias = None
        if not dense:       # one bias and one count a routed layer so far
            bias = jnp.zeros((c["routed"],), jnp.float32) \
                if biases is None else biases[len(all_counts)]

        @jax.checkpoint
        def layer(h, ws, bias, kind=kind, dense=dense):
            n = 4 if kind == "conv" else 7
            a = rms_norm(h, ws[0], c["eps"])
            h = h + (short_conv(a, ws[1:n], rnd) if kind == "conv"
                     else attention(a, ws[1:n], c, rnd))
            m = rms_norm(h, ws[n], c["eps"])
            if dense:
                return h + feed_forward(m, *ws[n + 1:], rnd), None
            f, counts = routed(m, ws[n + 1], bias, *ws[n + 2:], c["k"],
                               c["scale"], c["route_eps"], c["offset"], rnd)
            return h + f, counts

        h, counts = layer(h, ws, bias)
        if counts is not None:
            all_counts.append(counts)
    h = rms_norm(h, params[-1], c["eps"])
    head = params[0] if head is None else head
    return jnp.matmul(rnd(h), rnd(head).T), all_counts


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
    return bias + s["assumed"]["bias_update_rate"] \
        * jnp.sign(jnp.mean(n) - n)


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
