"""Mellum2-12B-A2.5B-Instruct's decoder, one of 8 chips' share, in plain
float32 ``jax.numpy``: forward, next-token loss, gradients and one Adam
step.  Independent of ``paddle_tpu``: no ops, no kernels, no AMP, and its
rotary tables are made here from ``rope_parameters``.

The layer (x: one sequence ``[T, 2304]``, layer i of the PUBLISHED model,
``layer_offset`` being the index of the first layer held; keys of the
source's config in backticks, the rest listed in ``config.json`` under
``assumed``)::

    a  = RMSNorm(x);  q = a Wq [T,32,128], k = a Wk [T,4,128],
                      v = a Wv [T,4,128]                         no bias
    q  = RMSNorm_head(q), k = RMSNorm_head(k)   (per head; `rms_norm_eps`)
    `layer_types`[i] == "sliding_attention":
        q, k = RoPE(q, k), rotate-half over all 128 columns,
            inv_freq_j = `rope_theta` ** (-2j / 128), j = 0..63
        key s counts for query t iff 0 <= t - s < `sliding_window`
    `layer_types`[i] == "full_attention", `rope_parameters` of that kind
    (`rope_type` yarn):
        f_j = `rope_theta` ** (-2j / 128)
        lo = floor(turns(`beta_fast`)), hi = ceil(turns(`beta_slow`)) with
            turns(r) = 128 ln(`original_max_position_embeddings` / (2 pi r))
                       / (2 ln `rope_theta`)                  (18 and 35)
        r_j = clip((j - lo) / (hi - lo), 0, 1)
        inv_freq_j = f_j / `factor` * r_j + f_j * (1 - r_j)
        cos and sin times `attention_factor`: q and k both carry it, so
            the logits carry its square
        key s counts iff s <= t
    o  = softmax(q k^T / sqrt(128) over the keys that count) v;  y = o Wo
    x1 = x + y;  m = RMSNorm(x1)
    s  = softmax(m Wr) in R^64;  E = top-8 of s;  w_e = s_e / sum_E s
    f  = sum_{e in E, held here} w_e W2_e(silu(W1_e m) * W3_e m), width 896
    x2 = x1 + f
    logits = RMSNorm_final(x_last) Whead; mean next-token cross-entropy

What the absent experts would add is left out; the router is whole.  No
shared expert, no dense layer, no capacity, no drop, no auxiliary loss.

Attention runs in query blocks under ``jax.checkpoint`` and every layer is
a checkpoint, so that the comparison at the timed sequence length fits
beside six float32 copies of the parameters.  ``matmul_dtype`` rounds the
inputs of every contraction to a narrower type: that is the CONTROL of the
comparison, never the reference.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
PER_LAYER = 12


def _dims(s):
    first = s["layer_offset"]
    return dict(
        d=s["hidden_size"], hq=s["num_attention_heads"],
        hkv=s["num_key_value_heads"], dh=s["head_dim"],
        routed=s["published"]["num_experts"], held=s["num_experts"],
        f=s["moe_intermediate_size"], k=s["num_experts_per_tok"],
        v=s["vocab_size"], eps=s["rms_norm_eps"],
        offset=s.get("expert_offset", 0),
        # per layer held: its window (0 in a full layer) and its rotary
        kinds=[(s["sliding_window"] if kind == "sliding_attention" else 0,
                rotary_table(s["rope_parameters"][kind], s["head_dim"]))
               for kind in s["layer_types"][
                   first:first + s["num_hidden_layers"]]])


def rotary_table(rope, dims):
    """(the ``dims // 2`` frequencies, what cos and sin are multiplied by)
    of one entry of ``rope_parameters``."""
    f = [float(rope["rope_theta"]) ** (-2.0 * j / dims)
         for j in range(dims // 2)]
    if rope["rope_type"] == "default":
        return tuple(f), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def turns(r):
        return dims * math.log(rope["original_max_position_embeddings"]
                               / (2 * math.pi * r)) \
            / (2 * math.log(rope["rope_theta"]))

    lo = max(math.floor(turns(rope["beta_fast"])), 0)
    hi = min(math.ceil(turns(rope["beta_slow"])), dims - 1)
    if hi == lo:
        hi += 0.001
    ramp = [min(max((j - lo) / (hi - lo), 0.0), 1.0)
            for j in range(dims // 2)]
    return (tuple(fj / rope["factor"] * r + fj * (1.0 - r)
                  for fj, r in zip(f, ramp)),
            float(rope["attention_factor"]))


def param_spec(s):
    """[(name, shape, init)] in the order the program creates its trainable
    parameters.  init: ("normal", std) | ("near", centre)."""
    c = _dims(s)
    d, std, one = c["d"], ("normal", 0.02), ("near", 1.0)
    spec = [("tok_emb", (c["v"], d), std)]
    for i in range(len(c["kinds"])):
        p = f"l{i}"
        spec += [
            (f"{p}_attn_norm", (d,), one),
            (f"{p}_q_w", (d, c["hq"] * c["dh"]), std),
            (f"{p}_q_norm", (c["dh"],), one),
            (f"{p}_k_w", (d, c["hkv"] * c["dh"]), std),
            (f"{p}_k_norm", (c["dh"],), one),
            (f"{p}_v_w", (d, c["hkv"] * c["dh"]), std),
            (f"{p}_o_w", (c["hq"] * c["dh"], d), std),
            (f"{p}_moe_norm", (d,), one),
            (f"{p}_router_w", (d, c["routed"]), std),
            (f"{p}_w1", (c["held"], d, c["f"]), std),
            (f"{p}_w3", (c["held"], d, c["f"]), std),
            (f"{p}_w2", (c["held"], c["f"], d), std)]
    return spec + [("final_norm", (d,), one),
                   ("lm_head_w", (d, c["v"]), std)]


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


def rope(x, table):
    """x: [T, H, d]; position t rotates pair (j, j + d/2) by t * inv_freq_j
    (the rotate-half form), cos and sin times the table's factor."""
    inv_freq, factor = table
    t, _, d = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = factor * jnp.concatenate([jnp.cos(ang), jnp.cos(ang)],
                                   -1)[:, None, :]
    sin = factor * jnp.concatenate([jnp.sin(ang), jnp.sin(ang)],
                                   -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(x, ws, c, window, table, rnd):
    """x: [T, hidden] (normed); ws: the layer's six attention weights;
    ``window`` 0 in a full layer; ``table``: the layer kind's rotary."""
    wq, gq, wk, gk, wv, wo = ws
    t = x.shape[0]
    hq, hkv, dh = c["hq"], c["hkv"], c["dh"]

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    q = rope(rms_norm(mm(x, wq).reshape(t, hq, dh), gq, c["eps"]), table)
    k = rope(rms_norm(mm(x, wk).reshape(t, hkv, dh), gk, c["eps"]), table)
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
    return mm(o, wo)


def feed_forward(x, w1, w3, w2, rnd=lambda a: a):
    h = jax.nn.silu(jnp.matmul(rnd(x), rnd(w1))) \
        * jnp.matmul(rnd(x), rnd(w3))
    return jnp.matmul(rnd(h), rnd(w2))


def routed(x, wr, w1, w3, w2, top_k, offset=0, rnd=lambda a: a):
    """What the experts ``[offset, offset + w1.shape[0])`` give.  x: [T,
    hidden]; wr: [hidden, routed], every token routes over all of it."""
    s = jax.nn.softmax(jnp.matmul(rnd(x), rnd(wr)), -1)
    vals, idx = jax.lax.top_k(s, top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        we = jnp.sum(jnp.where(idx == e + offset, vals, 0.0), -1)
        y = y + we[:, None] * feed_forward(x, w1[e], w3[e], w2[e], rnd)
    return y


def forward_one(params, tokens, s, matmul_dtype=None):
    """Logits [T, V] of one sequence."""
    c = _dims(s)
    rnd = _rounder(matmul_dtype)
    h = params[0][tokens]
    for i, (window, table) in enumerate(c["kinds"]):
        @jax.checkpoint
        def layer(h, ws, window=window, table=table):
            h = h + attention(rms_norm(h, ws[0], c["eps"]), ws[1:7], c,
                              window, table, rnd)
            return h + routed(rms_norm(h, ws[7], c["eps"]), *ws[8:12],
                              c["k"], c["offset"], rnd)

        h = layer(h, params[1 + PER_LAYER * i:1 + PER_LAYER * (i + 1)])
    h = rms_norm(h, params[-2], c["eps"])
    return jnp.matmul(rnd(h), rnd(params[-1]))


def loss_fn(params, feed, s, matmul_dtype=None):
    tokens, labels = feed["tokens"], feed["labels"][..., 0]
    total = 0.0
    for b in range(tokens.shape[0]):
        logp = jax.nn.log_softmax(
            forward_one(params, tokens[b], s, matmul_dtype), -1)
        total = total - jnp.mean(
            jnp.take_along_axis(logp, labels[b][:, None], -1))
    return total / tokens.shape[0]


def loss_and_grads(params, feed, s, matmul_dtype=None):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(list(params), feed, s,
                                           matmul_dtype)


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
