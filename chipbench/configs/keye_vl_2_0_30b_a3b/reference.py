"""Keye-VL-2.0-30B-A3B's decoder, one of 8 chips' share, in plain float32
``jax.numpy``: forward, next-token loss, gradients and one Adam step.
Independent of ``paddle_tpu``: no ops, no kernels, no AMP.

The layer (x: one sequence ``[T, hidden]``; text positions, so the three
``mrope_section`` components are equal and the rotary embedding is the
plain one over the whole head):

- ``h1 = h + Attn(RMSNorm(h))``, ``h2 = h1 + MoE(RMSNorm(h1))``; a final RMS
  norm, the untied head over the vocabulary slice, mean next-token
  cross-entropy over the slice.
- Attention: ``q = RoPE(RMSNorm_head(x Wq))``, ``k = RoPE(RMSNorm_head(x
  Wk))``, ``v = x Wv``; query head h reads key-value head ``h // group``.
- Indexer: ``qI = RoPE(x WqI)``, ``kI = RoPE(x WkI)`` (one head), ``w = x
  Ww``; ``I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s]) / sqrt(d_I)``; ``S_t``
  = the ``topk`` keys ``s <= t`` of largest ``I[t,s]`` (``lax.top_k`` on the
  full row: the lowest index wins a tie), every ``s <= t`` while
  ``t < topk``.  The selection is piecewise constant, so the three indexer
  weights get a gradient of exactly zero under this loss.
- ``o[t,h] = sum_{s in S_t} softmax_{S_t}(q[t,h] . k[s] / sqrt(d)) v[s]``.
- MoE: ``g = softmax(x Wr)`` over the router's published width; the top 8
  renormalized; ``y = sum_{e in top 8, e held} weight_e W2_e(silu(W1_e x) *
  W3_e x)`` over the experts ``[expert_offset, expert_offset + held)``.
  What the absent experts would add is left out.  No capacity, no drop.

Attention and index scores run in query blocks under ``jax.checkpoint`` and
every layer is a checkpoint, so that the comparison at 8,192 tokens fits
beside six float32 copies of the parameters.  ``matmul_dtype`` rounds the
inputs of every contraction to a narrower type: that is the CONTROL of the
comparison, never the reference.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def _dims(s):
    sa = s["sa_config"]
    return dict(
        d=s["hidden_size"], hq=s["num_attention_heads"],
        hkv=s["num_key_value_heads"], dh=s["head_dim"],
        hi=sa["indexer_num_heads"], di=sa["indexer_head_dim"],
        topk=sa["topk"], routed=s["published"]["num_experts"],
        held=s["num_experts"], f=s["moe_intermediate_size"],
        k=s["num_experts_per_tok"], v=s["vocab_size"],
        layers=s["num_hidden_layers"], eps=s["rms_norm_eps"],
        theta=float(s["rope_theta"]), offset=s.get("expert_offset", 0))


def param_spec(s):
    """[(name, shape, init)] in the order the program creates its trainable
    parameters.  init: ("normal", std) | ("near", centre)."""
    c = _dims(s)
    d, std = c["d"], ("normal", 0.02)
    spec = [("tok_emb", (c["v"], d), std)]
    for i in range(c["layers"]):
        p = f"l{i}"
        spec += [
            (f"{p}_attn_norm", (d,), ("near", 1.0)),
            (f"{p}_q_w", (d, c["hq"] * c["dh"]), std),
            (f"{p}_q_norm", (c["dh"],), ("near", 1.0)),
            (f"{p}_k_w", (d, c["hkv"] * c["dh"]), std),
            (f"{p}_k_norm", (c["dh"],), ("near", 1.0)),
            (f"{p}_v_w", (d, c["hkv"] * c["dh"]), std),
            (f"{p}_idx_q_w", (d, c["hi"] * c["di"]), std),
            (f"{p}_idx_k_w", (d, c["di"]), std),
            (f"{p}_idx_w_w", (d, c["hi"]), std),
            (f"{p}_o_w", (c["hq"] * c["dh"], d), std),
            (f"{p}_moe_norm", (d,), ("near", 1.0)),
            (f"{p}_router_w", (d, c["routed"]), std),
            (f"{p}_w1", (c["held"], d, c["f"]), std),
            (f"{p}_w3", (c["held"], d, c["f"]), std),
            (f"{p}_w2", (c["held"], c["f"], d), std),
        ]
    spec += [("final_norm", (d,), ("near", 1.0)),
             ("lm_head_w", (d, c["v"]), std)]
    return spec


PER_LAYER = 15


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


def selection(qi, ki, w, topk, q0, rnd=lambda a: a):
    """[bq, T] bool: the keys each of the block's queries attends.  qi:
    [bq, Hi, dI] (rotated), ki: [T, dI], w: [bq, Hi]; q0 is the block's
    first position."""
    bq, t = qi.shape[0], ki.shape[0]
    dots = jnp.einsum("qjd,sd->qjs", rnd(qi), rnd(ki))
    score = jnp.einsum("qj,qjs->qs", w, jax.nn.relu(dots)) \
        / math.sqrt(qi.shape[-1])
    causal = (q0 + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
    score = jnp.where(causal, score, -jnp.inf)
    k = min(topk, t)
    vals, idx = jax.lax.top_k(score, k)
    rows = jnp.broadcast_to(jnp.arange(bq)[:, None], idx.shape)
    return jnp.zeros((bq, t), bool).at[rows, idx].set(vals > -jnp.inf)


def attention(x, ws, c, rnd):
    """x: [T, hidden] (normed); ws: the layer's nine attention weights."""
    wq, gq, wk, gk, wv, wqi, wki, www, wo = ws
    t = x.shape[0]
    hq, hkv, dh = c["hq"], c["hkv"], c["dh"]

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    q = rope(rms_norm(mm(x, wq).reshape(t, hq, dh), gq, c["eps"]),
             c["theta"])
    k = rope(rms_norm(mm(x, wk).reshape(t, hkv, dh), gk, c["eps"]),
             c["theta"])
    v = mm(x, wv).reshape(t, hkv, dh)
    qi = rope(mm(x, wqi).reshape(t, c["hi"], c["di"]), c["theta"])
    ki = rope(mm(x, wki).reshape(t, 1, c["di"]), c["theta"])[:, 0]
    w = mm(x, www)
    bq = min(Q_BLOCK, t)
    assert t % bq == 0
    qb = q.reshape(t // bq, bq, hkv, hq // hkv, dh)

    @jax.checkpoint
    def block(args):
        i, qblk, qiblk, wblk = args
        sel = jax.lax.stop_gradient(
            selection(qiblk, ki, wblk, c["topk"], i * bq, rnd))
        s = jnp.einsum("qgrd,sgd->grqs", rnd(qblk), rnd(k)) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(sel[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("grqs,sgd->qgrd", rnd(p), rnd(v))
        return o.reshape(bq, hq * dh)

    o = jax.lax.map(block, (jnp.arange(t // bq), qb,
                            qi.reshape(t // bq, bq, c["hi"], c["di"]),
                            w.reshape(t // bq, bq, c["hi"])))
    return mm(o.reshape(t, hq * dh), wo)


def moe_layer(x, wr, w1, w3, w2, top_k, offset=0, rnd=lambda a: a):
    """The part of the expert layer that the experts ``[offset, offset +
    w1.shape[0])`` give.  x: [T, hidden]; wr: [hidden, routed]."""
    g = jax.nn.softmax(jnp.matmul(rnd(x), rnd(wr)), -1)
    vals, idx = jax.lax.top_k(g, top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        we = jnp.sum(jnp.where(idx == e + offset, vals, 0.0), -1)
        h = jax.nn.silu(jnp.matmul(rnd(x), rnd(w1[e]))) \
            * jnp.matmul(rnd(x), rnd(w3[e]))
        y = y + we[:, None] * jnp.matmul(rnd(h), rnd(w2[e]))
    return y


def forward_one(params, tokens, s, matmul_dtype=None):
    """Logits [T, V] of one sequence."""
    c = _dims(s)
    rnd = _rounder(matmul_dtype)
    h = params[0][tokens]

    @jax.checkpoint
    def layer(h, ws):
        a = attention(rms_norm(h, ws[0], c["eps"]), ws[1:10], c, rnd)
        h = h + a
        m = moe_layer(rms_norm(h, ws[10], c["eps"]), ws[11], ws[12],
                      ws[13], ws[14], c["k"], c["offset"], rnd)
        return h + m

    for i in range(c["layers"]):
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
