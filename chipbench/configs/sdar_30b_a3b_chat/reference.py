"""SDAR-30B-A3B-Chat's decoder, one of 8 chips' share, in plain float32
``jax.numpy``: forward, the block-diffusion loss, gradients and one Adam
step.  Independent of ``paddle_tpu``: no ops, no kernels, no AMP.

The layer (the Qwen3-MoE layer, whose keys the source's config has):

- ``h1 = h + Attn(RMSNorm(h))``, ``h2 = h1 + MoE(RMSNorm(h1))``, no bias; a
  final RMS norm and the untied head over the vocabulary slice.
- Attention: ``q = RoPE(RMSNorm_head(x Wq))``, ``k = RoPE(RMSNorm_head(x
  Wk))``, ``v = x Wv``; rotate-half over the whole head; scores times
  ``head_dim ** -0.5``; query head h reads key-value head ``h // group``.
- MoE: ``g = softmax(x Wr)`` over the router's published width in float32;
  the top 8 renormalized to sum 1; ``y = sum_{e in top 8, e held} weight_e
  W2_e(silu(W1_e x) * W3_e x)`` over the experts ``[expert_offset,
  expert_offset + held)``.  What the absent experts would add is left out.
  No capacity, no drop, no auxiliary loss.

The step, diffusion over blocks: one sequence is the ``2L`` rows ``[x |
x~]``, the clean document and its noised copy (``feed["tokens"]``,
``feed["noised"]``); row ``i`` and row ``L + i`` have rotary position ``i``
and block ``B = i // block_length``.  Attention's mask is written from the
rule's three sentences (``rule_mask``):

1. a clean query attends the clean keys of blocks ``<= `` its own (its own
   block whole, later tokens of it included);
2. a noised query attends the clean keys of blocks ``<`` its own and the
   noised keys of its own block, in one softmax;
3. no clean query attends a noised key.

Every other operation acts on each row alone.  The head reads the ``L``
noised rows; row ``i``'s logits predict ``x_i`` itself;
``loss = sum_i weights_i * CE(logits_i, x_i) / (batch * L)`` with
``feed["weights"]`` the data pipeline's ``m_i / t_B(i)``.

Departures from the published description, each listed under ``assumed`` in
``config.json``: the block length, the noise's distribution and the loss's
weight (the source gives none), no shift between a row and the token it
predicts, the per-head norms, and the mask id (the slice's last).

Attention runs in query blocks under ``jax.checkpoint`` and every layer is
a checkpoint, so that the comparison at 8,192 rows fits beside six float32
copies of the parameters.  ``matmul_dtype`` rounds the inputs of every
contraction to a narrower type: that is the CONTROL of the comparison,
never the reference.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def _dims(s):
    return dict(
        d=s["hidden_size"], hq=s["num_attention_heads"],
        hkv=s["num_key_value_heads"], dh=s["head_dim"],
        routed=s["published"]["num_experts"], held=s["num_experts"],
        f=s["moe_intermediate_size"], k=s["num_experts_per_tok"],
        v=s["vocab_size"], layers=s["num_hidden_layers"],
        eps=s["rms_norm_eps"], theta=float(s["rope_theta"]),
        offset=s.get("expert_offset", 0),
        block=s["block_diffusion"]["block_length"])


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


PER_LAYER = 12


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


def rope(x, positions, theta):
    """x: [rows, H, d]; the row at ``positions[r]`` rotates pair (i, i +
    d/2) by ``positions[r] * theta^(-2i/d)`` (the rotate-half form of the
    family's public modelling code)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def rule_mask(rows, length, block):
    """[len(rows), 2 * length] bool: the keys that the queries at ``rows``
    (indices into ``[x | x~]``) attend, from the rule's three sentences."""
    keys = jnp.arange(2 * length)
    q_clean, k_clean = (rows < length)[:, None], (keys < length)[None, :]
    q_block = ((rows % length) // block)[:, None]
    k_block = ((keys % length) // block)[None, :]
    clean_to_clean = q_clean & k_clean & (k_block <= q_block)       # 1
    noised_to_clean = ~q_clean & k_clean & (k_block < q_block)      # 2
    noised_to_noised = ~q_clean & ~k_clean & (k_block == q_block)   # 2
    # 3: a clean query and a noised key are in none of the three
    return clean_to_clean | noised_to_clean | noised_to_noised


def attention(x, ws, c, rnd):
    """x: [2L, hidden] (normed); ws: the layer's six attention weights."""
    wq, gq, wk, gk, wv, wo = ws
    rows = x.shape[0]
    length = rows // 2
    hq, hkv, dh = c["hq"], c["hkv"], c["dh"]
    positions = jnp.arange(rows) % length

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    q = rope(rms_norm(mm(x, wq).reshape(rows, hq, dh), gq, c["eps"]),
             positions, c["theta"])
    k = rope(rms_norm(mm(x, wk).reshape(rows, hkv, dh), gk, c["eps"]),
             positions, c["theta"])
    v = mm(x, wv).reshape(rows, hkv, dh)
    bq = min(Q_BLOCK, rows)
    assert rows % bq == 0
    qb = q.reshape(rows // bq, bq, hkv, hq // hkv, dh)

    @jax.checkpoint
    def block(args):
        i, qblk = args
        keep = rule_mask(i * bq + jnp.arange(bq), length, c["block"])
        s = jnp.einsum("qgrd,sgd->grqs", rnd(qblk), rnd(k)) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("grqs,sgd->qgrd", rnd(p), rnd(v))
        return o.reshape(bq, hq * dh)

    o = jax.lax.map(block, (jnp.arange(rows // bq), qb))
    return mm(o.reshape(rows, hq * dh), wo)


def moe_layer(x, wr, w1, w3, w2, top_k, offset=0, rnd=lambda a: a):
    """The part of the expert layer that the experts ``[offset, offset +
    w1.shape[0])`` give.  x: [rows, hidden]; wr: [hidden, routed]."""
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


def forward_one(params, tokens, noised, s, matmul_dtype=None):
    """Logits [L, V] of one document's noised rows."""
    c = _dims(s)
    rnd = _rounder(matmul_dtype)
    h = params[0][jnp.concatenate([tokens, noised])]

    @jax.checkpoint
    def layer(h, ws):
        a = attention(rms_norm(h, ws[0], c["eps"]), ws[1:7], c, rnd)
        h = h + a
        m = moe_layer(rms_norm(h, ws[7], c["eps"]), ws[8], ws[9], ws[10],
                      ws[11], c["k"], c["offset"], rnd)
        return h + m

    for i in range(c["layers"]):
        h = layer(h, params[1 + PER_LAYER * i:1 + PER_LAYER * (i + 1)])
    h = rms_norm(h[tokens.shape[0]:], params[-2], c["eps"])
    return jnp.matmul(rnd(h), rnd(params[-1]))


def loss_fn(params, feed, s, matmul_dtype=None):
    tokens, noised, weights = feed["tokens"], feed["noised"], feed["weights"]
    total = 0.0
    for b in range(tokens.shape[0]):
        logp = jax.nn.log_softmax(
            forward_one(params, tokens[b], noised[b], s, matmul_dtype), -1)
        ce = -jnp.take_along_axis(logp, tokens[b][:, None], -1)[:, 0]
        total = total + jnp.sum(weights[b] * ce) / tokens.shape[1]
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
