"""Qwen3-Next-80B-A3B-Instruct's decoder, one of 32 chips' share, in plain
float32 ``jax.numpy``: forward, next-token loss, gradients and one Adam
step.  Independent of ``paddle_tpu``: no ops, no kernels, no AMP, and the
delta rule as the token-by-token RECURRENCE, never the chunked form the
program computes.

For one sequence x ``[T, D]`` (D 2048; keys of the source's config in
backticks, the rest from the ``qwen3_next`` model type's public
implementation, listed in ``config.json`` under ``assumed``), published
layer i, ``a = RMSNorm(x)``::

    delta mixer ((i + 1) % `full_attention_interval` != 0; Hk 16 key heads,
                 Hv 32 value heads, dk = dv = 128, `linear_conv_kernel_dim` 4):
        [q | k | v | z] = a W_qkvz            2048, 2048, 4096, 4096 wide
        [b | al]        = a W_ba              Hv each
        [q | k | v]     = SiLU(filter([q | k | v]))   one causal 4-tap filter
                                              a channel, no bias, zero before 0
        beta_t = sigmoid(b_t);  g_t = -exp(A_log) * softplus(al_t + dt_bias)
        q = l2norm(q) * dk ** -0.5,  k = l2norm(k)    per head, eps 1e-6;
                                              key head j serves value heads
                                              2j and 2j + 1
        per value head, S_0 = 0 in R^{dk x dv}:
            S'  = exp(g_t) * S_{t-1}
            u_t = beta_t * (v_t - S'^T k_t)
            S_t = S' + k_t u_t^T
            o_t = S_t^T q_t
        y = (RMSNorm_head(o) * SiLU(z)) W_out       one scale of dv

    attention layer ((i + 1) % 4 == 0; 16 query / 2 key-value heads of 256):
        q = a W_q, gate = a W_g, k = a W_k, v = a W_v
        q, k = RMSNorm_head(q), RMSNorm_head(k)
        q, k = RoPE on their FIRST 64 columns (`partial_rotary_factor`
               0.25), rotate-half within them, base `rope_theta`
        o = causal softmax(q k^T * 256 ** -0.5) v,  query head h reads
            key-value head h // 8
        y = (o * sigmoid(gate)) W_o

    x1 = x + y;  m = RMSNorm(x1)
    s = softmax(m W_r) over all 512;  E = top 10;  w_e = s_e / sum_E s
    f = sigmoid(m w_sg) * Shared(m)
        + sum_{e in E, held here} w_e W2_e(silu(W1_e m) * W3_e m)
    x2 = x1 + f
    logits = RMSNorm(x_last) W_head;  loss = mean next-token cross-entropy

What the absent experts would add is left out; router, shared expert and
both mixers are whole.  The recurrence is COMPUTED IN BLOCKS: the scan over
tokens runs in blocks of ``TOKEN_BLOCK`` under ``jax.checkpoint``, so that
its gradient at 8,192 tokens keeps one state a block boundary (128 x 32 x
128 x 128 floats, 268 MB) and not one a token (17 GB); attention runs in
query blocks and every layer and expert is a checkpoint likewise.
``matmul_dtype`` rounds the inputs of every contraction (the state's two
reads among them) to a narrower type: that is the CONTROL of the
comparison, never the reference.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
TOKEN_BLOCK = 64
MIXER = 8           # parameters of either mixer, its first norm included
L2_EPS = 1e-6


def _dims(s):
    first, every = s["layer_offset"], s["full_attention_interval"]
    assert s["rope_scaling"] is None and not s["mlp_only_layers"]
    return dict(
        d=s["hidden_size"], hq=s["num_attention_heads"],
        hkv=s["num_key_value_heads"], dh=s["head_dim"],
        rot=int(s["head_dim"] * s["partial_rotary_factor"]),
        hk=s["linear_num_key_heads"], hv=s["linear_num_value_heads"],
        dk=s["linear_key_head_dim"], dv=s["linear_value_head_dim"],
        taps=s["linear_conv_kernel_dim"],
        routed=s["published"]["num_experts"], held=s["num_experts"],
        fe=s["moe_intermediate_size"],
        fs=s["shared_expert_intermediate_size"],
        k=s["num_experts_per_tok"], v=s["vocab_size"],
        eps=s["rms_norm_eps"], theta=float(s["rope_theta"]),
        offset=s.get("expert_offset", 0),
        # per layer held: is its mixer the delta rule?
        delta=[(i + 1) % every != 0
               for i in range(first, first + s["num_hidden_layers"])])


def mixer_spec(p, c, delta):
    d, std, one = c["d"], ("normal", 0.02), ("near", 1.0)
    if delta:
        keys, values = c["hk"] * c["dk"], c["hv"] * c["dv"]
        return [(f"{p}_attn_norm", (d,), one),
                (f"{p}_qkvz_w", (d, 2 * keys + 2 * values), std),
                (f"{p}_ba_w", (d, 2 * c["hv"]), std),
                (f"{p}_conv_w", (2 * keys + values, c["taps"]),
                 ("normal", 0.3)),
                (f"{p}_dt_bias", (c["hv"],), ("near", -3.0)),
                (f"{p}_a_log", (c["hv"],), ("near", 0.0)),
                (f"{p}_delta_norm", (c["dv"],), one),
                (f"{p}_o_w", (values, d), std)]
    return [(f"{p}_attn_norm", (d,), one),
            (f"{p}_q_w", (d, c["hq"] * c["dh"]), std),
            (f"{p}_q_norm", (c["dh"],), one),
            (f"{p}_k_w", (d, c["hkv"] * c["dh"]), std),
            (f"{p}_k_norm", (c["dh"],), one),
            (f"{p}_v_w", (d, c["hkv"] * c["dh"]), std),
            (f"{p}_gate_w", (d, c["hq"] * c["dh"]), std),
            (f"{p}_o_w", (c["hq"] * c["dh"], d), std)]


def experts_spec(p, c):
    d, std = c["d"], ("normal", 0.02)
    return [(f"{p}_moe_norm", (d,), ("near", 1.0)),
            (f"{p}_shared_w1", (d, c["fs"]), std),
            (f"{p}_shared_w3", (d, c["fs"]), std),
            (f"{p}_shared_w2", (c["fs"], d), std),
            (f"{p}_shared_gate_w", (d, 1), std),
            (f"{p}_router_w", (d, c["routed"]), std),
            (f"{p}_w1", (c["held"], d, c["fe"]), std),
            (f"{p}_w3", (c["held"], d, c["fe"]), std),
            (f"{p}_w2", (c["held"], c["fe"], d), std)]


PER_LAYER = MIXER + 9


def param_spec(s):
    """[(name, shape, init)] in the order the program creates its trainable
    parameters.  init: ("normal", std) | ("near", centre)."""
    c = _dims(s)
    spec = [("tok_emb", (c["v"], c["d"]), ("normal", 0.02))]
    for i, delta in enumerate(c["delta"]):
        spec += mixer_spec(f"l{i}", c, delta) + experts_spec(f"l{i}", c)
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


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def rope_first(x, theta, n):
    """x: [T, H, d]; position t rotates the pair (i, i + n/2) of the FIRST
    n columns by t * theta^(-2i/n); the other columns pass."""
    t = x.shape[0]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    part = x[..., :n]
    x1, x2 = part[..., :n // 2], part[..., n // 2:]
    turned = part * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, x[..., n:]], -1)


def causal_filter(z, w):
    """z: [T, C]; w: [C, L]: ``out[t] = sum_j w[:, j] z[t - (L - 1) + j]``,
    z zero before position 0."""
    taps, t = w.shape[1], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    return sum(w[:, j] * padded[j:j + t] for j in range(taps))


def delta_recurrence(q, k, v, g, beta, rnd=lambda a: a):
    """The rule token by token.  q, k: [T, H, dk] (a key head already
    repeated for its value heads); v: [T, H, dv]; g, beta: [T, H] ->
    [T, H, dv]."""
    t, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[:, None, None] * state
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", rnd(state),
                                             rnd(k_t)))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", rnd(state), rnd(q_t))

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blk = math.gcd(t, TOKEN_BLOCK)
    xs = tuple(a.reshape((t // blk, blk) + a.shape[1:])
               for a in (q, k, v, g, beta))
    _, out = jax.lax.scan(block, jnp.zeros((h, dk, dv), jnp.float32), xs)
    return out.reshape(t, h, dv)


def delta_mixer(x, ws, c, rnd):
    """x: [T, D] (normed); ws: the mixer's seven weights after its norm."""
    wqkvz, wba, wconv, dt_bias, a_log, gn, wo = ws
    t, hk, hv, dk, dv = x.shape[0], c["hk"], c["hv"], c["dk"], c["dv"]
    keys, values = hk * dk, hv * dv

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    qkvz = mm(x, wqkvz)
    ba = mm(x, wba)
    qkv = jax.nn.silu(causal_filter(qkvz[:, :2 * keys + values], wconv))
    z = qkvz[:, 2 * keys + values:]
    q = l2norm(qkv[:, :keys].reshape(t, hk, dk)) * dk ** -0.5
    k = l2norm(qkv[:, keys:2 * keys].reshape(t, hk, dk))
    v = qkv[:, 2 * keys:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, hv:] + dt_bias)
    o = delta_recurrence(jnp.repeat(q, hv // hk, 1),
                         jnp.repeat(k, hv // hk, 1), v, g, beta, rnd)
    o = rms_norm(o, gn, c["eps"]).reshape(t, values) * jax.nn.silu(z)
    return mm(o, wo)


def attention(x, ws, c, rnd):
    """x: [T, D] (normed); ws: the mixer's seven weights after its norm."""
    wq, gq, wk, gk, wv, wg, wo = ws
    t, hq, hkv, dh = x.shape[0], c["hq"], c["hkv"], c["dh"]

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    q = rope_first(rms_norm(mm(x, wq).reshape(t, hq, dh), gq, c["eps"]),
                   c["theta"], c["rot"])
    k = rope_first(rms_norm(mm(x, wk).reshape(t, hkv, dh), gk, c["eps"]),
                   c["theta"], c["rot"])
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
        return jnp.einsum("grqs,sgd->qgrd", rnd(p), rnd(v)).reshape(
            bq, hq * dh)

    o = jax.lax.map(block, (jnp.arange(t // bq), qb)).reshape(t, hq * dh)
    return mm(o * jax.nn.sigmoid(mm(x, wg)), wo)


def feed_forward(x, w1, w3, w2, rnd=lambda a: a):
    h = jax.nn.silu(jnp.matmul(rnd(x), rnd(w1))) \
        * jnp.matmul(rnd(x), rnd(w3))
    return jnp.matmul(rnd(h), rnd(w2))


def routed(x, wr, w1, w3, w2, top_k, offset=0, rnd=lambda a: a):
    """What the experts ``[offset, offset + w1.shape[0])`` give.  x:
    [T, D]; wr: [D, routed].  No shared expert in here."""
    s = jax.nn.softmax(jnp.matmul(rnd(x), rnd(wr)), -1)
    vals, idx = jax.lax.top_k(s, top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    expert = jax.checkpoint(lambda x, a, b, c: feed_forward(x, a, b, c, rnd))
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        we = jnp.sum(jnp.where(idx == e + offset, vals, 0.0), -1)
        y = y + we[:, None] * expert(x, w1[e], w3[e], w2[e])
    return y


def experts(x, ws, c, rnd):
    """x: [T, D] (normed); ws: the eight weights after the layer's second
    norm: the gated shared expert and the routed share."""
    s1, s3, s2, wsg, wr, w1, w3, w2 = ws
    gate = jax.nn.sigmoid(jnp.matmul(rnd(x), rnd(wsg)))
    return gate * feed_forward(x, s1, s3, s2, rnd) \
        + routed(x, wr, w1, w3, w2, c["k"], c["offset"], rnd)


def forward_one(params, tokens, s, matmul_dtype=None):
    """Logits [T, V] of one sequence."""
    c = _dims(s)
    rnd = _rounder(matmul_dtype)
    h = params[0][tokens]
    for i, delta in enumerate(c["delta"]):
        @jax.checkpoint
        def layer(h, ws, delta=delta):
            a = rms_norm(h, ws[0], c["eps"])
            h = h + (delta_mixer if delta else attention)(
                a, ws[1:MIXER], c, rnd)
            return h + experts(rms_norm(h, ws[MIXER], c["eps"]),
                               ws[MIXER + 1:], c, rnd)

        h = layer(h, params[1 + PER_LAYER * i:1 + PER_LAYER * (i + 1)])
    return jnp.matmul(rnd(rms_norm(h, params[-2], c["eps"])),
                      rnd(params[-1]))


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
