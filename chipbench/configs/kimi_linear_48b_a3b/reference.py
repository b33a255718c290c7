"""Kimi-Linear-48B-A3B-Instruct's decoder, one of 32 chips' share, in plain
float32 ``jax.numpy``: forward, next-token loss, gradients, one Adam step
and the routers' balancing rule.  Independent of ``paddle_tpu``: no ops, no
kernels, no AMP, and the delta rule as the token-by-token RECURRENCE, never
the chunked form the program computes.

For one sequence x ``[T, D]`` (D 2304; keys of the source's config in
backticks, the rest from the ``kimi_linear`` model type's public
implementation, listed in ``config.json`` under ``assumed``), published
layer i (counted from 1, as `linear_attn_config` counts), ``a =
RMSNorm(x)``::

    delta mixer (i in `kda_layers`; H = `num_heads` 32, dk = dv = `head_dim`
                 128, rank r = 128, `short_conv_kernel_size` 4):
        [q | k | v] = SiLU(filter(a W_qkv))    three times 4,096 channels; one
                                               causal 4-tap filter a channel,
                                               no bias, zero before 0
        g_t    = -exp(A_log_h) * softplus((a_t W_f1) W_f2 + dt_bias)
                                               [H, dk]: a decay a key channel
        beta_t = sigmoid(a_t W_b)              [H]
        q = l2norm(q) * dk ** -0.5,  k = l2norm(k)       per head, eps 1e-6
        per head, S_0 = 0 in R^{dk x dv}:
            S'  = Diag(exp(g_t)) S_{t-1}
            u_t = beta_t * (v_t - S'^T k_t)
            S_t = S' + k_t u_t^T
            o_t = S_t^T q_t
        y = (RMSNorm_head(o) * sigmoid((a W_g1) W_g2)) W_o    one scale of dv

    latent mixer (i in `full_attn_layers`; 32 heads, `q_lora_rank` null,
                  `mla_use_nope`: no position enters):
        q            = a W_q                    [T, H, 128 + 64]
        [c | kr]     = a W_kva                  `kv_lora_rank` 512 + 64
        [k_nope | v] = RMSNorm_c(c) W_kvb       [T, H, 128 + 128]
        k            = [k_nope | kr, the same for every head]
        o = causal softmax(q k^T * 192 ** -0.5) v          v 128 wide
        y = o W_o

    x1 = x + y;  m = RMSNorm(x1)
    feed-forward: layer <= `first_k_dense_replace`: W2(silu(W1 m) * W3 m) at
    `intermediate_size`; else
        s = sigmoid(m W_r) over all 256;  E = top-8 of s + b
        w_e = s_e / (sum_E s + 1e-20) * `routed_scaling_factor`
        f = Shared(m) + sum_{e in E, held here} w_e W2_e(silu(W1_e m) * W3_e m)
    x2 = x1 + f
    logits = RMSNorm(x_last) W_head;  loss = mean next-token cross-entropy
    after each step, per routed layer: b_e += 1e-3 sign(mean(n) - n_e)

``n_e`` is the step's assignments to expert e over all 256, held here or
not; b starts at 0 and gets no gradient.  What the absent experts would add
is left out; both mixers, the router, the shared expert and the dense layer
are whole.  The recurrence is COMPUTED IN BLOCKS: the scan over tokens runs
in blocks of ``TOKEN_BLOCK`` under ``jax.checkpoint``, so that its gradient
keeps one state a block boundary and not one a token; attention runs in
query blocks, the head's product and loss in row blocks, and every layer
and expert is a checkpoint likewise.  ``matmul_dtype`` rounds the inputs of
every contraction (the state's two reads among them) to a narrower type:
that is the CONTROL of the comparison, never the reference.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
TOKEN_BLOCK = 64
HEAD_BLOCK = 1024
L2_EPS = 1e-6
#: parameters of each part, its norm included
COUNT = {"delta": 12, "latent": 6, "dense": 4, "routed": 8}


def _dims(s):
    first, linear = s["layer_offset"], s["linear_attn_config"]
    assert s["rope_scaling"] is None and s["q_lora_rank"] is None \
        and s["mla_use_nope"] and s["num_nextn_predict_layers"] == 0
    full = set(linear["full_attn_layers"])
    return dict(
        d=s["hidden_size"], h=s["num_attention_heads"],
        rank=s["kv_lora_rank"], nope=s["qk_nope_head_dim"],
        rope=s["qk_rope_head_dim"], dv=s["v_head_dim"],
        lh=linear["num_heads"], dk=linear["head_dim"],
        taps=linear["short_conv_kernel_size"],
        routed=s["published"]["num_experts"], held=s["num_experts"],
        fe=s["moe_intermediate_size"],
        fs=s["moe_intermediate_size"] * s["num_shared_experts"],
        fd=s["intermediate_size"], k=s["num_experts_per_token"],
        v=s["vocab_size"], eps=s["rms_norm_eps"],
        offset=s.get("expert_offset", 0),
        route_scale=s["routed_scaling_factor"],
        route_eps=s["assumed"]["route_norm_eps"],
        # per layer held: (its mixer, its feed-forward); the source counts
        # layers from 1
        kinds=[("latent" if i + 1 in full else "delta",
                "dense" if i < s["first_k_dense_replace"] else "routed")
               for i in range(first, first + s["num_hidden_layers"])])


def mixer_spec(p, c, kind):
    d, std, one = c["d"], ("normal", 0.02), ("near", 1.0)
    if kind == "delta":
        wide, r = c["lh"] * c["dk"], c["dk"]
        return [(f"{p}_attn_norm", (d,), one),
                (f"{p}_qkv_w", (d, 3 * wide), std),
                (f"{p}_b_w", (d, c["lh"]), std),
                (f"{p}_g1_w", (d, r), std), (f"{p}_g2_w", (r, wide), std),
                (f"{p}_conv_w", (3 * wide, c["taps"]), ("normal", 0.3)),
                (f"{p}_f1_w", (d, r), std), (f"{p}_f2_w", (r, wide), std),
                (f"{p}_dt_bias", (wide,), ("near", -3.0)),
                (f"{p}_a_log", (c["lh"],), ("near", 0.0)),
                (f"{p}_delta_norm", (c["dk"],), one),
                (f"{p}_o_w", (wide, d), std)]
    return [(f"{p}_attn_norm", (d,), one),
            (f"{p}_q_w", (d, c["h"] * (c["nope"] + c["rope"])), std),
            (f"{p}_kva_w", (d, c["rank"] + c["rope"]), std),
            (f"{p}_kv_norm", (c["rank"],), one),
            (f"{p}_kvb_w", (c["rank"], c["h"] * (c["nope"] + c["dv"])), std),
            (f"{p}_o_w", (c["h"] * c["dv"], d), std)]


def feed_spec(p, c, kind):
    d, std, one = c["d"], ("normal", 0.02), ("near", 1.0)
    if kind == "dense":
        return [(f"{p}_mlp_norm", (d,), one),
                (f"{p}_mlp_w1", (d, c["fd"]), std),
                (f"{p}_mlp_w3", (d, c["fd"]), std),
                (f"{p}_mlp_w2", (c["fd"], d), std)]
    return [(f"{p}_moe_norm", (d,), one),
            (f"{p}_shared_w1", (d, c["fs"]), std),
            (f"{p}_shared_w3", (d, c["fs"]), std),
            (f"{p}_shared_w2", (c["fs"], d), std),
            (f"{p}_router_w", (d, c["routed"]), std),
            (f"{p}_w1", (c["held"], d, c["fe"]), std),
            (f"{p}_w3", (c["held"], d, c["fe"]), std),
            (f"{p}_w2", (c["held"], c["fe"], d), std)]


def param_spec(s):
    """[(name, shape, init)] in the order the program creates its trainable
    parameters.  init: ("normal", std) | ("near", centre)."""
    c = _dims(s)
    spec = [("tok_emb", (c["v"], c["d"]), ("normal", 0.02))]
    for i, (mixer, feed) in enumerate(c["kinds"]):
        spec += mixer_spec(f"l{i}", c, mixer) + feed_spec(f"l{i}", c, feed)
    assert all(len(mixer_spec("l", c, k)) == COUNT[k]
               for k in ("delta", "latent")) and all(
        len(feed_spec("l", c, k)) == COUNT[k] for k in ("dense", "routed"))
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


def causal_filter(z, w):
    """z: [T, C]; w: [C, L]: ``out[t] = sum_j w[:, j] z[t - (L - 1) + j]``,
    z zero before position 0."""
    taps, t = w.shape[1], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    return sum(w[:, j] * padded[j:j + t] for j in range(taps))


def delta_recurrence(q, k, v, g, beta, rnd=lambda a: a):
    """The rule token by token under a decay a key channel.  q, k, g:
    [T, H, dk]; v: [T, H, dv]; beta: [T, H] -> [T, H, dv]."""
    t, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[:, :, None] * state
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
    """x: [T, D] (normed); ws: the mixer's eleven weights after its norm."""
    wqkv, wb, wg1, wg2, wconv, wf1, wf2, dt_bias, a_log, gn, wo = ws
    t, h, dk = x.shape[0], c["lh"], c["dk"]
    wide = h * dk

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    qkv = jax.nn.silu(causal_filter(mm(x, wqkv), wconv))
    q = l2norm(qkv[:, :wide].reshape(t, h, dk)) * dk ** -0.5
    k = l2norm(qkv[:, wide:2 * wide].reshape(t, h, dk))
    v = qkv[:, 2 * wide:].reshape(t, h, dk)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        mm(mm(x, wf1), wf2) + dt_bias).reshape(t, h, dk)
    o = delta_recurrence(q, k, v, g, jax.nn.sigmoid(mm(x, wb)), rnd)
    gate = jax.nn.sigmoid(mm(mm(x, wg1), wg2))
    return mm(rms_norm(o, gn, c["eps"]).reshape(t, wide) * gate, wo)


def latent_attention(x, ws, c, rnd):
    """x: [T, D] (normed); ws: the mixer's five weights after its norm.
    No position enters and no head is normed."""
    wq, wkva, gc, wkvb, wo = ws
    t, h, nope, dv = x.shape[0], c["h"], c["nope"], c["dv"]
    qk = nope + c["rope"]

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    q = mm(x, wq).reshape(t, h, qk)
    kva = mm(x, wkva)
    lat, kr = kva[:, :c["rank"]], kva[:, c["rank"]:]
    kv = mm(rms_norm(lat, gc, c["eps"]), wkvb).reshape(t, h, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(kr[:, None, :], (t, h, c["rope"]))], -1)
    v = kv[..., nope:]
    bq = min(Q_BLOCK, t)
    assert t % bq == 0

    @jax.checkpoint
    def block(args):
        i, qblk = args
        counts = (i * bq + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.einsum("qhd,shd->hqs", rnd(qblk), rnd(k)) * qk ** -0.5
        p = jax.nn.softmax(jnp.where(counts[None], s, -jnp.inf), -1)
        return jnp.einsum("hqs,shd->qhd", rnd(p), rnd(v)).reshape(bq, h * dv)

    o = jax.lax.map(block, (jnp.arange(t // bq),
                            q.reshape(t // bq, bq, h, qk)))
    return mm(o.reshape(t, h * dv), wo)


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
    [T, hidden]; wr: [hidden, routed].  No shared expert in here."""
    vals, idx = route(x, wr, bias, top_k, scale, eps, rnd)
    expert = jax.checkpoint(lambda x, a, b, c: feed_forward(x, a, b, c, rnd))
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        we = jnp.sum(jnp.where(idx == e + offset, vals, 0.0), -1)
        y = y + we[:, None] * expert(x, w1[e], w3[e], w2[e])
    counts = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(wr.shape[-1]),
                     0).astype(jnp.int32)
    return y, counts


def layer(h, ws, bias, kinds, c, rnd):
    """(the stream after one layer, the router's counts or None)."""
    mixer, feed = kinds
    n = COUNT[mixer]
    mix = delta_mixer if mixer == "delta" else latent_attention
    h = h + mix(rms_norm(h, ws[0], c["eps"]), ws[1:n], c, rnd)
    m = rms_norm(h, ws[n], c["eps"])
    if feed == "dense":
        return h + feed_forward(m, *ws[n + 1:], rnd), None
    f, counts = routed(m, ws[n + 4], bias, *ws[n + 5:], c["k"],
                       c["route_scale"], c["route_eps"], c["offset"], rnd)
    return h + f + feed_forward(m, *ws[n + 1:n + 4], rnd), counts


def head_loss(h, gf, head, labels, c, rnd):
    """Mean next-token cross-entropy; the logits a block of rows at a time,
    each block a checkpoint, so that no [T, V] tensor outlives its block."""
    t = h.shape[0]
    blk = math.gcd(t, HEAD_BLOCK)

    @jax.checkpoint
    def block(args):
        rows, want = args
        logp = jax.nn.log_softmax(jnp.matmul(
            rnd(rms_norm(rows, gf, c["eps"])), rnd(head)), -1)
        return -jnp.sum(jnp.take_along_axis(logp, want[:, None], -1))

    return jnp.sum(jax.lax.map(block, (
        h.reshape(t // blk, blk, -1), labels.reshape(t // blk, blk)))) / t


def loss_one(params, tokens, labels, s, matmul_dtype=None, biases=None):
    """(loss, [counts [routed] per routed layer]) of one sequence.
    ``biases``: one [routed] per routed layer, zeros if None."""
    c = _dims(s)
    rnd = _rounder(matmul_dtype)
    h, at, all_counts = params[0][tokens], 1, []
    for kinds in c["kinds"]:
        n = COUNT[kinds[0]] + COUNT[kinds[1]]
        bias = None
        if kinds[1] == "routed":
            bias = jnp.zeros((c["routed"],), jnp.float32) if biases is None \
                else biases[len(all_counts)]
        step = jax.checkpoint(
            lambda h, ws, bias, kinds=kinds: layer(h, ws, bias, kinds, c,
                                                   rnd))
        h, counts = step(h, params[at:at + n], bias)
        at += n
        if counts is not None:
            all_counts.append(counts)
    assert at == len(params) - 2
    return head_loss(h, params[-2], params[-1], labels, c, rnd), all_counts


def loss_and_counts(params, feed, s, matmul_dtype=None, biases=None):
    """(mean loss over the batch, [the batch's assignments per routed
    layer])."""
    tokens, labels = feed["tokens"], feed["labels"][..., 0]
    total, counts = 0.0, None
    for b in range(tokens.shape[0]):
        loss, cs = loss_one(params, tokens[b], labels[b], s, matmul_dtype,
                            biases)
        total = total + loss
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
