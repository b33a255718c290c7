"""Instella-MoE-16B-A3B-Base's decoder, one of 8 chips' share, in plain
float32 ``jax.numpy``: forward, both losses, gradients, one Adam step and
the routers' balancing rule.  Independent of ``paddle_tpu``: no ops, no
kernels, no AMP.

For one sequence of ``T`` tokens (hidden ``D`` 2048, ``H`` 16 heads; keys of
the source's config in backticks, the rest from the ``deepseek_v3`` model
type's public implementation and AMD's FarSkip description, listed in
``config.json`` under ``assumed``), sub-blocks numbered ``j = 1..2L``
(mixer, feed-forward, mixer, ...), ``s_0 = Emb[tokens]``, ``s_{-1} := s_0``::

    residual (`farskip`):  o_j = F_j(RMSNorm_j(s_{j-2}));  s_j = s_{j-1} + o_j
             (off:         o_j = F_j(RMSNorm_j(s_{j-1})))
    logits = RMSNorm_f(s_{2L}) W_head

    latent mixer F (a = the normed input [T, D]; `q_lora_rank` null):
        q            = a W_q                     [T, H, 96 + 32]
        [c | kr]     = a W_kva                   `kv_lora_rank` 512 + 32
        [k_nope | v] = RMSNorm_c(c) W_kvb        [T, H, 96 + 128]
        k            = [k_nope | kr, the same for every head]
        q, k         = RMSNorm_head(q), RMSNorm_head(k)   (`qk_layernorm`)
        q[..., 96:], k[..., 96:] = RoPE_yarn, pairs (2i, 2i+1)
                                                  (`rope_interleave`)
        ctx = causal softmax(q k^T * scale) v
              scale = 128 ** -0.5 * m ** 2,  m = 0.1 ln 40 + 1
        y   = (ctx * sigmoid(a W_g)) W_o          (`gated_attention`)

    RoPE_yarn (`rope_scaling`; d = 32, base `rope_theta`):
        f_i = base ** (-2i/d);  dim(r) = d ln(original / (2 pi r)) / (2 ln base)
        low = max(floor(dim(beta_fast)), 0)
        high = min(ceil(dim(beta_slow)), d - 1)
        ramp_i = clip((i - low) / (high - low), 0, 1)
        inv_freq_i = f_i / factor * ramp_i + f_i * (1 - ramp_i)
        cos and sin times (0.1 mscale ln factor + 1)
                          / (0.1 mscale_all_dim ln factor + 1)

    feed-forward: layer < `first_k_dense_replace`: W2(silu(W1 m) * W3 m)
    at `intermediate_size`; else
        s = sigmoid(m W_r) over all 64;  E = top-6 of s + b
        w_e = s_e / (sum_E s + 1e-20) * `routed_scaling_factor`
        f = Shared(m) + sum_{e in E, held here} w_e W2_e(silu(W1_e m) * W3_e m)
        Shared: width `n_shared_experts` x `moe_intermediate_size`
    after each step, per routed block: b_e += 1e-3 sign(mean(n) - n_e)

    multi-token module (`num_nextn_predict_layers` 1):
        h'      = [RMSNorm_h(s_{2L}) | RMSNorm_e(Emb[tokens_{t+1}])] W_eh
        u       = one routed block, the residual rule from s_{-1} = s_0 = h'
        logits' = RMSNorm_m(u) W_head
        loss    = mean_t xent(logits_t, tokens_{t+1})
                  + 0.3 mean_t xent(logits'_t, tokens_{t+2})

``n_e`` is the step's assignments to expert e over all 64, held here or
not; b starts at 0 and gets no gradient.  What the absent experts would add
is left out; the mixer, the router, the shared experts and the dense layer
are whole.  ``Emb`` and ``W_head`` are ONE parameter each, used twice: the
gradient of each is the sum over its two uses.

Attention runs in query blocks under ``jax.checkpoint``, every block and
every expert's feed-forward is a checkpoint, so that the comparison at the
timed sequence length fits beside five float32 copies of the parameters.
``matmul_dtype`` rounds the inputs of every contraction to a narrower type:
that is the CONTROL of the comparison, never the reference.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
MIXER = 9           # parameters of a latent mixer, its first norm included


def _dims(s):
    first = s["layer_offset"]
    yarn = s["rope_scaling"]
    assert yarn["type"] == "yarn" and s["q_lora_rank"] is None
    return dict(
        d=s["hidden_size"], h=s["num_attention_heads"],
        rank=s["kv_lora_rank"], nope=s["qk_nope_head_dim"],
        rope=s["qk_rope_head_dim"], dv=s["v_head_dim"],
        routed=s["published"]["n_routed_experts"],
        held=s["n_routed_experts"], fe=s["moe_intermediate_size"],
        fs=s["moe_intermediate_size"] * s["n_shared_experts"],
        fd=s["intermediate_size"], k=s["num_experts_per_tok"],
        v=s["vocab_size"], eps=s["rms_norm_eps"],
        offset=s.get("expert_offset", 0),
        route_scale=s["routed_scaling_factor"],
        route_eps=s["assumed"]["route_norm_eps"],
        farskip=bool(s["farskip"]), gate=bool(s["gated_attention"]),
        mtp=s["num_nextn_predict_layers"],
        mtp_weight=s["assumed"]["mtp_loss_weight"],
        inv_freq=yarn_inv_freq(s["qk_rope_head_dim"], s["rope_theta"], yarn),
        table_scale=(0.1 * yarn["mscale"] * math.log(yarn["factor"]) + 1)
        / (0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1),
        softmax_scale=(s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) ** -0.5
        * (0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1) ** 2,
        # per layer held: dense?
        dense=[i < s["first_k_dense_replace"]
               for i in range(first, first + s["num_hidden_layers"])])


def yarn_inv_freq(d, base, yarn):
    """The d/2 frequencies of the rotated part, blended as the docstring's
    RoPE_yarn says; float64 on the host, float32 as a table."""
    def dim(turns):
        return d * math.log(yarn["original_max_position_embeddings"]
                            / (2 * math.pi * turns)) / (2 * math.log(base))

    low = max(math.floor(dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim(yarn["beta_slow"])), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    f = float(base) ** (-2.0 * i / d)
    ramp = np.clip((i - low) / (high - low if high > low else 0.001), 0, 1)
    return (f / yarn["factor"] * ramp + f * (1 - ramp)).astype(np.float32)


def mixer_spec(p, c):
    d, std, one = c["d"], ("normal", 0.02), ("near", 1.0)
    qk = c["nope"] + c["rope"]
    spec = [(f"{p}_attn_norm", (d,), one),
            (f"{p}_q_w", (d, c["h"] * qk), std),
            (f"{p}_q_norm", (qk,), one),
            (f"{p}_kva_w", (d, c["rank"] + c["rope"]), std),
            (f"{p}_kv_norm", (c["rank"],), one),
            (f"{p}_kvb_w", (c["rank"], c["h"] * (c["nope"] + c["dv"])), std),
            (f"{p}_k_norm", (qk,), one)]
    if c["gate"]:
        spec.append((f"{p}_gate_w", (d, c["h"] * c["dv"]), std))
    return spec + [(f"{p}_o_w", (c["h"] * c["dv"], d), std)]


def block_spec(p, c, dense):
    """One block's [(name, shape, init)], in the program's order."""
    d, std, one = c["d"], ("normal", 0.02), ("near", 1.0)
    if dense:
        return mixer_spec(p, c) + [
            (f"{p}_mlp_norm", (d,), one),
            (f"{p}_mlp_w1", (d, c["fd"]), std),
            (f"{p}_mlp_w3", (d, c["fd"]), std),
            (f"{p}_mlp_w2", (c["fd"], d), std)]
    return mixer_spec(p, c) + [
        (f"{p}_moe_norm", (d,), one),
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
    assert c["gate"] and c["mtp"] == 1, "the lists below are cut by count"
    d, std, one = c["d"], ("normal", 0.02), ("near", 1.0)
    spec = [("tok_emb", (c["v"], d), std)]
    for i, dense in enumerate(c["dense"]):
        spec += block_spec(f"l{i}", c, dense)
    spec += [("final_norm", (d,), one), ("lm_head_w", (d, c["v"]), std),
             ("mtp_h_norm", (d,), one), ("mtp_e_norm", (d,), one),
             ("mtp_merge_w", (2 * d, d), std)]
    return spec + block_spec("mtp", c, False) + [("mtp_norm", (d,), one)]


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


def rope_pairs(x, inv_freq, table_scale=1.0):
    """x: [T, H, d]; position t turns the pair (2i, 2i+1) by t * inv_freq_i."""
    t, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * table_scale)[:, None, :]
    sin = (jnp.sin(ang) * table_scale)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return turned.reshape(x.shape[:-1] + (d,))


def latent_attention(x, ws, c, rnd):
    """x: [T, D] (normed); ws: the mixer's eight weights after its norm."""
    wq, gq, wkva, gc, wkvb, gk, wg, wo = ws
    t, h, nope, dv = x.shape[0], c["h"], c["nope"], c["dv"]

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    q = mm(x, wq).reshape(t, h, nope + c["rope"])
    kva = mm(x, wkva)
    lat, kr = kva[:, :c["rank"]], kva[:, c["rank"]:]
    kv = mm(rms_norm(lat, gc, c["eps"]), wkvb).reshape(t, h, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, None, :], (t, h, c["rope"]))], -1)
    q, k = rms_norm(q, gq, c["eps"]), rms_norm(k, gk, c["eps"])

    def turn(z):
        return jnp.concatenate([z[..., :nope], rope_pairs(
            z[..., nope:], c["inv_freq"], c["table_scale"])], -1)

    q, k = turn(q), turn(k)
    bq = min(Q_BLOCK, t)
    assert t % bq == 0
    qb = q.reshape(t // bq, bq, h, nope + c["rope"])

    @jax.checkpoint
    def block(args):
        i, qblk = args
        counts = (i * bq + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.einsum("qhd,shd->hqs", rnd(qblk), rnd(k)) \
            * c["softmax_scale"]
        p = jax.nn.softmax(jnp.where(counts[None], s, -jnp.inf), -1)
        return jnp.einsum("hqs,shd->qhd", rnd(p), rnd(v)).reshape(bq, h * dv)

    o = jax.lax.map(block, (jnp.arange(t // bq), qb)).reshape(t, h * dv)
    return mm(o * jax.nn.sigmoid(mm(x, wg)), wo)


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


def block(before, h, ws, bias, dense, c, rnd):
    """One layer's two sub-blocks on the streams ``(s_{j-2}, s_{j-1})``:
    returns ``((s_j, s_{j+1}), counts)``."""
    y = latent_attention(
        rms_norm(before if c["farskip"] else h, ws[0], c["eps"]),
        ws[1:MIXER], c, rnd)
    s1 = h + y
    m = rms_norm(h if c["farskip"] else s1, ws[MIXER], c["eps"])
    if dense:
        return (s1, s1 + feed_forward(m, *ws[MIXER + 1:], rnd)), None
    f, counts = routed(m, ws[MIXER + 4], bias, *ws[MIXER + 5:], c["k"],
                       c["route_scale"], c["route_eps"], c["offset"], rnd)
    f = f + feed_forward(m, *ws[MIXER + 1:MIXER + 4], rnd)
    return (s1, s1 + f), counts


def _cut(params, c):
    """(one list a trunk layer, [final norm, head], the module's three,
    its block, its last norm)."""
    out, at = [], 1
    for dense in c["dense"]:
        n = MIXER + (4 if dense else 8)
        out.append(params[at:at + n])
        at += n
    rest = params[at:]
    assert len(rest) == 2 + 3 + MIXER + 8 + 1
    return out, rest[:2], rest[2:5], rest[5:-1], rest[-1]


def forward_one(params, tokens, nxt, s, matmul_dtype=None, biases=None,
                emb2=None, head2=None):
    """(logits [T, V], the module's logits [T, V], [counts [routed] per
    routed block, the module's last]) of one sequence; ``nxt`` [T]: the
    tokens that follow.  ``biases``: one [routed] per routed block, zeros
    if None.  ``emb2``, ``head2``: the table of the module's lookup and
    the matrix of its head product; the model's own (None) in the model,
    other arrays only to tell a parameter's two uses apart."""
    c = _dims(s)
    rnd = _rounder(matmul_dtype)
    layers, (gf, head), (gh, ge, weh), mtp_ws, gm = _cut(params, c)
    all_counts = []

    def bias_of():
        return jnp.zeros((c["routed"],), jnp.float32) if biases is None \
            else biases[len(all_counts)]

    h = params[0][tokens]
    before = h
    for dense, ws in zip(c["dense"], layers):
        step = jax.checkpoint(
            lambda b, h, ws, bias, dense=dense: block(b, h, ws, bias, dense,
                                                      c, rnd))
        (before, h), counts = step(before, h, ws,
                                   None if dense else bias_of())
        if counts is not None:
            all_counts.append(counts)
    logits = jnp.matmul(rnd(rms_norm(h, gf, c["eps"])), rnd(head))

    emb2 = params[0] if emb2 is None else emb2
    merged = jnp.concatenate([rms_norm(h, gh, c["eps"]),
                              rms_norm(emb2[nxt], ge, c["eps"])], -1)
    u = jnp.matmul(rnd(merged), rnd(weh))
    step = jax.checkpoint(
        lambda b, h, ws, bias: block(b, h, ws, bias, False, c, rnd))
    (_, u), counts = step(u, u, mtp_ws, bias_of())
    all_counts.append(counts)
    head2 = head if head2 is None else head2
    return (logits, jnp.matmul(rnd(rms_norm(u, gm, c["eps"])), rnd(head2)),
            all_counts)


def xent(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def loss_and_counts(params, feed, s, matmul_dtype=None, biases=None,
                    emb2=None, head2=None):
    """(mean of both losses' sum over the batch, [the batch's assignments
    per routed block])."""
    tokens, labels = feed["tokens"], feed["labels"][..., 0]
    labels2 = feed["labels2"][..., 0]
    weight = s["assumed"]["mtp_loss_weight"]
    total, counts = 0.0, None
    for b in range(tokens.shape[0]):
        logits, logits2, cs = forward_one(params, tokens[b], labels[b], s,
                                          matmul_dtype, biases, emb2, head2)
        total = total + xent(logits, labels[b]) \
            + weight * xent(logits2, labels2[b])
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
    """Every routed block's bias after one step on ``feed`` (from zeros
    where ``biases`` is None); the module's last."""
    with jax.default_matmul_precision("highest"):
        _, counts = loss_and_counts(params, feed, s, None, biases)
    zeros = jnp.zeros((s["published"]["n_routed_experts"],), jnp.float32)
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
