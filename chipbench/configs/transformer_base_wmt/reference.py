"""Transformer-base in plain float32 ``jax.numpy``: forward, loss, gradients
and one Adam step, after Vaswani et al. 2017 (post-layer-norm residual
blocks, sinusoid positions, label smoothing, loss averaged over non-pad
target tokens).  Independent of ``paddle_tpu``: no ops, no kernels, no AMP.

Weights come from the seed (``init_params``), never from the program.
``matmul_dtype`` rounds the inputs of every contraction to a narrower type
(and, through the transpose of the cast, the gradients flowing back): that
is the CONTROL of the comparison, never the reference.

Departures from the paper, all the program's: separate source and target
embeddings and output projection (the paper shares them), biases on the
feed-forward layers only, no warm-up schedule in the compared step.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e9
LN_EPS = 1e-5


def param_spec(s):
    """[(name, shape, init)] in the order the program creates its trainable
    parameters.  init: ("normal", std) | ("xavier",) | ("near", centre)."""
    d, f, v = s["d_model"], s["d_ff"], s["vocab_size"]
    spec = []

    def attn(p):
        spec.extend((f"{p}_{n}_w", (d, d), ("xavier",)) for n in "qkvo")

    def ln(p):
        spec.append((f"{p}_ln_scale", (d,), ("near", 1.0)))
        spec.append((f"{p}_ln_bias", (d,), ("near", 0.0)))

    def ffn(p):
        spec.append((f"{p}_ffn1_w", (d, f), ("xavier",)))
        spec.append((f"{p}_ffn1_b", (f,), ("near", 0.0)))
        spec.append((f"{p}_ffn2_w", (f, d), ("xavier",)))
        spec.append((f"{p}_ffn2_b", (d,), ("near", 0.0)))

    spec.append(("src_emb", (v, d), ("normal", d ** -0.5)))
    for i in range(s["num_encoder_layers"]):
        attn(f"enc{i}_self"); ln(f"enc{i}_self")
        ffn(f"enc{i}"); ln(f"enc{i}_ffn")
    spec.append(("tgt_emb", (v, d), ("normal", d ** -0.5)))
    for i in range(s["num_decoder_layers"]):
        attn(f"dec{i}_self"); ln(f"dec{i}_self")
        attn(f"dec{i}_cross"); ln(f"dec{i}_cross")
        ffn(f"dec{i}"); ln(f"dec{i}_ffn")
    spec.append(("out_proj_w", (d, v), ("xavier",)))
    spec.append(("out_proj_b", (v,), ("near", 0.0)))
    return spec


def init_params(seed, s):
    """All weights on the device in one jitted call, float32."""
    spec = param_spec(s)

    def make(key):
        out = []
        for i, (_, shape, init) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if init[0] == "normal":
                w = init[1] * jax.random.normal(k, shape, jnp.float32)
            elif init[0] == "xavier":
                lim = math.sqrt(6.0 / (shape[0] + shape[1]))
                w = jax.random.uniform(k, shape, jnp.float32, -lim, lim)
            else:
                w = init[1] + jax.random.uniform(k, shape, jnp.float32,
                                                 -0.05, 0.05)
            out.append(w)
        return out

    return jax.jit(make)(jax.random.PRNGKey(np.uint32(seed % (2 ** 32))))


def _positions(n, d):
    pos = np.arange(n, dtype=np.float64)[:, None]
    div = np.exp(np.arange(0, d, 2, dtype=np.float64) * -(math.log(1e4) / d))
    table = np.zeros((n, d), np.float32)
    table[:, 0::2] = np.sin(pos * div)
    table[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(table)


def loss_fn(params, feed, s, matmul_dtype=None):
    d, h = s["d_model"], s["num_heads"]
    dk = d // h
    it = iter(params)

    def q(a):
        return a if matmul_dtype is None else \
            a.astype(matmul_dtype).astype(jnp.float32)

    def mm(a, b):
        return jnp.matmul(q(a), q(b))

    def layer_norm(x):
        g, b = next(it), next(it)
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b

    def heads(x):
        b, n, _ = x.shape
        return x.reshape(b, n, h, dk).transpose(0, 2, 1, 3)

    def attention(xq, xkv, bias, causal):
        wq, wk, wv, wo = next(it), next(it), next(it), next(it)
        qh, kh, vh = heads(mm(xq, wq)), heads(mm(xkv, wk)), heads(mm(xkv, wv))
        logits = jnp.einsum("bhqd,bhkd->bhqk", q(qh * dk ** -0.5), q(kh))
        if causal:
            n = logits.shape[-1]
            logits = logits + jnp.triu(
                jnp.full((n, n), NEG_INF, jnp.float32), k=1)
        if bias is not None:
            logits = logits + bias
        p = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", q(p), q(vh))
        b, _, n, _ = ctx.shape
        return mm(ctx.transpose(0, 2, 1, 3).reshape(b, n, d), wo)

    def ffn(x):
        w1, b1, w2, b2 = next(it), next(it), next(it), next(it)
        return mm(jax.nn.relu(mm(x, w1) + b1), w2) + b2

    def embed(word, n):
        return next(it)[word] * d ** 0.5 + _positions(n, d)

    src, tgt = feed["src_word"], feed["tgt_word"]
    lbl = feed["lbl_word"][..., 0]
    src_bias = jnp.where(src == 0, jnp.float32(NEG_INF),
                         jnp.float32(0.0))[:, None, None, :]

    x = embed(src, s["src_len"])
    for _ in range(s["num_encoder_layers"]):
        x = layer_norm(x + attention(x, x, src_bias, False))
        x = layer_norm(x + ffn(x))
    y = embed(tgt, s["tgt_len"])
    for _ in range(s["num_decoder_layers"]):
        y = layer_norm(y + attention(y, y, None, True))
        y = layer_norm(y + attention(y, x, src_bias, False))
        y = layer_norm(y + ffn(y))
    w, b = next(it), next(it)
    logits = mm(y, w) + b

    eps, v = s["label_smoothing"], s["vocab_size"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]
    cost = -((1.0 - eps) * picked + eps / v * logp.sum(-1))
    non_pad = (lbl != 0).astype(jnp.float32)
    return (cost * non_pad).sum() / (non_pad.sum() + 1e-8)


def loss_and_grads(params, feed, s, matmul_dtype=None):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(list(params), feed, s,
                                           matmul_dtype)


def optimizer_step(param, grad, s):
    """The FIRST Adam step from zero moments (Kingma & Ba 2015, section 2's
    efficient form)."""
    o = s["optimizer"]
    b1, b2 = o["beta1"], o["beta2"]
    m = (1 - b1) * grad
    v = (1 - b2) * grad * grad
    lr_t = o["lr"] * math.sqrt(1 - b2) / (1 - b1)
    return param - lr_t * m / (jnp.sqrt(v) + o["epsilon"])


def step_size(s):
    return s["optimizer"]["lr"]
