"""Nemotron-Labs-TwoTower-30B-A3B-Base's language tower, one of 16 chips'
share, in plain float32 ``jax.numpy``: forward, next-token loss, gradients,
one Adam step and the routers' balancing rule.  Independent of
``paddle_tpu``: no ops, no kernels, no AMP, and the state-space scan as the
token-by-token RECURRENCE, never the chunked form the program computes.

For one sequence x ``[T, D]`` (D 2688; keys of the source's config in
backticks, the rest from the ``nemotron_h`` / ``mamba2`` model types' public
implementations, listed in ``config.json`` under ``assumed``), every
published layer i is ONE sub-block by its letter in
`hybrid_override_pattern`, ``x <- x + F_i(RMSNorm_i(x))``, a = the normed
input::

    M, a state-space mixer (H = `mamba_num_heads` 64 heads of P =
       `mamba_head_dim` 64; G = `n_groups` 8 groups of N = `ssm_state_size`
       128):
        [z | xBC | dt] = a W_in                4,096 | 6,144 | 64 columns
        xBC = SiLU(filter(xBC) + b_conv)       one causal `conv_kernel`-tap
                                               filter a channel, zero before 0
        [u | B | C] = xBC                      4,096 | 1,024 | 1,024; head h
                                               reads group h // 8
        delta_t = softplus(dt_t + dt_bias)     [H];  A = -exp(A_log)  [H]
        per head, S_0 = 0 in R^{P x N}:
            S_t = exp(delta_t A) S_{t-1} + delta_t u_t B_t^T
            y_t = S_t C_t + D u_t
        F = RMSNorm_groups(y * SiLU(z)) W_out  the gate first, the mean over
                                               each of the 8 groups of 512
    *, attention (`num_attention_heads` 32, `num_key_value_heads` 2,
       `head_dim` 128; no positions, no head norm):
        q = a W_q, k = a W_k, v = a W_v
        F = causal softmax(q k^T * 128 ** -0.5) v W_o      a group of 16
    E, a routed feed-forward (`mlp_hidden_act` relu2: two matrices):
        s = sigmoid(a W_r) over all 128;  E = top-6 of s + b
        w_e = s_e / (sum_E s + 1e-20) * `routed_scaling_factor`
        F = W2s relu(W1s a)^2 + sum_{e in E, held here} w_e W2_e relu(W1_e a)^2
    logits = RMSNorm(x_last) W_head;  loss = mean next-token cross-entropy
    after each step, per routed layer: b_e += 1e-3 sign(mean(n) - n_e)

``n_e`` is the step's assignments to expert e over all 128, held here or
not; b starts at 0 and gets no gradient.  What the absent experts would add
is left out; the mixers, attention, the router and the shared expert are
whole.  The recurrence is COMPUTED IN BLOCKS: the scan over tokens runs in
blocks of ``TOKEN_BLOCK`` under ``jax.checkpoint``, so that its gradient
keeps one state a block boundary and not one a token; attention runs in
query blocks, the head's product and loss in row blocks, and every layer
and expert is a checkpoint likewise.  ``matmul_dtype`` rounds the inputs of
every contraction (the state's write and read among them) to a narrower
type: that is the CONTROL of the comparison, never the reference.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
TOKEN_BLOCK = 64
HEAD_BLOCK = 1024
#: parameters of each kind of layer, its norm included
COUNT = {"M": 9, "*": 5, "E": 6}


def _dims(s):
    first = s["layer_offset"]
    assert s["mlp_hidden_act"] == "relu2" and s["n_group"] == 1 \
        and s["topk_group"] == 1 and not s["tie_word_embeddings"]
    return dict(
        d=s["hidden_size"], h=s["num_attention_heads"],
        kv=s["num_key_value_heads"], dh=s["head_dim"],
        mh=s["mamba_num_heads"], mp=s["mamba_head_dim"], g=s["n_groups"],
        n=s["ssm_state_size"], taps=s["conv_kernel"],
        conv_bias=s["use_conv_bias"],
        routed=s["published"]["n_routed_experts"],
        held=s["n_routed_experts"], fe=s["moe_intermediate_size"],
        fs=s["moe_shared_expert_intermediate_size"],
        k=s["num_experts_per_tok"], v=s["vocab_size"],
        eps=s["layer_norm_epsilon"], offset=s.get("expert_offset", 0),
        route_scale=s["routed_scaling_factor"],
        route_eps=s["assumed"]["route_norm_eps"],
        dt=(s["time_step_min"], s["time_step_max"], s["time_step_floor"]),
        out_std=0.02 / math.sqrt(s["published"]["num_hidden_layers"])
        if s["rescale_prenorm_residual"] else 0.02,
        letters=s["hybrid_override_pattern"][
            first:first + s["num_hidden_layers"]])


def layer_spec(p, c, letter):
    d, std, one = c["d"], ("normal", 0.02), ("near", 1.0)
    out = ("normal", c["out_std"])
    if letter == "M":
        inner, bc = c["mh"] * c["mp"], c["g"] * c["n"]
        assert c["conv_bias"], "the filter's bias is a parameter here"
        half = ("uniform", c["taps"] ** -0.5)
        return [(f"{p}_ssm_norm", (d,), one),
                (f"{p}_ssm_in_w", (d, 2 * inner + 2 * bc + c["mh"]), std),
                (f"{p}_conv_w", (inner + 2 * bc, c["taps"]), half),
                (f"{p}_conv_b", (inner + 2 * bc,), half),
                (f"{p}_dt_bias", (c["mh"],), ("dt_bias",) + c["dt"]),
                (f"{p}_a_log", (c["mh"],), ("a_log", 1.0, 16.0)),
                (f"{p}_ssm_d", (c["mh"],), one),
                (f"{p}_gate_norm", (inner,), one),
                (f"{p}_o_w", (inner, d), out)]
    if letter == "*":
        return [(f"{p}_attn_norm", (d,), one),
                (f"{p}_q_w", (d, c["h"] * c["dh"]), std),
                (f"{p}_k_w", (d, c["kv"] * c["dh"]), std),
                (f"{p}_v_w", (d, c["kv"] * c["dh"]), std),
                (f"{p}_o_w", (c["h"] * c["dh"], d), out)]
    return [(f"{p}_moe_norm", (d,), one),
            (f"{p}_shared_w1", (d, c["fs"]), std),
            (f"{p}_shared_w2", (c["fs"], d), std),
            (f"{p}_router_w", (d, c["routed"]), std),
            (f"{p}_w1", (c["held"], d, c["fe"]), std),
            (f"{p}_w2", (c["held"], c["fe"], d), std)]


def param_spec(s):
    """[(name, shape, init)] in the order the program creates its trainable
    parameters.  init: ("normal", std) | ("near", centre) | ("uniform",
    bound) | ("a_log", low, high): the log of a uniform draw | ("dt_bias",
    low, high, floor): the inverse softplus of a log-uniform draw."""
    c = _dims(s)
    spec = [("tok_emb", (c["v"], c["d"]), ("normal", 0.02))]
    for i, letter in enumerate(c["letters"]):
        spec += layer_spec(f"l{i}", c, letter)
    assert all(len(layer_spec("l", c, k)) == n for k, n in COUNT.items())
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
            elif init[0] == "near":
                w = init[1] + jax.random.uniform(k, shape, jnp.float32,
                                                 -0.05, 0.05)
            elif init[0] == "uniform":
                w = jax.random.uniform(k, shape, jnp.float32, -init[1],
                                       init[1])
            elif init[0] == "a_log":
                w = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                               init[1], init[2]))
            else:
                low, high, floor = init[1:]
                dt = jnp.maximum(jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(low), math.log(high))),
                    floor)
                w = dt + jnp.log(-jnp.expm1(-dt))
            out.append(w)
        return out

    return jax.jit(make)(jax.random.PRNGKey(np.uint32(seed % (2 ** 32))))


def _rounder(matmul_dtype):
    if matmul_dtype is None:
        return lambda a: a
    return lambda a: a.astype(matmul_dtype).astype(jnp.float32)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def group_norm(x, g, eps, groups):
    """RMS norm over each of ``groups`` equal groups of x's columns, one
    scale ``g`` of the whole width."""
    t, wide = x.shape
    xg = x.reshape(t, groups, wide // groups)
    xg = xg * jax.lax.rsqrt(jnp.mean(xg * xg, -1, keepdims=True) + eps)
    return xg.reshape(t, wide) * g


def causal_filter(z, w):
    """z: [T, C]; w: [C, L]: ``out[t] = sum_j w[:, j] z[t - (L - 1) + j]``,
    z zero before position 0."""
    taps, t = w.shape[1], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    return sum(w[:, j] * padded[j:j + t] for j in range(taps))


def ssm_recurrence(u, delta, a, b, c, d, rnd=lambda x: x):
    """The scan token by token.  u: [T, H, P]; delta: [T, H]; a, d: [H];
    b, c: [T, H, N] (a group's B and C repeated over its heads) ->
    [T, H, P]."""
    t, h, p = u.shape
    n = b.shape[-1]

    def token(state, x):
        u_t, dt, b_t, c_t = x
        state = jnp.exp(dt * a)[:, None, None] * state \
            + rnd(dt[:, None] * u_t)[:, :, None] * rnd(b_t)[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", rnd(state), rnd(c_t)) \
            + d[:, None] * u_t

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    blk = math.gcd(t, TOKEN_BLOCK)
    xs = tuple(x.reshape((t // blk, blk) + x.shape[1:])
               for x in (u, delta, b, c))
    _, out = jax.lax.scan(block, jnp.zeros((h, p, n), jnp.float32), xs)
    return out.reshape(t, h, p)


def ssm_mixer(x, ws, c, rnd):
    """x: [T, D] (normed); ws: the mixer's eight weights after its norm."""
    w_in, wconv, bconv, dt_bias, a_log, dd, gn, wo = ws
    t, h, p, g, n = x.shape[0], c["mh"], c["mp"], c["g"], c["n"]
    inner, bc = h * p, g * n

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    proj = mm(x, w_in)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * bc],
                  proj[:, 2 * inner + 2 * bc:])
    xbc = jax.nn.silu(causal_filter(xbc, wconv) + bconv)
    u = xbc[:, :inner].reshape(t, h, p)
    bm, cm = (jnp.repeat(part.reshape(t, g, n), h // g, 1) for part in (
        xbc[:, inner:inner + bc], xbc[:, inner + bc:]))
    y = ssm_recurrence(u, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
                       bm, cm, dd, rnd)
    gated = y.reshape(t, inner) * jax.nn.silu(z)
    return mm(group_norm(gated, gn, c["eps"], g), wo)


def attention(x, ws, c, rnd):
    """x: [T, D] (normed); ws: q, k, v, o.  No position enters and no head
    is normed; query head j reads key-value head j // (h // kv)."""
    wq, wk, wv, wo = ws
    t, h, kv, dh = x.shape[0], c["h"], c["kv"], c["dh"]

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    q = mm(x, wq).reshape(t, kv, h // kv, dh)
    k, v = mm(x, wk).reshape(t, kv, dh), mm(x, wv).reshape(t, kv, dh)
    bq = min(Q_BLOCK, t)
    assert t % bq == 0

    @jax.checkpoint
    def block(args):
        i, qblk = args
        counts = (i * bq + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.einsum("qgrd,sgd->grqs", rnd(qblk), rnd(k)) * dh ** -0.5
        prob = jax.nn.softmax(jnp.where(counts[None, None], s, -jnp.inf), -1)
        return jnp.einsum("grqs,sgd->qgrd", rnd(prob), rnd(v)).reshape(
            bq, h * dh)

    o = jax.lax.map(block, (jnp.arange(t // bq),
                            q.reshape(t // bq, bq, kv, h // kv, dh)))
    return mm(o.reshape(t, h * dh), wo)


def feed_forward(x, w1, w2, rnd=lambda a: a):
    """Two matrices about a squared ReLU."""
    h = jnp.square(jax.nn.relu(jnp.matmul(rnd(x), rnd(w1))))
    return jnp.matmul(rnd(h), rnd(w2))


def route(x, wr, bias, top_k, scale, eps, rnd=lambda a: a):
    """(weights [T, k], experts [T, k]): the bias chooses, the scores
    weigh."""
    s = jax.nn.sigmoid(jnp.matmul(rnd(x), rnd(wr)))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    vals = jnp.take_along_axis(s, idx, -1)
    return vals / (jnp.sum(vals, -1, keepdims=True) + eps) * scale, idx


def routed(x, wr, bias, w1, w2, top_k, scale, eps, offset=0,
           rnd=lambda a: a):
    """(what the experts ``[offset, offset + w1.shape[0])`` give, the
    assignments to each of the router's experts [routed] int32).  x:
    [T, hidden]; wr: [hidden, routed].  No shared expert in here."""
    vals, idx = route(x, wr, bias, top_k, scale, eps, rnd)
    expert = jax.checkpoint(lambda x, a, b: feed_forward(x, a, b, rnd))
    y = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        we = jnp.sum(jnp.where(idx == e + offset, vals, 0.0), -1)
        y = y + we[:, None] * expert(x, w1[e], w2[e])
    counts = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(wr.shape[-1]),
                     0).astype(jnp.int32)
    return y, counts


def layer(h, ws, bias, letter, c, rnd):
    """(the stream after one published layer, the router's counts or
    None)."""
    a = rms_norm(h, ws[0], c["eps"])
    if letter == "M":
        return h + ssm_mixer(a, ws[1:], c, rnd), None
    if letter == "*":
        return h + attention(a, ws[1:], c, rnd), None
    f, counts = routed(a, ws[3], bias, ws[4], ws[5], c["k"],
                       c["route_scale"], c["route_eps"], c["offset"], rnd)
    return h + f + feed_forward(a, ws[1], ws[2], rnd), counts


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
    for letter in c["letters"]:
        n = COUNT[letter]
        bias = None
        if letter == "E":
            bias = jnp.zeros((c["routed"],), jnp.float32) if biases is None \
                else biases[len(all_counts)]
        step = jax.checkpoint(
            lambda h, ws, bias, letter=letter: layer(h, ws, bias, letter, c,
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
