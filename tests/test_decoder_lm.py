"""The decoder path (ops/decoder_ops.py, ops/pallas_sparse_flash.py,
parallel/moe.routed_experts, models/decoder_lm.py) against the plain
float32 reference of ``chipbench/configs/keye_vl_2_0_30b_a3b`` at a tiny
size, on the CPU, with seeded random weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import decoder_ops, registry
from paddle_tpu.ops import pallas_sparse_flash as psf
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.ring_attention import full_attention

import decoder_reference
from decoder_reference import (compiled, counters, dense, moe_weights,
                               reference_step, seeded_program)

BUILD, REF, tiny_sizes = decoder_reference.load("keye_vl_2_0_30b_a3b")
# the window layers' section is tests/test_decoder_lm_window.py
T_BUILD, _, trinity_sizes = decoder_reference.load("trinity_mini")


# -- (a) the program against the reference ------------------------------

@pytest.mark.parametrize("flash", ["xla", "pallas"])
def test_program_equals_the_reference_where_the_selection_cuts(
        monkeypatch, flash):
    """Loss and every gradient through ``fluid.Executor`` with
    ``optimizer.minimize``, ``seq_len`` four times ``topk``; the indexer's
    three weights get a gradient of exactly zero on both sides."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash == "pallas" else "0")
    sizes = tiny_sizes()
    assert sizes["seq_len"] >= 4 * sizes["sa_config"]["topk"]
    assert sizes["num_experts"] < sizes["published"]["num_experts"]
    built, names, weights = seeded_program(BUILD, REF, sizes)
    feed = BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    outs = fluid.Executor(fluid.TPUPlace()).run(
        fluid.default_main_program(), feed=feed,
        fetch_list=[built["loss"]] + [n + "@GRAD" for n in names])
    ref_loss, ref_grads, _ = reference_step(REF, sizes, weights, feed)
    assert float(outs[0].reshape(-1)[0]) == pytest.approx(float(ref_loss),
                                                          rel=1e-5)
    for name, g, r in zip(names, outs[1:], ref_grads):
        g = np.asarray(g).reshape(r.shape)
        if "_idx_" in name:
            assert not np.any(g) and not np.any(np.asarray(r)), name
        else:
            assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-7, name
    # which path each layer took: once for its forward; the generic vjp
    # (the XLA path's, and the expert layer's) traces the forward again
    layers_ = sizes["num_hidden_layers"]
    (key, n), = counters("ops.sparse_attention.calls").items()
    assert f'path="{flash}"' in key and 'topk="16"' in key \
        and 'seq="64"' in key
    assert n == (1 if flash == "pallas" else 2) * layers_
    (key, n), = counters("ops.moe.calls").items()
    assert 'held="4"' in key and 'routed="8"' in key \
        and 'path="ragged_dot"' in key and n == 2 * layers_
    assert not counters("ops.sparse_attention.declined")


# -- (b) topk >= seq_len is dense causal attention ------------------------

@pytest.mark.parametrize("flash", [False, True])
def test_selecting_every_key_equals_causal_attention_through_ring_attention(
        monkeypatch, flash):
    """One switch for the whole program: ``ring_attention`` takes the same
    side as ``sparse_attention``, so the reference is the plain softmax
    (``parallel/ring_attention.full_attention``) called directly, and the
    fused op is held to it too."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash else "0")
    b, hq, hkv, t, d, hid = 2, 4, 2, 32, 16, 24
    rng = np.random.RandomState(0)
    x = layers.data(name="x", shape=[t, hid], dtype="float32")
    q = layers.data(name="q", shape=[hq, t, d], dtype="float32")
    k = layers.data(name="k", shape=[hkv, t, d], dtype="float32")
    v = layers.data(name="v", shape=[hkv, t, d], dtype="float32")
    kr = layers.data(name="kr", shape=[hq, t, d], dtype="float32")
    vr = layers.data(name="vr", shape=[hq, t, d], dtype="float32")
    sel = layers.sparse_indexer(x, num_heads=2, head_dim=8, topk=t,
                                name="idx")
    new = layers.sparse_attention(q, k, v, selection=sel)
    plain = layers.sparse_attention(q, k, v)
    old = layers.ring_attention(q, kr, vr, causal=True)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": rng.randn(b, t, hid).astype("float32"),
            "q": rng.randn(b, hq, t, d).astype("float32"),
            "k": rng.randn(b, hkv, t, d).astype("float32"),
            "v": rng.randn(b, hkv, t, d).astype("float32")}
    feed["kr"] = np.repeat(feed["k"], hq // hkv, axis=1)
    feed["vr"] = np.repeat(feed["v"], hq // hkv, axis=1)
    s, a, p, o = exe.run(feed=feed, fetch_list=[sel, new, plain, old])
    assert np.array_equal(np.asarray(s)[0], np.tril(np.ones((t, t))))
    want = full_attention(*(jnp.asarray(feed[n]) for n in ("q", "kr", "vr")),
                          True, None)
    for got in (a, p, o):
        np.testing.assert_allclose(got, want, atol=2e-6)
    path = "pallas" if flash else "xla"
    assert all(f'path="{path}"' in key
               for key in counters("ops.sparse_attention.calls"))


# -- (c) the share test ---------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """128 routed experts, 8 per token, 16 held by each of 8 chips
    (``expert_offset`` 0, 16, ..., 112): the parts add up to what the
    reference gives for the whole layer with all 128 experts."""
    routed, held, k = 128, 16, 8
    x, wr, w1, w3, w2 = moe_weights(np.random.RandomState(1), 48, 16, 8,
                                    routed)
    whole = REF.moe_layer(x, wr, w1, w3, w2, k, 0)
    total = 0.0
    for off in range(0, routed, held):
        part = moe.routed_experts(
            x, wr, w1[off:off + held], w3[off:off + held],
            w2[off:off + held], top_k=k, expert_offset=off)
        mine = REF.moe_layer(x, wr, w1[off:off + held], w3[off:off + held],
                             w2[off:off + held], k, off)
        np.testing.assert_allclose(part, mine, atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert float(jnp.abs(whole).max()) > 0.1


# -- (d) no assignment is lost when every token picks held experts --------

def test_every_token_choosing_held_experts_only_loses_no_assignment():
    routed, held, k, off, n = 16, 4, 4, 8, 40
    rng = np.random.RandomState(2)
    x, wr, w1, w3, w2 = moe_weights(rng, n, 12, 8, routed)
    # a constant feature whose router row lifts the held experts' logits
    # far above the others: every token's 4 choices are the 4 held
    x = x.at[:, 0].set(1.0)
    wr = wr.at[0].set(jnp.where((jnp.arange(routed) >= off)
                                & (jnp.arange(routed) < off + held),
                                60.0, 0.0))
    _, idx = moe.route_top_k(x, wr, k)
    assert bool(jnp.all((idx >= off) & (idx < off + held)))   # n*k rows
    w1, w3, w2 = (w[off:off + held] for w in (w1, w3, w2))

    def program(x, w1, w2):
        return jnp.sum(moe.routed_experts(x, wr, w1, w3, w2, top_k=k,
                                          expert_offset=off) ** 2)

    def reference(x, w1, w2):
        return jnp.sum(REF.moe_layer(x, wr, w1, w3, w2, k, off) ** 2)

    got = jax.jit(jax.value_and_grad(program, (0, 1, 2)))(x, w1, w2)
    want = jax.jit(jax.value_and_grad(reference, (0, 1, 2)))(x, w1, w2)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=1e-4 * float(jnp.abs(r).max()))


def test_every_row_of_a_grouped_product_lies_in_a_group(monkeypatch):
    """On the TPU XLA's grouped product leaves rows outside every group
    unwritten, results and cotangents alike (the chip's first run of PR 30
    read NaN gradients from them), and skips their tiles.  Today the
    group sizes cover all ``N * top_k`` rows (absent experts' assignments
    ride as zero rows in the last group: debt, ROADMAP S11); here every
    product poisons what lies outside, and the sizes are int32."""
    routed, held, k, n = 8, 2, 2, 24
    x, wr, w1, w3, w2 = moe_weights(np.random.RandomState(3), n, 8, 4,
                                    routed)
    w1, w3, w2 = (w[:held] for w in (w1, w3, w2))
    plain = jax.lax.ragged_dot
    seen = []

    def poisoned(lhs, rhs, sizes, **kw):
        rows = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(sizes)
        seen.append((lhs.shape[0], sizes.dtype))
        return jnp.where(rows, plain(lhs, rhs, sizes, **kw), jnp.nan)

    monkeypatch.setattr(moe.lax, "ragged_dot", poisoned)
    y = jax.jit(lambda *a: moe.routed_experts(*a, top_k=k))(
        x, wr, w1, w3, w2)
    np.testing.assert_allclose(
        y, jax.jit(lambda *a: REF.moe_layer(*a, k, 0))(x, wr, w1, w3, w2),
        atol=1e-5)
    # a row outside the groups would have made the result NaN
    assert seen == [(n * k, jnp.int32)] * 3      # the TPU refuses int64
    _, idx = moe.route_top_k(x, wr, k)
    assert int(jnp.sum(idx < held)) < n * k      # some rows ARE absent


def test_rows_without_a_held_assignment_poison_nothing(monkeypatch):
    """The cure of the unwritten rows does not rest on the group sizes
    covering them: here every product returns NaN in the rows that hold no
    held assignment, and hands NaN back as their cotangent, as the chip
    would were the sizes to stop at the last held assignment.  Result and
    every gradient still equal the reference's."""
    routed, held, k, n = 8, 2, 2, 24
    x, wr, w1, w3, w2 = moe_weights(np.random.RandomState(4), n, 8, 4,
                                    routed)
    w1, w3, w2 = (w[:held] for w in (w1, w3, w2))
    _, idx = moe.route_top_k(x, wr, k)
    live = int(jnp.sum(idx < held))
    assert 0 < live < n * k
    plain = jax.lax.ragged_dot

    def poisoned(lhs, rhs, sizes, **kw):
        dead = np.arange(lhs.shape[0])[:, None] >= live     # a constant

        @jax.custom_vjp
        def product(lhs, rhs, sizes):
            return jnp.where(dead, jnp.nan, plain(lhs, rhs, sizes, **kw))

        def fwd(lhs, rhs, sizes):
            return product(lhs, rhs, sizes), (lhs, rhs, sizes)

        def bwd(kept, g):
            lhs, rhs, sizes = kept
            dl, dr = jax.vjp(lambda a, b: plain(a, b, sizes, **kw),
                             lhs, rhs)[1](g)
            return jnp.where(dead, jnp.nan, dl), dr, None

        product.defvjp(fwd, bwd)
        return product(lhs, rhs, sizes)

    def reference(x, w1, w3, w2):
        return jnp.sum(REF.moe_layer(x, wr, w1, w3, w2, k, 0) ** 2)

    want = jax.jit(jax.value_and_grad(reference, (0, 1, 2, 3)))(
        x, w1, w3, w2)
    monkeypatch.setattr(moe.lax, "ragged_dot", poisoned)

    def program(x, w1, w3, w2):
        return jnp.sum(moe.routed_experts(x, wr, w1, w3, w2, top_k=k) ** 2)

    got = jax.jit(jax.value_and_grad(program, (0, 1, 2, 3)))(x, w1, w3, w2)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=1e-4 * float(jnp.abs(r).max()))


def test_moe_experts_op_refuses_a_share_outside_the_router():
    ctx = registry.ExecContext(
        "moe_experts",
        {"X": [jnp.zeros((4, 8))], "RouterW": [jnp.zeros((8, 16))],
         "W1": [jnp.zeros((4, 8, 2))], "W3": [jnp.zeros((4, 8, 2))],
         "W2": [jnp.zeros((4, 2, 8))]}, {"Out": ["y"]},
        {"num_routed": 16, "experts_held": 4, "expert_offset": 14,
         "top_k": 2})
    with pytest.raises(ValueError, match="expert_offset=14"):
        registry.get_op_def("moe_experts").fn(ctx)


# -- (e) grouped-query flash against full_attention -----------------------

@pytest.mark.parametrize("selected", [False, True])
@pytest.mark.parametrize("block", [64, 16])
def test_grouped_query_flash_forward_and_backward_at_head_width_128(
        monkeypatch, selected, block):
    """Interpreted; 8 query heads over 2 key-value heads of width 128.
    With a selection, some rows have no selected key in a whole tile."""
    monkeypatch.setattr(psf, "BLOCK", block)
    b, hq, hkv, t, d = 1, 8, 2, 64, 128
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
               for h in (hq, hkv, hkv))
    sel = None
    if selected:
        keep = (rng.rand(b, t, t) < 0.3) | np.eye(t, dtype=bool)
        keep[:, 40:, :16] = False            # an empty first tile
        sel = jnp.asarray(np.tril(keep).astype(np.int8))
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def kernel(q, k, v):
        return jnp.sum(psf.sparse_flash_attention(q, k, v, sel, None, True)
                       * w)

    def plain(q, k, v):
        return jnp.sum(dense(q, k, v, sel) * w)

    want = jax.jit(lambda *a: dense(*a, sel))(q, k, v)
    np.testing.assert_allclose(
        jax.jit(lambda *a: psf.sparse_flash_attention(
            *a, sel, None, True))(q, k, v), want, atol=2e-5)
    for g, r in zip(jax.jit(jax.grad(kernel, (0, 1, 2)))(q, k, v),
                    jax.jit(jax.grad(plain, (0, 1, 2)))(q, k, v)):
        np.testing.assert_allclose(g, r, atol=2e-4)
    # the blocked XLA path is the same function
    np.testing.assert_allclose(
        jax.jit(lambda *a: decoder_ops.blocked_attention(
            *a, sel, d ** -0.5, block=block))(q, k, v), want, atol=2e-5)


def test_operands_the_kernels_do_not_take_are_declined_with_a_reason(
        monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")
    q = jnp.ones((1, 4, 12, 8))          # 12 positions: no tile of 8 rows
    k = jnp.ones((1, 2, 12, 8))
    ctx = registry.ExecContext("sparse_attention",
                               {"Q": [q], "K": [k], "V": [k]},
                               {"Out": ["o"]}, {"topk": 0})
    got = registry.get_op_def("sparse_attention").fn(ctx)
    out = got["Out"]
    assert got["Lse"].shape == (1, 4, 12, 1)
    np.testing.assert_allclose(out, dense(q, k, k, None), atol=1e-6)
    assert counters("ops.sparse_attention.declined") == {
        'ops.sparse_attention.declined{why="ragged"}': 1}
    (key, _), = counters("ops.sparse_attention.calls").items()
    assert 'path="xla"' in key


# -- the selection --------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_select_top_k_is_lax_top_k_with_its_tie_break(seed):
    """Scores with a heavy atom at zero (the indexer's relu makes one): the
    bisection picks exactly what ``lax.top_k`` picks, lowest index first."""
    rng = np.random.RandomState(seed)
    bq, tk, topk, q0 = 16, 64, 8, 48
    score = rng.randn(bq, tk).astype(np.float32)
    score[rng.rand(bq, tk) < 0.5] = 0.0
    got = np.asarray(decoder_ops.select_top_k(jnp.asarray(score), q0, topk))
    causal = (q0 + np.arange(bq))[:, None] >= np.arange(tk)[None]
    vals, idx = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), topk)
    want = np.zeros((bq, tk), np.int8)
    for r in range(bq):
        want[r, np.asarray(idx[r])[np.asarray(vals[r]) > -np.inf]] = 1
    assert np.array_equal(got, want)
    assert got.sum(1).tolist() == [topk] * bq


def test_selection_of_the_program_is_the_references():
    sizes = tiny_sizes()
    c = REF._dims(sizes)
    rng = np.random.RandomState(7)
    t, d = sizes["seq_len"], sizes["hidden_size"]
    x = jnp.asarray(rng.randn(1, t, d), jnp.float32)
    wq, wk, ww = (jnp.asarray(0.1 * rng.randn(d, n), jnp.float32)
                  for n in (c["hi"] * c["di"], c["di"], c["hi"]))
    got = jax.jit(lambda *a: decoder_ops.index_select(
        *a, c["hi"], c["topk"], c["theta"]))(x, wq, wk, ww)

    def stated(x, wq, wk, ww):
        qi = REF.rope((x[0] @ wq).reshape(t, c["hi"], c["di"]), c["theta"])
        ki = REF.rope((x[0] @ wk).reshape(t, 1, c["di"]), c["theta"])[:, 0]
        return REF.selection(qi, ki, x[0] @ ww, c["topk"], 0)

    want = jax.jit(stated)(x, wq, wk, ww)
    assert np.array_equal(np.asarray(got[0]) > 0, np.asarray(want))
    assert np.asarray(got[0]).sum(1).tolist() == \
        [min(i + 1, c["topk"]) for i in range(t)]


# -- infer rules ------------------------------------------------------------

def test_infer_rules_name_what_is_wrong():
    from paddle_tpu import analysis

    q = layers.data(name="q", shape=[4, 16, 8], dtype="float32")
    k = layers.data(name="k", shape=[3, 16, 8], dtype="float32")
    out = layers.sparse_attention(q, k, k)
    report = analysis.verify_program(fluid.default_main_program(),
                                     fetch_list=[out])
    assert any("do not group over the 3 key-value heads" in d.message
               for d in report.errors), report.format()


def test_infer_rules_give_the_shapes_of_the_new_ops():
    from paddle_tpu.ops.registry import get_infer_rule

    class Op:
        def __init__(self, **attrs):
            self.attrs, self.inputs, self.type = attrs, {}, "t"

        def attr(self, name, default=None):
            return self.attrs.get(name, default)

    x = ((2, 16, 32), "float32")
    out = get_infer_rule("sparse_indexer")(
        Op(num_heads=4, topk=8), {"X": [x], "WQ": [((32, 64), "float32")],
                                  "WK": [((32, 16), "float32")],
                                  "WW": [((32, 4), "float32")]})
    assert out == {"Sel": [((2, 16, 16), "int8")]}
    assert get_infer_rule("rms_norm")(
        Op(), {"X": [x], "Scale": [((32,), "float32")]}) == {"Y": [x]}
    assert get_infer_rule("moe_experts")(
        Op(num_routed=8, experts_held=4, expert_offset=4, top_k=2),
        {"X": [x], "RouterW": [((32, 8), "float32")],
         "W1": [((4, 32, 8), "float32")]}) == {"Out": [x]}
    with pytest.raises(registry.InferMismatch, match="do not fit a router"):
        get_infer_rule("moe_experts")(
            Op(num_routed=8, experts_held=4, expert_offset=6, top_k=2), {})


# -- the expert layer's backward: rows go back through the kept plan ------

def per_expert_loop(x, wr, w1, w3, w2, idx, off, score, norm, norm_eps,
                    scale):
    """The share in float64, plain: the router's scores, the weights of
    the choices ``idx`` (renormalized if ``norm``), then every held expert
    in turn over ALL tokens, weighted by what each token gave it (zero for
    most)."""
    logits = x @ wr
    scores = jax.nn.softmax(logits, -1) if score == "softmax" \
        else jax.nn.sigmoid(logits)
    vals = jnp.take_along_axis(scores, idx, -1)
    if norm:
        vals = vals / (vals.sum(-1, keepdims=True) + norm_eps)
    vals, y = scale * vals, 0.0
    for j in range(w1.shape[0]):
        weight = jnp.sum(jnp.where(idx == off + j, vals, 0.0), -1)
        y = y + weight[:, None] * (
            (jax.nn.silu(x @ w1[j]) * (x @ w3[j])) @ w2[j])
    return y


@pytest.mark.parametrize("top_k", [1, 8])
@pytest.mark.parametrize("off,held", [(12, 4), (4, 4), (0, 12)],
                         ids=["none_held", "some_held", "all_held"])
@pytest.mark.parametrize("score", ["softmax", "sigmoid_bias"])
def test_gradients_of_the_share_equal_a_per_expert_loop(score, off, held,
                                                        top_k):
    """Gradients w.r.t. x, the router and the three expert weights through
    the kept plan and the gathers, against a per-expert loop in float64;
    experts 12-15 are chosen by no token, so a share that holds them holds
    no assignment, and one that holds 0-11 holds every one.  One choice a
    token is not renormalized (its weight would be 1 and the router's
    gradient zero).  The program routes, activates and combines in float32
    whatever it is handed, hence 1e-5 of each gradient's largest entry and
    not float64's own 1e-9."""
    routed, n, d, f = 16, 24, 8, 4
    rng = np.random.RandomState(11)
    x, wr, w1, w3, w2 = (jnp.asarray(a, jnp.float64) for a in moe_weights(
        rng, n, d, f, routed))
    x = x.at[:, 0].set(1.0)
    wr = wr.at[0, 12:].set(-60.0)
    w1, w3, w2 = (w[off:off + held] for w in (w1, w3, w2))
    sigmoid = score != "softmax"
    kw = dict(norm_topk=top_k > 1)
    if sigmoid:
        kw.update(score="sigmoid", bias=jnp.asarray(
            0.1 * rng.randn(routed), jnp.float32).at[12:].set(-10.0),
            norm_eps=1e-20, scale=2.5)
    _, idx = moe.route_top_k(x, wr, top_k, **kw)
    lands = int(jnp.sum((idx >= off) & (idx < off + held)))
    assert lands == {12: 0, 4: lands, 0: n * top_k}[off]
    assert off != 4 or 0 < lands < n * top_k
    mix = jnp.asarray(rng.randn(n, d))

    def program(*args):
        return jnp.sum(mix * moe.routed_experts(
            *args, top_k=top_k, expert_offset=off, **kw))

    def plain(*args):
        return jnp.sum(mix * per_expert_loop(
            *args, idx, off, kw.get("score", "softmax"), top_k > 1,
            kw.get("norm_eps", 0.0), kw.get("scale", 1.0)))

    args = (x, wr, w1, w3, w2)
    got = jax.jit(jax.value_and_grad(program, range(5)))(*args)
    want = jax.jit(jax.value_and_grad(plain, range(5)))(*args)
    assert got[1][0].dtype == jnp.float64
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-12)
    for name, g, r in zip("x router w1 w3 w2".split(), got[1], want[1]):
        assert np.abs(g - r).max() <= 1e-5 * np.abs(r).max(), name
        assert bool(np.any(r)) == (lands > 0), name


def plain_share(x, wr, w1, w3, w2, top_k, expert_offset=0, **router):
    """THE REFERENCE of ``moe.routed_experts``'s hand-written backward: the
    share in its plain formulation, differentiated by jax.  The same
    router (``moe.route_top_k``), the assignments sorted by expert with the
    absent ones last, rows moved by ``jnp.take`` (whose transpose is a
    scatter-add), XLA's own grouped product, the last product MADE and
    read for the gate's cotangent, the combine an einsum over ``[N, k,
    D]``: what the program did before its backward was written by hand,
    less the checkpoint.  Nothing of ``moe._share`` is used."""
    e, n = w1.shape[0], x.shape[0]
    vals, idx = moe.route_top_k(x, wr, top_k, **router)
    local = idx - expert_offset
    held = (local >= 0) & (local < e)
    group = jnp.where(held, local, e).reshape(-1)
    order = jnp.argsort(group, stable=True)
    counts = jnp.sum(group[:, None] == jnp.arange(e + 1), axis=0,
                     dtype=jnp.int32)
    sizes = counts[:e].at[e - 1].add(counts[e])
    live = (jnp.arange(n * top_k) < jnp.sum(counts[:e]))[:, None]
    xs = jnp.take(x, order // top_k, axis=0)
    h = jax.nn.silu(jnp.where(live, jax.lax.ragged_dot(xs, w1, sizes), 0)) \
        * jnp.where(live, jax.lax.ragged_dot(xs, w3, sizes), 0)
    ys = jnp.take(jax.lax.ragged_dot(h, w2, sizes), jnp.argsort(order),
                  axis=0).reshape(n, top_k, -1)
    return jnp.einsum("nk,nkd->nd", jnp.where(held, vals, 0.0),
                      jnp.where(held[..., None], ys, 0))


def share_case(case, score, seed):
    """Operands of a share at a lane-aligned size (512 rows of width 128:
    what the Pallas kernels take), 3 of 8 experts held from expert 2 on, so
    that most assignments are absent; ``an_empty_expert``: the held expert
    3 is chosen by no token."""
    n, d, f, routed, held, k, off = 256, 128, 128, 8, 3, 2, 2
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d), jnp.float32).at[:, 0].set(1.0)
    wr = jnp.asarray(rng.randn(d, routed) / np.sqrt(d), jnp.float32)
    if case == "an_empty_expert":
        wr = wr.at[0, 3].set(-60.0)
    w1, w3 = (jnp.asarray(rng.randn(held, d, f) / np.sqrt(d), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.randn(held, f, d) / np.sqrt(f), jnp.float32)
    kw = dict(top_k=k, expert_offset=off)
    if score == "sigmoid_bias":
        bias = jnp.asarray(0.1 * rng.randn(routed), jnp.float32)
        kw.update(score="sigmoid", norm_eps=1e-20, scale=2.5, bias=bias.at[
            3].set(-10.0) if case == "an_empty_expert" else bias)
    _, idx = moe.route_top_k(x, wr, k, **{
        a: b for a, b in kw.items() if a not in ("top_k", "expert_offset")})
    lands = np.bincount(np.asarray(idx).reshape(-1), minlength=routed)
    assert 0 < lands[off:off + held].sum() < n * k       # some are absent
    assert (lands[3] == 0) == (case == "an_empty_expert")
    mix = jnp.asarray(rng.randn(n, d), jnp.float32)
    return (x, wr, w1, w3, w2), kw, mix


@pytest.mark.parametrize("case", ["absent_assignments", "an_empty_expert"])
@pytest.mark.parametrize("score", ["softmax", "sigmoid_bias"])
@pytest.mark.parametrize("path", ["ragged_dot", "pallas"])
def test_the_handwritten_backward_equals_autodiff_of_the_plain_share(
        monkeypatch, path, score, case):
    """All five gradients (x, router, w1, w3, w2) of ``routed_experts``,
    whose backward is written by hand, against jax's own differentiation of
    ``plain_share``, in float32, on both product paths (the Pallas kernels
    interpreted).  The two differ in what they sum and in which order (the
    gate's cotangent over the hidden width, not over D; the rows' sums over
    a token's choices by gather, not by scatter-add), hence the per-expert
    loop's tolerance: 1e-5 of each gradient's largest entry."""
    from paddle_tpu.ops import pallas_grouped

    args, kw, mix = share_case(case, score, seed=21)
    if path == "ragged_dot":
        monkeypatch.setattr(pallas_grouped, "supported", lambda *a: "off")
    assert moe.product_path(args[0], args[2], args[4], kw["top_k"]) == path
    got = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
        mix * moe.routed_experts(*a, **kw)), range(5)))(*args)
    want = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
        mix * plain_share(*a, **kw)), range(5)))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, g, r in zip("x router w1 w3 w2".split(), got[1], want[1]):
        assert g.dtype == r.dtype == jnp.float32
        assert np.abs(g - r).max() <= 1e-5 * np.abs(r).max(), name
        assert np.any(np.asarray(r)), name
    if case == "an_empty_expert":
        for g in got[1][2:]:
            assert not np.any(np.asarray(g[1]))


@pytest.mark.parametrize("path", ["ragged_dot", "pallas"])
def test_the_gates_cotangent_is_dy_against_the_last_product(monkeypatch,
                                                            path):
    """The backward makes no last product: what it gives the gate is
    ``<dy @ w2^T, h>`` over a sorted row.  Against ``<dy, ys>`` computed
    outright, every token through every held expert it chose, and exactly
    zero where the choice is absent."""
    from paddle_tpu.ops import pallas_grouped

    (x, wr, w1, w3, w2), kw, dy = share_case("absent_assignments",
                                             "softmax", seed=22)
    if path == "ragged_dot":
        monkeypatch.setattr(pallas_grouped, "supported", lambda *a: "off")
    vals, idx = moe.route_top_k(x, wr, kw["top_k"])

    def weighted(vals):
        # the router's weights handed in as they are: their cotangent is
        # the gate's
        monkeypatch.setattr(moe, "route_top_k", lambda *a, **k: (vals, idx))
        return jnp.sum(dy * moe.routed_experts(x, wr, w1, w3, w2, **kw))

    got = jax.jit(jax.grad(weighted))(vals)
    off = kw["expert_offset"]

    def outright(x, w1, w3, w2):
        ys = jnp.stack([(jax.nn.silu(x @ w1[j]) * (x @ w3[j])) @ w2[j]
                        for j in range(w1.shape[0])], 1)      # [N, held, D]
        return jnp.einsum("nd,njd->nj", dy, ys,
                          precision=jax.lax.Precision.HIGHEST)

    dots = jax.jit(outright)(x, w1, w3, w2)
    held = (idx >= off) & (idx < off + w1.shape[0])
    want = jnp.where(held, jnp.take_along_axis(
        dots, jnp.clip(idx - off, 0, w1.shape[0] - 1), 1), 0.0)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert not np.any(np.asarray(got)[~np.asarray(held)])
    assert np.any(np.asarray(held)) and not np.all(np.asarray(held))


@pytest.mark.parametrize("top_k", [1, 8])
def test_rows_come_home_as_the_transpose_of_going_out(top_k):
    """The backward's third gather: ``moe._rows_home`` through the inverse
    permutation, summed over a token's ``top_k`` choices, is the transpose
    of ``moe._rows_out`` (the gather both passes take the tokens' rows out
    to the sorted assignments with, and the backward the combine's
    cotangent), against the scatter-add that is ``jnp.take``'s own
    transpose: float64, to the last bits (the sum over the rows of one
    token in another order).  These two stand where ``_take_rows``'s
    custom vjp stood until PR 39."""
    rng = np.random.RandomState(top_k)
    n, d = 40, 6
    rows = jnp.asarray(rng.randn(n, d))
    order = jnp.asarray(rng.permutation(n * top_k), jnp.int32)
    back = jnp.argsort(order).astype(jnp.int32)
    mix = jnp.asarray(rng.randn(n * top_k, d))
    held = jnp.ones((n, top_k), bool)
    np.testing.assert_array_equal(order[back], np.arange(n * top_k))
    np.testing.assert_array_equal(
        moe._rows_out(rows, order, top_k, "forward"),
        jnp.take(rows, order // top_k, axis=0))
    got = jnp.sum(moe._rows_home(mix, back, held, "backward"), axis=1)
    want = jax.grad(lambda r: jnp.sum(mix * jnp.take(
        r, order // top_k, axis=0)))(rows)
    assert got.dtype == jnp.float64
    np.testing.assert_allclose(got, want, rtol=1e-13, atol=1e-15)
    # in bfloat16 the share's backward sums the rows of one token in
    # float32, and the cotangent rows of an absent assignment (poisoned
    # here, as a product may leave them) count for 0
    held = jnp.asarray(rng.rand(n, top_k) < 0.7)
    cot = jnp.where(held.reshape(-1)[order][:, None], mix,
                    jnp.nan).astype(jnp.bfloat16)
    low = jnp.sum(moe._rows_home(cot, back, held, "backward"), axis=1,
                  dtype=jnp.float32)
    assert low.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(low)))
    np.testing.assert_allclose(
        low, jnp.sum(jnp.where(
            held[..., None], jnp.take(cot, back, axis=0)
            .astype(jnp.float32).reshape(n, top_k, d), 0), 1), rtol=1e-6)
    assert counters("ops.moe.row_moves") == {
        'ops.moe.row_moves{how="gather",pass="backward"}': 2,
        'ops.moe.row_moves{how="gather",pass="forward"}': 1}


def test_the_chosen_scores_cotangent_lands_where_lax_top_k_puts_it():
    """Repeated scores: router columns 1, 2 and 5 are copies of column 0,
    so every token's scores tie.  The one-hot read puts the cotangent on
    the columns ``lax.top_k`` chose (the lower index first) and on no
    other, as ``lax.top_k``'s own vjp does."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(32, 8), jnp.float32)
    wr = jnp.asarray(rng.randn(8, 6), jnp.float32)
    wr = wr.at[:, 1].set(wr[:, 0]).at[:, 2].set(wr[:, 0]) \
        .at[:, 5].set(wr[:, 0])
    mix = jnp.asarray(rng.randn(32, 2), jnp.float32)

    def scores_of(w):
        return jax.nn.sigmoid(jnp.matmul(x, w, precision="highest"))

    vals, idx = moe.route_top_k(x, wr, 2, False, "sigmoid")
    own_vals, own_idx = jax.lax.top_k(scores_of(wr), 2)
    np.testing.assert_array_equal(idx, own_idx)
    np.testing.assert_array_equal(vals, own_vals)
    tied = np.asarray(idx[:, 0] == 0)
    assert tied.any() and np.all(np.asarray(idx[:, 1])[tied] == 1)
    got = jax.jit(jax.grad(lambda w: jnp.sum(mix * moe.route_top_k(
        x, w, 2, False, "sigmoid")[0])))(wr)
    want = jax.jit(jax.grad(lambda w: jnp.sum(mix * jax.lax.top_k(
        scores_of(w), 2)[0])))(wr)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # a sigmoid's score is its own column's: columns 2 and 5 tie with 0
    # and 1 in every row and are never the first two
    assert np.any(np.asarray(got[:, 0])) and np.any(np.asarray(got[:, 1]))
    assert not np.any(np.asarray(got[:, [2, 5]]))


def lowered_ops(lowered):
    """(name, the locations it lies under, elements a scatter updates, its
    results' shapes) for every operation of a lowered module as it runs:
    jax lowers a jitted helper (``argsort``) once, as a private function,
    however often it is called, so a private function's operations count
    once for every call of it, under the call's location too."""
    from jax._src.lib.mlir import ir

    bodies, out = {}, []
    module = lowered.compiler_ir("stablehlo")
    for func in module.body.operations:
        ops = bodies[ir.StringAttr(func.attributes["sym_name"]).value] = []

        def visit(op, ops=ops):
            callee = updates = None
            if op.name == "func.call":
                callee = ir.FlatSymbolRefAttr(op.attributes["callee"]).value
            elif op.name == "stablehlo.scatter":
                updates = int(np.prod(op.operands[2].type.shape))
            shapes = [tuple(r.type.shape) for r in op.results
                      if isinstance(r.type, ir.RankedTensorType)]
            ops.append((op.name, str(op.location), callee, updates, shapes))
            return ir.WalkResult.ADVANCE

        func.operation.walk(visit)

    def run(name, under):
        for op, location, callee, updates, shapes in bodies[name]:
            if callee is None:
                out.append((op, under + (location,), updates, shapes))
            else:
                run(callee, under + (location,))

    run("main", ())
    return out


#: what may give a value of a token's ``top_k`` rows side by side, ``[N,
#: top_k, D]``: views of gathered rows on their way into a sum over the
#: choices (and the zeros a select puts in).  No arithmetic: until PR 39
#: the combine's transpose made ``gate * dy`` at that size and gathered it
VIEWS = {"stablehlo.reshape", "stablehlo.select", "stablehlo.convert",
         "stablehlo.broadcast_in_dim", "stablehlo.constant"}


def makers_of(ops, shape):
    return {op for op, _, _, shapes in ops if shape in shapes}


@pytest.mark.parametrize("score", ["softmax", "sigmoid_bias"])
def test_the_share_backward_lowers_no_row_scatter_and_one_sort(score):
    """StableHLO of ``jax.grad`` of the share: the one scatter left adds
    one count to one group size (``sizes``); the assignments are sorted
    once, by the forward, and the backward takes the plan as it is; every
    row move is a gather and counted, two forward and three backward; and
    nothing is computed at ``[N, top_k, D]``."""
    n, k, d = 32, 4, 8
    x, wr, w1, w3, w2 = moe_weights(np.random.RandomState(6), n, d, 4, 16)
    kw = {} if score == "softmax" else dict(
        score="sigmoid", bias=jnp.zeros(16), norm_eps=1e-20, scale=2.0)
    ops = lowered_ops(jax.jit(jax.grad(
        lambda *a: jnp.sum(moe.routed_experts(
            *a, top_k=k, expert_offset=4, **kw) ** 2), range(5))).lower(
        x, wr, w1[:4], w3[:4], w2[:4]))
    names = [op for op, *_ in ops]
    assert [u for op, _, u, _ in ops if op == "stablehlo.scatter"] == [1]
    assert names.count("stablehlo.sort") == 1
    row_gathers = [op for op, _, _, shapes in ops
                   if op == "stablehlo.gather" and shapes == [(n * k, d)]]
    assert len(row_gathers) == 5
    assert makers_of(ops, (n, k, d)) <= VIEWS
    assert "stablehlo.reshape" in makers_of(ops, (n, k, d))
    assert counters("ops.moe.row_moves") == {
        'ops.moe.row_moves{how="gather",pass="forward"}': 2,
        'ops.moe.row_moves{how="gather",pass="backward"}': 3}


def kernel_calls(fn, *args):
    """How often each Pallas kernel is called in ``fn``'s jaxpr, by name."""
    calls = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                calls[name] = calls.get(name, 0) + 1
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return calls


def test_a_layer_and_its_backward_call_eight_products_and_three_gradients():
    """On the Pallas path, as traced: the forward calls ``grouped_matmul``
    three times, the backward five times more (the two hidden products
    again, the three rows' cotangents; NOT the last product again) and
    ``grouped_matmul_t`` three times."""
    (x, wr, w1, w3, w2), kw, mix = share_case("absent_assignments",
                                              "softmax", seed=23)

    def layer(*a):
        return jnp.sum(mix * moe.routed_experts(*a, **kw))

    assert moe.product_path(x, w1, w2, kw["top_k"]) == "pallas"
    assert kernel_calls(layer, x, wr, w1, w3, w2) == {"grouped_matmul": 3}
    assert kernel_calls(jax.grad(layer, range(5)), x, wr, w1, w3, w2) == {
        "grouped_matmul": 8, "grouped_matmul_t": 3}


@pytest.mark.parametrize("path", ["pallas", "ragged_dot"])
def test_every_kernel_call_traced_counts_its_column_tile(monkeypatch, path):
    """``ops.moe.column_tiles{kernel,width,tile,tiles,ragged}``: one for
    each kernel call traced (eight products and three weights' gradients in
    a layer's backward), with the tile ``pallas_grouped.tile`` gives; at a
    budget that holds 384 of 640 = 5 x 128 columns the tile is ragged (384
    + 256) for the products and the weights' gradients alike, since no
    divisor but one lane row fits; nothing on the ``ragged_dot`` path."""
    from paddle_tpu.ops import pallas_grouped

    n, d, f, routed, held, k = 256, 128, 640, 8, 3, 2
    rng = np.random.RandomState(24)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    wr = jnp.asarray(rng.randn(d, routed), jnp.float32)
    w1, w3 = (jnp.asarray(0.1 * rng.randn(held, d, f), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(0.1 * rng.randn(held, f, d), jnp.float32)
    monkeypatch.setattr(pallas_grouped, "VMEM_BUDGET", 3 << 20)
    if path == "ragged_dot":
        monkeypatch.setattr(pallas_grouped, "supported", lambda *a: "off")
    assert moe.product_path(x, w1, w2, k) == path

    def layer(*a):
        return jnp.sum(moe.routed_experts(*a, top_k=k, expert_offset=2))

    jax.make_jaxpr(jax.grad(layer, range(5)))(x, wr, w1, w3, w2)
    if path == "ragged_dot":
        assert counters("ops.moe.column_tiles") == {}
        return
    m = n * k
    tiles = {(kernel, width): pallas_grouped.tile(
                 m, other, width, 4, kernel == "grouped_matmul_t")[1]
             for kernel in ("grouped_matmul", "grouped_matmul_t")
             for width, other in ((f, d), (d, f))}
    assert tiles == {("grouped_matmul", f): 384, ("grouped_matmul", d): 128,
                     ("grouped_matmul_t", f): 384,
                     ("grouped_matmul_t", d): 128}
    # 640 wide: xs @ w1, xs @ w3 forward and again backward, dy_s @ w2^T;
    # 128 wide: h @ w2, da @ w1^T, db @ w3^T
    calls = {("grouped_matmul", f): 5, ("grouped_matmul", d): 3,
             ("grouped_matmul_t", f): 2, ("grouped_matmul_t", d): 1}
    assert counters("ops.moe.column_tiles") == {
        f'ops.moe.column_tiles{{kernel="{kernel}",'
        f'ragged="{int(width % tn > 0)}",tile="{tn}",'
        f'tiles="{-(-width // tn)}",width="{width}"}}':
        calls[kernel, width]
        for (kernel, width), tn in tiles.items()}


@pytest.mark.parametrize("case,losses,per_grad_op", [
    ("trunk", 1, ["_xent_bwd_kernel"]),
    ("trunk_and_mtp_head", 2, ["_xent_bwd_kernel"]),
    # a program from before the op had the slot: the generic grad, which
    # runs the forward kernel again for the log-sum-exp it had thrown away
    ("no_lse_slot", 1, ["_xent_partial_kernel", "_xent_bwd_kernel"]),
])
def test_the_training_step_runs_the_loss_forward_once(monkeypatch, case,
                                                      losses, per_grad_op):
    """The tiny decoder's whole training step, lowered with the loss
    kernels on: each loss op holds ``_xent_partial_kernel`` once, each grad
    op ``_xent_bwd_kernel`` once and NO forward kernel, its log-sum-exp
    being the forward's ``Lse`` (two of each with the multi-token
    module's head)."""
    from lowered_kernels import loss_kernel_calls
    from paddle_tpu.models import decoder_lm

    monkeypatch.setenv("PADDLE_TPU_FUSED", "1")
    cfg = decoder_lm.tiny_config()
    if case == "trunk_and_mtp_head":
        cfg.mtp_depth, cfg.mtp_weight = 1, 0.3
    seq = 16
    tokens, labels, loss = decoder_lm.build(cfg, seq_len=seq)
    main = fluid.default_main_program()
    loss_ops = [op for op in main.global_block().ops
                if op.type.startswith("softmax_with_cross_entropy")]
    assert [op.type for op in loss_ops] == \
        ["softmax_with_cross_entropy"] * losses + \
        ["softmax_with_cross_entropy_grad"] * losses
    for op in loss_ops:
        slots = op.inputs if op.type.endswith("_grad") else op.outputs
        lse = main.global_block().var(slots["Lse"][0])
        assert (tuple(lse.shape[1:]), lse.dtype, lse.stop_gradient) == \
            ((seq, 1), "float32", True)
        if case == "no_lse_slot":
            del slots["Lse"]
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(1, seq + 2)).astype(np.int64)
    feed = {"tokens": ids[:, :seq], "labels": ids[:, 1:seq + 1, None]}
    if cfg.mtp_depth:
        feed["labels2"] = ids[:, 2:, None]
    calls = loss_kernel_calls(exe.lower_step(main, feed, [loss]))
    assert calls == \
        [("softmax_with_cross_entropy", "_xent_partial_kernel")] * losses \
        + [("softmax_with_cross_entropy_grad", k) for k in per_grad_op] \
        * losses
    path = "generic" if case == "no_lse_slot" else "from_lse"
    assert counters("ops.softmax_xent.grad_calls") == {
        f'ops.softmax_xent.grad_calls{{path="{path}"}}': losses}


def test_the_training_step_scatters_no_row_under_the_expert_layer():
    """The tiny decoder's whole training step, lowered: under the expert
    layer's two ops (``moe_experts`` and ``moe_experts_grad`` in the
    locations) no scatter moves more than one element, each routed layer
    sorts once in the forward op and once in the grad op's own trace of
    the forward, of which the plan alone has a reader (jax drops the rest
    before it lowers), so the grad op holds the backward's three row
    gathers and nothing computed at ``[N, top_k, D]``.  Row moves as
    TRACED: two for each of those two traces of the forward, three for the
    backward."""
    from paddle_tpu.models import decoder_lm

    cfg = decoder_lm.tiny_config()
    seq = 32
    tokens, labels, loss = decoder_lm.build(cfg, seq_len=seq)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(1, seq + 1)).astype(np.int64)
    ops = lowered_ops(exe.lower_step(
        fluid.default_main_program(),
        {"tokens": ids[:, :-1], "labels": ids[:, 1:, None]}, [loss]))
    layer = [(op, where, u, shapes) for op, where, u, shapes in ops
             if any("moe_experts" in w for w in where)]
    grad = [(op, where, u, shapes) for op, where, u, shapes in layer
            if any("moe_experts_grad" in w for w in where)]
    rows = (seq * cfg.experts_per_token, cfg.hidden_size)
    assert sum(op == "stablehlo.gather" and shapes == [rows]
               for op, _, _, shapes in grad) == 3 * cfg.num_layers
    assert makers_of(grad, (seq, cfg.experts_per_token,
                            cfg.hidden_size)) <= VIEWS
    assert [u for op, _, u, _ in layer if op == "stablehlo.scatter"] \
        == [1] * 2 * cfg.num_layers
    # the step does scatter rows elsewhere (the embedding's gradient)
    assert any(op == "stablehlo.scatter" and u > 1 for op, _, u, _ in ops)
    assert [op for op, *_ in layer].count("stablehlo.sort") \
        == 2 * cfg.num_layers
    assert [op for op, *_ in grad].count("stablehlo.sort") == cfg.num_layers
    assert counters("ops.moe.row_moves") == {
        'ops.moe.row_moves{how="gather",pass="forward"}': 4 * cfg.num_layers,
        'ops.moe.row_moves{how="gather",pass="backward"}':
            3 * cfg.num_layers}


# == gated short-convolution layers beside an attention one of head width ==
# == 64, a tied head, a 32-way sigmoid router with top-4 and a bias: the  ==
# == program against the reference of ``chipbench/configs/lfm2_8b_a1b``   ==

L_BUILD, L_REF, lfm2_sizes = decoder_reference.load("lfm2_8b_a1b")


def three_shift_sum(x, w):
    """The reference's own words for the op, a batch at a time: [B | C | u]
    chunks, ``C * sum over three shifted copies of B * u``."""
    c = w.shape[0]
    return jnp.stack([
        xb[:, c:2 * c] * L_REF.causal_filter(xb[:, :c] * xb[:, 2 * c:], w)
        for xb in x])


@pytest.mark.parametrize("flash", ["xla", "pallas"])
def test_conv_program_equals_the_reference_tied_head_and_bias(
        monkeypatch, flash):
    """Loss, every gradient and every router's bias after the step through
    ``fluid.Executor`` with ``optimizer.minimize``: published layers 1-5 (a
    conv layer with the dense feed-forward, then attention, conv, conv,
    conv with routed experts).  The embedding is the head: ONE parameter,
    its gradient the lookup's rows plus the head product's, one Adam
    update."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash == "pallas" else "0")
    monkeypatch.setattr(psf, "BLOCK", 16)
    sizes = lfm2_sizes()
    assert sizes["num_experts"] < sizes["published"]["num_experts"]
    built, names, weights = seeded_program(L_BUILD, L_REF, sizes)
    main, scope = fluid.default_main_program(), fluid.global_scope()
    # a conv layer has no attention parameter, the attention layer no
    # filter; the head is no parameter of its own
    assert names.count("tok_emb") == 1 and "lm_head_w" not in names
    for i, kind in enumerate(sizes["layer_types"][1:6]):
        mine = {n[len(f"l{i}_"):] for n in names if n.startswith(f"l{i}_")}
        attn = {"attn_norm", "q_w", "q_norm", "k_w", "k_norm", "v_w", "o_w"}
        conv = {"conv_norm", "conv_in_w", "conv_w", "conv_out_w"}
        assert (mine & (attn | conv)) == (conv if kind == "conv" else attn)
    routers = [f"l{i}_route_bias" for i in range(1, 5)]
    block = main.global_block()
    for name in routers:
        assert not np.any(np.asarray(scope.get(name)))
        assert not block.has_var(name + "@GRAD") and name not in names
    assert sum(1 for op in block.ops if op.type == "adam"
               and "tok_emb" in op.inputs["Param"]) == 1
    feed = L_BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    outs = fluid.Executor(fluid.TPUPlace()).run(
        main, feed=feed, fetch_list=[built["loss"]]
        + [n + "@GRAD" for n in names])
    ref_loss, ref_grads, after = reference_step(L_REF, sizes, weights, feed)
    assert float(outs[0].reshape(-1)[0]) == pytest.approx(float(ref_loss),
                                                          rel=1e-5)
    for name, g, r in zip(names, outs[1:], ref_grads):
        g = np.asarray(g).reshape(r.shape)
        assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-7, name

    # the tied gradient's two parts, from the reference with the two uses
    # of the embedding told apart: the rows the lookup touched + the head
    # product's [vocab, hidden], which is dense
    def told_apart(lookup, head):
        total = 0.0
        for b in range(2):
            logits, _ = L_REF.forward_one(
                [lookup] + list(weights[1:]), feed["tokens"][b], sizes,
                head=head)
            total -= jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits, -1), feed["labels"][b], -1))
        return total / 2

    with jax.default_matmul_precision("highest"):
        rows, dense_part = jax.jit(jax.grad(told_apart, (0, 1)))(
            weights[0], weights[0])
    untouched = np.setdiff1d(np.arange(sizes["vocab_size"]),
                             np.unique(feed["tokens"]))
    assert untouched.size and not np.any(np.asarray(rows)[untouched])
    assert np.all(np.abs(np.asarray(dense_part)).sum(-1) > 0)
    emb = np.asarray(outs[1 + names.index("tok_emb")])
    both = np.asarray(rows + dense_part)
    assert np.abs(emb - both).max() <= 2e-4 * np.abs(both).max()
    assert np.abs(emb - np.asarray(dense_part)).max() \
        > 0.1 * np.abs(both).max()

    # one Adam update of the embedding, from the summed gradient
    want = compiled(L_REF, "optimizer_step", sizes)(weights[0], ref_grads[0])
    np.testing.assert_allclose(np.asarray(scope.get("tok_emb")), want,
                               atol=2e-6)
    rate = sizes["assumed"]["bias_update_rate"]
    for name, want in zip(routers, after):
        got = np.asarray(scope.get(name))
        np.testing.assert_allclose(got, want, atol=1e-9)
        assert set(np.round(np.abs(got) / rate)) <= {0.0, 1.0} \
            and np.any(got > 0) and np.any(got < 0)
    per = 1 if flash == "pallas" else 2
    assert counters("ops.sparse_attention.calls") == {
        f'ops.sparse_attention.calls{{path="{flash}",seq="64",'
        f'topk="0"}}': per}
    assert counters("ops.short_conv.calls") == {
        'ops.short_conv.calls{channels="64",path="xla",taps="3"}': 4}
    (key, n), = counters("ops.moe.calls").items()
    assert 'score="sigmoid"' in key and 'routed="8"' in key \
        and 'held="4"' in key and n == 2 * 4
    assert counters("ops.moe.bias_updates") == {"ops.moe.bias_updates": 4}
    assert not counters("ops.sparse_attention.declined")


@pytest.mark.parametrize("t", [1, 2, 13, 64])
def test_short_conv_op_equals_the_three_shift_sum_gradients_too(t):
    """The op through the executor, a batch of 2, at T under the filter's
    three taps, at T no multiple of 8 and at the tiny cell's: output and
    both gradients against the reference's sum over three shifted copies."""
    b, c, taps = 2, 8, 3
    rng = np.random.RandomState(t)
    x = layers.data(name="x", shape=[t, 3 * c], dtype="float32")
    x.stop_gradient = False
    out = layers.short_conv(
        x, taps, param_attr=fluid.ParamAttr(
            name="filter", initializer=fluid.initializer.
            NormalInitializer(0.0, 0.5)))
    assert tuple(out.shape[1:]) == (t, c)
    w = layers.assign(np.cos(np.arange(c, dtype="float32")))
    loss = layers.reduce_sum(layers.elementwise_mul(out, w))
    fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    filt = jnp.asarray(np.asarray(fluid.global_scope().get("filter")))
    assert filt.shape == (c, taps)
    feed = {"x": rng.randn(b, t, 3 * c).astype("float32")}
    got = exe.run(feed=feed, fetch_list=[out, "x@GRAD", "filter@GRAD"])
    xs = jnp.asarray(feed["x"])
    np.testing.assert_allclose(got[0], jax.jit(three_shift_sum)(xs, filt),
                               atol=1e-6)
    want = jax.jit(jax.grad(lambda x, f: jnp.sum(
        three_shift_sum(x, f) * jnp.cos(jnp.arange(c))), (0, 1)))(xs, filt)
    np.testing.assert_allclose(got[1], want[0], atol=1e-5)
    np.testing.assert_allclose(got[2], want[1], atol=1e-5)
    # the backward is the op's own and is not counted as a call
    assert counters("ops.short_conv.calls") == {
        f'ops.short_conv.calls{{channels="{c}",path="xla",taps="3"}}': 1}


def test_short_conv_leaks_nothing_from_the_future_or_across_sequences():
    """Perturbing token t of sequence 0 leaves its outputs before t and
    every output of sequence 1 unchanged, and moves outputs t .. t + 2 and
    no later one: the filter looks two tokens back and never ahead."""
    c, t, at = 4, 12, 5
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, t, 3 * c), jnp.float32)
    w = jnp.asarray(rng.randn(c, 3), jnp.float32)
    base = decoder_ops.gated_short_conv(x, w)
    moved = decoder_ops.gated_short_conv(x.at[0, at].add(1.0), w)
    np.testing.assert_array_equal(moved[1], base[1])
    np.testing.assert_array_equal(moved[0, :at], base[0, :at])
    assert np.all(np.any(np.asarray(moved[0, at:at + 3] != base[0, at:at + 3]),
                         -1))
    np.testing.assert_array_equal(moved[0, at + 3:], base[0, at + 3:])
    # the first token sees itself alone: the last tap
    np.testing.assert_allclose(
        base[:, 0], x[:, 0, c:2 * c] * w[:, 2] * x[:, 0, :c] * x[:, 0, 2 * c:],
        rtol=1e-6)
    # bf16 activations: the gates in bf16, the taps summed in float32
    low = decoder_ops.gated_short_conv(x.astype(jnp.bfloat16), w)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(low.astype(jnp.float32), base, atol=0.15,
                               rtol=0.05)
    dx, dw = jax.vjp(decoder_ops.gated_short_conv, x.astype(jnp.bfloat16),
                     w)[1](jnp.ones_like(low))
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32


def test_mixers_are_read_at_the_published_index():
    """``layer_offset`` 1 over the source's own list: conv, attention,
    conv, conv, conv; ``None`` is attention everywhere, as every
    configuration before had it."""
    from paddle_tpu.models import decoder_lm

    sizes = lfm2_sizes()
    cfg = L_BUILD.config_of(sizes)
    assert cfg.layer_offset == 1 and cfg.num_layers == 5
    assert [cfg.layer_mixer(i) for i in range(5)] == [
        "conv", "attention", "conv", "conv", "conv"]
    assert [cfg.layer_is_dense(i) for i in range(5)] == [True] + [False] * 4
    assert cfg.tie_head and cfg.conv_taps == 3 and cfg.head_dim == 16
    assert cfg.mixers[:3] == ("conv", "conv", "attention") \
        and len(cfg.mixers) == 24
    for build in (BUILD.config_of(tiny_sizes()),
                  T_BUILD.config_of(trinity_sizes()),
                  decoder_lm.tiny_config()):
        assert build.mixers is None and not build.tie_head \
            and build.layer_mixer(0) == "attention"
    with pytest.raises(ValueError, match="one of"):
        decoder_lm.Config(128, 64, 2, 4, 2, 16, 32, 8, 4, 2,
                          mixers=["conv", "scan"], conv_taps=3)
    with pytest.raises(ValueError, match="one of"):     # a list too short
        decoder_lm.Config(128, 64, 3, 4, 2, 16, 32, 8, 4, 2, layer_offset=1,
                          mixers=["conv"] * 3, conv_taps=3)
    with pytest.raises(ValueError, match="needs conv_taps"):
        decoder_lm.Config(128, 64, 2, 4, 2, 16, 32, 8, 4, 2,
                          mixers=["conv", "attention"])
    # the cell's build reads the kinds back off the parameters it made
    L_BUILD.build(fluid, sizes)
    main = fluid.default_main_program()
    assert L_BUILD.mixers_built(main, sizes) == sizes["layer_types"][1:6]
    with pytest.raises(ValueError, match="layer 5 has no mixer"):
        L_BUILD.mixers_built(main, {**sizes, "num_hidden_layers": 6})
    with pytest.raises(ValueError, match="no such mixer"):
        L_BUILD.config_of({**sizes, "layer_types": ["conv", "mamba"] * 12})


@pytest.mark.parametrize("block", [64, 16])
def test_grouped_query_flash_at_head_width_64_group_of_four(monkeypatch,
                                                            block):
    """Interpreted; 8 query heads over 2 key-value heads of width 64 (half
    a lane row; the cell's 32 over 8), no selection: the three kernels
    against ``blocked_attention`` and dense float32, gradients too, and
    ``supported`` takes the operands."""
    monkeypatch.setattr(psf, "BLOCK", block)
    b, hq, hkv, t, d = 1, 8, 2, 64, 64
    rng = np.random.RandomState(6)
    q, k, v = (jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
               for h in (hq, hkv, hkv))
    assert psf.supported(q, k, None) == ""
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def kernel(q, k, v):
        return jnp.sum(psf.sparse_flash_attention(q, k, v, None, None, True)
                       * w)

    def blocked(q, k, v):
        return jnp.sum(decoder_ops.blocked_attention(
            q, k, v, None, d ** -0.5, block=block) * w)

    out = jax.jit(lambda *a: psf.sparse_flash_attention(
        *a, None, None, True))(q, k, v)
    np.testing.assert_allclose(
        out, jax.jit(lambda *a: dense(*a, None))(q, k, v), atol=2e-5)
    np.testing.assert_allclose(
        out, jax.jit(lambda *a: decoder_ops.blocked_attention(
            *a, None, d ** -0.5, block=block))(q, k, v), atol=2e-5)
    for g, r in zip(jax.jit(jax.grad(kernel, (0, 1, 2)))(q, k, v),
                    jax.jit(jax.grad(blocked, (0, 1, 2)))(q, k, v)):
        np.testing.assert_allclose(g, r, atol=2e-4)


def test_the_references_bias_chooses_and_does_not_weigh():
    """The reference's router with a large bias on one expert: the expert
    enters every token's choice, the weights are the scores' alone over
    their sum + 1e-6, and are the program's router's."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(40, 16), jnp.float32)
    wr = jnp.asarray(rng.randn(16, 32), jnp.float32)
    bias = jnp.zeros(32).at[5].set(10.0)
    with jax.default_matmul_precision("highest"):
        vals, idx = L_REF.route(x, wr, bias, 4, 1.0, 1e-6)
        _, idx0 = L_REF.route(x, wr, jnp.zeros(32), 4, 1.0, 1e-6)
        scores = jax.nn.sigmoid(jnp.matmul(x, wr))
    assert bool(jnp.all(jnp.any(idx == 5, -1)))
    assert not bool(jnp.all(jnp.any(idx0 == 5, -1)))
    chosen = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        vals, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    mine, mine_idx = moe.route_top_k(x, wr, 4, True, "sigmoid", bias, 1e-6,
                                     1.0)
    np.testing.assert_array_equal(mine_idx, idx)
    np.testing.assert_allclose(mine, vals, rtol=1e-5)
    # one step of the rule from the counts, as the program's op has it
    counts = moe.assignment_counts(idx, 32)
    np.testing.assert_allclose(
        L_REF.bias_step(bias, counts, lfm2_sizes()),
        moe.balance_bias(bias, counts, 0.001), atol=1e-9)


def test_infer_rule_of_the_short_convolution():
    from paddle_tpu import analysis
    from paddle_tpu.ops.registry import get_infer_rule

    class Op:
        def __init__(self, **attrs):
            self.attrs, self.inputs, self.type = attrs, {}, "t"

        def attr(self, name, default=None):
            return self.attrs.get(name, default)

    x = ((2, 16, 96), "bfloat16")
    assert get_infer_rule("short_conv")(
        Op(), {"X": [x], "Filter": [((32, 3), "float32")]}) == {
            "Out": [((2, 16, 32), "bfloat16")]}
    with pytest.raises(registry.InferMismatch, match="3 \\* channels"):
        get_infer_rule("short_conv")(
            Op(), {"X": [x], "Filter": [((48, 3), "float32")]})
    with pytest.raises(registry.InferMismatch, match="3 \\* channels"):
        get_infer_rule("short_conv")(Op(), {"X": [((2, 16, 97), "float32")]})
    with pytest.raises(ValueError, match="not 3 \\* channels wide"):
        layers.short_conv(layers.data(name="odd", shape=[16, 97],
                                      dtype="float32"), 3)
    x = layers.data(name="x", shape=[16, 96], dtype="float32")
    out = layers.short_conv(x, 3)
    report = analysis.verify_program(fluid.default_main_program(),
                                     fetch_list=[out])
    assert not report.errors, report.format()
