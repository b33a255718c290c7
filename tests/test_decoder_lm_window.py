"""The decoder path's window layers, sigmoid router and balancing bias
(ops/pallas_sparse_flash.py's band, parallel/moe.py's bias) against the plain
float32 reference of ``chipbench/configs/trinity_mini`` at a tiny size, on the
CPU.  A section of ``tests/test_decoder_lm.py`` until PR 63, a file of its own
under the rule that no file of ``tests/`` is more than 300 s of one worker
(docs/COVERAGE.md); what the two share is ``tests/decoder_reference.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import decoder_ops, registry
from paddle_tpu.ops import pallas_sparse_flash as psf
from paddle_tpu.parallel import moe

import decoder_reference
from decoder_reference import (counters, dense, moe_weights, reference_step,
                               seeded_program)

# == window layers beside global ones, a sigmoid router with a balancing ==
# == bias beside a shared expert and a leading dense layer: the program   ==
# == against the reference of ``chipbench/configs/trinity_mini``          ==

T_BUILD, T_REF, trinity_sizes = decoder_reference.load("trinity_mini")
# the other references that ``reference_router`` names
L_REF = decoder_reference.load("lfm2_8b_a1b")[1]
I_REF = decoder_reference.load("instella_moe_16b_a3b")[1]
M_REF = decoder_reference.load("mellum2_12b_a2_5b")[1]


def windowed(q, k, v, window):
    """Dense float32: key s counts for query t iff 0 <= t - s < window."""
    g, t = q.shape[1] // k.shape[1], q.shape[2]
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, g, axis=1)) \
        * q.shape[-1] ** -0.5
    s = jnp.where((back >= 0) & (back < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                      jnp.repeat(v, g, axis=1))


@pytest.mark.parametrize("flash", ["xla", "pallas"])
def test_window_program_equals_the_reference_and_moves_the_bias(
        monkeypatch, flash):
    """Loss, every gradient and every router's bias after the step through
    ``fluid.Executor`` with ``optimizer.minimize``: published layers 1-5 (a
    dense window layer, then window, global, window, window routed ones),
    ``seq_len`` four windows; the bias is state without a gradient."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash == "pallas" else "0")
    monkeypatch.setattr(psf, "BLOCK", 16)       # a band of 2 tiles of 4
    sizes = trinity_sizes()
    assert sizes["seq_len"] == 4 * sizes["sliding_window"]
    assert sizes["num_experts"] < sizes["published"]["num_experts"]
    built, names, weights = seeded_program(T_BUILD, T_REF, sizes)
    main, scope = fluid.default_main_program(), fluid.global_scope()
    routers = [f"l{i}_route_bias" for i in range(1, 5)]
    block = main.global_block()
    for name in routers:
        assert not np.any(np.asarray(scope.get(name)))
        assert not block.has_var(name + "@GRAD") and name not in names
    feed = T_BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    outs = fluid.Executor(fluid.TPUPlace()).run(
        main, feed=feed, fetch_list=[built["loss"]]
        + [n + "@GRAD" for n in names])
    ref_loss, ref_grads, after = reference_step(T_REF, sizes, weights, feed)
    assert float(outs[0].reshape(-1)[0]) == pytest.approx(float(ref_loss),
                                                          rel=1e-5)
    for name, g, r in zip(names, outs[1:], ref_grads):
        g = np.asarray(g).reshape(r.shape)
        assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-7, name
    # the rule moved every bias as the reference's does: by +-coeff, up for
    # the experts that got fewer assignments than the mean
    for name, want in zip(routers, after):
        got = np.asarray(scope.get(name))
        np.testing.assert_allclose(got, want, atol=1e-9)
        assert set(np.round(np.abs(got) / sizes["load_balance_coeff"])) \
            <= {0.0, 1.0} and np.any(got > 0) and np.any(got < 0)
    # which path each layer took: the window label on 4 layers, none on 1
    calls = counters("ops.sparse_attention.calls")
    per = 1 if flash == "pallas" else 2
    assert calls == {
        f'ops.sparse_attention.calls{{path="{flash}",seq="64",topk="0",'
        f'window="16"}}': 4 * per,
        f'ops.sparse_attention.calls{{path="{flash}",seq="64",'
        f'topk="0"}}': per}
    (key, n), = counters("ops.moe.calls").items()
    assert 'score="sigmoid"' in key and 'routed="8"' in key and n == 2 * 4
    assert counters("ops.moe.bias_updates") == {"ops.moe.bias_updates": 4}
    assert not counters("ops.sparse_attention.declined")


@pytest.mark.parametrize("stated", [-1, 1])
def test_a_saved_program_that_states_a_wish_runs_as_one_that_does_not(
        monkeypatch, stated):
    """Programs saved before PR 47 carry ``"flash"`` (-1 from every model,
    0 / 1 from a layer's argument) in the descs of their attention ops and
    of the grad ops made from them.  The attribute loads and is not read:
    same loss, same gradients, same path as the program without it."""
    monkeypatch.delenv("PADDLE_TPU_FLASH", raising=False)
    b, hq, hkv, t, d = 2, 4, 2, 32, 16
    q = layers.data(name="q", shape=[hq, t, d], dtype="float32")
    k = layers.data(name="k", shape=[hkv, t, d], dtype="float32")
    q.stop_gradient = k.stop_gradient = False
    mixed = layers.elementwise_add(
        layers.sparse_attention(q, k, k, window=8),
        layers.ring_attention(q, q, q, causal=True))
    loss = layers.reduce_sum(layers.elementwise_mul(mixed, mixed))
    fluid.backward.append_backward(loss)
    main = fluid.default_main_program()
    rng = np.random.RandomState(1)
    feed = {"q": rng.randn(b, hq, t, d).astype("float32"),
            "k": rng.randn(b, hkv, t, d).astype("float32")}
    fetch = [loss.name, "q@GRAD", "k@GRAD"]
    exe = fluid.Executor(fluid.TPUPlace())
    want = exe.run(main, feed=feed, fetch_list=fetch)
    first = counters("ops.sparse_attention.calls")

    old = fluid.Program.parse_from_string(main.serialize_to_string())
    touched = [op for op in old.global_block().ops
               if op.type.split("_grad")[0] in ("sparse_attention",
                                                "ring_attention")]
    assert len(touched) == 4
    for op in touched:
        assert not op.has_attr("flash")
        op._set_attr("flash", stated)
    old = fluid.Program.parse_from_string(old.serialize_to_string())
    assert all(op.attr("flash") == stated for op in old.global_block().ops
               if op.type in ("sparse_attention", "ring_attention"))
    got = exe.run(old, feed=feed, fetch_list=fetch)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert first and all('path="xla"' in key for key in first)
    assert counters("ops.sparse_attention.calls") == {
        key: 2 * n for key, n in first.items()}


@pytest.mark.parametrize("window", [16, 24, 40, 64, 100])
@pytest.mark.parametrize("flash", [False, True])
def test_window_op_on_both_paths(monkeypatch, flash, window):
    """window < T on and off the tile (16), and window >= T, which is the
    global path: the op against dense float32, through the executor."""
    monkeypatch.setattr(psf, "BLOCK", 16)
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash else "0")
    b, hq, hkv, t, d = 2, 4, 2, 64, 16
    rng = np.random.RandomState(window)
    q = layers.data(name="q", shape=[hq, t, d], dtype="float32")
    k = layers.data(name="k", shape=[hkv, t, d], dtype="float32")
    v = layers.data(name="v", shape=[hkv, t, d], dtype="float32")
    q.stop_gradient = k.stop_gradient = v.stop_gradient = False
    out = layers.sparse_attention(q, k, v, window=window)
    plain = layers.sparse_attention(q, k, v)
    w = layers.assign(np.cos(np.arange(d, dtype="float32")))
    loss = layers.reduce_sum(layers.elementwise_mul(out, w))
    fluid.backward.append_backward(loss)
    feed = {n: rng.randn(b, h, t, d).astype("float32")
            for n, h in (("q", hq), ("k", hkv), ("v", hkv))}
    got = fluid.Executor(fluid.TPUPlace()).run(
        feed=feed, fetch_list=[out, plain, "q@GRAD", "k@GRAD", "v@GRAD"])
    args = [jnp.asarray(feed[n]) for n in "qkv"]
    np.testing.assert_allclose(
        got[0], jax.jit(lambda *a: windowed(*a, window))(*args), atol=2e-5)
    if window >= t:
        np.testing.assert_array_equal(got[0], got[1])
    want = jax.jit(jax.grad(lambda *a: jnp.sum(windowed(*a, window)
                                               * jnp.cos(jnp.arange(d))),
                            (0, 1, 2)))(*args)
    for g, r in zip(got[2:], want):
        np.testing.assert_allclose(g, r, atol=2e-4)
    path = "pallas" if flash else "xla"
    assert any(f'path="{path}"' in key and f'window="{window}"' in key
               for key in counters("ops.sparse_attention.calls"))


@pytest.mark.parametrize("window,block,tiles", [
    (16, 16, 2), (17, 16, 2), (18, 16, 3), (5, 16, 2), (1, 16, 1),
    (40, 16, 4), (50, 16, 4), (32, 64, 1)])
def test_window_kernels_equal_blocked_attention_gradients_too(
        monkeypatch, window, block, tiles):
    """The three kernels, interpreted, 8 query heads over 2 key-value heads
    of width 128, against the XLA path; the band is ``tiles`` wide."""
    monkeypatch.setattr(psf, "BLOCK", block)
    b, hq, hkv, t, d = 1, 8, 2, 64, 128
    assert psf.band_tiles(window, block, t // block) == tiles
    rng = np.random.RandomState(window)
    q, k, v = (jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
               for h in (hq, hkv, hkv))
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def kernel(q, k, v):
        return jnp.sum(psf.sparse_flash_attention(q, k, v, None, None, True,
                                                  window) * w)

    def blocked(q, k, v):
        return jnp.sum(decoder_ops.blocked_attention(
            q, k, v, None, d ** -0.5, block=block, window=window) * w)

    np.testing.assert_allclose(
        jax.jit(lambda *a: psf.sparse_flash_attention(
            *a, None, None, True, window))(q, k, v),
        jax.jit(lambda *a: windowed(*a, window))(q, k, v), atol=2e-5)
    for g, r in zip(jax.jit(jax.grad(kernel, (0, 1, 2)))(q, k, v),
                    jax.jit(jax.grad(blocked, (0, 1, 2)))(q, k, v)):
        np.testing.assert_allclose(g, r, atol=2e-4)


def _against_blocked(q, k, v, sel, window, block):
    """The three kernels, interpreted, against ``blocked_attention`` at the
    flash tests' tolerances: output and dQ / dK / dV."""
    d = q.shape[-1]
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def kernel(q, k, v):
        return jnp.sum(psf.sparse_flash_attention(q, k, v, sel, None, True,
                                                  window) * w)

    def blocked(q, k, v):
        return jnp.sum(decoder_ops.blocked_attention(
            q, k, v, sel, d ** -0.5, block=block, window=window) * w)

    np.testing.assert_allclose(
        jax.jit(lambda *a: psf.sparse_flash_attention(
            *a, sel, None, True, window))(q, k, v),
        jax.jit(lambda *a: decoder_ops.blocked_attention(
            *a, sel, d ** -0.5, block=block, window=window))(q, k, v),
        atol=2e-5)
    for g, r in zip(jax.jit(jax.grad(kernel, (0, 1, 2)))(q, k, v),
                    jax.jit(jax.grad(blocked, (0, 1, 2)))(q, k, v)):
        np.testing.assert_allclose(g, r, atol=2e-4)


@pytest.mark.parametrize("d,group,window,tiles", [
    (64, 1, 0, (0, 10)), (64, 4, 32, (3, 6)), (128, 8, 40, (3, 7)),
    (128, 4, 33, (3, 6)), (256, 8, 0, (0, 10)), (256, 1, 48, (5, 5)),
    (192, 4, 0, (0, 10)), (192, 1, 32, (3, 6))])
def test_interior_and_edge_tiles_equal_blocked_attention(
        monkeypatch, d, group, window, tiles):
    """Four tiles of 16 a row, so a row's tiles are interior (no positional
    mask made), diagonal and, under a window, the band's far edge: a window
    that is a multiple of the tile (32, 48) and one that is not (33, 40),
    head widths 64 / 128 / 256 and one that is no multiple of a register's
    lanes (192), groups of 1 / 4 / 8.  Without a window (and without a
    selection) one body masks every live tile: none is interior."""
    monkeypatch.setattr(psf, "BLOCK", 16)
    t, hq = 64, 8
    assert psf.tile_counts(t, window) == tiles
    rng = np.random.RandomState(d + group + window)
    q, k, v = (jnp.asarray(rng.randn(1, h, t, d), jnp.float32)
               for h in (hq, hq // group, hq // group))
    _against_blocked(q, k, v, None, window, 16)


@pytest.mark.parametrize("case", ["no_key_of_an_interior_tile",
                                  "no_key_of_the_first_tile", "not_causal"])
def test_a_selection_cuts_interior_tiles_and_the_diagonal_stays_causal(
        monkeypatch, case):
    """An interior tile reads its mask from the selection alone; the
    diagonal tile still ANDs the causal rule, whatever ``Sel`` holds: a
    selection that is not causal gives causal-AND-selection, as
    ``blocked_attention`` does."""
    monkeypatch.setattr(psf, "BLOCK", 16)
    b, hq, hkv, t, d = 1, 8, 2, 64, 128
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
               for h in (hq, hkv, hkv))
    keep = (rng.rand(b, t, t) < 0.3) | np.eye(t, dtype=bool)
    if case == "no_key_of_an_interior_tile":
        keep[:, 40:, 16:32] = False
    elif case == "no_key_of_the_first_tile":
        keep[:, 40:, :16] = False
    if case != "not_causal":
        keep = np.tril(keep)
    else:
        assert np.triu(keep[0], 1).any()
    sel = jnp.asarray(keep.astype(np.int8))
    _against_blocked(q, k, v, sel, 0, 16)
    causal = jnp.asarray(np.tril(keep).astype(np.int8))
    np.testing.assert_allclose(
        jax.jit(lambda *a: psf.sparse_flash_attention(
            *a, sel, None, True))(q, k, v),
        jax.jit(lambda *a: dense(*a, causal))(q, k, v), atol=2e-5)


@pytest.mark.parametrize("t,window,selected,interior,edge", [
    (64, 0, True, 6, 4),        # n (n - 1) / 2 and n at n = 4
    (64, 0, False, 0, 10),      # one compare to drop: one body, all edge
    (192, 64, False, 30, 20),   # trinity_mini's band in small: 12 tiles, 5 wide
    (192, 0, True, 66, 12),
    (64, 40, False, 3, 7),      # a ragged window: two far tiles a row are edge
    (16, 0, True, 0, 1)])       # a single tile: the diagonal one
def test_every_kernel_call_lowered_counts_its_tiles_by_kind(
        monkeypatch, t, window, selected, interior, edge):
    """``ops.sparse_attention.tiles{kernel,kind}``: the live tiles one head
    walks in each of the three calls lowered, interior (a body of their own
    with no positional mask: only under a selection or a window) and edge."""
    monkeypatch.setattr(psf, "BLOCK", 16)
    q = jnp.ones((1, 4, t, 128), jnp.bfloat16)
    k = jnp.ones((1, 2, t, 128), jnp.bfloat16)
    sel = jnp.ones((1, t, t), jnp.int8) if selected else None
    jax.jit(jax.grad(lambda q: psf.sparse_flash_attention(
        q, k, k, sel, None, True, window).astype(jnp.float32).sum())
        ).lower(q)
    family = "window_flash" if window else "sparse_flash"
    assert counters("ops.sparse_attention.tiles") == {
        f'ops.sparse_attention.tiles{{kernel="{family}_{kernel}",'
        f'kind="{kind}"}}': count
        for kernel in ("fwd", "dq", "dkv")
        for kind, count in (("interior", interior), ("edge", edge))}
    n = t // 16
    if selected:
        assert (interior, edge) == (n * (n - 1) // 2, n)
    assert interior + edge == sum(
        min(j + 1, psf.band_tiles(window, 16, n) if window else n)
        for j in range(n))


def test_a_window_lowers_no_t_by_t_operand(monkeypatch):
    """The window is a static band: the lowered calls hold its table
    [tiles, band] and nothing [.., T, T]; a selection beside a window is
    declined to the XLA path, which masks both."""
    monkeypatch.setattr(psf, "BLOCK", 64)
    b, hq, hkv, t, d = 1, 4, 2, 256, 128
    q = jnp.ones((b, hq, t, d), jnp.bfloat16)
    k = jnp.ones((b, hkv, t, d), jnp.bfloat16)
    text = jax.jit(lambda q, k: jax.grad(
        lambda q: psf.sparse_flash_attention(q, k, k, None, None, True, 32)
        .astype(jnp.float32).sum())(q)).lower(q, k).as_text()
    assert f"{t}x{t}x" not in text and "tensor<4x2xi32>" in text
    assert psf.supported(q, k, jnp.ones((b, t, t), jnp.int8), 32) \
        == "window_selection"
    assert psf.supported(q, k, None, 32) == ""


def reference_router(case):
    """A cell's reference's router as ``routed(x, wr, bias, w1, w3, w2,
    top_k, scale, offset)``, whatever its own argument list."""
    ref, eps = {"trinity": (T_REF, None), "lfm2": (L_REF, 1e-6),
                "instella": (I_REF, 1e-20), "mellum2": (M_REF, 0.0)}[case]
    if eps is None:         # the norm's epsilon is a constant of the file
        return ref, ref.routed
    if case == "mellum2":   # a softmax router: no bias, scale or counts
        return ref, lambda x, wr, bias, w1, w3, w2, k, scale, off=0: (
            ref.routed(x, wr, w1, w3, w2, k, off), None)
    return ref, lambda x, wr, bias, w1, w3, w2, k, scale, off=0: ref.routed(
        x, wr, bias, w1, w3, w2, k, scale, eps, off)


@pytest.mark.parametrize("case,routed,held,k,scale,eps,shared", [
    ("trinity", 128, 8, 8, 2.826, 1e-20, 8),
    ("lfm2", 32, 8, 4, 1.0, 1e-6, 0),
    ("instella", 64, 8, 6, 2.5, 1e-20, 16),
    ("mellum2", 64, 8, 8, 1.0, 0.0, 0)])
def test_the_shares_and_the_shared_expert_once_add_up_to_the_layer(
        case, routed, held, k, scale, eps, shared):
    """A sigmoid router under a selection bias, ``k`` per token over all
    ``routed``, ``held`` by each of ``routed / held`` chips (Trinity: 16
    shares of 128 with top-8; LFM2: 4 shares of 32 with top-4 and no shared
    expert; Instella: 8 shares of 64 with top-6, scale 2.5 and two shared
    experts as one of twice the width; Mellum2: 8 shares of 64 with top-8,
    experts 8c..8c+7 on chip c, under a SOFTMAX router without a bias and
    no shared expert): the shares, and the shared expert counted ONCE, add
    up to what the cell's reference gives for the uncut layer; every share
    reports the same assignments, over all ``routed``."""
    rng = np.random.RandomState(1)
    x, wr, w1, w3, w2 = moe_weights(rng, 48, 16, 8, routed)
    softmax = case == "mellum2"
    bias = None if softmax \
        else jnp.asarray(0.1 * rng.randn(routed), jnp.float32)
    ref, ref_routed = reference_router(case)
    whole, n_whole = ref_routed(x, wr, bias, w1, w3, w2, k, scale)
    if softmax:             # the reference counts nothing: the op's own
        n_whole = moe.routed_experts(x, wr, w1, w3, w2, top_k=k,
                                     with_counts=True)[1]
    assert int(n_whole.sum()) == 48 * k
    total = 0.0
    if shared:
        s1, s3, s2 = (jnp.asarray(0.3 * rng.randn(*s), jnp.float32)
                      for s in ((16, shared), (16, shared), (shared, 16)))
        total = ref.feed_forward(x, s1, s3, s2)
        whole = whole + total
    for off in range(0, routed, held):
        part, n = moe.routed_experts(
            x, wr, w1[off:off + held], w3[off:off + held],
            w2[off:off + held], top_k=k, expert_offset=off,
            score="softmax" if softmax else "sigmoid", bias=bias,
            norm_eps=eps, scale=scale, with_counts=True)
        mine, _ = ref_routed(x, wr, bias, w1[off:off + held],
                             w3[off:off + held], w2[off:off + held], k,
                             scale, off)
        np.testing.assert_allclose(part, mine, atol=1e-5)
        np.testing.assert_array_equal(n, n_whole)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=3e-5)
    assert float(jnp.abs(whole).max()) > 0.1


def test_the_bias_chooses_and_does_not_weigh():
    """A large bias on one expert puts it into every token's choice; the
    weights stay those of the scores alone, renormalized and scaled, and
    the bias gets no gradient."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(40, 16), jnp.float32)
    wr = jnp.asarray(rng.randn(16, 12), jnp.float32)
    none, idx0 = moe.route_top_k(x, wr, 3, True, "sigmoid", jnp.zeros(12),
                                 1e-20, 2.0)
    bias = jnp.zeros(12).at[5].set(10.0)
    vals, idx = moe.route_top_k(x, wr, 3, True, "sigmoid", bias, 1e-20, 2.0)
    assert bool(jnp.all(jnp.any(idx == 5, -1)))
    assert not bool(jnp.all(jnp.any(idx0 == 5, -1)))
    scores = jax.nn.sigmoid(jnp.matmul(x, wr, precision="highest"))
    chosen = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        vals, 2.0 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(vals.sum(-1), 2.0, rtol=1e-6)
    # the choice without a bias is the top of the scores themselves
    np.testing.assert_array_equal(idx0, jax.lax.top_k(scores, 3)[1])
    grad = jax.grad(lambda b: moe.route_top_k(
        x, wr, 3, True, "sigmoid", b, 1e-20, 2.0)[0][:, 0].sum())(bias)
    assert not np.any(np.asarray(grad))
    with pytest.raises(ValueError, match="neither 'softmax' nor 'sigmoid'"):
        moe.route_top_k(x, wr, 3, score="tanh")


def test_the_balancing_rule_moves_toward_the_mean():
    counts = jnp.asarray([0, 4, 8, 4], jnp.int32)
    bias = jnp.asarray([0.5, 0.0, 0.0, -0.25], jnp.float32)
    np.testing.assert_allclose(
        moe.balance_bias(bias, counts, 0.001),
        [0.501, 0.0, -0.001, -0.25], atol=1e-9)
    np.testing.assert_array_equal(
        moe.assignment_counts(jnp.asarray([[0, 2], [2, 3]]), 5),
        [1, 0, 2, 1, 0])


def test_infer_rules_of_the_window_and_the_bias():
    from paddle_tpu.ops.registry import get_infer_rule

    class Op:
        def __init__(self, **attrs):
            self.attrs, self.inputs, self.type = attrs, {}, "t"

        def attr(self, name, default=None):
            return self.attrs.get(name, default)

    x = ((2, 16, 32), "float32")
    ins = {"X": [x], "RouterW": [((32, 8), "float32")],
           "W1": [((4, 32, 8), "float32")], "Bias": [((8,), "float32")]}
    op = Op(num_routed=8, experts_held=4, expert_offset=4, top_k=2,
            score="sigmoid")
    assert get_infer_rule("moe_experts")(op, ins) == {
        "Out": [x], "Counts": [((8,), "int32")]}
    with pytest.raises(registry.InferMismatch, match="one per routed"):
        get_infer_rule("moe_experts")(op, {**ins,
                                           "Bias": [((4,), "float32")]})
    with pytest.raises(registry.InferMismatch, match="neither 'softmax'"):
        get_infer_rule("moe_experts")(
            Op(num_routed=8, experts_held=4, top_k=2, score="tanh"), {})
    assert get_infer_rule("moe_bias_update")(
        Op(coeff=0.001), {"Bias": [((8,), "float32")],
                          "Counts": [((8,), "int32")]}) == {
        "BiasOut": [((8,), "float32")]}
    with pytest.raises(registry.InferMismatch, match="one float and one"):
        get_infer_rule("moe_bias_update")(
            Op(coeff=0.001), {"Bias": [((8,), "float32")],
                              "Counts": [((8,), "float32")]})
    q = ((1, 4, 16, 8), "float32")
    with pytest.raises(registry.InferMismatch, match="is negative"):
        get_infer_rule("sparse_attention")(
            Op(window=-1), {"Q": [q], "K": [q], "V": [q]})


def test_layer_kinds_follow_the_published_index():
    from paddle_tpu.models import decoder_lm

    cfg = T_BUILD.config_of(trinity_sizes())
    assert [bool(cfg.layer_window(i)) for i in range(5)] == [
        True, True, False, True, True]
    assert [cfg.layer_is_dense(i) for i in range(5)] == [
        True, False, False, False, False]
    whole = decoder_lm.Config(
        128, 64, 8, 4, 2, 16, 32, 8, 4, 2, window=16, global_every=4,
        dense_layers=2, dense_width=96)
    assert [whole.layer_window(i) for i in range(8)] == [
        16, 16, 16, 0, 16, 16, 16, 0]
    assert [whole.layer_is_dense(i) for i in range(8)] == [True] * 2 \
        + [False] * 6
    with pytest.raises(ValueError, match="no layer kind is defined"):
        decoder_lm.Config(128, 64, 2, 4, 2, 16, 32, 8, 4, 2, window=16,
                          index_topk=8)
    with pytest.raises(ValueError, match="needs dense_width"):
        decoder_lm.Config(128, 64, 2, 4, 2, 16, 32, 8, 4, 2,
                          dense_layers=1)
