"""Training by diffusion over blocks (PR 65: ``models/decoder_lm.py``'s
``BlockDiffusion``, the block rule of ``sparse_attention`` on both paths,
``ops/pallas_sparse_flash.py``'s third kind, ``weighted_mean``) against the
plain float32 reference of ``chipbench/configs/sdar_30b_a3b_chat`` and
against the rule's three sentences written as a dense mask, at tiny sizes
on the CPU.  A file of its own beside the other decoder tests (no file of
``tests/`` is more than 300 s of one worker: docs/COVERAGE.md); what they
share is ``tests/decoder_reference.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observe
from paddle_tpu.fluid import layers
from paddle_tpu.models import decoder_lm
from paddle_tpu.ops import decoder_ops, registry
from paddle_tpu.ops import pallas_sparse_flash as psf
from paddle_tpu.parallel import moe

import decoder_reference
from decoder_reference import (compiled, counters, moe_weights,
                               reference_step, seeded_program)

BUILD, REF, sdar_sizes = decoder_reference.load("sdar_30b_a3b_chat")


def rule_mask(tokens, block):
    """[2L, 2L] bool over ``[x | x~]`` from the rule's three sentences."""
    at = np.arange(2 * tokens)
    clean, blk = at < tokens, (at % tokens) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return ((q_clean & k_clean & (kb <= qb))          # 1: its own block whole
            | (~q_clean & k_clean & (kb < qb))        # 2: the blocks before
            | (~q_clean & ~k_clean & (kb == qb)))     # 2: its own, noised
    # 3: a clean query and a noised key are in none


def dense_rule(q, k, v, tokens, block):
    """Grouped-query attention in dense float32 under ``rule_mask``."""
    g = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, g, axis=1)) \
        * q.shape[-1] ** -0.5
    s = jnp.where(jnp.asarray(rule_mask(tokens, block)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                      jnp.repeat(v, g, axis=1))


# -- (a) the program against the reference ----------------------------------

@pytest.mark.parametrize("flash", ["xla", "pallas"])
def test_program_equals_the_reference_and_its_adam_step(monkeypatch, flash):
    """Loss, every gradient and every parameter after one Adam step through
    ``fluid.Executor`` with ``optimizer.minimize``: four routed layers
    under the block rule, 2 x 64 positions in blocks of 4 (four tiles of 16
    a copy on the kernels' path), the noise the data pipeline's."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash == "pallas" else "0")
    monkeypatch.setattr(psf, "BLOCK", 16)
    sizes = sdar_sizes()
    assert (sizes["seq_len"], sizes["block_diffusion"]["block_length"],
            sizes["num_hidden_layers"], sizes["num_experts"],
            sizes["published"]["num_experts"]) == (64, 4, 4, 4, 8)
    built, names, weights = seeded_program(BUILD, REF, sizes)
    main, scope = fluid.default_main_program(), fluid.global_scope()
    ops = main.global_block().ops
    ruled = [op for op in ops if op.type == "sparse_attention"]
    assert [(op.attr("copy_tokens"), op.attr("block")) for op in ruled] \
        == [(64, 4)] * 4
    assert all(op.attr("period") == 64 for op in ops
               if op.type == "rotary_embedding")
    assert [op.type for op in ops].count("weighted_mean") == 1
    assert "mean" not in [op.type for op in ops]
    assert all(op.attrs.get("op_namescope") for op in ops
               if not op.type.endswith("_grad") and op.type != "adam"
               and "@GRAD" not in "".join(op.output_arg_names))
    feed = BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    assert set(feed) == {"tokens", "noised", "weights"}
    outs = fluid.Executor(fluid.TPUPlace()).run(
        main, feed=feed, fetch_list=[built["loss"]]
        + [n + "@GRAD" for n in names])
    ref_loss, ref_grads, _ = reference_step(REF, sizes, weights, feed)
    assert float(outs[0].reshape(-1)[0]) == pytest.approx(float(ref_loss),
                                                          rel=1e-5)
    assert 3.0 < float(ref_loss) < 7.0    # ln(128) = 4.85, about half masked
    for name, g, r in zip(names, outs[1:], ref_grads):
        g = np.asarray(g).reshape(r.shape)
        assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-7, name
        assert np.abs(r).max() > 0, name
    adam = compiled(REF, "optimizer_step", sizes)
    for name, w, g in zip(names, weights, outs[1:]):
        np.testing.assert_allclose(
            np.asarray(scope.get(name)).reshape(w.shape),
            adam(w, jnp.asarray(g).reshape(w.shape)), atol=2e-6,
            err_msg=name)
    per = 1 if flash == "pallas" else 2
    assert counters("ops.sparse_attention.calls") == {
        f'ops.sparse_attention.calls{{block="4",path="{flash}",seq="128",'
        'topk="0"}': 4 * per}
    assert not counters("ops.sparse_attention.declined")
    tiles = counters("ops.sparse_attention.tiles")
    if flash == "pallas":       # four tiles a copy: 12 interior, 12 edge
        assert tiles == {
            f'ops.sparse_attention.tiles{{kernel="blockdiff_flash_{k}",'
            f'kind="{kind}"}}': 4 * 12
            for k in ("fwd", "dq", "dkv") for kind in ("interior", "edge")}
    else:
        assert not tiles
    # the share of the tokens that bear loss, from the feed it was given
    (_, _, _, gauges), = observe.step_gauges(wait=True)[-1:]
    live = gauges['ops.weighted_mean.live_rows{scope="head"}']
    assert live == float((feed["weights"] > 0).sum())
    assert gauges['ops.weighted_mean.rows{scope="head"}'] == 2 * 64
    assert 0.3 < live / 128 < 0.7


def test_the_fp8_control_misses_what_float32_meets():
    """The reference with float8 contraction inputs in the program's place:
    outside the tiny limits, by the gradients."""
    from chipbench import check

    sizes = sdar_sizes()
    weights = REF.init_params(7, sizes)
    feed = BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    numbers = check.control(REF, sizes, weights, feed, jnp.float8_e4m3fn)
    assert check.decide(numbers, sizes["limits"]) is False
    assert numbers["grad_rel"] > 1.5 * sizes["limits"]["grad_rel"]


def test_the_noise_is_a_level_a_block_and_weighs_by_it():
    """``decoder_lm.noise``: every block has ONE level, a masked token reads
    the mask id and weighs 1 / level, any other keeps its id and weighs 0;
    about half the tokens are masked, and E[weight] = 1."""
    cfg = BUILD.config_of(sdar_sizes())
    tokens = np.random.RandomState(0).randint(0, 127, size=(8, 4096))
    noised, weights = decoder_lm.noise(cfg, tokens, np.random.RandomState(1))
    masked = noised == 127
    assert np.array_equal(masked, weights > 0)
    assert np.array_equal(noised[~masked], tokens[~masked])
    by_block = weights.reshape(8, -1, 4)
    for b in by_block.reshape(-1, 4)[:200]:
        assert len(set(b[b > 0])) <= 1       # one level a block
    assert weights.max() <= 1e3 + 1 and weights[masked].min() >= 1.0
    assert 0.48 < masked.mean() < 0.52
    assert 0.9 < weights.mean() < 1.1
    with pytest.raises(ValueError, match="plain attention"):
        decoder_lm.Config(128, 64, 2, 4, 2, 16, 32, 8, 4, 2, window=8,
                          block_diffusion=(4, 127))
    with pytest.raises(ValueError, match="an id of the vocabulary"):
        decoder_lm.Config(128, 64, 2, 4, 2, 16, 32, 8, 4, 2,
                          block_diffusion=(4, 128))
    with pytest.raises(ValueError, match="no whole number of blocks"):
        decoder_lm.forward(decoder_lm.Config(
            128, 64, 2, 4, 2, 16, 32, 8, 4, 2, block_diffusion=(4, 127)), 30)


# -- (b) the mask: twin, kernels and the walk's table ------------------------

@pytest.mark.parametrize("tokens,block,tile", [
    (64, 4, 16), (96, 4, 32), (64, 16, 16), (40, 4, 16), (64, 16, 32),
    (24, 4, 16), (48, 16, 512)])
def test_keep_sets_of_both_paths_are_the_three_sentences(monkeypatch, tokens,
                                                         block, tile):
    """With q = 0 every kept key weighs alike, and with v the identity a
    row of the output is its keep-set over its size: the twin's and the
    interpreted kernels' against the dense mask, for a copy that is a whole
    number of tiles (64 in 16s), one that is not (96, 40 and 24: the tile
    halves until it divides) and one that is a single tile; blocks of 4 and
    of 16."""
    monkeypatch.setattr(psf, "BLOCK", tile)
    t = 2 * tokens
    want = rule_mask(tokens, block)
    q = jnp.zeros((1, 2, t, t), jnp.float32)
    k = jnp.ones((1, 1, t, t), jnp.float32)
    v = jnp.eye(t, dtype=jnp.float32)[None, None]
    twin = jax.jit(lambda *a: decoder_ops.blocked_attention(
        *a, None, 1.0, block=tile, rule=(tokens, block)))(q, k, v)
    share = want / want.sum(1, keepdims=True)
    np.testing.assert_allclose(twin[0, 0], share, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(twin[0, 1]) > 0, want)
    assert psf.supported(q, k, None, 0, v, (tokens, block)) == ""
    kern = jax.jit(lambda *a: psf.sparse_flash_attention(
        *a, None, 1.0, True, 0, (tokens, block)))(q, k, v)
    np.testing.assert_allclose(kern[0, 1], share, atol=1e-6)
    # a clean query's own block whole, a noised one's clean block not
    assert want[0, block - 1] and not want[tokens, 0]
    assert want[tokens, tokens + block - 1] and not want[0, tokens]


def test_the_walk_at_the_cells_size_is_80_tiles_56_interior_24_edge():
    """4,096 tokens a copy in tiles of 512: 8 tiles a copy; clean tile j
    walks j interior tiles and its own, noised tile j the same j, the clean
    tile at its place and its own; causal attention over the 8,192
    positions walks 136.  dK/dV walks the transpose, a group's query heads
    in turn under each key tile."""
    assert psf.rule_tiles(4096) == (512, 8)
    walk = psf.rule_walk(8)
    assert len(walk) == 80 and psf.rule_tile_counts(4096) == (56, 24)
    assert sum(psf.tile_counts(8192)) == 136
    kinds = [m for _, _, m in walk]
    assert (kinds.count(psf.EDGE_LE), kinds.count(psf.EDGE_LT),
            kinds.count(psf.EDGE_EQ)) == (8, 8, 8)
    assert [(q, k) for q, k, m in walk if m == psf.EDGE_EQ] \
        == [(8 + j, 8 + j) for j in range(8)]
    assert all(k < 8 and k < q % 8 for q, k, m in walk if m == psf.INTERIOR)
    # every live tile holds a needed pair, and every needed pair is in one
    mask = rule_mask(4096, 4).reshape(16, 512, 16, 512).any((1, 3))
    assert {(q, k) for q, k, _ in walk} == set(zip(*np.nonzero(mask)))
    assert int(rule_mask(4096, 4).sum()) == 16_793_600 <= 80 * 512 * 512
    table = np.asarray(psf._walk_table(8))
    assert table.shape == (5, 80) and table.dtype == np.int32
    first = table[psf.FLAGS] & psf.FIRST
    assert first.sum() == 16 == (table[psf.FLAGS] & psf.LAST != 0).sum()
    assert np.array_equal(np.nonzero(first)[0], np.nonzero(
        np.r_[1, np.diff(table[psf.RESIDENT])])[0])
    by_key = np.asarray(psf._walk_table(8, group=8, by_key=True))
    assert by_key.shape == (5, 640)
    assert np.all(np.diff(by_key[psf.RESIDENT]) >= 0)       # key-major
    assert sorted(zip(by_key[psf.STREAMED][by_key[psf.MEMBER] == 3],
                      by_key[psf.RESIDENT][by_key[psf.MEMBER] == 3])) \
        == sorted((q, k) for q, k, _ in walk)
    # a noised key tile is read by its own query tile alone, a member each
    assert (by_key[psf.RESIDENT] == 15).sum() == 8


@pytest.mark.parametrize("why,tokens,block,over", [
    ("block", 64, 6, {}), ("block", 64, 32, {}), ("rule", 60, 4, {}),
    ("ragged", 36, 4, {}), ("rule", 64, 4, {"window": 8})])
def test_operands_the_rules_kernels_do_not_take_are_declined(
        monkeypatch, why, tokens, block, over):
    """A block that is no power of two or that a tile of the copy
    straddles, positions that are not two copies, a copy that is no whole
    number of sublanes, a window beside the rule."""
    monkeypatch.setattr(psf, "BLOCK", 16)
    q = jnp.zeros((1, 2, 128 if tokens != 36 else 72, 16), jnp.float32)
    assert psf.supported(q, q, None, over.get("window", 0), q,
                         (tokens, block)) == why


# -- (c) the kernels, interpreted, against the twin --------------------------

@pytest.mark.parametrize("hq,hkv,d,tokens,block", [
    (8, 1, 128, 64, 4), (4, 4, 128, 64, 4), (8, 2, 64, 96, 16),
    (16, 2, 128, 32, 4)])
def test_rule_kernels_equal_the_twin_gradients_too(monkeypatch, hq, hkv, d,
                                                   tokens, block):
    """Output, dQ, dK and dV at a group of 8 and of 1 (and of 4 at half a
    lane row): against the twin, and the output against the dense mask."""
    monkeypatch.setattr(psf, "BLOCK", 16)
    rule = (tokens, block)
    rng = np.random.RandomState(hq + d)
    q, k, v = (jnp.asarray(rng.randn(1, h, 2 * tokens, d), jnp.float32)
               for h in (hq, hkv, hkv))
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def kernel(q, k, v):
        return psf.sparse_flash_attention(q, k, v, None, None, True, 0, rule)

    def blocked(q, k, v):
        return decoder_ops.blocked_attention(q, k, v, None, d ** -0.5,
                                             block=16, rule=rule)

    want = jax.jit(blocked)(q, k, v)
    np.testing.assert_allclose(jax.jit(kernel)(q, k, v), want, atol=2e-5)
    np.testing.assert_allclose(
        want, jax.jit(lambda *a: dense_rule(*a, *rule))(q, k, v), atol=2e-5)
    for g, r in zip(
            jax.jit(jax.grad(lambda *a: jnp.sum(kernel(*a) * w),
                             (0, 1, 2)))(q, k, v),
            jax.jit(jax.grad(lambda *a: jnp.sum(blocked(*a) * w),
                             (0, 1, 2)))(q, k, v)):
        np.testing.assert_allclose(g, r, atol=2e-4)


@pytest.mark.parametrize("flash", [False, True])
def test_the_op_under_the_rule_on_both_paths(monkeypatch, flash):
    """Through the executor and the grad op, against dense float32."""
    monkeypatch.setattr(psf, "BLOCK", 16)
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash else "0")
    b, hq, hkv, tokens, d = 2, 4, 2, 32, 16
    q = layers.data(name="q", shape=[hq, 2 * tokens, d], dtype="float32")
    k = layers.data(name="k", shape=[hkv, 2 * tokens, d], dtype="float32")
    v = layers.data(name="v", shape=[hkv, 2 * tokens, d], dtype="float32")
    q.stop_gradient = k.stop_gradient = v.stop_gradient = False
    out = layers.sparse_attention(q, k, v, block_rule=(tokens, 4))
    w = layers.assign(np.cos(np.arange(d, dtype="float32")))
    loss = layers.reduce_sum(layers.elementwise_mul(out, w))
    fluid.backward.append_backward(loss)
    rng = np.random.RandomState(4)
    feed = {n: rng.randn(b, h, 2 * tokens, d).astype("float32")
            for n, h in (("q", hq), ("k", hkv), ("v", hkv))}
    got = fluid.Executor(fluid.TPUPlace()).run(
        feed=feed, fetch_list=[out, "q@GRAD", "k@GRAD", "v@GRAD"])
    args = [jnp.asarray(feed[n]) for n in "qkv"]
    np.testing.assert_allclose(
        got[0], jax.jit(lambda *a: dense_rule(*a, tokens, 4))(*args),
        atol=2e-5)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(dense_rule(*a, tokens, 4)
                           * jnp.cos(jnp.arange(d))), (0, 1, 2)))(*args)
    for g, r in zip(got[1:], want):
        np.testing.assert_allclose(g, r, atol=2e-4)
    path = "pallas" if flash else "xla"
    assert any(f'path="{path}"' in key and 'block="4"' in key
               for key in counters("ops.sparse_attention.calls"))


def test_infer_rules_of_the_rule_the_period_and_the_weighted_mean():
    from paddle_tpu.ops.registry import get_infer_rule

    class Op:
        def __init__(self, **attrs):
            self.attrs, self.inputs, self.type = attrs, {}, "t"

        def attr(self, name, default=None):
            return self.attrs.get(name, default)

    q, kv = ((2, 4, 64, 16), "float32"), ((2, 2, 64, 16), "float32")
    ins = {"Q": [q], "K": [kv], "V": [kv]}
    assert get_infer_rule("sparse_attention")(
        Op(copy_tokens=32, block=4), ins)["Out"] == [q]
    for bad in (dict(copy_tokens=30, block=4), dict(copy_tokens=32, block=5),
                dict(copy_tokens=32, block=4, window=8)):
        with pytest.raises(registry.InferMismatch, match="the block rule"):
            get_infer_rule("sparse_attention")(Op(**bad), ins)
    x = ((2, 64, 4, 16), "float32")
    assert get_infer_rule("rotary_embedding")(
        Op(period=32), {"X": [x]}) == {"Out": [x]}
    with pytest.raises(registry.InferMismatch, match="period"):
        get_infer_rule("rotary_embedding")(Op(period=-1), {"X": [x]})
    rows, weight = ((2, 64, 1), "float32"), ((2, 64), "float32")
    assert get_infer_rule("weighted_mean")(
        Op(), {"X": [rows], "Weight": [weight]}) == {
            "Out": [((1,), "float32")]}
    with pytest.raises(registry.InferMismatch, match="not a weight"):
        get_infer_rule("weighted_mean")(
            Op(), {"X": [rows], "Weight": [((2, 32), "float32")]})


def test_rotary_under_a_period_turns_both_copies_alike():
    x = jnp.asarray(np.random.RandomState(0).randn(1, 24, 2, 8), jnp.float32)
    once = decoder_ops.rotary(x[:, :12], 1e4)
    both = decoder_ops.rotary(jnp.concatenate([x[:, :12], x[:, :12]], 1),
                              1e4, period=12)
    np.testing.assert_allclose(both[:, :12], once, atol=1e-6)
    np.testing.assert_allclose(both[:, 12:], once, atol=1e-6)
    assert not np.allclose(decoder_ops.rotary(x, 1e4)[:, 12:],
                           decoder_ops.rotary(x, 1e4, period=12)[:, 12:])


# -- (d) what makes it diffusion over blocks ---------------------------------

@pytest.fixture
def logits_of():
    """(tokens, noised) -> (logits [L, V] of the noised rows, the trunk's
    last stream [2L, hidden]) of a two-layer model at seeded weights."""
    cfg = decoder_lm.Config(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, expert_width=16, num_routed=4,
        experts_held=4, experts_per_token=2,
        block_diffusion=decoder_lm.BlockDiffusion(block=4, mask_id=63))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, _, _, logits = decoder_lm.forward(cfg, 24)
        head = [op for op in main.global_block().ops if op.type == "slice"]
        assert len(head) == 1
        stream = head[0].input_arg_names[0]
    exe = fluid.Executor(fluid.TPUPlace())
    startup.random_seed = 11
    exe.run(startup)

    def run(tokens, noised):
        out, h = exe.run(main, feed={
            "tokens": tokens[None], "noised": noised[None],
            "weights": np.ones((1, 24), "float32")},
            fetch_list=[logits, stream])
        return np.asarray(out)[0], np.asarray(h)[0]

    return run


def test_what_a_noised_block_sees_and_what_it_does_not(logits_of):
    """Block 2 (positions 8-11) of six blocks of 4.  Its logits do not
    change with any token of a LATER block in either copy, with the noised
    copy of ANOTHER block, or with a clean token of its OWN block; they do
    change with a clean token of an earlier block and with a noised token
    of its own.  The clean rows never read a noised token, and a clean row
    reads the LATER tokens of its own block: the clean copy is causal over
    blocks, not over tokens."""
    rng = np.random.RandomState(5)
    tokens = rng.randint(0, 63, size=24).astype("int64")
    noised = np.where(rng.rand(24) < 0.5, 63, tokens).astype("int64")
    noised[8], noised[9] = 63, tokens[9]
    base, stream = logits_of(tokens, noised)
    mine = slice(8, 12)

    def changed(at, copy):
        t, n = tokens.copy(), noised.copy()
        (t if copy == "clean" else n)[at] = (
            (t if copy == "clean" else n)[at] + 7) % 63
        got, h = logits_of(t, n)
        return np.abs(got - base).max(1), np.abs(h - stream).max(1)

    for at in (12, 17, 23):                     # a later block, either copy
        for copy in ("clean", "noised"):
            assert changed(at, copy)[0][mine].max() == 0, (at, copy)
    for at in (0, 5, 7):                        # another block's noised copy
        assert changed(at, "noised")[0][mine].max() == 0, at
    for at in (8, 9, 11):                       # its own block's CLEAN copy
        assert changed(at, "clean")[0][mine].max() == 0, at
    for at in (0, 5, 7):                        # an earlier block, clean
        assert changed(at, "clean")[0][mine].min() > 1e-6, at
    for at in (8, 9, 11):                       # its own block, noised
        assert changed(at, "noised")[0][mine].min() > 1e-6, at
    # the head reads the noised rows: its own row's noised token moves it
    assert changed(10, "noised")[0][10] > 1e-6
    # the clean rows (0..23 of the stream): no noised token reaches them,
    for at in (0, 9, 23):
        assert changed(at, "noised")[1][:24].max() == 0, at
    # a clean row reads the later tokens of its own block and no later one
    rows = changed(11, "clean")[1][:24]
    assert rows[8:12].min() > 1e-6 and rows[:8].max() == 0
    assert changed(12, "clean")[1][8:12].max() == 0


# -- (e) the share -----------------------------------------------------------

@pytest.mark.parametrize("routed,held", [(8, 4), (128, 16)])
def test_the_shares_add_up_to_the_uncut_layer(routed, held):
    """A softmax router ``routed`` wide, the top of it renormalized,
    ``held`` experts on each of ``routed / held`` chips (``tiny``: two
    shares of 4 of 8 with 2 a token; the cell: eight of 16 of 128 with 8):
    the parts add up to what the reference gives for the whole layer."""
    k = 2 if routed == 8 else 8
    x, wr, w1, w3, w2 = moe_weights(np.random.RandomState(1), 48, 16, 8,
                                    routed)
    whole = REF.moe_layer(x, wr, w1, w3, w2, k, 0)
    total = 0.0
    for off in range(0, routed, held):
        part = moe.routed_experts(
            x, wr, w1[off:off + held], w3[off:off + held],
            w2[off:off + held], top_k=k, expert_offset=off)
        np.testing.assert_allclose(part, REF.moe_layer(
            x, wr, w1[off:off + held], w3[off:off + held],
            w2[off:off + held], k, off), atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert float(jnp.abs(whole).max()) > 0.1
