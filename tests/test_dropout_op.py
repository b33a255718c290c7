"""The dropout op and its keep mask (``ops/random_ops.keep_mask``): 32
threefry bits per element, counted in uint32, against an integer threshold
made on the host.  Statistics of the mask, the op's contract (``Out = X * Mask``,
the mask's value sets, ``is_test``, the ends of the rate), the backward on
the forward's own mask, replay under ``random_seed`` and the ``seed``
attribute, and what the lowered step carries: no float64 and no uint64."""

from __future__ import annotations

import collections
import re

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import executor as _executor
from paddle_tpu.fluid.backward import append_backward
from paddle_tpu.ops import random_ops
from paddle_tpu.parallel import transformer_stack

DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}
WIDE = re.compile(r"tensor<((?:[0-9?]+x)*)(f64|i64|ui64)>")


def _dropout_program(shape, dtype="float32", n_ops=1, backward=False, **kw):
    """X -> n_ops dropout ops in a row; names of every op's Out and Mask."""
    x = fluid.layers.data(name="x", shape=list(shape), dtype=dtype,
                          append_batch_size=False)
    x.stop_gradient = False
    block = fluid.default_main_program().global_block()
    h, outs, masks = x, [], []
    for _ in range(n_ops):
        h = fluid.layers.dropout(h, **kw)
        outs.append(h.name)
        masks.append(block.ops[-1].output("Mask")[0])
    if backward:
        append_backward(fluid.layers.reduce_sum(fluid.layers.cast(h, "float32")))
    return outs, masks


def _run(feed, fetch, seed=None):
    prog = fluid.default_main_program()
    if seed is not None:
        prog.random_seed = seed
    exe = fluid.Executor(fluid.CPUPlace())
    return exe, [np.asarray(v) for v in exe.run(prog, feed=feed, fetch_list=fetch)]


def _x(shape, dtype="float32", seed=0):
    rng = np.random.RandomState(seed)
    return (rng.uniform(0.5, 2.0, size=shape)).astype(DTYPES[dtype])


# --------------------------------------------------------------- statistics

@pytest.mark.parametrize("p", [0.1, 0.5, 0.9])
def test_keep_rate_is_within_five_sigma(p):
    n = 2 ** 20
    _, masks = _dropout_program((1024, 1024), dropout_prob=p)
    _, (mask,) = _run({"x": _x((1024, 1024))}, masks, seed=11)
    sigma = np.sqrt(p * (1 - p) / n)
    assert abs(mask.mean() - (1 - p)) < 5 * sigma, (mask.mean(), 1 - p, sigma)


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_no_striping_along_any_axis(axis):
    """Each slice along ``axis`` of a [64, 128, 128] draw keeps 1 - p of its
    elements: counters that repeated along an axis would show as slices all
    alike or far off."""
    p, shape = 0.1, (64, 128, 128)
    mask = np.asarray(random_ops.keep_mask(jax.random.PRNGKey(3), 1 - p, shape))
    other = tuple(a for a in range(3) if a != axis)
    rates = mask.mean(axis=other)
    n = mask.size // shape[axis]
    sigma = np.sqrt(p * (1 - p) / n)
    assert np.abs(rates - (1 - p)).max() < 5 * sigma, rates
    # and no two slices are the same draw
    flat = np.moveaxis(mask, axis, 0).reshape(shape[axis], -1)
    assert len({row.tobytes() for row in flat}) == shape[axis]


def test_every_element_has_a_counter_of_its_own():
    """Two elements that shared a threefry counter would always agree; over
    64 keys every pair of positions of a small draw disagrees somewhere."""
    shape = (3, 5, 7)
    draws = np.stack([
        np.asarray(random_ops.keep_mask(jax.random.PRNGKey(k), 0.5, shape)).ravel()
        for k in range(64)])
    agree = (draws[:, :, None] == draws[:, None, :]).all(axis=0)
    assert (agree == np.eye(draws.shape[1], dtype=bool)).all()


@pytest.mark.parametrize("shape", [(), (7,), (1,), (5, 3), (0, 4), (2, 3, 5, 7)])
def test_keep_mask_takes_any_rank_and_odd_sizes(shape):
    mask = random_ops.keep_mask(jax.random.PRNGKey(1), 0.5, shape)
    assert mask.shape == shape and mask.dtype == jnp.bool_


@pytest.mark.parametrize("keep_prob,expected", [
    (0.0, False), (-0.5, False), (1e-11, False),
    (1.0, True), (1.5, True), (1.0 - 1e-11, True)])
def test_the_ends_are_decided_on_the_host(keep_prob, expected):
    """A threshold of 0 or 2**32 is no uint32: no bits are drawn, the key
    is not even looked at."""
    mask = random_ops.keep_mask(None, keep_prob, (4, 6))
    assert mask.dtype == jnp.bool_ and bool(mask.all()) == expected
    assert bool(mask.any()) == expected


def test_threshold_is_the_stated_probability_to_32_bits():
    jaxpr = str(jax.make_jaxpr(
        lambda k: random_ops.keep_mask(k, 0.9, (4, 8)))(jax.random.PRNGKey(0)))
    assert str(round(0.9 * 2 ** 32)) + ":u32[]" in jaxpr
    assert "f64" not in jaxpr and "u64" not in jaxpr and "f32" not in jaxpr


# ------------------------------------------------------------ op contract

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("impl", ["downgrade_in_infer", "upscale_in_train"])
def test_out_is_x_times_mask_and_mask_has_two_values(impl, dtype):
    p, shape = 0.25, (32, 64)
    outs, masks = _dropout_program(shape, dtype, dropout_prob=p,
                                   dropout_implementation=impl)
    x = _x(shape, dtype)
    _, (out, mask) = _run({"x": x}, outs + masks, seed=5)
    assert out.dtype == x.dtype and mask.dtype == x.dtype
    kept = np.asarray(1.0 / (1.0 - p) if impl == "upscale_in_train" else 1.0,
                      x.dtype)
    assert set(np.unique(mask.astype(np.float32))) == {0.0, float(kept)}
    np.testing.assert_array_equal(out.astype(np.float32),
                                  (x * mask).astype(np.float32))


@pytest.mark.parametrize("impl,scale", [("downgrade_in_infer", 0.75),
                                        ("upscale_in_train", 1.0)])
def test_is_test_scales_and_draws_nothing(impl, scale):
    outs, masks = _dropout_program((8, 16), dropout_prob=0.25, is_test=True,
                                   dropout_implementation=impl)
    x = _x((8, 16))
    _, (out, mask) = _run({"x": x}, outs + masks)
    np.testing.assert_allclose(out, x * scale, rtol=1e-6)
    assert (mask == 1.0).all()


@pytest.mark.parametrize("impl", ["downgrade_in_infer", "upscale_in_train"])
@pytest.mark.parametrize("p,kept", [(0.0, True), (1.0, False)])
def test_rate_zero_keeps_all_and_rate_one_drops_all(p, kept, impl):
    outs, masks = _dropout_program((8, 16), dropout_prob=p,
                                   dropout_implementation=impl)
    x = _x((8, 16))
    _, (out, mask) = _run({"x": x}, outs + masks, seed=2)
    assert np.isfinite(mask).all()
    np.testing.assert_array_equal(out, x if kept else np.zeros_like(x))


@pytest.mark.parametrize("impl", ["downgrade_in_infer", "upscale_in_train"])
def test_backward_reuses_the_forward_mask(impl):
    """X@GRAD == Out@GRAD * Mask with the mask the forward drew, through a
    program with append_backward (Out@GRAD is ones: the loss is a sum)."""
    outs, masks = _dropout_program((16, 32), dropout_prob=0.5, backward=True,
                                   dropout_implementation=impl)
    _, (mask, xgrad) = _run({"x": _x((16, 32))}, masks + ["x@GRAD"], seed=9)
    assert 0 < (mask == 0).sum() < mask.size
    np.testing.assert_array_equal(xgrad, mask)


# ------------------------------------------------------------------ replay

def test_random_seed_replays_in_a_fresh_scope_and_ops_differ():
    shape = (64, 64)
    _, masks = _dropout_program(shape, n_ops=2, dropout_prob=0.5)
    feed = {"x": _x(shape)}
    exe, (a1, a2) = _run(feed, masks, seed=123)
    assert (a1 != a2).any(), "two dropout ops of one program drew one mask"
    (b1,) = exe.run(fluid.default_main_program(), feed=feed,
                    fetch_list=masks[:1])
    assert (np.asarray(b1) != a1).any(), "the key did not advance with the step"
    _executor._global_scope = _executor.Scope()
    _, (c1, c2) = _run(feed, masks, seed=123)
    np.testing.assert_array_equal(a1, c1)
    np.testing.assert_array_equal(a2, c2)
    _executor._global_scope = _executor.Scope()
    _, (d1, _) = _run(feed, masks, seed=124)
    assert (d1 != a1).any()


def test_seed_attribute_gives_the_same_mask_every_step():
    shape = (64, 64)
    _, masks = _dropout_program(shape, dropout_prob=0.5, seed=77)
    feed = {"x": _x(shape)}
    exe, (m1,) = _run(feed, masks, seed=1)
    (m2,) = exe.run(fluid.default_main_program(), feed=feed, fetch_list=masks)
    np.testing.assert_array_equal(m1, np.asarray(m2))
    assert 0 < (m1 == 0).sum() < m1.size


# ------------------------------------------------------------ one helper

def test_transformer_stack_dropout_goes_through_the_same_helper(monkeypatch):
    key, shape = jax.random.PRNGKey(4), (8, 16, 32)
    x = jnp.asarray(_x(shape))
    want = x * random_ops.keep_mask(key, 0.9, shape).astype(x.dtype)
    np.testing.assert_array_equal(
        np.asarray(transformer_stack._dropout(x, key, 0.1, False)),
        np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(transformer_stack._dropout(x, key, 0.1, True)),
        np.asarray(x) * 0.9, rtol=1e-6)
    calls = []
    monkeypatch.setattr(
        transformer_stack, "keep_mask",
        lambda k, q, s: calls.append((q, s)) or jnp.ones(s, jnp.bool_))
    transformer_stack._dropout(x, key, 0.1, False)
    assert calls == [(0.9, shape)]


def test_no_bernoulli_with_a_python_float_is_left():
    import pathlib

    root = pathlib.Path(random_ops.__file__).resolve().parents[1]
    hits = [f"{p.relative_to(root)}:{i + 1}"
            for p in root.rglob("*.py")
            for i, line in enumerate(p.read_text().splitlines())
            if "bernoulli(" in line]
    assert hits == [], hits


# ------------------------------------------------- what the lowered step has

def _wide_types(text):
    """{(type, dims): count} of the 64-bit tensor types in a lowered step."""
    return collections.Counter((m.group(2), m.group(1))
                               for m in WIDE.finditer(text))


def _by_type(found):
    total = collections.Counter()
    for (kind, _), n in found.items():
        total[kind] += n
    return dict(total)


def _assert_no_wide_mask(found):
    """What a dropout op may leave: the key split of the RNG thread
    (``jax.random.split`` counts its two keys in uint64: ``2xui64`` and
    scalars), and on the CPU threefry's rolled rounds (scalar i64 loop
    counters).  No f64 of any size, no 64-bit type of the mask's size."""
    assert not [k for k in found if k[0] == "f64" and k[1]], found
    assert not [k for k in found if k[0] == "ui64" and k[1] not in ("", "2x")], found


def test_lowered_dropout_op_has_no_64_bit_mask():
    """One dropout op and nothing else.  The parent lowered it to
    ``bernoulli(p: f64)`` over ``ui64`` words and ``f64`` uniforms of the
    mask's size."""
    shape = (16, 32)
    outs, masks = _dropout_program(shape, dropout_prob=0.1)
    prog = fluid.default_main_program()
    prog.random_seed = 3
    exe = fluid.Executor(fluid.CPUPlace())
    text = exe.lower_step(prog, {"x": _x(shape)}, outs + masks).as_text()
    found = _wide_types(text)
    assert "threefry2x32" in text
    assert "f64" not in _by_type(found), found
    _assert_no_wide_mask(found)
    assert all(dims == "" for (t, dims) in found if t == "i64"), found


def test_lowered_transformer_step_has_no_64_bit_mask():
    """The tiny Transformer's train step with dropout 0.1 (18 dropout ops).
    What is left of 64-bit types, for the next reader of ROADMAP D13 (the
    parent: i64 125, f64 114, ui64 95 = 334): the int64 ids and labels and
    the scalars around them (163, of which 13 ``[4,32]``, 22 ``[4,32,1]``
    and 9 ``[4,32,1,1]``; the rest scalars, threefry's loop counters on
    the CPU among them), the key split's 11 ``ui64``, and 8 scalar
    ``f64``: the NaN that ``jnp.var`` (layer_norm) writes as a Python
    float, folded by XLA.  125 of ``i64`` until PR 49: the smoothed loss
    now reads the int64 labels itself, and on this CPU path XLA's twin
    picks the label's logit with ``take_along_axis``, which keeps its
    index arithmetic in 64 bits under the package's x64 mode (+38, as in
    every decoder's step here; on the chip the kernel takes the int32
    column and none of them exists)."""
    from paddle_tpu.models import transformer

    cfg = transformer.tiny_config()
    assert cfg.dropout == 0.1
    batch, seq = 4, 32
    _, _, _, loss = transformer.build(cfg, src_len=seq, tgt_len=seq, lr=1e-3)
    prog = fluid.default_main_program()
    prog.random_seed = fluid.default_startup_program().random_seed = 7
    assert sum(op.type == "dropout" for op in prog.global_block().ops) == 18
    rng = np.random.RandomState(7)
    tgt = rng.randint(1, cfg.tgt_vocab_size, size=(batch, seq))
    feed = {"src_word": rng.randint(1, cfg.src_vocab_size,
                                    size=(batch, seq)).astype(np.int64),
            "tgt_word": tgt.astype(np.int64),
            "lbl_word": tgt[..., None].astype(np.int64)}
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    found = _wide_types(exe.lower_step(prog, feed, [loss]).as_text())
    _assert_no_wide_mask(found)
    assert _by_type(found) == {"f64": 8, "i64": 163, "ui64": 11}, found
