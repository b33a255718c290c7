"""Transformer model tests (driver metric #2; ref transformer coverage:
test_parallel_executor_transformer.py + tests/unittests/transformer_model.py)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.models import transformer


def _feed(rng, cfg, batch, src_len, tgt_len):
    return {
        "src_word": rng.randint(1, cfg.src_vocab_size,
                                size=(batch, src_len)).astype(np.int64),
        "tgt_word": rng.randint(1, cfg.tgt_vocab_size,
                                size=(batch, tgt_len)).astype(np.int64),
        "lbl_word": rng.randint(1, cfg.tgt_vocab_size,
                                size=(batch, tgt_len, 1)).astype(np.int64),
    }


def test_transformer_trains():
    cfg = transformer.tiny_config()
    cfg.dropout = 0.0  # deterministic overfit check
    src, tgt, lbl, loss = transformer.build(cfg, src_len=12, tgt_len=12,
                                            lr=3e-3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(7)
    feed = _feed(rng, cfg, batch=4, src_len=12, tgt_len=12)
    losses = []
    for _ in range(15):
        (l,) = exe.run(fluid.default_main_program(), feed=feed,
                       fetch_list=[loss])
        losses.append(float(np.asarray(l).reshape(-1)[0]))
    assert np.isfinite(losses).all()
    # single repeated batch: must overfit decisively
    assert losses[-1] < losses[0] - 0.5, losses


@pytest.mark.parametrize("fused", ["0", "1"])
def test_transformer_padding_masks_loss(monkeypatch, fused):
    """Pad targets (id 0) must not contribute to the loss: the masked loss
    must equal the label-smoothed CE recomputed in numpy over only the
    non-pad positions of the fetched logits.  The program's loss op reads
    the labels and ``smooth_epsilon`` (below); the formula is the
    distribution's, through XLA's lowering and through the kernels."""
    monkeypatch.setenv("PADDLE_TPU_FUSED", fused)
    cfg = transformer.tiny_config()
    cfg.dropout = 0.0
    src_w, tgt_w, lbl_w, avg_cost, logits = transformer.forward(
        cfg, src_len=8, tgt_len=8)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(3)
    feed = _feed(rng, cfg, batch=2, src_len=8, tgt_len=8)
    feed["lbl_word"][:, 4:, :] = 0  # pad out the tail
    l_half, lg = exe.run(fluid.default_main_program(), feed=feed,
                         fetch_list=[avg_cost, logits])
    lg = np.asarray(lg, np.float64)
    eps, V = cfg.label_smooth, cfg.tgt_vocab_size
    logp = lg - lg.max(-1, keepdims=True)
    logp = logp - np.log(np.exp(logp).sum(-1, keepdims=True))
    lbl = feed["lbl_word"][..., 0]
    # layers.label_smooth: (1-eps)*hot + eps/V
    soft = np.full(lg.shape, eps / V)
    np.put_along_axis(soft, lbl[..., None], 1.0 - eps + eps / V, axis=-1)
    per_tok = -(soft * logp).sum(-1)
    expected = per_tok[lbl != 0].sum() / (lbl != 0).sum()
    assert np.isclose(float(np.asarray(l_half).reshape(-1)[0]), expected,
                      rtol=1e-4), (l_half, expected)


def _loss_ops(program):
    ops = program.global_block().ops
    return ([op for op in ops if op.type == "softmax_with_cross_entropy"],
            [op for op in ops if op.type == "softmax_with_cross_entropy_grad"])


def test_label_smoothing_is_an_attribute_of_the_loss_op():
    """The Transformer's head is written the reference's way (one_hot ->
    label_smooth -> soft-label loss); what the program holds is the loss
    over the int labels with ``smooth_epsilon``, on the op and on its grad
    op, and neither reads the ``[.., V]`` distribution.  The one_hot and
    the scale stay in the block with no reader and no grad op."""
    cfg = transformer.tiny_config()
    assert cfg.label_smooth == 0.1
    transformer.build(cfg, src_len=8, tgt_len=8)
    block = fluid.default_main_program().global_block()
    (fwd,), (bwd,) = _loss_ops(fluid.default_main_program())
    (hot,) = [op for op in block.ops if op.type == "one_hot"]
    (smooth,) = [op for op in block.ops if op.type == "scale"
                 and op.input("X") == hot.output("Out")]
    dense = set(hot.output_arg_names + smooth.output_arg_names)
    for op in (fwd, bwd):
        assert op.attrs["smooth_epsilon"] == pytest.approx(0.1, rel=1e-12)
        assert op.attrs["soft_label"] is False
        assert op.input("Label") == ["lbl_word"]
        assert block.var("lbl_word").dtype == "int64"
        assert not dense & set(op.input_arg_names)
        wide = {n for n in op.input_arg_names
                if n and block.var(n).shape[-1] == cfg.tgt_vocab_size}
        assert wide <= set(fwd.input("Logits") + fwd.output("Softmax"))
    readers = [op.type for op in block.ops
               if dense & set(op.input_arg_names)]
    assert readers == ["scale"]            # the smoothing of the one-hot
    assert not [op for op in block.ops
                if op.type in ("one_hot_grad", "scale_grad")
                and dense & set(op.input_arg_names)]


def _smoothed_loss(kind, logits, ids, width, eps=0.1):
    """A loss over ``label_smooth(one_hot(ids))``, written five ways."""
    hot = layers.one_hot(ids, width)
    if kind == "uniform":
        label = layers.label_smooth(hot, epsilon=eps)
    elif kind == "prior_dist":
        prior = layers.fill_constant([1, width], "float32", 1.0 / width)
        label = layers.label_smooth(hot, prior_dist=prior, epsilon=eps)
    elif kind == "fed":
        label = layers.data(name="dist", shape=[width], dtype="float32")
    elif kind == "other_bias":
        label = layers.scale(hot, scale=1.0 - eps, bias=eps / (width - 1))
    elif kind == "other_width":
        label = layers.label_smooth(layers.one_hot(ids, width + 1),
                                    epsilon=eps)
    return layers.softmax_with_cross_entropy(logits, label, soft_label=True)


@pytest.mark.parametrize("kind", ["uniform", "prior_dist", "fed",
                                  "other_bias", "other_width"])
def test_only_uniform_smoothing_of_a_one_hot_leaves_the_soft_path(kind):
    """``layers.softmax_with_cross_entropy(soft_label=True)`` looks at what
    made its label.  ``label_smooth`` of a ``one_hot`` as wide as the logits
    becomes hard labels with ``smooth_epsilon``; a ``prior_dist``, a fed
    distribution, a ``scale`` whose bias is not ``eps / V`` and a one-hot
    of another depth keep the soft path."""
    width = 24
    x = layers.data(name="x", shape=[width], dtype="float32")
    ids = layers.data(name="ids", shape=[1], dtype="int64")
    _smoothed_loss(kind, x, ids, width)
    (op,), _ = _loss_ops(fluid.default_main_program())
    if kind == "uniform":
        assert op.attrs["soft_label"] is False
        assert op.attrs["smooth_epsilon"] == pytest.approx(0.1, rel=1e-12)
        assert op.input("Label") == ["ids"]
    else:
        assert op.attrs["soft_label"] is True
        assert "smooth_epsilon" not in op.attrs
        assert op.input("Label") != ["ids"]


@pytest.mark.parametrize("fused", ["0", "1"])
def test_smoothed_hard_labels_equal_the_distribution(monkeypatch, fused):
    """The same numbers either way: the loss and the logits' gradient of
    the program that reads labels and ``smooth_epsilon`` equal those of the
    program that is fed the uniform prior and so keeps the dense
    distribution (XLA's lowering, and the kernels interpreted)."""
    monkeypatch.setenv("PADDLE_TPU_FUSED", fused)
    width, rng = 24, np.random.RandomState(5)
    feed = {"x": (3.0 * rng.normal(size=(6, width))).astype("float32"),
            "ids": rng.randint(0, width, size=(6, 1)).astype("int64")}
    got = {}
    for kind in ("uniform", "prior_dist"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="x", shape=[width], dtype="float32")
            x.stop_gradient = False
            ids = layers.data(name="ids", shape=[1], dtype="int64")
            loss = _smoothed_loss(kind, x, ids, width)
            w = layers.assign(np.linspace(0.5, 1.5, 6, dtype="float32")
                              .reshape(6, 1))
            total = layers.reduce_sum(layers.elementwise_mul(loss, w))
            (dx,) = fluid.backward.calc_gradient([total], [x])
        (op,), _ = _loss_ops(main)
        assert op.attrs["soft_label"] is (kind == "prior_dist")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got[kind] = [np.asarray(v) for v in
                     exe.run(main, feed=feed, fetch_list=[loss, dx])]
    for a, b in zip(got["uniform"], got["prior_dist"]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_a_lowering_counts_the_form_its_loss_took(monkeypatch):
    """``ops.fused.softmax_xent{target=...}``: one lowering of the
    Transformer's training step reads ``smoothed`` ONCE (the op; its grad
    op takes the forward's Lse and traces no forward) and nothing else; a
    decoder's reads ``hard``.  The lowered step holds the forward kernel
    once, under the op, and the backward kernel once, under the grad op,
    which is counted as ``from_lse``."""
    from lowered_kernels import loss_kernel_calls
    from paddle_tpu.models import decoder_lm

    monkeypatch.setenv("PADDLE_TPU_FUSED", "1")
    names = ("ops.fused.softmax_xent", "ops.softmax_xent.grad_calls")

    def grown_by(lower):
        before = dict(fluid.profiler.counters())
        calls = loss_kernel_calls(lower())
        return calls, {k: v - before.get(k, 0)
                       for k, v in fluid.profiler.counters().items()
                       if k.startswith(names) and v != before.get(k, 0)}

    cfg = transformer.tiny_config()
    rng = np.random.RandomState(2)

    def lower_transformer():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss = transformer.build(cfg, src_len=8, tgt_len=8)[3]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return exe.lower_step(main, _feed(rng, cfg, 2, 8, 8), [loss])

    def lower_decoder():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss = decoder_lm.build(decoder_lm.tiny_config(), seq_len=16)[2]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        tokens = rng.randint(0, 128, size=(2, 16)).astype("int64")
        return exe.lower_step(main, {"tokens": tokens, "labels": tokens},
                              [loss])

    one_of_each = [
        ("softmax_with_cross_entropy", "_xent_partial_kernel"),
        ("softmax_with_cross_entropy_grad", "_xent_bwd_kernel")]
    assert grown_by(lower_transformer) == (one_of_each, {
        'ops.fused.softmax_xent{target="smoothed"}': 1,
        'ops.softmax_xent.grad_calls{path="from_lse"}': 1})
    assert grown_by(lower_decoder) == (one_of_each, {
        'ops.fused.softmax_xent{target="hard"}': 1,
        'ops.softmax_xent.grad_calls{path="from_lse"}': 1})


def test_transformer_causal_mask():
    """Future target tokens must not influence earlier positions' logits."""
    cfg = transformer.tiny_config()
    cfg.dropout = 0.0
    src_w, tgt_w, lbl_w, avg_cost, logits = transformer.forward(
        cfg, src_len=6, tgt_len=6)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    feed = _feed(rng, cfg, batch=1, src_len=6, tgt_len=6)
    (lg1,) = exe.run(fluid.default_main_program(), feed=feed,
                     fetch_list=[logits])
    feed2 = {k: v.copy() for k, v in feed.items()}
    feed2["tgt_word"][0, 4:] = (feed2["tgt_word"][0, 4:] % 900) + 1  # perturb tail
    (lg2,) = exe.run(fluid.default_main_program(), feed=feed2,
                     fetch_list=[logits])
    lg1, lg2 = np.asarray(lg1), np.asarray(lg2)
    # positions 0..3 attend only to themselves and earlier -> unchanged
    np.testing.assert_allclose(lg1[0, :4], lg2[0, :4], rtol=1e-4, atol=1e-4)
    assert not np.allclose(lg1[0, 4:], lg2[0, 4:], atol=1e-4)


def test_moe_transformer_trains_and_shards():
    """Switch-style MoE transformer (moe_config): trains single-device and
    its expert weights shard over an "ep" mesh axis with Adam moments
    following (expert parallelism on the flagship model family)."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    import paddle_tpu.fluid.executor as _executor
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.spmd import ShardedTrainStep

    fluid.default_main_program().random_seed = 17
    fluid.default_startup_program().random_seed = 17
    cfg = transformer.moe_config()
    cfg.dropout = 0.0
    src, tgt, lbl, loss = transformer.build(cfg, src_len=8, tgt_len=8,
                                            lr=2e-3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(7)
    losses = []
    for _ in range(3):
        feed = {
            "src_word": rng.randint(1, cfg.src_vocab_size,
                                    size=(8, 8)).astype(np.int64),
            "tgt_word": rng.randint(1, cfg.tgt_vocab_size,
                                    size=(8, 8)).astype(np.int64),
            "lbl_word": rng.randint(1, cfg.tgt_vocab_size,
                                    size=(8, 8, 1)).astype(np.int64)}
        (l,) = exe.run(fluid.default_main_program(), feed=feed,
                       fetch_list=[loss])
        losses.append(float(np.asarray(l).reshape(-1)[0]))
    assert np.isfinite(losses).all()

    mesh = make_mesh(8, tp=4, axis_names=("dp", "ep"))
    step = ShardedTrainStep(fluid.default_main_program(),
                            ["src_word", "tgt_word", "lbl_word"],
                            [loss.name], mesh)
    ep_sharded = [n for n, s in step.specs.items()
                  if s is not None and "ep" in tuple(s)]
    # 2 layers x (enc+dec) x 4 expert params, plus Adam moments
    assert len(ep_sharded) >= 16, ep_sharded
    state = step.place_state()
    feed = step.place_feed({
        "src_word": rng.randint(1, cfg.src_vocab_size,
                                size=(8, 8)).astype(np.int64),
        "tgt_word": rng.randint(1, cfg.tgt_vocab_size,
                                size=(8, 8)).astype(np.int64),
        "lbl_word": rng.randint(1, cfg.tgt_vocab_size,
                                size=(8, 8, 1)).astype(np.int64)})
    fetches, _ = step(feed, state)
    assert np.isfinite(float(np.asarray(fetches[0]).reshape(-1)[0]))
