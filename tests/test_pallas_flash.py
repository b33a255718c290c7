"""Pallas flash-attention kernels (ops/pallas_flash.py) — forward AND
backward — run in interpret mode on the CPU mesh (the same kernel code
compiles for the chip: tests/test_tpu_compile.py).  The backward kernels are
verified against BOTH the jnp recompute reference (flash_bwd_reference)
and full_attention autodiff."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas_flash import (flash_attention,
                                         flash_bwd_reference)
from paddle_tpu.parallel.ring_attention import full_attention


def _qkv(rng, b=2, h=2, t=64, d=16):
    mk = lambda: jnp.asarray(rng.normal(size=(b, h, t, d))
                             .astype(np.float32))
    return mk(), mk(), mk()


def _key_bias(rng, b, t):
    """Additive key-padding bias: last positions masked for some rows."""
    bias = np.zeros((b, 1, 1, t), np.float32)
    bias[:, :, :, -3:] = -1e9
    return jnp.asarray(bias)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_full(causal):
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng)
    ref = np.asarray(full_attention(q, k, v, causal))
    out = np.asarray(flash_attention(q, k, v, causal=causal,
                                     block_q=32, block_k=32))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_flash_bias_matches_full():
    rng = np.random.RandomState(4)
    q, k, v = _qkv(rng, t=32)
    bias = _key_bias(rng, 2, 32)
    ref = np.asarray(full_attention(q, k, v, False, bias=bias))
    out = np.asarray(flash_attention(q, k, v, bias, block_q=16,
                                     block_k=16))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_flash_uneven_blocks():
    """T not divisible by the requested block: the launcher halves the
    block size until it divides."""
    rng = np.random.RandomState(1)
    q, k, v = _qkv(rng, t=48)
    ref = np.asarray(full_attention(q, k, v, True))
    out = np.asarray(flash_attention(q, k, v, causal=True, block_q=32,
                                     block_k=32))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,with_bias", [(False, False),
                                              (True, False),
                                              (False, True),
                                              (True, True)])
def test_flash_pallas_backward_matches_references(causal, with_bias):
    """The Pallas dQ and dK/dV kernels against (a) the jnp recompute
    formulation and (b) full_attention autodiff — multi-block so the
    scratch accumulator carry across grid steps is exercised."""
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng, t=32)
    bias = _key_bias(rng, 2, 32) if with_bias else None
    do = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

    _, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, bias, causal=causal, block_q=16, block_k=16), q, k, v)
    dq, dk, dv = vjp(do)

    rq, rk, rv = flash_bwd_reference(q, k, v, do, bias=bias,
                                     causal=causal)
    _, vjp_full = jax.vjp(lambda q, k, v: full_attention(
        q, k, v, causal, bias=bias), q, k, v)
    fq, fk, fv = vjp_full(do)
    for got, ref_j, ref_f, n in ((dq, rq, fq, "dq"), (dk, rk, fk, "dk"),
                                 (dv, rv, fv, "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_j),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"{n} vs jnp recompute")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_f),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"{n} vs full autodiff")


def test_flash_bias_backward_gradcheck_and_no_grad_contract():
    """ISSUE 12 satellite: interpret-mode gradcheck of flash attention
    with key-padding bias + causal against the ring_attention
    .full_attention reference, differentiating ALL FOUR operands — and
    the bias-no-grad contract as an executable assertion (it was only a
    comment): the bias cotangent is exactly zero (the bias derives from
    input padding and is never trained), while q/k/v grads still match
    the reference computed WITH the bias on the path."""
    rng = np.random.RandomState(7)
    q, k, v = _qkv(rng, t=32)
    bias = _key_bias(rng, 2, 32)
    do = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

    _, vjp = jax.vjp(lambda q, k, v, b: flash_attention(
        q, k, v, b, causal=True, block_q=16, block_k=16), q, k, v, bias)
    dq, dk, dv, dbias = vjp(do)

    # the no-grad contract, executable: exact zeros, right shape/dtype
    assert dbias.shape == bias.shape and dbias.dtype == bias.dtype
    np.testing.assert_array_equal(np.asarray(dbias),
                                  np.zeros_like(np.asarray(bias)))

    # gradcheck vs full_attention autodiff (bias and causal both live)
    _, vjp_full = jax.vjp(lambda q, k, v: full_attention(
        q, k, v, True, bias=bias), q, k, v)
    fq, fk, fv = vjp_full(do)
    for got, ref, n in ((dq, fq, "dq"), (dk, fk, "dk"), (dv, fv, "dv")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"{n} vs full autodiff")


def test_flash_backward_is_pallas():
    """The vjp must run the hand-scheduled kernels, not the jnp fallback:
    the backward jaxpr contains pallas_call primitives."""
    rng = np.random.RandomState(5)
    q, k, v = _qkv(rng, t=32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                       block_k=16) ** 2)

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert jaxpr.count("pallas_call") >= 3  # forward + dq + dkv


def test_flash_gradients_match():
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng, t=32)

    f = lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True,
                                                block_q=16,
                                                block_k=16) ** 2)
    g = lambda q, k, v: jnp.sum(full_attention(q, k, v, True) ** 2)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gg, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=n)


def test_flash_bf16_inputs():
    rng = np.random.RandomState(3)
    q, k, v = _qkv(rng, t=32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = np.asarray(full_attention(q, k, v, False))
    out = np.asarray(flash_attention(qb, kb, vb, block_q=16, block_k=16)
                     .astype(jnp.float32))
    # bf16 operand rounding only; fp32 accumulation inside the kernel
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


_CASES = [(False, False), (True, False), (False, True), (True, True)]


def _kernel_dots(jaxpr, found=None):
    """Every ``dot_general`` inside the ``pallas_call`` bodies of a jaxpr:
    ``{kernel name: [(lhs dtype, rhs dtype, result dtype), ...]}``."""
    found = {} if found is None else found

    def walk(jp, kernel):
        for eqn in jp.eqns:
            inner = kernel
            if eqn.primitive.name == "pallas_call":
                inner = eqn.params["jaxpr"].debug_info.func_name
            elif kernel and eqn.primitive.name == "dot_general":
                found.setdefault(kernel, []).append(
                    tuple(str(v.aval.dtype)
                          for v in (*eqn.invars, eqn.outvars[0])))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inner)

    walk(jaxpr.jaxpr, None)
    return found


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("block", [16, 32], ids=["two_tiles", "one_tile"])
@pytest.mark.parametrize("causal,with_bias", _CASES)
def test_flash_contracts_in_the_operands_dtype(causal, with_bias, block,
                                               dtype):
    """The nine contractions of the three kernels take q, k, v and dO in
    the dtype they arrive in and accumulate in float32, whether the tile
    pair's result goes through scratch or straight out; a float32 program
    keeps float32 products."""
    rng = np.random.RandomState(8)
    q, k, v = (x.astype(dtype) for x in _qkv(rng, t=32))
    bias = _key_bias(rng, 2, 32) if with_bias else None

    def loss(q, k, v):
        return flash_attention(q, k, v, bias, causal=causal, block_q=block,
                               block_k=block).astype(jnp.float32).sum()

    dots = _kernel_dots(jax.make_jaxpr(loss)(q, k, v))
    _kernel_dots(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v), dots)
    assert sorted(dots) == ["_dkv_kernel", "_dq_kernel", "_flash_kernel"]
    # 2 contractions in the forward, traced for the loss and for its grad
    assert [len(dots[n]) for n in sorted(dots)] == [4, 3, 4]
    for name, found in dots.items():
        assert set(found) == {(dtype, dtype, "float32")}, (name, found)


def _rel_l2(got, ref):
    got, ref = (np.asarray(a, np.float64) for a in (got, ref))
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


@pytest.mark.parametrize("block", [128, 256], ids=["two_tiles", "one_tile"])
@pytest.mark.parametrize("causal,with_bias", _CASES)
def test_flash_bf16_gradients_against_float32_reference(causal, with_bias,
                                                        block):
    """bf16 q, k, v and dO at the cell's [.,.,256,64] a head, as one tile
    pair (the cell's own: straight to the results) and as two (the state
    carried through scratch): dQ, dK, dV against the float32 reference on
    the float32 casts of the SAME inputs.  What separates them is the
    bf16 rounding of P, dS and of the results (readings here 2.3e-3 to
    2.5e-3; 1.7e-3 to 1.8e-3 with float32 products on the CPU, the
    results' own rounding; on the v5e both read the same bits)."""
    rng = np.random.RandomState(9)
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(rng, h=4, t=256, d=64))
    do = jnp.asarray(rng.normal(size=q.shape), jnp.bfloat16)
    bias = _key_bias(rng, 2, 256) if with_bias else None

    _, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, bias, causal=causal, block_q=block, block_k=block),
        q, k, v)
    got = vjp(do)
    ref = flash_bwd_reference(*(x.astype(jnp.float32)
                                for x in (q, k, v, do)),
                              bias=bias, causal=causal)
    for g, r, n in zip(got, ref, ("dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16
        assert _rel_l2(g.astype(jnp.float32), r) <= 5e-3, n


@pytest.mark.parametrize("block", [16, 32], ids=["two_tiles", "one_tile"])
@pytest.mark.parametrize("causal,with_bias", _CASES)
def test_flash_float32_is_still_float32(causal, with_bias, block):
    """float32 inputs (AMP off): output and gradients at the float32
    tolerances this file already holds, with a scale that is no power of
    two (d = 24), now applied to the scores and not to q."""
    rng = np.random.RandomState(10)
    q, k, v = _qkv(rng, t=32, d=24)
    do = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
    bias = _key_bias(rng, 2, 32) if with_bias else None

    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, bias, causal=causal, block_q=block, block_k=block),
        q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(full_attention(q, k, v, causal,
                                                   bias=bias)),
        rtol=2e-5, atol=2e-5)
    ref = flash_bwd_reference(q, k, v, do, bias=bias, causal=causal)
    for g, r, n in zip(vjp(do), ref, ("dq", "dk", "dv")):
        assert g.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=5e-4, atol=5e-4, err_msg=n)


@pytest.mark.parametrize("tq,tk", [(64, 32), (32, 64)])
def test_flash_cross_lengths_mix_one_and_two_tiles(tq, tk):
    """Cross attention whose one side is a single tile and whose other is
    two: the kernels that carry state and those that write straight to
    their results meet in one backward."""
    rng = np.random.RandomState(12)
    q, _, _ = _qkv(rng, t=tq)
    _, k, v = _qkv(rng, t=tk)
    do = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
    bias = _key_bias(rng, 2, tk)
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, bias, block_q=32, block_k=32), q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(full_attention(q, k, v, False,
                                                   bias=bias)),
        rtol=2e-5, atol=2e-5)
    ref = flash_bwd_reference(q, k, v, do, bias=bias)
    for g, r, n in zip(vjp(do), ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=5e-4, atol=5e-4, err_msg=n)


def test_flash_op_hookup_env_gated(monkeypatch):
    import paddle_tpu.fluid as fluid

    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")
    fluid.default_startup_program().random_seed = 7
    x = fluid.layers.data(name="x", shape=[2, 16, 8], dtype="float32")
    att = fluid.layers.ring_attention(x, x, x, causal=True)
    loss = fluid.layers.reduce_mean(att)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xa = np.random.RandomState(0).normal(size=(2, 2, 16, 8)) \
        .astype(np.float32)
    (l1,) = exe.run(fluid.default_main_program(), feed={"x": xa},
                    fetch_list=[loss])
    monkeypatch.setenv("PADDLE_TPU_FLASH", "0")
    exe2 = fluid.Executor(fluid.CPUPlace())
    (l2,) = exe2.run(fluid.default_main_program(), feed={"x": xa},
                     fetch_list=[loss])
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5)


def test_flash_contraction_counter_names_the_operands_dtype(monkeypatch):
    """Lowering the two-layer Transformer under bf16 AMP counts
    ``ops.fused.flash_contraction{operands="bfloat16"}`` once for each of
    its 6 attention ops and once for each grad op (which traces the
    forward again), none under float32, and leaves the unlabelled
    ``ops.fused.flash_attention`` what it was; the same program without AMP
    counts them under float32."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import transformer

    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")
    cfg = transformer.tiny_config()
    batch, seq = 2, 16
    _, _, _, loss = transformer.build(cfg, src_len=seq, tgt_len=seq, lr=1e-3)
    prog = fluid.default_main_program()
    ops = [op.type for op in prog.global_block().ops]
    assert ops.count("ring_attention") == 6 == ops.count(
        "ring_attention_grad")
    rng = np.random.RandomState(11)
    tgt = rng.randint(1, cfg.tgt_vocab_size, size=(batch, seq))
    feed = {"src_word": rng.randint(1, cfg.src_vocab_size,
                                    size=(batch, seq)).astype(np.int64),
            "tgt_word": tgt.astype(np.int64),
            "lbl_word": tgt[..., None].astype(np.int64)}
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    def lowered_counts():
        before = fluid.profiler.counters()
        exe.lower_step(prog, feed, [loss])
        after = fluid.profiler.counters()
        return {k.replace("ops.fused.", ""): after[k] - before.get(k, 0)
                for k in after if k.startswith("ops.fused.flash")
                and after[k] != before.get(k, 0)}

    try:
        fluid.amp.enable("bfloat16", keep_activations=True)
        assert lowered_counts() == {
            "flash_attention": 12,
            'flash_contraction{operands="bfloat16"}': 12}
    finally:
        fluid.amp.disable()
    assert lowered_counts() == {
        "flash_attention": 12, 'flash_contraction{operands="float32"}': 12}


def test_flash_trains_flagship_transformer(monkeypatch):
    """The flash gate open: the STACKED flagship transformer trains
    through the Pallas fwd+bwd kernels (interpret mode here) with losses
    matching the XLA-softmax build — flash is a training path, not a demo.
    Padding bias included, so the kernels' bias handling is on the path."""
    import paddle_tpu.fluid as fluid
    import paddle_tpu.fluid.executor as _executor
    from paddle_tpu.models import transformer

    losses = {}
    for flash in (False, True):
        from paddle_tpu.fluid import framework, unique_name

        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch()
        _executor._global_scope = _executor.Scope()
        monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash else "0")
        fluid.default_main_program().random_seed = 21
        fluid.default_startup_program().random_seed = 21
        cfg = transformer.Config(
            "t", src_vocab_size=50, tgt_vocab_size=47, d_model=16,
            d_inner=32, n_head=2, n_layer=2, dropout=0.0,
            label_smooth=0.0, stacked=True, n_microbatches=2)
        src, tgt, lbl, loss = transformer.build(cfg, src_len=8, tgt_len=8,
                                                lr=5e-3)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rng = np.random.RandomState(6)
        sw = rng.randint(1, 50, size=(4, 8))
        sw[:, -2:] = 0  # real padding: bias path exercised
        feed = {"src_word": sw.astype(np.int64),
                "tgt_word": rng.randint(1, 47, size=(4, 8))
                .astype(np.int64),
                "lbl_word": rng.randint(1, 47, size=(4, 8, 1))
                .astype(np.int64)}
        out = []
        for _ in range(3):  # fixed batch: loss must strictly fall
            (l,) = exe.run(fluid.default_main_program(), feed=feed,
                           fetch_list=[loss])
            out.append(float(np.asarray(l).reshape(-1)[0]))
        losses[flash] = out
    assert losses[True][-1] < losses[True][0]
    np.testing.assert_allclose(losses[True], losses[False], rtol=2e-4,
                               atol=2e-4)


def test_flash_gate_precedence(monkeypatch):
    """The rule has two levels: PADDLE_TPU_FLASH where it is set (0 closes
    the gate on any backend, 1 opens it on any), else the backend.  The two
    groups read their own switch; off the TPU a kernel is interpreted."""
    from paddle_tpu.ops import kernel_choice

    monkeypatch.delenv("PADDLE_TPU_FUSED", raising=False)
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        monkeypatch.setenv("PADDLE_TPU_FLASH", "0")
        assert kernel_choice.gate("flash") is False     # closed anywhere
        monkeypatch.setenv("PADDLE_TPU_FLASH", "1")
        assert kernel_choice.gate("flash") is True      # open anywhere
        assert kernel_choice.gate("fused") is (backend == "tpu")
        assert kernel_choice.switches() == {"flash": "1", "fused": ""}
        for unset in ("auto", ""):
            monkeypatch.setenv("PADDLE_TPU_FLASH", unset)
            assert kernel_choice.gate("flash") is (backend == "tpu")
        monkeypatch.delenv("PADDLE_TPU_FLASH")
        assert kernel_choice.gate("flash") is (backend == "tpu")
        assert kernel_choice.interpret() is (backend != "tpu")
        assert kernel_choice.interpret(True) is True
        assert kernel_choice.interpret(False) is False
