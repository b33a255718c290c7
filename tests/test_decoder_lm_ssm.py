"""The decoder path's state-space mixer, layers of one sub-block and experts
of two matrices (PR 58) against the plain float32 reference of
``chipbench/configs/nemotron_twotower_30b_a3b``, at tiny sizes on the CPU.
A file of its own beside ``tests/test_decoder_lm.py`` and
``tests/test_decoder_lm_mixers.py`` (no file of ``tests/`` is more than
300 s of one worker: docs/COVERAGE.md); what the three share is
``tests/decoder_reference.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import pallas_sparse_flash as psf
from paddle_tpu.parallel import moe

import decoder_reference
from decoder_reference import (compiled, counters, reference_step,
                               seeded_program)


# == three state-space mixers, three routed layers of two-matrix experts  ==
# == and one attention layer at a group of two with no positions, every   ==
# == published layer ONE sub-block: the program against the reference of  ==
# == ``chipbench/configs/nemotron_twotower_30b_a3b``                      ==

N_BUILD, N_REF, nemotron_sizes = decoder_reference.load(
    "nemotron_twotower_30b_a3b")


def test_ssm_program_equals_the_reference_adam_step_and_bias(monkeypatch):
    """Loss, every gradient, every parameter after one Adam step and every
    router's bias after its rule, through ``fluid.Executor`` with
    ``optimizer.minimize``: published layers 6-12, ``EMEMEM*``, the CHUNKED
    scan (four chunks of 16, groups of four heads) against the reference's
    token-by-token recurrence, experts of two matrices about a squared
    ReLU, attention with neither positions nor head norms (by the Pallas
    kernels, interpreted; the XLA path is the other decoders' tests')."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")
    monkeypatch.setattr(psf, "BLOCK", 16)
    sizes = nemotron_sizes()
    assert sizes["seq_len"] == 4 * sizes["chunk_size"]
    assert sizes["hybrid_override_pattern"][6:13] == "EMEMEM*"
    built, names, weights = seeded_program(N_BUILD, N_REF, sizes)
    main, scope = fluid.default_main_program(), fluid.global_scope()
    ssm = {"ssm_norm", "ssm_in_w", "conv_w", "conv_b", "dt_bias", "a_log",
           "ssm_d", "gate_norm", "o_w"}
    routed = {"moe_norm", "shared_w1", "shared_w2", "router_w", "w1", "w2"}
    plain = {"attn_norm", "q_w", "k_w", "v_w", "o_w"}
    for i, want in enumerate([routed, ssm] * 3 + [plain]):
        # a layer of one sub-block has that one's parameters and no other
        assert {n[3:] for n in names if n.startswith(f"l{i}_")} == want, i
    ops = main.global_block().ops
    assert [op.type for op in ops].count("ssd_scan") == 3
    assert not any(op.type == "rotary_embedding" for op in ops)
    assert all("W3" not in op.inputs for op in ops
               if op.type == "moe_experts")
    scopes = {op.attrs.get("op_namescope", "") for op in ops}
    assert {"layer0.ffn", "layer1.mixer", "layer1.mixer.ssm",
            "layer6.mixer"} <= scopes
    assert not {"layer0.mixer", "layer1.ffn", "layer6.ffn"} & scopes
    routers = ["l0_route_bias", "l2_route_bias", "l4_route_bias"]
    feed = N_BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    exe = fluid.Executor(fluid.TPUPlace())
    outs = exe.run(main, feed=feed, fetch_list=[built["loss"]]
                   + [n + "@GRAD" for n in names])
    ref_loss, ref_grads, after = reference_step(N_REF, sizes, weights, feed)
    assert float(outs[0].reshape(-1)[0]) == pytest.approx(float(ref_loss),
                                                          rel=1e-5)
    for name, g, r in zip(names, outs[1:], ref_grads):
        g = np.asarray(g).reshape(r.shape)
        assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-7, name
        assert np.abs(r).max() > 0, name
    # one Adam step of every parameter, from the program's own gradient
    # (an out-projection's, at a fifth of the other matrices' scale, has
    # entries near Adam's epsilon, where the step follows the last bit)
    adam = compiled(N_REF, "optimizer_step", sizes)
    for name, w, g in zip(names, weights, outs[1:]):
        np.testing.assert_allclose(
            np.asarray(scope.get(name)).reshape(w.shape),
            adam(w, jnp.asarray(g).reshape(w.shape)), atol=2e-6,
            err_msg=name)
    for name, want in zip(routers, after):
        np.testing.assert_allclose(np.asarray(scope.get(name)), want,
                                   atol=1e-7, err_msg=name)
    assert counters("models.decoder.blocks") == {
        'models.decoder.blocks{mixer="none",parts="ffn",residual='
        '"sequential",where="trunk"}': 3,
        'models.decoder.blocks{mixer="ssm",parts="mixer",residual='
        '"sequential",where="trunk"}': 3,
        'models.decoder.blocks{mixer="attention",parts="mixer",residual='
        '"sequential",where="trunk"}': 1}
    assert counters("models.decoder.ssm") == {
        'models.decoder.ssm{conv_bias="1",groups="2",heads="8",'
        'state="16"}': 3}
    assert sum(counters("ops.ssd.scans").values()) == 3
    assert sum(counters("ops.ssd.grad_scans").values()) == 3
    # the op and its grad op each trace the layer's forward
    assert counters("ops.moe.ungated_layers") == {
        "ops.moe.ungated_layers": 6}
    assert counters("ops.short_conv.calls") == {
        'ops.short_conv.calls{bias="1",channels="128",gated="0",path="xla",'
        'taps="4"}': 3}


@pytest.mark.parametrize("field", ["ssm", "sub_blocks", "expert_gate",
                                   "qk_norm"])
def test_each_new_field_of_the_config_alone_builds_and_trains(field):
    """One new field at a time beside an ordinary layer: an ``ssm`` mixer
    in a layer of both sub-blocks (filter without a bias), a pattern of
    sub-blocks over attention layers, two-matrix feed-forwards dense,
    shared and routed, attention without head norms."""
    from paddle_tpu.models import decoder_lm

    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                num_kv_heads=1, head_dim=8, expert_width=16, num_routed=4,
                experts_held=2, experts_per_token=2, dense_layers=1,
                dense_width=16, shared_width=16)
    over = {
        "ssm": dict(mixers=["ssm", "attention"],
                    ssm=decoder_lm.Ssm(4, 8, 2, 4, chunk=8, conv_bias=False)),
        "sub_blocks": dict(mixers=["attention", None, "attention"],
                           sub_blocks=["mixer", "ffn", "both"],
                           num_layers=3),
        "expert_gate": dict(expert_gate=False),
        "qk_norm": dict(qk_norm=False)}[field]
    cfg = decoder_lm.Config(**{**base, **over})
    _, _, loss = decoder_lm.build(cfg, seq_len=16)
    names = {p.name for p in
             fluid.default_main_program().global_block().all_parameters()}
    assert ("l0_ssm_in_w" in names) == (field == "ssm")
    assert "l0_conv_b" not in names
    assert ("l1_shared_w3" in names) == ("l1_w3" in names) \
        == (field != "expert_gate")
    assert {"l1_shared_w1", "l1_shared_w2", "l1_w1", "l1_w2"} <= names
    if field == "sub_blocks":
        assert [cfg.layer_parts(i) for i in range(3)] == [
            "mixer", "ffn", "both"]
        # the dense layer is published layer 0, which is a mixer alone
        assert {"l0_q_w", "l1_router_w", "l2_q_w", "l2_router_w"} <= names
        assert not {n for n in names
                    if n.startswith(("l0_mlp", "l0_moe", "l1_q", "l1_attn"))}
    else:
        assert ("l0_mlp_w3" in names) == (field != "expert_gate")
        assert ("l1_q_norm" in names) == (field in ("ssm", "expert_gate"))
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    ids = np.random.RandomState(0).randint(0, 64, (2, 17)).astype(np.int64)
    feed = {"tokens": ids[:, :-1], "labels": ids[:, 1:, None]}
    losses = [float(np.asarray(exe.run(feed=feed, fetch_list=[loss])[0])
                    .reshape(-1)[0]) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_config_refuses_an_ssm_layer_or_a_pattern_it_cannot_build():
    from paddle_tpu.models import decoder_lm

    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim=16, expert_width=32, num_routed=8,
                experts_held=4, experts_per_token=2)
    ssm = decoder_lm.Ssm(8, 8, 2, 16)
    cfg = decoder_lm.Config(**base, mixers=["ssm", None],
                            sub_blocks=["mixer", "ffn"], ssm=tuple(ssm))
    assert cfg.ssm == ssm and tuple(ssm)[4:] == (4, 128, True)
    assert [cfg.layer_mixer(i) for i in range(2)] == ["ssm", None]
    assert decoder_lm.Config(**base).layer_parts(1) == "both"
    assert decoder_lm.Config(**base).expert_gate is True
    with pytest.raises(ValueError, match="needs the record `ssm`"):
        decoder_lm.Config(**base, mixers=["ssm", "attention"])
    with pytest.raises(ValueError, match="no multi-token module"):
        decoder_lm.Config(**base, mixers=["attention", "ssm"], ssm=ssm,
                          mtp_depth=1)
    with pytest.raises(ValueError, match="multiple of the groups"):
        decoder_lm.Config(**base, mixers=["ssm"] * 2,
                          ssm=ssm._replace(groups=3))
    with pytest.raises(ValueError, match="a chunk hold"):
        decoder_lm.Config(**base, mixers=["ssm"] * 2,
                          ssm=ssm._replace(chunk=0))
    # a pattern of sub-blocks: beside mixers, one of three words a layer,
    # no mixer named where the layer has none, and no multi-token module
    for wrong in (dict(sub_blocks=["mixer", "ffn"]),
                  dict(mixers=["attention"] * 2, sub_blocks=["mixer", "mlp"]),
                  dict(mixers=["attention"] * 2, sub_blocks=["mixer"]),
                  dict(mixers=["attention"] * 2, sub_blocks=["both"] * 2,
                       mtp_depth=1)):
        with pytest.raises(ValueError, match="sub_blocks names"):
            decoder_lm.Config(**base, **wrong)
    with pytest.raises(ValueError, match="None where sub_blocks says"):
        decoder_lm.Config(**base, mixers=["attention"] * 2,
                          sub_blocks=["mixer", "ffn"])
    with pytest.raises(ValueError, match="and gated experts"):
        decoder_lm.Config(**base, shared_width=32, shared_gate=True,
                          expert_gate=False)
    assert decoder_lm.MIXERS[-1] == "ssm"
    assert decoder_lm.SUB_BLOCKS == ("both", "mixer", "ffn")


def test_the_16_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """A sigmoid router 32 wide with 6 a token and a selection bias,
    renormalized with the eps, times 2.5; 2 two-matrix experts held by each
    of 16 chips (``expert_offset`` 0, 2, ..., 30): the program's shares,
    and the shared expert counted ONCE, add up to what the cell's reference
    gives for the UNCUT layer (every expert held)."""
    sizes = nemotron_sizes()
    routed, held, k, n, d, f = 32, 2, 6, 24, 16, 8
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    wr = jnp.asarray(rng.randn(d, routed), jnp.float32)
    w1 = jnp.asarray(0.3 * rng.randn(routed, d, f), jnp.float32)
    w2 = jnp.asarray(0.3 * rng.randn(routed, f, d), jnp.float32)
    s1, s2 = (jnp.asarray(0.3 * rng.randn(*s), jnp.float32)
              for s in ((d, 2 * f), (2 * f, d)))
    bias = jnp.asarray(0.2 * rng.randn(routed), jnp.float32)
    scale, eps = sizes["routed_scaling_factor"], \
        sizes["assumed"]["route_norm_eps"]
    with jax.default_matmul_precision("highest"):
        shared = N_REF.feed_forward(x, s1, s2)
        whole = shared + N_REF.routed(x, wr, bias, w1, w2, k, scale, eps)[0]
        total, seen = shared, 0
        for off in range(0, routed, held):
            part = moe.routed_experts(
                x, wr, w1[off:off + held], None, w2[off:off + held],
                top_k=k, expert_offset=off, score="sigmoid", bias=bias,
                norm_eps=eps, scale=scale)
            mine, counts = N_REF.routed(x, wr, bias, w1[off:off + held],
                                        w2[off:off + held], k, scale, eps,
                                        off)
            np.testing.assert_allclose(part, mine, atol=1e-5)
            total, seen = total + part, seen + 1
    assert seen == sizes["deployment"]["chips_sharing_a_layer"] == 16
    assert int(counts.sum()) == n * k          # over ALL the router's experts
    np.testing.assert_allclose(total, whole, atol=3e-5)
    assert float(jnp.abs(whole - shared).max()) > 0.1 \
        and float(jnp.abs(shared).max()) > 0.1


@pytest.mark.parametrize("slabs", [False, True])
def test_two_matrix_experts_and_their_backward_by_hand(slabs):
    """``routed_experts`` with no ``w3``, in one walk (a quarter of the
    experts held) and in slabs (a sixteenth, under a bias): the share and
    all four gradients against the dense sum over the held experts."""
    routed, held, k, n, d, f = (32, 2, 4, 64, 16, 8) if slabs \
        else (8, 2, 2, 32, 16, 8)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    wr = jnp.asarray(rng.randn(d, routed), jnp.float32)
    w1 = jnp.asarray(0.3 * rng.randn(held, d, f), jnp.float32)
    w2 = jnp.asarray(0.3 * rng.randn(held, f, d), jnp.float32)
    bias = jnp.zeros(routed, jnp.float32)
    probe = jnp.asarray(rng.randn(n, d), jnp.float32)
    assert (moe.walk_of(x, wr, w1, w2, k, bias)[2] < n * k) == slabs

    def mine(x, wr, w1, w2):
        return jnp.sum(probe * moe.routed_experts(
            x, wr, w1, None, w2, top_k=k, expert_offset=2, score="sigmoid",
            bias=bias))

    def dense(x, wr, w1, w2):
        return jnp.sum(probe * N_REF.routed(x, wr, bias, w1, w2, k, 1.0,
                                            0.0, 2)[0])

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(mine, range(4)))(x, wr, w1, w2)
        want = jax.jit(jax.value_and_grad(dense, range(4)))(x, wr, w1, w2)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, g, w in zip(("x", "router", "w1", "w2"), got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5, err_msg=name)
        assert float(jnp.abs(w).max()) > 0, name


def test_the_filters_bias_and_the_grouped_norm_through_a_program():
    """``short_conv(gated=False, bias_attr=...)`` and ``rms_norm(groups=2)``
    in one program: outputs and the gradients of the input, the filter, the
    bias and the scale against plain ``jax.numpy``; the gated form refuses a
    bias and a width that the groups do not divide is refused."""
    rng = np.random.RandomState(1)
    b, t, c, taps, groups = 2, 12, 8, 4, 2
    xv = rng.randn(b, t, c).astype("float32")
    x = layers.data(name="x", shape=[t, c], dtype="float32")
    x.stop_gradient = False
    y = layers.short_conv(x, taps, gated=False, param_attr="f_w",
                          bias_attr="f_b")
    z = layers.rms_norm(y, epsilon=1e-5, param_attr="g_w", groups=groups)
    fluid.backward.append_backward(layers.reduce_sum(layers.square(z)))
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    w, fb, g = (rng.randn(c, taps).astype("float32"),
                rng.randn(c).astype("float32"),
                rng.uniform(0.5, 1.5, c).astype("float32"))
    for name, v in (("f_w", w), ("f_b", fb), ("g_w", g)):
        assert np.shape(scope.get(name)) == v.shape
        scope.set(name, jnp.asarray(v))

    def plain(x, w, fb, g):
        pad = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        acc = sum(w[:, j] * pad[:, j:j + t] for j in range(taps)) + fb
        y = jax.nn.silu(acc).reshape(b, t, groups, c // groups)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5)
        return y.reshape(b, t, c) * g

    got = exe.run(feed={"x": xv}, fetch_list=[
        z, "x@GRAD", "f_w@GRAD", "f_b@GRAD", "g_w@GRAD"])
    np.testing.assert_allclose(got[0], jax.jit(plain)(xv, w, fb, g),
                               atol=2e-6)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), range(4)))(
        jnp.asarray(xv), w, fb, g)
    for name, m, r in zip("x w b g".split(), got[1:], want):
        np.testing.assert_allclose(m, r, rtol=1e-4, atol=1e-5, err_msg=name)
    with pytest.raises(ValueError, match="gated form has no bias"):
        layers.short_conv(layers.data(name="x3", shape=[t, 3 * c],
                                      dtype="float32"), taps, bias_attr=True)
    with pytest.raises(ValueError, match="do not divide into 3 groups"):
        layers.rms_norm(y, groups=3)


@pytest.mark.parametrize("gated,slabs", [(False, True), (True, False)])
def test_an_expert_width_off_the_lane_rows_takes_the_kernels_filled_up(
        monkeypatch, gated, slabs):
    """An expert width of 192 = 1.5 lane rows (the cell's is 1,856 = 14.5):
    the layer takes the Pallas kernels (interpreted here) at 256, zero
    columns of w1 and w3 and zero rows of w2 made in the pass that casts
    them; the share and every gradient, at the parameters' own shapes,
    against the dense sum over the held experts."""
    monkeypatch.setenv("PADDLE_TPU_FUSED", "1")
    routed, held, k, n, d, f = (64, 2, 4, 512, 128, 192) if slabs \
        else (8, 2, 2, 512, 128, 192)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    wr = jnp.asarray(rng.randn(d, routed), jnp.float32)
    w1, w3 = (jnp.asarray(0.1 * rng.randn(held, d, f), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(0.1 * rng.randn(held, f, d), jnp.float32)
    bias = jnp.zeros(routed, jnp.float32)
    probe = jnp.asarray(rng.randn(n, d), jnp.float32)
    path, rows, slab = moe.walk_of(x, wr, w1, w2, k, bias)
    assert path == "pallas" and (slab < rows) == slabs
    assert moe.product_path(x, w1[:, :, :100], w2[:, :100], k) == "pallas"
    assert moe.product_path(x[:, :100], w1[:, :100], w2[:, :, :100], k) \
        == "ragged_dot"        # the hidden width itself is never filled up

    def mine(x, wr, w1, w2, w3):
        return jnp.sum(probe * moe.routed_experts(
            x, wr, w1, w3 if gated else None, w2, top_k=k, expert_offset=2,
            score="sigmoid", bias=bias))

    def dense(x, wr, w1, w2, w3):
        vals, idx = N_REF.route(x, wr, bias, k, 1.0, 0.0)
        y = 0.0
        for e in range(held):
            we = jnp.sum(jnp.where(idx == e + 2, vals, 0.0), -1)
            h = jax.nn.silu(x @ w1[e]) * (x @ w3[e]) if gated \
                else jnp.square(jax.nn.relu(x @ w1[e]))
            y = y + we[:, None] * (h @ w2[e])
        return jnp.sum(probe * y)

    args = (x, wr, w1, w2, w3)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(mine, range(5)))(*args)
        want = jax.jit(jax.value_and_grad(dense, range(5)))(*args)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, g, w in zip(("x", "router", "w1", "w2", "w3"), got[1],
                          want[1]):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-5 * float(
            jnp.abs(w).max()) + 1e-12, err_msg=name)
    assert counters("ops.moe.column_tiles")
