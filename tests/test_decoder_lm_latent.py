"""The decoder path's latent attention (a gated output, a partial interleaved
YaRN rotary), the FarSkip residual and the multi-token module against the
plain float32 reference of ``chipbench/configs/instella_moe_16b_a3b``, at
tiny sizes on the CPU.  The first section of
``tests/test_decoder_lm_mixers.py`` until PR 63, a file of its own under the
rule that no file of ``tests/`` is more than 300 s of one worker
(docs/COVERAGE.md); what the two share is ``tests/decoder_reference.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import decoder_ops, registry
from paddle_tpu.ops import pallas_sparse_flash as psf

import decoder_reference
from decoder_reference import (compiled, counters, reference_step,
                               seeded_program)

# == latent attention with a gated output and a partial interleaved YaRN  ==
# == rotary, the FarSkip residual, two shared experts and a multi-token   ==
# == module that shares the embedding and the head: the program against   ==
# == the reference of ``chipbench/configs/instella_moe_16b_a3b``          ==

I_BUILD, I_REF, instella_sizes = decoder_reference.load(
    "instella_moe_16b_a3b")


@pytest.mark.parametrize("flash", ["xla", "pallas"])
def test_latent_program_equals_the_reference_adam_step_and_bias(
        monkeypatch, flash):
    """Both losses' sum, every gradient, every parameter after one Adam
    step and every router's bias after its rule, through ``fluid.Executor``
    with ``optimizer.minimize``: the leading dense layer, three routed
    layers and the multi-token module's block, five latent mixers under
    the FarSkip rule."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash == "pallas" else "0")
    monkeypatch.setattr(psf, "BLOCK", 16)
    sizes = instella_sizes()
    assert sizes["n_routed_experts"] < sizes["published"]["n_routed_experts"]
    built, names, weights = seeded_program(I_BUILD, I_REF, sizes)
    main, scope = fluid.default_main_program(), fluid.global_scope()
    # one embedding, one head, each used twice; the module's own parameters
    assert names.count("tok_emb") == names.count("lm_head_w") == 1
    block = main.global_block()
    assert sum(op.type == "lookup_table" for op in block.ops) == 2
    assert sum(op.type == "mul" and "lm_head_w" in op.inputs["Y"]
               for op in block.ops) == 2
    assert {"mtp_h_norm", "mtp_e_norm", "mtp_merge_w", "mtp_kva_w",
            "mtp_router_w", "mtp_norm"} <= set(names)
    for p in ["l0", "l1", "l2", "l3", "mtp"]:
        mine = {n[len(p) + 1:] for n in names if n.startswith(p + "_")}
        assert {"attn_norm", "q_w", "q_norm", "kva_w", "kv_norm", "kvb_w",
                "k_norm", "gate_w", "o_w"} <= mine
        assert not {"k_w", "v_w", "conv_w"} & mine
        assert ("mlp_w1" in mine) == (p == "l0")
        assert ("shared_w1" in mine) == ("router_w" in mine) == (p != "l0")
    routers = ["l1_route_bias", "l2_route_bias", "l3_route_bias",
               "mtp_route_bias"]
    for name in routers:
        assert not np.any(np.asarray(scope.get(name)))
        assert not block.has_var(name + "@GRAD") and name not in names
    feed = I_BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    assert set(feed) == {"tokens", "labels", "labels2"}
    np.testing.assert_array_equal(feed["labels"][:, 1:, 0],
                                  feed["labels2"][:, :-1, 0])
    exe = fluid.Executor(fluid.TPUPlace())
    outs = exe.run(main, feed=feed, fetch_list=[built["loss"]]
                   + [n + "@GRAD" for n in names])
    ref_loss, ref_grads, after = reference_step(I_REF, sizes, weights, feed)
    assert float(outs[0].reshape(-1)[0]) == pytest.approx(float(ref_loss),
                                                          rel=1e-5)
    # the module's loss is in it: the trunk's alone is smaller by 0.3 of a
    # loss near ln(vocabulary)
    assert float(ref_loss) > 1.2 * np.log(sizes["vocab_size"])
    for name, g, r in zip(names, outs[1:], ref_grads):
        g = np.asarray(g).reshape(r.shape)
        assert np.abs(g - r).max() <= 2e-4 * np.abs(r).max() + 1e-7, name
        assert np.abs(r).max() > 0, name
    # one Adam step of every parameter, from the summed gradients
    adam = compiled(I_REF, "optimizer_step", sizes)
    for name, w, r in zip(names, weights, ref_grads):
        np.testing.assert_allclose(
            np.asarray(scope.get(name)).reshape(w.shape), adam(w, r),
            atol=2e-6, err_msg=name)
    rate = sizes["assumed"]["bias_update_rate"]
    assert len(after) == len(routers)
    for name, want in zip(routers, after):
        got = np.asarray(scope.get(name))
        np.testing.assert_allclose(got, want, atol=1e-9)
        assert set(np.round(np.abs(got) / rate)) <= {0.0, 1.0} \
            and np.any(got > 0) and np.any(got < 0)
    # what ran, as the counters say it
    per = 1 if flash == "pallas" else 2
    assert counters("ops.sparse_attention.calls") == {
        f'ops.sparse_attention.calls{{path="{flash}",seq="64",'
        f'topk="0"}}': 5 * per}
    assert not counters("ops.sparse_attention.declined")
    assert counters("ops.rotary.calls") == {
        'ops.rotary.calls{dims="8",pairing="interleaved",scaled="1"}': 10}
    assert counters("models.decoder.blocks") == {
        'models.decoder.blocks{mixer="latent",residual="farskip",'
        'where="trunk"}': 4,
        'models.decoder.blocks{mixer="latent",residual="farskip",'
        'where="mtp"}': 1}
    (key, n), = counters("ops.moe.calls").items()
    assert 'score="sigmoid"' in key and 'routed="8"' in key \
        and 'held="4"' in key and n == 2 * 4
    assert counters("ops.moe.bias_updates") == {"ops.moe.bias_updates": 4}
    # every op under a name, the latent's and the module's among them
    scopes = {op.attrs.get("op_namescope", "") for op in main.all_ops()}
    assert "" not in scopes
    assert {"embed", "head", "mtp.merge", "mtp.mixer", "mtp.mixer.latent",
            "mtp.ffn", "mtp.head"} | {
        f"layer{i}.{part}" for i in range(4)
        for part in ("mixer", "mixer.latent", "ffn")} == scopes
    update = [op for op in block.ops if op.type == "moe_bias_update"]
    assert [op.attrs["op_namescope"] for op in update] == [
        "layer1.ffn", "layer2.ffn", "layer3.ffn", "mtp.ffn"]


def test_both_uses_of_the_embedding_and_of_the_head_reach_their_gradient():
    """``tok_emb`` is looked up twice (the tokens, and the next tokens in
    the module's merge) and ``lm_head_w`` multiplied twice (the trunk's
    logits and the module's): the program's gradient of each is the sum of
    the two parts that the reference gives with the two uses told apart,
    and neither part alone."""
    sizes = instella_sizes()
    built, names, weights = seeded_program(I_BUILD, I_REF, sizes)
    feed = I_BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    outs = fluid.Executor(fluid.TPUPlace()).run(
        fluid.default_main_program(), feed=feed,
        fetch_list=["tok_emb@GRAD", "lm_head_w@GRAD"])
    at = {n: names.index(n) for n in ("tok_emb", "lm_head_w")}

    def told_apart(emb, emb2, head, head2):
        params = list(weights)
        params[at["tok_emb"]], params[at["lm_head_w"]] = emb, head
        return I_REF.loss_and_counts(params, feed, sizes, emb2=emb2,
                                     head2=head2)[0]

    emb, head = weights[at["tok_emb"]], weights[at["lm_head_w"]]
    with jax.default_matmul_precision("highest"):
        parts = jax.jit(jax.grad(told_apart, (0, 1, 2, 3)))(
            emb, emb, head, head)
    for got, (first, second) in zip(outs, (parts[:2], parts[2:])):
        got, both = np.asarray(got), np.asarray(first + second)
        big = np.abs(both).max()
        assert np.abs(got - both).max() <= 2e-4 * big
        for part in (first, second):
            assert np.abs(got - np.asarray(part)).max() > 0.05 * big
    # a lookup's part is rows: untouched ids have none
    first, second = (np.asarray(p) for p in parts[:2])
    assert not np.any(first[np.setdiff1d(np.arange(sizes["vocab_size"]),
                                         np.unique(feed["tokens"]))])
    assert not np.any(second[np.setdiff1d(np.arange(sizes["vocab_size"]),
                                          np.unique(feed["labels"]))])


def sub_block_inputs(main, names):
    """The variable each named norm reads: the input of its sub-block."""
    by_scale = {op.inputs["Scale"][0]: op.inputs["X"][0]
                for op in main.global_block().ops if op.type == "rms_norm"}
    return [by_scale[n] for n in names]


@pytest.mark.parametrize("residual", ["farskip", "sequential"])
def test_a_sub_block_reads_the_stream_the_residual_rule_names(residual):
    """Sub-blocks j = 1.. are layer 0's mixer and feed-forward, layer 1's
    mixer, ...  Changing sub-block 2's output (layer 0's down projection)
    under FarSkip leaves what sub-block 3 reads (s_1) bit-equal and changes
    what sub-block 4 reads (s_2); under the sequential rule sub-block 3
    reads s_2 and changes at once.  Sub-block 1 reads the embedding either
    way, and under FarSkip so does sub-block 2."""
    sizes = instella_sizes(farskip=residual == "farskip")
    built, names, weights = seeded_program(I_BUILD, I_REF, sizes)
    main = fluid.default_main_program()
    norms = ["l0_attn_norm", "l0_mlp_norm", "l1_attn_norm", "l1_moe_norm"]
    reads = sub_block_inputs(main, norms)
    embedded = next(op.outputs["Out"][0] for op in main.global_block().ops
                    if op.type == "lookup_table")
    assert reads[0] == embedded
    assert (reads[1] == embedded) == (residual == "farskip")
    assert len(set(reads)) == (3 if residual == "farskip" else 4)
    feed = I_BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    # forward only: a program without the optimizer, same names and scope
    fwd = fluid.Program()
    with fluid.program_guard(fwd, fluid.Program()), \
            fluid.unique_name.guard():
        from paddle_tpu.models import decoder_lm
        decoder_lm.forward(I_BUILD.config_of(sizes), sizes["seq_len"])
    reads = sub_block_inputs(fwd, norms)
    exe = fluid.Executor(fluid.TPUPlace())
    before = exe.run(fwd, feed=feed, fetch_list=reads[2:])
    scope = fluid.global_scope()
    scope.set("l0_mlp_w2", 1.5 * jnp.asarray(scope.get("l0_mlp_w2")))
    after = exe.run(fwd, feed=feed, fetch_list=reads[2:])
    third_moved = not np.array_equal(before[0], after[0])
    assert third_moved == (residual == "sequential")
    assert not np.array_equal(before[1], after[1])
    # and the reference's rule is the same one
    ref_loss, _, _ = reference_step(I_REF, sizes, weights, feed)
    other, _, _ = reference_step(
        I_REF, {**sizes, "farskip": not sizes["farskip"]}, weights, feed)
    assert abs(float(ref_loss) - float(other)) > 1e-4


def complex_rotary(x, start, inv_freq):
    """x [B, T, H, D] float64: the columns from ``start`` on as complex
    numbers (2i, 2i+1) -> (real, imaginary), each times exp(i t f_i)."""
    x = np.asarray(x, np.float64)
    part = x[..., start:]
    z = part[..., 0::2] + 1j * part[..., 1::2]
    ang = np.arange(x.shape[1])[:, None] * np.asarray(inv_freq, np.float64)
    z = z * np.exp(1j * ang)[None, :, None, :]
    out = x.copy()
    out[..., start::2], out[..., start + 1::2] = z.real, z.imag
    return out


def test_partial_interleaved_yarn_rotary_is_a_complex_rotation():
    """``rotary_embedding`` on the last 8 of 24 columns, pairs (2i, 2i+1),
    with a YaRN table whose ramp is neither all 0 nor all 1: the op through
    the executor equals the complex-number rotation, its gradient the
    rotation by the opposite angles, and the columns outside pass."""
    from paddle_tpu.models import decoder_lm

    table = decoder_lm.yarn_inv_freq(8, 100.0, 4.0, 64, beta_fast=4,
                                     beta_slow=1)
    plain = [100.0 ** (-2 * i / 8) for i in range(4)]
    np.testing.assert_allclose(
        np.asarray(table) / plain, [1, 1 - 0.75 / 3, 1 - 0.75 * 2 / 3, 0.25])
    np.testing.assert_allclose(
        table, I_REF.yarn_inv_freq(8, 100, instella_sizes()["rope_scaling"]),
        rtol=1e-6)
    # the published table: 3 pairs as they are, 4 blended, 9 over 40
    full = np.asarray(decoder_lm.yarn_inv_freq(32, 8e6, 40, 4096))
    ratio = full / 8e6 ** (-np.arange(16) / 16)
    np.testing.assert_allclose(ratio[:4], 1)
    assert np.all(np.diff(ratio[3:8]) < 0)
    np.testing.assert_allclose(ratio[7:], 1 / 40)
    assert decoder_lm.yarn_softmax_scale(128, 40) == pytest.approx(
        128 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)

    x = layers.data(name="x", shape=[13, 3, 24], dtype="float32")
    x.stop_gradient = False
    out = layers.rotary_embedding(x, start=16, dims=8, interleaved=True,
                                  inv_freq=table)
    w = layers.data(name="w", shape=[13, 3, 24], dtype="float32")
    loss = layers.reduce_sum(layers.elementwise_mul(out, w))
    fluid.backward.append_backward(loss)
    rng = np.random.RandomState(0)
    xv, wv = (rng.randn(2, 13, 3, 24).astype(np.float32) for _ in range(2))
    got, dx = fluid.Executor(fluid.TPUPlace()).run(
        fluid.default_main_program(), feed={"x": xv, "w": wv},
        fetch_list=[out, "x@GRAD"])
    np.testing.assert_allclose(got, complex_rotary(xv, 16, table), atol=2e-6)
    np.testing.assert_array_equal(got[..., :16], xv[..., :16])
    np.testing.assert_allclose(
        dx, complex_rotary(wv, 16, -np.asarray(table)), atol=2e-6)
    assert counters("ops.rotary.calls") == {
        'ops.rotary.calls{dims="8",pairing="interleaved",scaled="1"}': 1}


def test_rotary_with_default_attrs_is_bit_equal_to_the_rotate_half_op():
    """No new attr written into the op, the whole head in rotate-half
    pairs by theta's own table: bit for bit the function as it stood
    before the attrs (copied here), forward and gradient, eager and
    jitted."""
    def before(x, theta):
        t, d = x.shape[1], x.shape[-1]
        inv = jnp.float32(theta) ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
        return (xf * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(
            x.dtype)

    x = layers.data(name="x", shape=[11, 2, 16], dtype="float32")
    x.stop_gradient = False
    out = layers.rotary_embedding(x, theta=1e6)
    op = fluid.default_main_program().global_block().ops[-1]
    assert set(op.attrs) - {"op_role", "op_namescope"} == {"theta"}
    fluid.backward.append_backward(layers.reduce_sum(
        layers.elementwise_mul(out, out)))
    xv = np.random.RandomState(1).randn(3, 11, 2, 16).astype(np.float32)
    got, dx = fluid.Executor(fluid.TPUPlace()).run(
        fluid.default_main_program(), feed={"x": xv},
        fetch_list=[out, "x@GRAD"])
    # through the executor the step is one jitted program, whose fusions
    # round apart from an eager call's: close here, bit-equal below where
    # both run the same way
    want, vjp = jax.vjp(lambda a: before(a, 1e6), jnp.asarray(xv))
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(dx, vjp(2 * want)[0], atol=4e-6)
    for dtype in (jnp.bfloat16, jnp.float32):
        z = jnp.asarray(xv, dtype)
        for run in (lambda f: f, jax.jit):
            now, back_now = jax.vjp(run(
                lambda a: decoder_ops.rotary(a, 1e4)), z)
            was, back_was = jax.vjp(run(lambda a: before(a, 1e4)), z)
            np.testing.assert_array_equal(np.asarray(now, np.float32),
                                          np.asarray(was, np.float32))
            np.testing.assert_array_equal(
                np.asarray(back_now(now)[0], np.float32),
                np.asarray(back_was(was)[0], np.float32))
    assert counters("ops.rotary.calls") == {
        'ops.rotary.calls{dims="16",pairing="half",scaled="0"}': 1}


def test_infer_rule_of_a_partial_rotary_and_its_table():
    from paddle_tpu.ops.registry import get_infer_rule

    class Op:
        def __init__(self, **attrs):
            self.attrs, self.type = attrs, "rotary_embedding"
            self.inputs = {"X": ["x"]}

        def attr(self, name, default=None):
            return self.attrs.get(name, default)

    rule = get_infer_rule("rotary_embedding")
    x = ((2, 16, 4, 128), "bfloat16")
    assert rule(Op(), {"X": [x]}) == {"Out": [x]}
    assert rule(Op(start=96, dims=32, interleaved=True,
                   inv_freq=[0.5] * 16), {"X": [x]}) == {"Out": [x]}
    for attrs, said in (
            (dict(start=100, dims=32), "the 32 columns from 100 on"),
            (dict(start=96, dims=31), "the 31 columns from 96 on"),
            (dict(start=96, dims=32, inv_freq=[0.5] * 8),
             "8 frequencies for the 16 pairs")):
        with pytest.raises(registry.InferMismatch, match=said):
            rule(Op(**attrs), {"X": [x]})
    with pytest.raises(registry.InferMismatch, match="an even head width"):
        rule(Op(), {"X": [((2, 16, 4, 127), "float32")]})


def test_config_refuses_a_latent_layer_it_cannot_build():
    from paddle_tpu.models import decoder_lm

    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=4, head_dim=16, expert_width=32, num_routed=8,
                experts_held=4, experts_per_token=2)
    latent = decoder_lm.Latent(rank=32, nope=8, rope=8, value=16)
    cfg = decoder_lm.Config(**base, mixers=["latent"] * 2, latent=latent)
    assert cfg.latent == latent and cfg.mtp_mixer() == "latent"
    assert decoder_lm.Config(**base).mtp_mixer() == "attention"
    assert decoder_lm.Config(**base, latent=tuple(latent)).latent == latent
    with pytest.raises(ValueError, match="needs the record `latent`"):
        decoder_lm.Config(**base, mixers=["latent"] * 2)
    with pytest.raises(ValueError, match="needs the record `latent`"):
        decoder_lm.Config(**base, mixers=["attention", "attention",
                                          "latent"], mtp_depth=1)
    for wrong in (latent._replace(nope=4), latent._replace(value=0),
                  latent._replace(nope=9, rope=7)):
        with pytest.raises(ValueError, match="add up to head_dim"):
            decoder_lm.Config(**base, mixers=["latent"] * 2, latent=wrong)
    # a value of another width than the key's is a layer like any other
    assert decoder_lm.Config(**base, mixers=["latent"] * 2,
                             latent=latent._replace(value=32)
                             ).latent.value == 32
    with pytest.raises(ValueError, match="its own key and value"):
        decoder_lm.Config(**{**base, "num_kv_heads": 2},
                          mixers=["latent"] * 2, latent=latent)
    with pytest.raises(ValueError, match="residual 'skip'"):
        decoder_lm.Config(**base, residual="skip")
    with pytest.raises(ValueError, match="one module after the trunk"):
        decoder_lm.Config(**base, mtp_depth=2)
    assert "latent" in decoder_lm.MIXERS
