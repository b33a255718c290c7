"""The decoder path's later mixers and records (the delta rule under either
decay, a global layer's own rotary, a value narrower than its key) against
the plain float32 references of their ``chipbench/configs/<name>``, at tiny
sizes on the CPU.  The second half of ``tests/test_decoder_lm.py``, a file of
its own since PR 52 (no file of ``tests/`` is more than 300 s of one worker:
docs/COVERAGE.md); what the decoder files share is
``tests/decoder_reference.py``'s."""

import json
import math
import os

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
from decoder_reference import (ROOT, compiled, counters, moe_weights,
                               reference_step, seeded_program)



# == the older programs, op for op: the latent mixer's section is           ==
# == tests/test_decoder_lm_latent.py                                        ==

def program_digest():
    """(ops, (how many, sha256 of every op's type, inputs, outputs and
    attrs in order)) of the default main program's block 0.  The digests
    below were taken while ``sparse_attention`` and its grad op still
    carried the per-op request ``flash: -1`` (gone in PR 47; nothing read
    any other value): it is put back for the hash, so that they stay the
    digests of the commits they were taken on.  Likewise the loss op had
    no ``Lse`` output, and its grad op no such input, until PR 50: the slot
    is asserted here and left out of the hash."""
    import hashlib

    def attrs(op):
        gone = {"flash": -1} if op.type.startswith("sparse_attention") else {}
        return sorted((k, repr(v)) for k, v in {**op.attrs, **gone}.items())

    def slots(op, named):
        if op.type == "softmax_with_cross_entropy" + \
                ("_grad" if named is op.inputs else ""):
            assert len(named["Lse"]) == 1
            assert named.get("Lse@GRAD", [""]) == [""]   # nobody's cotangent
            named = {k: v for k, v in named.items()
                     if k not in ("Lse", "Lse@GRAD")}
        return sorted((k, list(v)) for k, v in named.items())

    ops = fluid.default_main_program().global_block().ops
    assert not any("flash" in op.attrs or "fused" in op.attrs for op in ops)
    text = "\n".join(repr((
        op.type, slots(op, op.inputs), slots(op, op.outputs), attrs(op)))
        for op in ops)
    return ops, (len(ops), hashlib.sha256(text.encode()).hexdigest())


#: the three decoder programs at their tiny sizes, as they stood before the
#: latent mixer, the residual rule and the multi-token module: (ops,
#: sha256 of every op's type, inputs, outputs and attrs in order)
PROGRAMS_BEFORE = {
    "keye_vl_2_0_30b_a3b": (142, "fedca0735a9850ad39adeb9f10ea447ed"
                                 "78f623517c6b1043c32b501d9c4a405"),
    "trinity_mini": (448, "0d4c886bcacc9d824b54bb8baa6ae189"
                          "dfed068f077aa22ea78aa6daca7e28bc"),
    "lfm2_8b_a1b": (193, "7ddcf33f4599cbcf97621110842376aa"
                         "f2c49015fc494c29eda5e67e69c7b9e0"),
}


@pytest.mark.parametrize("config", sorted(PROGRAMS_BEFORE))
def test_programs_without_the_new_fields_are_op_for_op_what_they_were(
        config):
    """``latent``, ``residual``, ``mtp_depth`` unset: the builder appends
    the ops it appended before this kind existed, names, attrs and name
    scopes included (a digest taken on the commit before)."""
    build, _, sizes = decoder_reference.load(config)
    build.build(fluid, sizes())
    ops, digest = program_digest()
    assert digest == PROGRAMS_BEFORE[config]
    assert not {"split", "concat", "expand", "cast"} & {o.type for o in ops}
    assert not any(set(op.attrs) & {"start", "dims", "interleaved",
                                    "inv_freq"} for op in ops)
    assert not counters("models.decoder.blocks{mixer=\"latent\"")


# == three gated-delta-rule mixers to one gated attention layer with a    ==
# == rotary on a quarter of the head, a softmax router and a gated shared ==
# == expert: the program against the reference of                         ==
# == ``chipbench/configs/qwen3_next_80b_a3b``                             ==

Q_BUILD, Q_REF, qwen_sizes = decoder_reference.load("qwen3_next_80b_a3b")


@pytest.mark.parametrize("flash", ["xla", "pallas"])
def test_delta_program_equals_the_reference_and_its_adam_step(
        monkeypatch, flash):
    """Loss, every gradient and every parameter after one Adam step through
    ``fluid.Executor`` with ``optimizer.minimize``: the CHUNKED rule (four
    chunks of 16) in three layers against the reference's token-by-token
    recurrence, then the attention layer with its partial rotary, and in
    every layer the gated shared expert beside the routed share."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash == "pallas" else "0")
    monkeypatch.setattr(psf, "BLOCK", 16)
    sizes = qwen_sizes()
    assert sizes["seq_len"] == 4 * sizes["delta_chunk"]
    assert sizes["num_experts"] < sizes["published"]["num_experts"]
    built, names, weights = seeded_program(Q_BUILD, Q_REF, sizes)
    main, scope = fluid.default_main_program(), fluid.global_scope()
    for i in range(4):
        mine = {n[3:] for n in names if n.startswith(f"l{i}_")}
        delta = {"qkvz_w", "ba_w", "conv_w", "dt_bias", "a_log",
                 "delta_norm"}
        plain = {"q_w", "q_norm", "k_w", "k_norm", "v_w", "gate_w"}
        assert (delta <= mine, bool(plain & mine)) == (i < 3, i == 3), i
        assert {"attn_norm", "o_w", "moe_norm", "shared_w1", "shared_w3",
                "shared_w2", "shared_gate_w", "router_w", "w1", "w3",
                "w2"} <= mine
    feed = Q_BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    exe = fluid.Executor(fluid.TPUPlace())
    outs = exe.run(main, feed=feed, fetch_list=[built["loss"]]
                   + [n + "@GRAD" for n in names])
    ref_loss, ref_grads, _ = reference_step(Q_REF, sizes, weights, feed)
    assert float(outs[0].reshape(-1)[0]) == pytest.approx(float(ref_loss),
                                                          rel=1e-5)
    for name, g, r in zip(names, outs[1:], ref_grads):
        g = np.asarray(g).reshape(r.shape)
        assert np.abs(g - r).max() <= 3e-4 * np.abs(r).max() + 1e-7, name
        assert np.abs(r).max() > 0, name
    # one Adam step of every parameter, from the program's own gradient
    # (where a gradient is near Adam's epsilon the step follows its last
    # digits, which the two algorithms do not share)
    adam = compiled(Q_REF, "optimizer_step", sizes)
    for name, w, g in zip(names, weights, outs[1:]):
        np.testing.assert_allclose(
            np.asarray(scope.get(name)).reshape(w.shape),
            adam(w, jnp.asarray(g).reshape(w.shape)), atol=2e-6,
            err_msg=name)
    # what ran, as the counters say it
    per = 1 if flash == "pallas" else 2
    assert counters("ops.sparse_attention.calls") == {
        f'ops.sparse_attention.calls{{path="{flash}",seq="64",'
        f'topk="0"}}': per}
    assert not counters("ops.sparse_attention.declined")
    assert counters("ops.rotary.calls") == {
        'ops.rotary.calls{dims="4",pairing="half",scaled="0"}': 2}
    assert counters("ops.delta_rule.calls") == {
        'ops.delta_rule.calls{chunk="16",dim="8",key_heads="2",path="xla",'
        'value_heads="4"}': 3}
    assert counters("ops.short_conv.calls") == {
        'ops.short_conv.calls{channels="64",gated="0",path="xla",'
        'taps="4"}': 3}
    assert counters("models.decoder.blocks") == {
        'models.decoder.blocks{mixer="delta",residual="sequential",'
        'where="trunk"}': 3,
        'models.decoder.blocks{mixer="attention",residual="sequential",'
        'where="trunk"}': 1}
    (key, n), = counters("ops.moe.calls").items()
    assert "score" not in key and 'routed="8"' in key and 'held="4"' in key \
        and n == 2 * 4
    # every op under a name; a delta mixer's own under ``.delta``, its two
    # plain products not
    scopes = {op.attrs.get("op_namescope", "") for op in main.all_ops()}
    assert {"embed", "head"} | {f"layer{i}.{part}" for i in range(4)
                                for part in ("mixer", "ffn")} | {
        f"layer{i}.mixer.delta" for i in range(3)} == scopes
    block = main.global_block()
    for op in block.ops:
        if op.type in ("short_conv", "gated_delta_rule"):
            assert op.attrs["op_namescope"].endswith(".mixer.delta")
        if op.type == "mul" and op.inputs["Y"][0].endswith(
                ("_qkvz_w", "_ba_w", "_o_w")):
            assert op.attrs["op_namescope"].endswith(".mixer")


def test_the_fp8_control_of_the_delta_cell_misses_what_float32_meets():
    """The reference with float8 contraction inputs (the state's two reads
    among them) in the program's place: far outside the tiny limits; the
    same comparison of the reference with itself reads zero."""
    from chipbench import check

    sizes = qwen_sizes()
    weights = Q_REF.init_params(7, sizes)
    feed = Q_BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    numbers = check.control(Q_REF, sizes, weights, feed, jnp.float8_e4m3fn)
    assert check.decide(numbers, sizes["limits"]) is False
    assert numbers["grad_rel"] > 3 * sizes["limits"]["grad_rel"]


@pytest.mark.parametrize("routed,held,k", [(32, 4, 5), (16, 8, 3)])
def test_the_shares_and_the_gated_shared_expert_once_add_up_to_the_layer(
        routed, held, k):
    """A softmax router ``routed`` wide with ``k`` a token, ``held`` by
    each of ``routed / held`` chips: the program's shares, and the shared
    expert behind its sigmoid gate counted ONCE, add up to what the cell's
    reference gives for the UNCUT layer (``experts`` with every expert
    held)."""
    rng = np.random.RandomState(2)
    x, wr, w1, w3, w2 = moe_weights(rng, 48, 16, 8, routed)
    s1, s3, s2 = (jnp.asarray(0.3 * rng.randn(*s), jnp.float32)
                  for s in ((16, 8), (16, 8), (8, 16)))
    wsg = jnp.asarray(rng.randn(16, 1), jnp.float32)
    c = {"k": k, "offset": 0}
    with jax.default_matmul_precision("highest"):
        whole = Q_REF.experts(x, (s1, s3, s2, wsg, wr, w1, w3, w2), c,
                              lambda a: a)
        gated = jax.nn.sigmoid(x @ wsg) * Q_REF.feed_forward(x, s1, s3, s2)
        total = gated
        for off in range(0, routed, held):
            part = moe.routed_experts(
                x, wr, w1[off:off + held], w3[off:off + held],
                w2[off:off + held], top_k=k, expert_offset=off)
            mine = Q_REF.routed(x, wr, w1[off:off + held],
                                w3[off:off + held], w2[off:off + held], k,
                                off)
            np.testing.assert_allclose(part, mine, atol=1e-5)
            total = total + part
    np.testing.assert_allclose(total, whole, atol=3e-5)
    assert float(jnp.abs(whole - gated).max()) > 0.1 \
        and float(jnp.abs(gated).max()) > 0.1


#: the two other decoder programs at their tiny sizes as they stood before
#: the delta mixer: Instella's, and ``decoder_lm.build()``'s own default
MORE_PROGRAMS_BEFORE = {
    "instella_moe_16b_a3b": (504, "7a603a8af2c505857474e1823bd3d412"
                                  "9de0ef9bfad2e68a66506c8b9c8ae455"),
    "tiny_config": (142, "ea22fd7171f49fe2ec0ac217b545cb95"
                         "affd0975690a1a2a9e51a1e8662ed90f"),
}


@pytest.mark.parametrize("config", sorted(MORE_PROGRAMS_BEFORE))
def test_programs_without_delta_rotary_dims_and_shared_gate_are_unchanged(
        config):
    """``delta``, ``rotary_dims``, ``shared_gate`` unset: with the three
    programs of ``PROGRAMS_BEFORE`` (whose digests hold too), the five
    older programs are op for op what they were on the commit before."""
    from paddle_tpu.models import decoder_lm

    if config == "tiny_config":
        decoder_lm.build()
    else:
        build, _, sizes = decoder_reference.load(config)
        build.build(fluid, sizes())
    ops, digest = program_digest()
    assert digest == MORE_PROGRAMS_BEFORE[config]
    assert not {"gated_delta_rule"} & {o.type for o in ops}
    assert not any("gated" in op.attrs for op in ops)
    assert not counters("models.decoder.blocks{mixer=\"delta\"")


def test_config_refuses_a_delta_layer_it_cannot_build():
    from paddle_tpu.models import decoder_lm

    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim=16, expert_width=32, num_routed=8,
                experts_held=4, experts_per_token=2)
    delta = decoder_lm.Delta(key_heads=2, value_heads=4, key_dim=8,
                             value_dim=8)
    cfg = decoder_lm.Config(**base, mixers=["delta", "attention"],
                            delta=delta, rotary_dims=4, shared_width=32,
                            shared_gate=True)
    assert cfg.delta == delta and (delta.taps, delta.chunk) == (4, 64)
    assert [cfg.layer_mixer(i) for i in range(2)] == ["delta", "attention"]
    assert decoder_lm.Config(**base, delta=tuple(delta)).delta == delta
    plain = decoder_lm.Config(**base)
    assert (plain.delta, plain.rotary_dims, plain.shared_gate) == (
        None, 0, False)
    with pytest.raises(ValueError, match="needs the record `delta`"):
        decoder_lm.Config(**base, mixers=["delta"] * 2)
    with pytest.raises(ValueError, match="a multiple of the key"):
        decoder_lm.Config(**base, mixers=["delta"] * 2,
                          delta=delta._replace(value_heads=3))
    with pytest.raises(ValueError, match="hold a token at least"):
        decoder_lm.Config(**base, mixers=["delta"] * 2,
                          delta=delta._replace(chunk=0))
    for wrong in (3, 18, -2):
        with pytest.raises(ValueError, match="an even part of the head"):
            decoder_lm.Config(**base, rotary_dims=wrong)
    with pytest.raises(ValueError, match="needs shared_width"):
        decoder_lm.Config(**base, shared_gate=True)
    assert "delta" in decoder_lm.MIXERS


# == three window layers to one global layer whose rotary is a YaRN table ==
# == of its own at a softmax scale of its own, a softmax router and no    ==
# == other feed-forward: the program against the reference of             ==
# == ``chipbench/configs/mellum2_12b_a2_5b``                              ==

MELLUM = "configs/mellum2_12b_a2_5b"
M_BUILD, M_REF, mellum_sizes = decoder_reference.load("mellum2_12b_a2_5b")


@pytest.mark.parametrize("flash", ["xla", "pallas"])
def test_rotary_by_layer_kind_program_equals_the_reference_and_its_adam_step(
        monkeypatch, flash):
    """Loss, every gradient and every parameter after one Adam step through
    ``fluid.Executor`` with ``optimizer.minimize``: three window layers of
    16 keys over 64 tokens (a band of 3 tiles of 8, one interior, as 1,024
    over tiles of 512) that rotate by ``rope_theta``, then the global layer
    that rotates by the YaRN table at the YaRN scale; the reference makes
    both tables itself.  With the two tables SWAPPED in the reference the
    same gradients are far off."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1" if flash == "pallas" else "0")
    monkeypatch.setattr(psf, "BLOCK", 8)
    sizes = mellum_sizes()
    assert sizes["seq_len"] == 4 * sizes["sliding_window"]
    assert sizes["num_experts"] < sizes["published"]["num_experts"]
    assert sizes["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    built, names, weights = seeded_program(M_BUILD, M_REF, sizes)
    main, scope = fluid.default_main_program(), fluid.global_scope()
    assert {n[3:] for n in names if n.startswith("l3_")} == {
        "attn_norm", "q_w", "q_norm", "k_w", "k_norm", "v_w", "o_w",
        "moe_norm", "router_w", "w1", "w3", "w2"}
    feed = M_BUILD.make_feed(sizes, 2, np.random.RandomState(3))
    outs = fluid.Executor(fluid.TPUPlace()).run(
        main, feed=feed, fetch_list=[built["loss"]]
        + [n + "@GRAD" for n in names])
    ref_loss, ref_grads, _ = reference_step(M_REF, sizes, weights, feed)
    assert float(outs[0].reshape(-1)[0]) == pytest.approx(float(ref_loss),
                                                          rel=1e-5)
    for name, g, r in zip(names, outs[1:], ref_grads):
        g = np.asarray(g).reshape(r.shape)
        assert np.abs(g - r).max() <= 3e-4 * np.abs(r).max() + 1e-7, name
        assert np.abs(r).max() > 0, name
    adam = compiled(M_REF, "optimizer_step", sizes)
    for name, w, g in zip(names, weights, outs[1:]):
        np.testing.assert_allclose(
            np.asarray(scope.get(name)).reshape(w.shape),
            adam(w, jnp.asarray(g).reshape(w.shape)), atol=2e-6,
            err_msg=name)
    # the tables the other way round: no such model
    rope = sizes["rope_parameters"]
    swapped = {**sizes, "rope_parameters": {
        "full_attention": rope["sliding_attention"],
        "sliding_attention": rope["full_attention"]}}
    from chipbench import check

    def grad_rel(loss, grads):      # the number that decides ``correct``
        return float(check._errors(outs[0], outs[1:], loss,
                                   grads)["grad_rel"])

    assert grad_rel(ref_loss, ref_grads) < 1e-4
    assert grad_rel(*reference_step(M_REF, swapped, weights, feed)[:2]) > 0.1
    # what ran, as the counters say it
    per = 1 if flash == "pallas" else 2
    assert counters("ops.sparse_attention.calls") == {
        f'ops.sparse_attention.calls{{path="{flash}",seq="64",topk="0",'
        f'window="16"}}': 3 * per,
        f'ops.sparse_attention.calls{{path="{flash}",seq="64",'
        f'topk="0"}}': per}
    assert not counters("ops.sparse_attention.declined")
    assert counters("ops.rotary.calls") == {
        'ops.rotary.calls{dims="16",pairing="half",scaled="0"}': 6,
        'ops.rotary.calls{dims="16",pairing="half",scaled="1"}': 2}
    assert counters("models.decoder.rotary") == {
        'models.decoder.rotary{kind="global",scope="layer3",'
        'table="given"}': 1}
    assert counters("models.decoder.blocks") == {
        'models.decoder.blocks{mixer="attention",residual="sequential",'
        'where="trunk"}': 4}
    (key, n), = counters("ops.moe.calls").items()
    assert "score" not in key and 'routed="8"' in key and 'held="4"' in key \
        and n == 2 * 4
    if flash == "pallas":       # 8 rows of tiles: 1 + 2 + 6 x 3, 7 interior
        tiles = counters("ops.sparse_attention.tiles")
        for kernel in ("fwd", "dq", "dkv"):
            assert [tiles[f'ops.sparse_attention.tiles{{kernel="window_flash_'
                          f'{kernel}",kind="{kind}"}}']
                    for kind in ("interior", "edge")] == [3 * 7, 3 * 14]
    # every op under a name; all of the global layer's mixer but the
    # residual add under ``.global``, no window layer's
    scopes = {op.attrs.get("op_namescope", "") for op in main.all_ops()}
    assert {"embed", "head", "layer3.mixer.global"} | {
        f"layer{i}.{part}" for i in range(4)
        for part in ("mixer", "ffn")} == scopes


def test_window_layers_lower_theta_and_the_global_layer_the_record():
    """A window layer's two rotaries carry ``theta`` and no table, its
    attention ``head_dim ** -0.5``; the global layer's carry the record's
    frequencies, its attention the record's scale."""
    sizes = mellum_sizes()
    M_BUILD.build(fluid, sizes)
    record = M_BUILD.config_of(sizes).global_rotary
    assert record == M_BUILD.global_rotary(sizes)
    block = fluid.default_main_program().global_block()
    seen = {"window": 0, "global": 0}
    for op in block.ops:
        if op.type not in ("rotary_embedding", "sparse_attention"):
            continue
        own = op.attrs["op_namescope"] == "layer3.mixer.global"
        seen["global" if own else "window"] += 1
        if op.type == "rotary_embedding":
            assert op.attrs["theta"] == 100.0
            assert op.attrs.get("inv_freq") == (
                list(record.inv_freq) if own else None)
        else:
            assert op.attrs.get("window", 0) == (0 if own else 16)
            assert op.attrs["scale"] == pytest.approx(
                record.scale if own else 16 ** -0.5, rel=1e-12)
    assert seen == {"window": 9, "global": 3}
    assert record.scale == pytest.approx(0.25 * 1.63139, rel=1e-5)


def test_the_yarn_table_by_hand():
    """Mellum2's ``full_attention`` table at the published sizes: pairs
    0-18 turn more than 32 times over 8,192 positions and stay as they are,
    pairs 35-63 turn less than once and are divided by 16, a linear blend
    between; the temperature is ``(0.1 ln 16 + 1) ** 2`` on ``128 **
    -0.5``.  The builder's record and the reference's own table agree."""
    sizes = json.load(open(os.path.join(ROOT, "chipbench", MELLUM,
                                        "config.json")))
    record = M_BUILD.config_of(sizes).global_rotary
    plain = [500000.0 ** (-2.0 * j / 128) for j in range(64)]
    assert len(record.inv_freq) == 64
    np.testing.assert_allclose(record.inv_freq[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(record.inv_freq[35:],
                               [f / 16 for f in plain[35:]], rtol=1e-12)
    for j in range(19, 35):
        r = (j - 18) / 17
        assert record.inv_freq[j] == pytest.approx(
            plain[j] / 16 * r + plain[j] * (1 - r), rel=1e-12)
        assert plain[j] / 16 < record.inv_freq[j] < plain[j]
    m = 1.2772588722239782
    assert m == pytest.approx(0.1 * math.log(16) + 1, rel=1e-15)
    assert m * m == pytest.approx(1.63139, rel=1e-5)
    assert record.scale == pytest.approx(128 ** -0.5 * m * m, rel=1e-12)
    table, factor = M_REF.rotary_table(
        sizes["rope_parameters"]["full_attention"], 128)
    np.testing.assert_allclose(table, record.inv_freq, rtol=1e-12)
    assert factor == m
    assert M_REF.rotary_table(
        sizes["rope_parameters"]["sliding_attention"], 128) == (
            tuple(plain), 1.0)
    rope = sizes["rope_parameters"]
    with pytest.raises(ValueError, match="is not the 0.1 ln"):
        M_BUILD.config_of({**sizes, "rope_parameters": {
            **rope, "full_attention": {**rope["full_attention"],
                                       "attention_factor": 1.2}}})
    with pytest.raises(ValueError, match="a plain table for the window"):
        M_BUILD.config_of({**sizes, "rope_parameters": {
            **rope, "sliding_attention": rope["full_attention"]}})


def test_config_refuses_a_global_rotary_it_cannot_build():
    from paddle_tpu.models import decoder_lm

    base = dict(vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
                num_kv_heads=2, head_dim=16, expert_width=32, num_routed=8,
                experts_held=4, experts_per_token=2, window=16,
                global_every=4)
    record = decoder_lm.Rotary(inv_freq=tuple(0.5 ** j for j in range(8)),
                               scale=0.3)
    cfg = decoder_lm.Config(**base, global_rotary=record)
    assert cfg.global_rotary == record
    assert decoder_lm.Config(
        **base, global_rotary=(list(record.inv_freq), 0.3)
    ).global_rotary == record
    assert decoder_lm.Rotary(record.inv_freq).scale == 0.0
    assert decoder_lm.Config(**base).global_rotary is None
    with pytest.raises(ValueError, match="goes without positions"):
        decoder_lm.Config(**base, rope_global=False, global_rotary=record)
    with pytest.raises(ValueError, match="each of the 8 pairs"):
        decoder_lm.Config(**base, global_rotary=record._replace(
            inv_freq=record.inv_freq[:4]))
    assert decoder_lm.Config(
        **base, rotary_dims=8, global_rotary=record._replace(
            inv_freq=record.inv_freq[:4])).global_rotary.scale == 0.3


@pytest.mark.parametrize("config", sorted(PROGRAMS_BEFORE)
                         + sorted(MORE_PROGRAMS_BEFORE)
                         + ["qwen3_next_80b_a3b"])
def test_programs_without_a_global_rotary_have_no_scope_or_counter_of_it(
        config):
    """``global_rotary`` unset: no op under ``.global``, no rotary from a
    table in a plain attention layer, no ``models.decoder.rotary`` (the six
    older programs' digests, above, hold too)."""
    from paddle_tpu.models import decoder_lm

    if config == "tiny_config":
        decoder_lm.build()
    else:
        build, _, sizes = decoder_reference.load(config)
        build.build(fluid, sizes())
    ops = list(fluid.default_main_program().all_ops())
    assert ops and not any(
        op.attrs.get("op_namescope", "").endswith(".global") for op in ops)
    assert not counters("models.decoder.rotary")


# == delta mixers whose decay is a vector a key channel, with the decay and ==
# == a sigmoid output gate each through a rank, beside a latent layer       ==
# == without positions or head norms whose values are narrower than its     ==
# == keys: the program against the reference of                             ==
# == ``chipbench/configs/kimi_linear_48b_a3b``                              ==

K_BUILD, K_REF, kimi_sizes = decoder_reference.load("kimi_linear_48b_a3b")


def test_channel_decay_and_a_narrow_value_program_equals_the_reference(
        monkeypatch):
    """Loss, every gradient, every parameter after one Adam step and every
    router's bias after its rule through ``fluid.Executor`` with
    ``optimizer.minimize``: the dense layer, then delta, delta, latent,
    delta; four chunks of 16 in sub-blocks of 4 against the reference's
    token-by-token recurrence; keys 16 wide and values 12.  The flash gate
    is OPEN: the kernels decline the value's width and say so."""
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")
    sizes = kimi_sizes()
    assert sizes["v_head_dim"] != sizes["qk_nope_head_dim"] \
        + sizes["qk_rope_head_dim"]
    assert sizes["seq_len"] == 4 * sizes["delta_chunk"] == 64
    built, names, weights = seeded_program(K_BUILD, K_REF, sizes)
    main, scope = fluid.default_main_program(), fluid.global_scope()
    assert {n[3:] for n in names if n.startswith("l1_")} == {
        "attn_norm", "qkv_w", "b_w", "g1_w", "g2_w", "conv_w", "f1_w",
        "f2_w", "dt_bias", "a_log", "delta_norm", "o_w", "moe_norm",
        "shared_w1", "shared_w3", "shared_w2", "router_w", "w1", "w3", "w2"}
    assert {n[3:] for n in names if n.startswith("l3_")} >= {
        "q_w", "kva_w", "kv_norm", "kvb_w", "o_w"}
    assert not {"l3_q_norm", "l3_k_norm", "l3_gate_w", "l0_qkvz_w",
                "l0_ba_w"} & set(names)
    feed = K_BUILD.make_feed(sizes, 1, np.random.RandomState(3))
    outs = fluid.Executor(fluid.TPUPlace()).run(
        main, feed=feed, fetch_list=[built["loss"]]
        + [n + "@GRAD" for n in names])
    ref_loss, ref_grads, want = reference_step(K_REF, sizes, weights, feed)
    assert float(outs[0].reshape(-1)[0]) == pytest.approx(float(ref_loss),
                                                          rel=1e-5)
    for name, g, r in zip(names, outs[1:], ref_grads):
        g = np.asarray(g).reshape(r.shape)
        assert np.abs(g - r).max() <= 3e-4 * np.abs(r).max() + 1e-7, name
        assert np.abs(r).max() > 0, name
    adam = compiled(K_REF, "optimizer_step", sizes)
    for name, w, g in zip(names, weights, outs[1:]):
        np.testing.assert_allclose(
            np.asarray(scope.get(name)).reshape(w.shape),
            adam(w, jnp.asarray(g).reshape(w.shape)), atol=2e-6,
            err_msg=name)
    assert len(want) == 4
    for i, b in enumerate(want, start=1):
        np.testing.assert_array_equal(scope.get(f"l{i}_route_bias"), b)
        assert float(jnp.abs(b).max()) == pytest.approx(
            sizes["assumed"]["bias_update_rate"])
    # what ran, as the counters say it
    assert counters("ops.delta_rule") == {
        'ops.delta_rule.calls{chunk="16",dim="8",key_heads="4",path="xla",'
        'value_heads="4"}': 4,
        'ops.delta_rule.channel_calls{chunk="16",dim="8",key_heads="4",'
        'sub="4"}': 4,
        'ops.delta_rule.grad_calls{chunk="16",path="by_hand"}': 4}
    assert counters("ops.sparse_attention") == {
        'ops.sparse_attention.calls{path="xla",seq="64",topk="0"}': 2,
        'ops.sparse_attention.declined{why="value_width"}': 2}
    assert counters("models.decoder.delta") == {
        'models.decoder.delta{decay="channel",gate="sigmoid"}': 4}
    assert counters("models.decoder.latent") == {
        'models.decoder.latent{head_norm="0",rotary="0",value="12"}': 1}
    assert not counters("ops.rotary")
    # every op under a name: the pairs through the rank beneath ``.delta``
    scopes = {op.attrs.get("op_namescope", "") for op in main.all_ops()}
    assert {"embed", "head", "layer3.mixer.latent"} | {
        f"layer{i}.{part}" for i in range(5) for part in ("mixer", "ffn")
    } | {f"layer{i}.mixer.delta{g}" for i in (0, 1, 2, 4)
         for g in ("", ".gates")} == scopes
    gates = [op for op in main.global_block().ops
             if op.attrs.get("op_namescope") == "layer1.mixer.delta.gates"]
    assert {"mul", "sigmoid", "softplus", "exp", "mul_grad",
            "softplus_grad", "adam"} <= {op.type for op in gates}
    assert "gated_delta_rule" not in {op.type for op in gates}


def test_config_takes_the_delta_gates_and_latent_fields_and_refuses_the_rest():
    from paddle_tpu.models import decoder_lm

    base = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                num_kv_heads=4, head_dim=16, expert_width=32, num_routed=8,
                experts_held=4, experts_per_token=2, mixers=["delta"] * 2,
                delta=decoder_lm.Delta(key_heads=2, value_heads=4, key_dim=8,
                                       value_dim=8, chunk=16))
    plain = decoder_lm.Config(**base)
    assert tuple(plain.delta_gates) == (0, "silu", 0)
    assert len(plain.delta) == 6                # the record PR 44 gave it
    ranked = decoder_lm.DeltaGates(decay_rank=8, gate="sigmoid", gate_rank=8)
    cfg = decoder_lm.Config(**base, delta_gates=tuple(ranked))
    assert cfg.delta_gates == ranked
    assert decoder_lm.GATES == ("silu", "sigmoid")
    for wrong in (ranked._replace(gate="tanh"),
                  ranked._replace(decay_rank=-1),
                  ranked._replace(gate_rank=-8)):
        with pytest.raises(ValueError, match="a rank is 0 or more"):
            decoder_lm.Config(**base, delta_gates=wrong)
    latent = decoder_lm.Latent(rank=32, nope=8, rope=8, value=12)
    assert (latent.rotary, latent.head_norm) == (True, True)
    bare = latent._replace(rotary=False, head_norm=False)
    assert decoder_lm.Config(**{**base, "mixers": ["latent"] * 2},
                             latent=tuple(bare)).latent == bare


@pytest.mark.parametrize("decay_rank,gate,gate_rank", [
    (0, "sigmoid", 0), (4, "silu", 0), (0, "silu", 4)])
def test_each_new_delta_field_alone_builds_and_trains(decay_rank, gate,
                                                      gate_rank):
    """The three fields do not need each other: a channel decay beside the
    gate from the projection in, a ranked gate beside a scalar decay, a
    sigmoid on the projection's own gate; one step, finite, and the
    parameters each asks for."""
    from paddle_tpu.models import decoder_lm

    cfg = decoder_lm.Config(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=2, head_dim=8, expert_width=16, num_routed=4,
        experts_held=2, experts_per_token=2, mixers=["delta"],
        dense_layers=1, dense_width=16,     # the mixer is what is new
        delta=decoder_lm.Delta(2, 4, 8, 8, chunk=8),
        delta_gates=decoder_lm.DeltaGates(decay_rank, gate, gate_rank))
    _, _, loss = decoder_lm.build(cfg, seq_len=16)
    names = {p.name for p in
             fluid.default_main_program().global_block().all_parameters()}
    assert ("l0_f1_w" in names) == ("l0_b_w" in names) == bool(decay_rank)
    assert ("l0_g1_w" in names) == ("l0_qkv_w" in names) == bool(gate_rank)
    assert ("l0_ba_w" in names) == (not decay_rank)
    assert ("l0_qkvz_w" in names) == (not gate_rank)
    rule, = [op for op in fluid.default_main_program().global_block().ops
             if op.type == "gated_delta_rule"]
    assert "sub" not in rule.attrs
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    ids = np.random.RandomState(0).randint(0, 64, (2, 17)).astype(np.int64)
    first, = exe.run(feed={"tokens": ids[:, :-1], "labels": ids[:, 1:, None]},
                     fetch_list=[loss])
    assert np.isfinite(first).all()
    assert counters("models.decoder.delta") == {
        'models.decoder.delta{decay="%s",gate="%s"}' % (
            "channel" if decay_rank else "scalar", gate): 1}


def test_attention_takes_values_narrower_than_its_keys_and_says_why_not():
    """The blocked XLA path against a plain softmax over the causal half at
    keys 24 wide and values 16, through the op with its gradients; the
    kernels decline such a value, and one whose batch, heads or length are
    not the key's, each with a reason."""
    b, h, t, d, dv = 2, 3, 40, 24, 16
    rng = np.random.RandomState(0)
    q, k = (jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, h, t, dv), jnp.float32)
    assert psf.supported(q, k, None, 0, v) == "value_width"
    assert psf.supported(q, k, None, 0, k) == psf.supported(q, k, None) == ""
    for wrong in (v[:1], v[:, :1], v[:, :, :32]):
        assert psf.supported(q, k, None, 0, wrong) == "shape"

    def plain(q, k, v):
        s = jnp.einsum("bhqd,bhsd->bhqs", q, k) * d ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqs,bhsd->bhqd", jax.nn.softmax(s, -1), v)

    got = jax.jit(lambda *a: decoder_ops.blocked_attention(
        *a, None, d ** -0.5, block=16))(q, k, v)
    assert got.shape == (b, h, t, dv)
    np.testing.assert_allclose(got, jax.jit(plain)(q, k, v), atol=2e-6)
    data = [layers.data(name=n, shape=list(a.shape[1:]), dtype="float32")
            for n, a in (("q", q), ("k", k), ("v", v))]
    for x in data:
        x.stop_gradient = False
    out = layers.sparse_attention(*data)
    assert tuple(out.shape[1:]) == (h, t, dv)
    fluid.backward.append_backward(layers.reduce_sum(layers.square(out)))
    got = fluid.Executor(fluid.TPUPlace()).run(
        feed={"q": np.asarray(q), "k": np.asarray(k), "v": np.asarray(v)},
        fetch_list=[out, "q@GRAD", "k@GRAD", "v@GRAD"])
    np.testing.assert_allclose(got[0], jax.jit(plain)(q, k, v), atol=2e-6)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(plain(*a) ** 2),
                            (0, 1, 2)))(q, k, v)
    for g, w in zip(got[1:], want):
        np.testing.assert_allclose(g, w, atol=2e-5)
    rule = registry.get_infer_rule("sparse_attention")

    class Op:
        type, inputs = "sparse_attention", {s: [s.lower()] for s in "QKV"}

        def attr(self, name, default=None):
            return default

    shapes = {"Q": [((b, h, t, d), "float32")], "K": [((b, h, t, d),
                                                       "float32")]}
    assert rule(Op(), {**shapes, "V": [((b, h, t, dv), "float32")]})[
        "Out"] == [((b, h, t, dv), "float32")]
    with pytest.raises(registry.InferMismatch, match="but for their width"):
        rule(Op(), {**shapes, "V": [((b, h, t - 8, dv), "float32")]})


#: the two youngest of the six older programs at their tiny sizes, as they
#: stood before the channel decay, the ranked gates and the latent layer's
#: two switches
LATER_PROGRAMS_BEFORE = {
    "qwen3_next_80b_a3b": (382, "a62773b7549c85aa463e32e59ed46c89"
                                "85e703931984953b514286f66aaeabdf"),
    "mellum2_12b_a2_5b": (250, "449a1fcaf8188cd4977b10457bdd7381"
                               "25f871cc93c6a57dc9e0c9e191a9e625"),
}


@pytest.mark.parametrize("config", sorted(LATER_PROGRAMS_BEFORE))
def test_programs_without_a_decay_rank_or_a_bare_latent_are_unchanged(
        config):
    """``delta_gates`` unset and ``Latent.rotary``, ``head_norm`` at their
    defaults: with the five programs of
    ``PROGRAMS_BEFORE`` and ``MORE_PROGRAMS_BEFORE`` (whose digests hold
    too, Instella's latent layers among them), the six older programs are
    op for op what they were on the commit before: no op under ``.gates``,
    and the counters they emitted keep their names and label sets."""
    build, _, sizes = decoder_reference.load(config)
    build.build(fluid, sizes())
    ops, digest = program_digest()
    assert digest == LATER_PROGRAMS_BEFORE[config]
    assert not any(op.attrs.get("op_namescope", "").endswith(".gates")
                   for op in ops)
    assert not counters('models.decoder.delta{decay="channel"')
    assert not counters("models.decoder.latent")
    if config == "qwen3_next_80b_a3b":
        assert counters("models.decoder.delta") == {
            'models.decoder.delta{decay="scalar",gate="silu"}': 3}
        assert counters("models.decoder.blocks") == {
            'models.decoder.blocks{mixer="delta",residual="sequential",'
            'where="trunk"}': 3,
            'models.decoder.blocks{mixer="attention",residual="sequential",'
            'where="trunk"}': 1}
