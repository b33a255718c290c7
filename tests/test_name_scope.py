"""``fluid.name_scope`` made real: the path an op carries, who inherits it
(grad ops, gradient sums, the loss seed, a parameter's update, a pass's
replacement), and that it reaches the compiled step as metadata ONLY, one
segment beneath the op type."""

import os
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework
from paddle_tpu.fluid.framework import NAME_SCOPE_ATTR as ATTR
from paddle_tpu.fluid.framework import NAME_SCOPE_MARK as MARK

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def path_of(op):
    return op.attr(ATTR, "")


def ops_of(program=None):
    return (program or fluid.default_main_program()).global_block().ops


def by_type(t, program=None):
    return [op for op in ops_of(program) if op.type == t]


# -- the stack --------------------------------------------------------------

def test_nested_scopes_join_with_a_dot_and_close():
    assert framework.current_name_scope() == ""
    with fluid.name_scope("stage2"):
        with fluid.name_scope("block1"):
            assert framework.current_name_scope() == "stage2.block1"
        assert framework.current_name_scope() == "stage2"
        with fluid.name_scope("a.b"):
            assert framework.current_name_scope() == "stage2.a.b"
        with fluid.name_scope():            # the reference's default
            assert framework.current_name_scope() == "stage2"
    assert framework.current_name_scope() == ""


def test_an_exception_closes_the_scope_and_fresh_session_resets():
    with pytest.raises(RuntimeError):
        with fluid.name_scope("left"):
            raise RuntimeError("out")
    assert framework.current_name_scope() == ""
    cm = fluid.name_scope("open")
    cm.__enter__()
    assert framework.current_name_scope() == "open"
    framework.fresh_session()
    assert framework.current_name_scope() == ""


def test_name_scope_at_is_absolute_and_restores():
    with fluid.name_scope("outer"):
        with framework.name_scope_at("elsewhere.deep"):
            assert framework.current_name_scope() == "elsewhere.deep"
            with fluid.name_scope("x"):
                assert framework.current_name_scope() == "elsewhere.deep.x"
        assert framework.current_name_scope() == "outer"
        with framework.name_scope_at(""):
            assert framework.current_name_scope() == ""
        assert framework.current_name_scope() == "outer"


# -- what carries the path --------------------------------------------------

def mlp(clip=None, decay=None, shared=False):
    """body.l1 (fc+relu) -> [body.l2 with the SAME weight] -> head."""
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    attr = fluid.ParamAttr(name="w1", regularizer=decay,
                           gradient_clip=clip)
    with fluid.name_scope("body"):
        with fluid.name_scope("l1"):
            h = fluid.layers.fc(x, 16, act="relu", param_attr=attr,
                                bias_attr=False)
        if shared:
            with fluid.name_scope("l2"):
                h = fluid.layers.fc(h, 16, param_attr=attr, bias_attr=False)
    with fluid.name_scope("head"):
        p = fluid.layers.fc(h, 4, act="softmax",
                            param_attr=fluid.ParamAttr(name="w2"))
        loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
    return loss


def test_no_scope_open_no_attribute():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    fluid.layers.fc(x, 2)
    assert all(not op.has_attr(ATTR) for op in ops_of())


def test_forward_ops_and_initializers_carry_the_path():
    mlp()
    assert [(op.type, path_of(op)) for op in ops_of()] == [
        ("mul", "body.l1"), ("relu", "body.l1"), ("mul", "head"),
        ("elementwise_add", "head"), ("softmax", "head"),
        ("cross_entropy", "head"), ("mean", "head")]
    startup = fluid.default_startup_program().global_block().ops
    assert [path_of(op) for op in startup] == ["body.l1", "head", "head"]
    w1 = fluid.default_main_program().global_block().var("w1")
    assert w1.name_scope == "body.l1"


def test_grad_ops_the_seed_and_the_updates():
    loss = mlp()
    fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    grads = [op for op in ops_of() if op.type.endswith("_grad")]
    assert len(grads) == 7
    for g in grads:
        fwd = ops_of()[g.attr("__fwd_op_idx__")]
        assert g.type == fwd.type + "_grad" and path_of(g) == path_of(fwd)
    assert {path_of(g) for g in grads} == {"body.l1", "head"}
    seed, = by_type("fill_any_like")
    assert seed.attr("__loss_seed__") and path_of(seed) == "head"
    updates = {op.inputs["Param"][0]: path_of(op)
               for op in by_type("momentum")}
    assert updates == {"w1": "body.l1", "w2": "head", "fc_1.w_0": "head"}
    assert all(path_of(op) for op in ops_of())
    startup = fluid.default_startup_program().global_block().ops
    velocity = {op.outputs["Out"][0]: path_of(op) for op in startup
                if op.outputs["Out"][0].startswith("velocity_")}
    assert velocity["velocity_w1_0"] == "body.l1"
    lr = [op for op in startup
          if op.outputs["Out"][0].startswith("learning_rate")]
    assert [path_of(op) for op in lr] == ["optimizer"]


def test_a_gradient_sum_goes_where_its_variable_was_made():
    loss = mlp(shared=True)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    sums = by_type("sum")
    # w1 feeds two layers: its partial gradients are summed for the
    # parameter, under the scope it was created in
    assert [(op.outputs["Out"][0], path_of(op)) for op in sums] == [
        ("w1@GRAD", "body.l1")]
    assert {op.inputs["Param"][0]: path_of(op) for op in by_type("sgd")}[
        "w1"] == "body.l1"


def test_a_sum_for_an_activation_takes_its_producers_scope():
    x = fluid.layers.data(name="x", shape=[8], dtype="float32")
    with fluid.name_scope("trunk"):
        h = fluid.layers.fc(x, 8)
    with fluid.name_scope("left"):
        a = fluid.layers.fc(h, 8)
    with fluid.name_scope("right"):
        b = fluid.layers.fc(h, 8)
    with fluid.name_scope("head"):
        loss = fluid.layers.mean(fluid.layers.elementwise_add(a, b))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    (s,) = by_type("sum")
    assert s.outputs["Out"][0] == h.name + "@GRAD"
    assert path_of(s) == "trunk"


def test_clip_decay_and_the_groups_own_ops():
    loss = mlp(clip=fluid.clip.GradientClipByGlobalNorm(1.0),
               decay=fluid.regularizer.L2Decay(1e-4))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    after = ops_of()[ops_of().index(by_type("mul_grad")[-1]) + 1:]
    got = [(op.type, path_of(op)) for op in after]
    # w1's own: the square and its sum, the scaled gradient, the decay
    assert ("elementwise_mul", "body.l1") in got
    assert ("reduce_sum", "body.l1") in got
    assert ("scale", "body.l1") in got and ("sgd", "body.l1") in got
    # the group's: the sum of norms, its root, the clip constant, the scale
    assert {t for t, p in got if p == "optimizer"} == {
        "sum", "sqrt", "fill_constant", "elementwise_max",
        "elementwise_div"}
    assert all(p for _, p in got)


def test_a_parameter_made_outside_any_scope_updates_under_optimizer():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    loss = fluid.layers.mean(fluid.layers.fc(x, 2))
    fluid.optimizer.Adam(learning_rate=0.1).minimize(loss)
    assert {path_of(op) for op in by_type("adam")} == {"optimizer"}
    assert all(not path_of(op) for op in ops_of()
               if op.type not in ("adam", "scale"))


def test_learning_rate_schedule_and_loss_scale_go_under_optimizer():
    from paddle_tpu.models import transformer

    fluid.amp.enable("float16")
    try:
        transformer.build(transformer.tiny_config(), src_len=8, tgt_len=8,
                          warmup_steps=100)
    finally:
        fluid.amp.disable()
    under = [op for op in ops_of() if path_of(op) == "optimizer"]
    assert {"increment", "elementwise_min"} <= {op.type for op in under}
    unscale = [op for op in by_type("elementwise_div")
               if fluid.amp.LOSS_SCALE_VAR in op.input_arg_names]
    assert unscale and all(
        path_of(op).split(".")[0] in ("embed", "encoder", "decoder", "head")
        for op in unscale)
    startup = fluid.default_startup_program().global_block().ops
    scale = [op for op in startup
             if op.outputs["Out"][0] == fluid.amp.LOSS_SCALE_VAR]
    assert [path_of(op) for op in scale] == ["optimizer"]
    assert all(path_of(op) for op in ops_of())


def test_clone_proto_round_trip_to_string_and_fingerprint():
    from paddle_tpu.compile_cache.fingerprint import program_fingerprint

    loss = mlp()
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    main = fluid.default_main_program()
    want = [path_of(op) for op in ops_of()]
    with fluid.name_scope("open_while_cloning"):
        clone = main.clone()
    assert [path_of(op) for op in ops_of(clone)] == want
    test = main.clone(for_test=True)
    assert [path_of(op) for op in ops_of(test)] == want[:7]
    back = fluid.Program.parse_from_string(main.serialize_to_string())
    assert [path_of(op) for op in ops_of(back)] == want
    assert "'op_namescope': 'body.l1'" in main.to_string()
    before = program_fingerprint(main, include_versions=False)
    assert program_fingerprint(clone, include_versions=False) == before
    by_type("relu", clone)[0]._set_attr(ATTR, "body.other")
    assert program_fingerprint(clone, include_versions=False) != before


# -- passes -----------------------------------------------------------------

def test_conv_bn_fuse_gives_the_add_the_norms_path():
    img = fluid.layers.data(name="img", shape=[3, 8, 8], dtype="float32")
    with fluid.name_scope("stem"):
        c = fluid.layers.conv2d(input=img, num_filters=4, filter_size=3,
                                padding=1, bias_attr=False)
        with fluid.name_scope("norm"):
            fluid.layers.batch_norm(input=c, act=None)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    infer = fluid.default_main_program().clone(for_test=True)
    fluid.InferenceTranspiler().transpile(infer, fluid.CPUPlace(),
                                          fluid.global_scope())
    assert [(op.type, path_of(op)) for op in ops_of(infer)] == [
        ("conv2d", "stem"), ("elementwise_add", "stem.norm")]


def test_int8_transpiler_gives_the_dequantize_its_consumers_path():
    from paddle_tpu.fluid.transpiler.int8_transpiler import (
        Int8WeightTranspiler)

    x = fluid.layers.data(name="x", shape=[64], dtype="float32")
    with fluid.name_scope("layer0.ffn"):
        fluid.layers.fc(x, 64, bias_attr=False)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    main = fluid.default_main_program()
    assert Int8WeightTranspiler(min_elements=32).transpile(main)
    assert [(op.type, path_of(op)) for op in ops_of()] == [
        ("dequantize_weight", "layer0.ffn"), ("mul", "layer0.ffn")]


def test_error_clip_takes_its_grad_ops_path():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    with fluid.name_scope("trunk"):
        h = fluid.layers.fc(x, 4)
        h.error_clip = fluid.clip.ErrorClipByValue(1.0)
    with fluid.name_scope("head"):
        loss = fluid.layers.mean(fluid.layers.fc(h, 1))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    (clip,) = by_type("clip")
    assert clip.inputs["X"] == [h.name + "@GRAD"]
    assert path_of(clip) == "head"      # made by the head's mul_grad


# -- the compiled step: metadata only, beneath the op type -------------------

def build_resnet():
    from paddle_tpu.models import resnet

    _, _, _, loss, _ = resnet.build(class_dim=10, depth=18,
                                    image_shape=(3, 64, 64))
    rng = np.random.RandomState(0)
    return loss, {"img": rng.normal(size=(2, 3, 64, 64)).astype(np.float32),
                  "label": rng.randint(0, 10, size=(2, 1)).astype(np.int64)}


def build_transformer():
    from paddle_tpu.models import transformer

    cfg = transformer.tiny_config()
    loss = transformer.build(cfg, src_len=16, tgt_len=16)[3]
    rng = np.random.RandomState(0)
    ids = rng.randint(1, cfg.src_vocab_size, size=(2, 16)).astype(np.int64)
    return loss, {"src_word": ids, "tgt_word": ids,
                  "lbl_word": ids[..., None]}


def build_decoder():
    from paddle_tpu.models import decoder_lm

    cfg = decoder_lm.tiny_config()
    loss = decoder_lm.build(cfg, seq_len=32)[2]
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(2, 33)).astype(np.int64)
    return loss, {"tokens": ids[:, :-1], "labels": ids[:, 1:, None]}


def lowered_step(build):
    framework.fresh_session()
    loss, feed = build()
    main = fluid.default_main_program()
    main.random_seed = fluid.default_startup_program().random_seed = 3
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return main, exe.lower_step(main, feed, [loss])


@pytest.mark.parametrize("build", [build_resnet, build_transformer,
                                   build_decoder],
                         ids=["resnet", "transformer", "decoder_lm"])
def test_names_are_metadata_only_and_sit_beneath_the_op_type(
        build, monkeypatch):
    from chipbench import hlo, scope_time

    main, lowered = lowered_step(build)
    assert all(path_of(op) for op in ops_of(main))
    text = lowered.as_text()
    compiled = lowered.compile().as_text()

    monkeypatch.setattr(framework, "current_name_scope", lambda: "")
    bare_main, bare = lowered_step(build)
    assert not any(op.has_attr(ATTR) for op in ops_of(bare_main))
    assert bare.as_text() == text           # byte-equal

    # every op_name with a marked segment has the op type right before it
    types = {op.type for op in main.all_ops()}
    marked = 0
    for line in compiled.splitlines():
        at = line.find('op_name="')
        if at < 0:
            continue
        segments = [s for s in line[at + 9:line.find('"', at + 9)].split("/")
                    if s]
        where = [i for i, s in enumerate(segments) if s.startswith(MARK)]
        if where:
            marked += 1
            assert segments[where[0] - 1] in types, segments
            assert segments[where[0]][1:] in {path_of(op) for op in
                                              main.all_ops()}
    assert marked > 50
    # the first level is what it was: op types and arguments' own names
    first = set(hlo.instruction_scopes(compiled).values())
    assert not any(MARK in s for s in first)
    assert first == set(hlo.instruction_scopes(
        bare.compile().as_text()).values())
    assert first & types and {"mut_state", "const_state"} <= first
    # and the second is there for the readers
    paths = {p for _, p in scope_time.paths_of(compiled).values() if p}
    assert {p.split(".")[0] for p in paths} >= {"head"}
    assert len(paths) > 3
