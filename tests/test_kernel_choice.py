"""Kernel or twin (``ops/kernel_choice.py``): for every gated family of
Pallas kernels that a CPU can lower, and every state of its group's switch,
the step a tiny program lowers to holds the family's ``pallas_call`` or does
not, and the family's counter says the same.  For the grouped products, which
have no gate, the two classes of shapes stand in the switch's place.  And a
guard: no layer has a ``flash`` / ``fused`` argument and no op reads an
attribute of either name, so the choice stays where it is.

On the CPU a kernel is interpreted and leaves no custom call behind; what
the lowered text keeps of it, with debug information, is its name stack:
the op that made it, the ``pallas_call``'s name where it states one, and
``pallas_call`` (function names in locations are no evidence: jax keeps the
locations of a traced helper from whichever program traced it first).
"""

import inspect
import os
import re

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import kernel_choice


def counters(prefix):
    return {k: v for k, v in fluid.profiler.counters().items()
            if k.startswith(prefix)}


def grown(before, prefix):
    return {k: v - before.get(k, 0) for k, v in counters(prefix).items()
            if v != before.get(k, 0)}


def lowered(feed, fetch, program=None):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe.lower_step(program or fluid.default_main_program(), feed,
                          fetch).as_text(debug_info=True)


# -- one tiny program a family: (feed, fetch) --------------------------------

def _qkv(t, hq=4, hkv=2, d=16):
    q = layers.data(name="q", shape=[hq, t, d], dtype="float32")
    k = layers.data(name="k", shape=[hkv, t, d], dtype="float32")
    v = layers.data(name="v", shape=[hkv, t, d], dtype="float32")
    feed = {n: np.ones((2, h, t, d), "float32")
            for n, h in (("q", hq), ("k", hkv), ("v", hkv))}
    return q, k, v, feed


def flash():
    q, k, v, feed = _qkv(32, hkv=4)
    return feed, [layers.ring_attention(q, k, v, causal=True)]


def sparse_selection():
    q, k, v, feed = _qkv(32)
    x = layers.data(name="x", shape=[32, 24], dtype="float32")
    sel = layers.sparse_indexer(x, num_heads=2, head_dim=8, topk=8,
                                name="idx")
    feed["x"] = np.ones((2, 32, 24), "float32")
    return feed, [layers.sparse_attention(q, k, v, selection=sel)]


def sparse_window():
    q, k, v, feed = _qkv(64)
    return feed, [layers.sparse_attention(q, k, v, window=16)]


def xent():
    x = layers.data(name="x", shape=[64], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="int64")
    loss = layers.softmax_with_cross_entropy(x, y)
    return {"x": np.ones((8, 64), "float32"),
            "y": np.zeros((8, 1), "int64")}, [loss]


def xent_smoothed():
    """The reference's way of writing a label-smoothed loss: the layer
    hands the op the labels and ``smooth_epsilon``."""
    feed, _ = xent()
    block = fluid.default_main_program().global_block()
    smooth = layers.label_smooth(layers.one_hot(block.var("y"), 64),
                                 epsilon=0.1)
    return feed, [layers.softmax_with_cross_entropy(block.var("x"), smooth,
                                                    soft_label=True)]


def xent_soft():
    x = layers.data(name="x", shape=[64], dtype="float32")
    y = layers.data(name="y", shape=[64], dtype="float32")
    loss = layers.softmax_with_cross_entropy(x, y, soft_label=True)
    return {"x": np.ones((8, 64), "float32"),
            "y": np.full((8, 64), 1 / 64, "float32")}, [loss]


def _trained(optimizer):
    x = layers.data(name="x", shape=[128], dtype="float32")
    loss = layers.mean(layers.fc(x, 128, bias_attr=False))
    optimizer.minimize(loss)
    return {"x": np.ones((8, 128), "float32")}, [loss]


def adam():
    return _trained(fluid.optimizer.Adam(learning_rate=1e-3))


def momentum():
    return _trained(fluid.optimizer.Momentum(learning_rate=1e-3,
                                             momentum=0.9))


def paged():
    s_n, n_pages, ps, d = 2, 2, 4, 8
    shapes = {"q": ([s_n, 1, d], "float32"), "ck": ([5, ps, d], "float32"),
              "cv": ([5, ps, d], "float32"), "pt": ([s_n, n_pages], "int64"),
              "bias": ([s_n, 1, n_pages * ps], "float32")}
    args = [layers.data(n, shape=s, dtype=t, append_batch_size=False)
            for n, (s, t) in shapes.items()]
    return ({n: np.zeros(s, t) for n, (s, t) in shapes.items()},
            [layers.paged_attention(*args, scale=0.25)])


def delta_rule():
    """One chunk of one key head's two value heads, heads of 128: the
    least the scalar rule's kernels take."""
    t, d = 64, 128
    shapes = {"q": [t, 1, d], "k": [t, 1, d], "v": [t, 2, d], "g": [t, 2],
              "beta": [t, 2]}
    args = [layers.data(name=n, shape=s, dtype="float32")
            for n, s in shapes.items()]
    feed = {n: np.full([1] + s, -0.5 if n == "g" else 0.5, "float32")
            for n, s in shapes.items()}
    return feed, [layers.gated_delta_rule(*args, chunk=64)]


def delta_channel():
    """One chunk of two heads of 128 whose decay is a vector along the key:
    the least the channel rule's kernels take."""
    t, d = 64, 128
    shapes = {"q": [t, 2, d], "k": [t, 2, d], "v": [t, 2, d],
              "g": [t, 2, d], "beta": [t, 2]}
    args = [layers.data(name=n, shape=s, dtype="float32")
            for n, s in shapes.items()]
    feed = {n: np.full([1] + s, -0.5 if n == "g" else 0.5, "float32")
            for n, s in shapes.items()}
    return feed, [layers.gated_delta_rule(*args, chunk=64)]


def ssd_scan():
    """One chunk of one group of two heads of 64 over a state of 128: the
    least the scan's kernels take."""
    t, h, p, n = 128, 2, 64, 128
    shapes = {"u": [1, t, h, p], "delta": [1, t, h], "a": [h],
              "b": [1, t, n], "c": [1, t, n], "d": [h]}
    args = [layers.data(name=n_, shape=s, dtype="float32",
                        append_batch_size=False) for n_, s in shapes.items()]
    feed = {n_: np.full(s, -0.5 if n_ == "a" else 0.5, "float32")
            for n_, s in shapes.items()}
    return feed, [layers.ssd_scan(*args, chunk=128, groups=1)]


#: family -> (its program, its group, the kernel's name stack in the
#: lowered text, the counter that says a kernel ran, the one that says its
#: twin did)
FAMILIES = {
    "flash": (flash, "flash", "ring_attention/pallas_call",
              "ops.fused.flash_attention", None),
    "sparse_flash_selection": (
        sparse_selection, "flash",
        "sparse_attention/sparse_flash_fwd/pallas_call",
        'ops.sparse_attention.calls{path="pallas"',
        'ops.sparse_attention.calls{path="xla"'),
    "sparse_flash_window": (
        sparse_window, "flash",
        "sparse_attention/window_flash_fwd/pallas_call",
        'ops.sparse_attention.calls{path="pallas"',
        'ops.sparse_attention.calls{path="xla"'),
    "delta_rule": (
        delta_rule, "flash", "gated_delta_rule/delta_rule_fwd/pallas_call",
        'ops.delta_rule.calls{chunk="64",dim="128",key_heads="1",'
        'path="pallas"',
        'ops.delta_rule.calls{chunk="64",dim="128",key_heads="1",'
        'path="xla"'),
    "delta_channel": (
        delta_channel, "flash",
        "gated_delta_rule/delta_channel_fwd/pallas_call",
        'ops.delta_rule.calls{chunk="64",dim="128",key_heads="2",'
        'path="pallas"',
        'ops.delta_rule.calls{chunk="64",dim="128",key_heads="2",'
        'path="xla"'),
    "ssd_scan": (
        ssd_scan, "flash", "ssd_scan/ssd_scan_fwd/pallas_call",
        'ops.ssd.scans{chunk="128",dim="64",groups="1",heads="2",'
        'path="pallas"',
        'ops.ssd.scans{chunk="128",dim="64",groups="1",heads="2",'
        'path="xla"'),
    "xent": (xent, "fused", "softmax_with_cross_entropy/pallas_call",
             'ops.fused.softmax_xent{target="hard"', None),
    "xent_smoothed": (xent_smoothed, "fused",
                      "softmax_with_cross_entropy/pallas_call",
                      'ops.fused.softmax_xent{target="smoothed"', None),
    "xent_soft": (xent_soft, "fused",
                  "softmax_with_cross_entropy/pallas_call",
                  'ops.fused.softmax_xent{target="soft"', None),
    "adam": (adam, "fused", "adam/~optimizer/pallas_call", "ops.fused.adam",
             None),
    "momentum": (momentum, "fused", "momentum/~optimizer/pallas_call",
                 "ops.fused.momentum", None),
    "paged_attention": (paged, "fused", "paged_attention/pallas_call",
                        "ops.fused.paged_attention", None),
}


@pytest.mark.parametrize("switch", ["unset", "1", "0"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_familys_kernel_runs_where_its_gate_is_open(monkeypatch, family,
                                                      switch):
    """Unset, the gate is the platform's: closed on this CPU.  ``1`` opens
    it (the kernel interpreted), ``0`` closes it.  The OTHER group's switch
    stands against the family's throughout and changes nothing."""
    build, group, kernel, ran, twin = FAMILIES[family]
    other = next(g for g in kernel_choice.SWITCHES if g != group)
    want = switch == "1"
    monkeypatch.setenv(kernel_choice.SWITCHES[other], "0" if want else "1")
    if switch == "unset":
        monkeypatch.delenv(kernel_choice.SWITCHES[group], raising=False)
    else:
        monkeypatch.setenv(kernel_choice.SWITCHES[group], switch)
    assert kernel_choice.gate(group) is want
    before = counters("ops.")
    feed, fetch = build()
    text = lowered(feed, fetch)
    assert (f'"jit(fn)/{kernel}"' in text) is want
    assert ("pallas_call" in text) is want      # and no other family's
    moved = grown(before, "ops.")
    assert any(k.startswith(ran) for k in moved) is want
    if twin:
        assert any(k.startswith(twin) for k in moved) is not want
    assert not any("declined" in k for k in moved)


@pytest.mark.parametrize("shapes,path", [
    ((512, 128, 128), "pallas"),        # lane-aligned, whole row tiles
    ((512, 96, 128), "ragged_dot"),     # a width off the lanes
    ((256, 128, 128), "ragged_dot"),    # half a row tile
])
def test_the_grouped_products_follow_their_operands(monkeypatch, shapes,
                                                    path):
    """No switch has a say: with both set against it the expert layer
    still takes the kernels where its operands fit, and with both set for
    it XLA's grouped product where they do not."""
    for name in kernel_choice.SWITCHES.values():
        monkeypatch.setenv(name, "0" if path == "pallas" else "1")
    rows, d, f = shapes
    x = layers.data(name="x", shape=[rows // 2, d], dtype="float32")
    out = layers.moe_experts(x, num_routed=4, experts_held=2, hidden_size=f,
                             top_k=2, name="moe")
    before = counters("ops.moe.calls")
    text = lowered({"x": np.ones((1, rows // 2, d), "float32")}, [out])
    assert ('"jit(fn)/moe_experts/grouped_matmul/pallas_call"' in text) \
        is (path == "pallas")
    assert ("pallas_call" in text) is (path == "pallas")
    (key,) = grown(before, "ops.moe.calls")
    assert f'path="{path}"' in key


def test_the_choice_has_no_argument_and_no_attribute():
    """The option does not come back: no function of ``fluid.layers`` takes
    ``flash`` or ``fused``, no file under ``paddle_tpu/ops/`` reads an op
    attribute of either name, and the gate takes no request."""
    for name, fn in inspect.getmembers(layers, inspect.isfunction):
        assert not {"flash", "fused"} & set(inspect.signature(fn).parameters), \
            name
    ops_dir = os.path.dirname(kernel_choice.__file__)
    reads = re.compile(r"""attr\(\s*["'](flash|fused)["']""")
    for fname in sorted(os.listdir(ops_dir)):
        if fname.endswith(".py"):
            with open(os.path.join(ops_dir, fname)) as f:
                assert not reads.search(f.read()), fname
    assert list(inspect.signature(kernel_choice.gate).parameters) == ["group"]
    assert not inspect.signature(kernel_choice.switches).parameters
    assert jax.default_backend() == "cpu" and kernel_choice.interpret()
