"""A decoder cell's whole routed layer with its hand-written backward,
lowered and compiled for the described v5e chip (``tests/described_chip.py``)
as ``tests/test_tpu_compile.py`` compiles the kernels one at a time.  A file of
its own under the rule that no file of ``tests/`` is more than 300 s of one
worker (docs/COVERAGE.md): a cell's layer compiles for half a minute."""

import collections
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import paddle_tpu  # noqa: F401  (x64 mode on, as every user has it)
from paddle_tpu.ops import kernel_choice, pallas_grouped

from described_chip import (  # noqa: F401  (the two fixtures)
    BF16, F32, GROUPED_CELLS, no_persistent_cache, topo)


#: a cell's routed layer: tokens a step, choices a token, routed experts,
#: whether its router has a balancing bias (a slab is 4 times the even share
#: of the rows under one and 8 times without: ``moe.slab_rows``' ``balanced``)
#: (``GROUPED_CELLS`` has the rows, the widths and the experts held)
GROUPED_LAYERS = {"keye": (8192, 8, 128, False),
                  "trinity": (6144, 8, 128, True),
                  "lfm2": (8192, 4, 32, True),
                  "instella": (8192, 6, 64, True),
                  "qwen3_next": (8192, 10, 512, False),
                  "mellum2": (8192, 8, 64, False),
                  "kimi_linear": (2048, 8, 256, True)}


@pytest.mark.parametrize("cell", sorted(GROUPED_CELLS))
def test_a_routed_layer_and_its_backward_lower_eleven_calls(
        topo, monkeypatch, cell):
    """``routed_experts`` with its hand-written backward at a cell's
    sizes, under the cells' AMP, lowered and compiled for the described
    chip: 8 ``grouped_matmul`` (3 forward; the two hidden products again
    and three rows' cotangents backward, NOT the last product again) and 3
    ``grouped_matmul_t``, in the six signatures that
    ``test_grouped_signatures_are_the_benchmarks`` pins one product at a
    time, each counted as ``2 * M * D * F`` FLOPs by the benchmark's files,
    M the rows of a walk: the slab's in Trinity, Kimi-Linear, Instella and
    Qwen3-Next, whose eleven calls stand ONCE, in the bodies of the layer's
    two loops;
    XLA drops none and adds none."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench import hlo
    from chipbench.plugins import load
    from paddle_tpu.fluid import amp
    from paddle_tpu.parallel import moe

    m, d, f, g = GROUPED_CELLS[cell]
    tokens, top_k, routed, balanced = GROUPED_LAYERS[cell]
    assert moe.slab_rows(tokens * top_k, g, routed, True, balanced) == m
    bias = jnp.zeros((routed,), F32) if balanced else None
    monkeypatch.setattr(kernel_choice, "interpret", lambda stated=None: False)

    def layer(x, wr, w1, w3, w2):
        return moe.routed_experts(x, wr, w1, w3, w2, top_k=top_k,
                                  bias=bias).astype(F32).sum()

    chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in
            (((1, tokens, d), BF16), ((d, routed), F32), ((g, d, f), F32),
             ((g, d, f), F32), ((g, f, d), F32))]
    amp.enable("bfloat16", keep_activations=True)
    try:
        lowered = jax.jit(jax.value_and_grad(layer, range(5))).lower(*args)
    finally:
        amp.disable()
    calls = hlo.custom_calls(lowered.as_text())
    steps = m // pallas_grouped.ROW_TILE + g - 1
    tables = f"s32[{g + 1}],s32[{steps}],s32[{steps}]"
    rows, hidden = f"bf16[{m},{d}]", f"bf16[{m},{f}]"
    up, down = f"bf16[{g},{d},{f}]", f"bf16[{g},{f},{d}]"
    for call in calls:
        assert load("kernels", call.kernel).flops(
            call.operands, call.results) == 2.0 * m * d * f
    assert collections.Counter(
            (call.kernel, hlo.signature(call)) for call in calls) == {
        ("grouped_matmul", f"{hidden}<-{tables},{rows},{up}"): 4,
        ("grouped_matmul", f"{rows}<-{tables},{hidden},{down}"): 1,
        ("grouped_matmul", f"{hidden}<-{tables},{rows},{down}"): 1,
        ("grouped_matmul", f"{rows}<-{tables},{hidden},{up}"): 2,
        ("grouped_matmul_t", f"{up}<-{tables},{rows},{hidden}"): 2,
        ("grouped_matmul_t", f"{down}<-{tables},{hidden},{rows}"): 1}
    assert lowered.compile().as_text().count(
        'custom_call_target="tpu_custom_call"') == 11
