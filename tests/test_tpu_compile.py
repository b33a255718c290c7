"""The Pallas kernels of the main path, compiled for the chip without the chip.

The TPU's compiler is installed beside jax and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``): each case lowers
one kernel with ``interpret=False`` at a shape the Transformer-base train
step (``chip_smoke.py``: batch 64, length 256, 8 heads of 64, vocabulary
30,000, bf16 AMP) or the decode engine really dispatches, and compiles it for
one described v5e chip — or, for the tp-sharded lowering, a 2x2 mesh of them.
What Mosaic refuses here it refuses on the chip: 64-bit index-map results
under the package's x64 mode, blocks whose lane extent is neither
128-aligned nor the array's, too much VMEM.  Interpret mode sees none of it.

Nothing runs, so these say nothing about results (the interpret-mode tests
do) or times (only a chip run does).  Skipped where the topology cannot be
described.  jax's persistent compilation cache is off around them: an
executable compiled for a described chip cannot be read back without one
(both in ``tests/described_chip.py``).  A cell's whole routed layer is
``tests/test_tpu_compile_routed_layer.py``'s.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import paddle_tpu  # noqa: F401  (x64 mode on, as every user has it)
from paddle_tpu.ops import (kernel_choice, pallas_flash, pallas_fused,
                            pallas_grouped, pallas_paged, pallas_sparse_flash,
                            registry)

from described_chip import (  # noqa: F401  (the two fixtures)
    BF16, F32, GROUPED_CELLS, I32, no_persistent_cache, topo)

B, H, T, D = 64, 8, 256, 64          # attention: [batch, heads, len, d_head]
R, V = B * T, 30000                  # loss head: [batch*len, vocab]


def _flash(causal, bias, t=T):
    def fwd(q, k, v, *b):
        return pallas_flash.flash_attention(
            q, k, v, b[0] if b else None, None, causal, 256, 256, False)

    qkv = [((B, H, t, D), BF16)] * 3
    return fwd, qkv + ([((B, 1, 1, t), F32)] if bias else [])


def _flash_bwd(causal, bias, t=T):
    fwd, shapes = _flash(causal, bias, t)

    def bwd(q, k, v, *b):
        return jax.grad(lambda *a: fwd(*a, *b).astype(F32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    return bwd, shapes


def _xent(soft, eps=0.0):
    def fwd(x, lab):
        return pallas_fused.softmax_xent(x, lab, soft, -100, 256, 512,
                                         False, eps)

    # the AMP step hands the kernel bf16 logits and its int32 labels with
    # ``smooth_epsilon`` (fp32 smoothed labels where a program keeps the
    # distribution); the hard-label cases keep fp32 logits (a run without
    # AMP)
    if soft:
        return fwd, [((R, V), BF16), ((R, V), F32)]
    return fwd, [((R, V), BF16 if eps else F32), ((R, 1), I32)]


def _xent_bwd(soft, eps=0.0):
    fwd, shapes = _xent(soft, eps)
    return (lambda x, lab: jax.grad(lambda a: fwd(a, lab)[0].sum())(x),
            shapes)


def _sweep(kernel, n_arrays, shape):
    def fn(*arrays_lr):
        return pallas_fused._opt_sweep(kernel, list(arrays_lr[:-1]),
                                       arrays_lr[-1], n_arrays - 1, False)

    return fn, [(shape, F32)] * n_arrays + [((), F32)]


_ADAM = functools.partial(pallas_fused._adam_kernel, b1=0.9, b2=0.98,
                          eps=1e-9)
_MOMENTUM = functools.partial(pallas_fused._momentum_kernel, mu=0.9,
                              nesterov=False)


def _paged():
    # the engine phase of chip_smoke.py: 4 slots, max_len 32, page_size 4,
    # decode_lm_config's d_model 16
    s, d, ps, n = 4, 16, 4, 8

    def fn(q, ck, cv, pt, bias):
        return pallas_paged.paged_attention(q, ck, cv, pt, bias, 0.25,
                                            False)

    cache = ((s * n + 1, ps, d), F32)
    return fn, [((s, 1, d), F32), cache, cache, ((s, n), I32),
                ((s, 1, n * ps), F32)]


def _sparse_flash(selected, window=0, t=8192, hkv=4, d=128, hq=32):
    """The decoder cells' attention (``keye_vl_2_0_30b_a3b``: one sequence
    of 8,192 tokens, 32 query heads over 4 key-value heads of width 128,
    bf16, an int8 selection; ``trinity_mini``: the same heads under a
    causal window of 2,048, and with none, at 6,144 tokens;
    ``lfm2_8b_a1b``: 32 query heads over 8 key-value heads of width 64,
    half a lane row, plain causal at 8,192; ``instella_moe_16b_a3b``: 16
    query heads each over its own key-value head of width 128, a group of
    one, plain causal at 8,192; ``qwen3_next_80b_a3b``: 16 query heads over
    2 key-value heads of width 256, two lane rows and a group of 8, plain
    causal at 8,192), forward and the two backward kernels."""

    def fn(q, k, v, sel):
        def loss(q, k, v):
            return pallas_sparse_flash.sparse_flash_attention(
                q, k, v, sel if selected else None, None,
                False, window).astype(F32).sum()

        return jax.grad(loss, (0, 1, 2))(q, k, v)

    kv = ((1, hkv, t, d), BF16)
    return fn, [((1, hq, t, d), BF16), kv, kv, ((1, t, t), jnp.int8)]


def _blockdiff_flash(tokens=4096, block=4, hkv=4, d=128, hq=32):
    """``sdar_30b_a3b_chat``'s attention: a clean and a noised copy of one
    sequence of 4,096 tokens side by side, 8,192 positions under the block
    rule in blocks of 4, 32 query heads over 4 key-value heads of width
    128, bf16; forward and the two backward kernels, each over its walk's
    table as the scalar-prefetch operand."""

    def fn(q, k, v):
        def loss(q, k, v):
            return pallas_sparse_flash.sparse_flash_attention(
                q, k, v, None, None, False, 0,
                (tokens, block)).astype(F32).sum()

        return jax.grad(loss, (0, 1, 2))(q, k, v)

    kv = ((1, hkv, 2 * tokens, d), BF16)
    return fn, [((1, hq, 2 * tokens, d), BF16), kv, kv]


def _delta_layer(t=8192, hk=16, hv=32, d=128, taps=4, chunk=64):
    """``qwen3_next_80b_a3b``'s delta mixer between its two plain products,
    at the cell's shapes under the cells' AMP: the four-tap filter and SiLU
    over 8,192 channels, the gated delta rule in 128 chunks of 64 (16 key
    heads, 32 value heads of 128, the [128, 128] float32 state a head) and
    the backward of both from their inputs alone.  No Pallas kernel: what
    is compiled is the XLA lowering the chip runs."""
    from paddle_tpu.fluid import amp
    from paddle_tpu.ops import decoder_ops, delta_rule

    keys, values = hk * d, hv * d

    def fn(x, w, g, beta):
        def loss(x, w, g, beta):
            qkv = decoder_ops.silu_short_conv(x, w)
            q, k, v = (qkv[..., :keys], qkv[..., keys:2 * keys],
                       qkv[..., 2 * keys:])
            with amp.amp_guard("bfloat16", keep_activations=True):
                out = delta_rule.chunked(
                    q.reshape(1, t, hk, d), k.reshape(1, t, hk, d),
                    v.reshape(1, t, hv, d), g, beta, chunk=chunk,
                    norm_eps=1e-6)
            return out.astype(F32).sum()

        return jax.grad(loss, (0, 1, 2, 3))(x, w, g, beta)

    gate = ((1, t, hv), F32)
    return fn, [((1, t, 2 * keys + values), BF16),
                ((2 * keys + values, taps), F32), gate, gate]


#: no cell's: an expert width of 13 lane rows, whose only dividing tile is
#: one lane row as at Instella's 11 (ragged tiles of 896 + 768 and 384 x 4 +
#: 128 where the result is that wide: ``plain`` and ``weights_gradient`` of
#: ``up``, ``transposed`` of ``down``); compiled alone, not as a layer
GROUPED_WIDTHS = {**GROUPED_CELLS, "width_1664": (49152, 2048, 1664, 8),
                  # ``nemotron_twotower_30b_a3b``'s (PR 58): a slab of a
                  # quarter of its 49,152 rows, the first hidden width of 21
                  # lane rows, and an expert width of 1,856 = 14.5 lane rows
                  # that ``parallel/moe._low`` fills up to 15 (two matrices
                  # an expert: its layer has four of these six products
                  # twice and none a third time); compiled alone
                  "nemotron": (12288, 2688, 1920, 8)}


def _grouped(cell, form, which):
    """One of an expert layer's six products at a cell's shapes (bf16, as
    AMP hands them over): ``which`` the up (``[G, hidden, width]``) or the
    down (``[G, width, hidden]``) weights; ``form`` the product itself
    (``plain``), the rows' cotangent (``transposed``: the weights as they
    lie, contracted over their last axis) or the weights' gradient.  The
    tiles have to fit the VMEM a kernel gets by DEFAULT (the module states
    no ``vmem_limit_bytes``: a step with a larger one hung on the chip), the
    column chunks are dynamic slices of the lane dimension, and Mosaic
    wants the scalar-prefetch tables in 32 bits."""
    m, d, f, g = GROUPED_WIDTHS[cell]
    k, n = (d, f) if which == "up" else (f, d)
    rows, out, w = ((m, k), BF16), ((m, n), BF16), ((g, k, n), BF16)
    sizes = ((g,), I32)
    if form == "plain":
        return (lambda a, b, s: pallas_grouped.grouped_matmul(
            a, b, s, interpret=False)), [rows, w, sizes]
    if form == "transposed":
        return (lambda a, b, s: pallas_grouped.grouped_matmul(
            a, b, s, transpose=True, interpret=False)), [out, w, sizes]
    return (lambda a, b, s: pallas_grouped.grouped_matmul_t(
        a, b, s, interpret=False)), [rows, out, sizes]


#: name -> (builder, number of ``tpu_custom_call`` the compiled text holds)
CASES = {
    "sparse_flash_selected": (lambda: _sparse_flash(True), 3),
    "sparse_flash_causal": (lambda: _sparse_flash(False), 3),
    "sparse_flash_causal_heads_of_64": (
        lambda: _sparse_flash(False, 0, 8192, 8, 64), 3),
    "sparse_flash_causal_group_of_one": (
        lambda: _sparse_flash(False, 0, 8192, 16, 128, 16), 3),
    "sparse_flash_causal_heads_of_256": (
        lambda: _sparse_flash(False, 0, 8192, 2, 256, 16), 3),
    "delta_layer": (lambda: _delta_layer(), 0),
    "window_flash": (lambda: _sparse_flash(False, 2048, 6144), 3),
    "window_flash_global_layer": (lambda: _sparse_flash(False, 0, 6144), 3),
    "window_flash_four_windows": (lambda: _sparse_flash(False, 2048), 3),
    # a window that is no multiple of the tile: one more tile in the band
    "window_flash_ragged": (lambda: _sparse_flash(False, 1000), 3),
    "blockdiff_flash": (lambda: _blockdiff_flash(), 3),
    "blockdiff_flash_blocks_of_16_group_of_one": (
        lambda: _blockdiff_flash(2048, 16, 8, 128, 8), 3),
    "flash_fwd_causal": (lambda: _flash(True, False), 1),     # decoder self
    "flash_fwd_key_bias": (lambda: _flash(False, True), 1),   # encoder/cross
    "flash_bwd_causal": (lambda: _flash_bwd(True, False), 3),
    "flash_bwd_key_bias": (lambda: _flash_bwd(False, True), 3),
    # two tiles a sequence: the state carried through VMEM scratch, which
    # the step's own length (one tile pair) never enters
    "flash_bwd_causal_two_tiles": (lambda: _flash_bwd(True, False, 512), 3),
    "flash_bwd_key_bias_two_tiles": (lambda: _flash_bwd(False, True, 512),
                                     3),
    "xent_fwd_smoothed": (lambda: _xent(False, 0.1), 1),   # the step's
    "xent_bwd_smoothed": (lambda: _xent_bwd(False, 0.1), 2),
    "xent_fwd_soft": (lambda: _xent(True), 1),    # a distribution kept
    "xent_bwd_soft": (lambda: _xent_bwd(True), 2),
    "xent_fwd_hard": (lambda: _xent(False), 1),
    "xent_bwd_hard": (lambda: _xent_bwd(False), 2),
    "adam_largest_param": (lambda: _sweep(_ADAM, 4, (V, 512)), 1),
    "adam_ragged_param": (lambda: _sweep(_ADAM, 4, (V,)), 1),  # out_proj bias
    "momentum_largest_param": (lambda: _sweep(_MOMENTUM, 3, (V, 512)), 1),
    "momentum_ragged_param": (lambda: _sweep(_MOMENTUM, 3, (V,)), 1),
    "paged_step": (_paged, 1),
    **{f"grouped_{form}_{cell}_{which}": (
        functools.partial(_grouped, cell, form, which), 1)
       for cell in GROUPED_WIDTHS for which in ("up", "down")
       for form in ("plain", "transposed", "weights_gradient")},
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(topo, name):
    build, n_calls = CASES[name]
    fn, shapes = build()
    chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= n_calls


@pytest.mark.parametrize("hk,hv", [(16, 32), (16, 16)])
def test_the_delta_rules_kernels_compile_for_v5e(topo, monkeypatch, hk, hv):
    """``delta_layer`` again with the flash gate open: the scalar rule's
    three kernels (``ops/pallas_delta_rule``) at the cell's shapes, two
    value heads a key head as Qwen3-Next has them and one, under bf16 AMP.
    The grad of the layer holds the states pass and the backward walk and
    NOT the forward walk, whose output nothing reads; each call declares
    the operands ``chipbench/kernels/delta_rule_*.py`` count from."""
    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "1")
    monkeypatch.setattr(kernel_choice, "interpret",
                        lambda stated=None: False)
    fn, shapes = _delta_layer(hk=hk, hv=hv)
    chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for kernel, there in (("delta_rule_states", True),
                          ("delta_rule_bwd", True),
                          ("delta_rule_fwd", False)):
        assert (f"({kernel}))/pallas_call" in text) is there, kernel
        assert (kernel in text) is there, kernel
    states = f"bf16[1,{hv // 2},128,2,128,128]"
    assert states in text


def test_the_channel_delta_rules_kernels_compile_for_v5e(topo, monkeypatch):
    """The rule under a decay a key channel at ``kimi_linear_48b_a3b``'s
    shapes (one sequence of 2,048 tokens, 32 heads of 128, chunks of 64)
    under bf16 AMP with the flash gate open: the grad holds the states pass
    and the backward walk and NOT the forward walk; each call declares the
    operands ``chipbench/kernels/delta_channel_*.py`` count from (q, k, g
    ``[B, T, H * dk]`` float32, ``beta``'s columns, the transposed states),
    and within Mosaic's default VMEM."""
    from paddle_tpu.fluid import amp
    from paddle_tpu.ops import delta_rule

    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "1")
    monkeypatch.setattr(kernel_choice, "interpret",
                        lambda stated=None: False)
    t, h, d = 2048, 32, 128

    def rule(q, k, v, g, beta):
        with amp.amp_guard("bfloat16", keep_activations=True):
            return delta_rule.chunked(q, k, v, g, beta, chunk=64,
                                      norm_eps=1e-6)

    def grads(*xs):
        return jax.grad(lambda *a: rule(*a).astype(F32).sum(),
                        range(5))(*xs)

    chip = SingleDeviceSharding(topo.devices[0])
    head = ((1, t, h, d), BF16)
    args = [jax.ShapeDtypeStruct(s, ty, sharding=chip) for s, ty in (
        head, head, head, ((1, t, h, d), F32), ((1, t, h), F32))]
    text = jax.jit(rule).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "/delta_channel_fwd/pallas_call" in text
    text = jax.jit(grads).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for kernel, there in (("delta_channel_states", True),
                          ("delta_channel_bwd", True),
                          ("delta_channel_fwd", False)):
        assert (f"({kernel}))/pallas_call" in text) is there, kernel
    wide, cols = f"f32[1,{t},{h * d}]", f"f32[1,{h // 2},{t * 2},128]"
    states = f"bf16[1,{h // 2},{t // 64},2,{d},{d}]"
    for declared in (wide, cols, states):
        assert declared in text, declared


@pytest.mark.parametrize("p", [64, 128])
def test_the_ssd_scans_kernels_compile_for_v5e(topo, monkeypatch, p):
    """The selective scan at ``nemotron_twotower_30b_a3b``'s shapes (one
    sequence of 8,192 tokens, 64 heads of 64 in 8 groups, a state of 128,
    chunks of 128) under bf16 AMP with the flash gate open, and at heads of
    128: the op holds ``ssd_scan_fwd``; the grad holds the states pass and
    the backward walk and NOT the forward walk; each call declares the
    operands ``chipbench/kernels/ssd_scan_*.py`` count from (u ``[B, T, H *
    P]``, b and c ``[B, T, G * N]``, the rows, what is as wide as the
    states, the states), and within Mosaic's default VMEM."""
    from paddle_tpu.fluid import amp
    from paddle_tpu.ops import ssd

    monkeypatch.setenv(kernel_choice.SWITCHES["flash"], "1")
    monkeypatch.setattr(kernel_choice, "interpret",
                        lambda stated=None: False)
    t, h, g, n = 8192, 4096 // p, 8, 128

    def scan(*xs):
        with amp.amp_guard("bfloat16", keep_activations=True):
            return ssd.chunked(*xs, chunk=128, groups=g)

    def grads(*xs):
        return jax.grad(lambda *a: scan(*a).astype(F32).sum(),
                        range(6))(*xs)

    chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, ty, sharding=chip) for s, ty in (
        ((1, t, h, p), BF16), ((1, t, h), F32), ((h,), F32),
        ((1, t, g * n), BF16), ((1, t, g * n), BF16), ((h,), F32))]
    text = jax.jit(scan).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "/ssd_scan_fwd/pallas_call" in text
    text = jax.jit(grads).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for kernel, there in (("ssd_scan_states", True), ("ssd_scan_bwd", True),
                          ("ssd_scan_fwd", False)):
        assert (f"({kernel}))/pallas_call" in text) is there, kernel
    rep, wide = h // g, h * p // g
    for declared in (f"bf16[1,{t},{h * p}]", f"bf16[1,{t},{g * n}]",
                     f"f32[1,{g},{2 * rep + -2 * rep % 8},{t}]",
                     f"f32[1,{g},{t // 128},3,{wide}]",
                     f"bf16[1,{g},{t // 128},{n},{wide}]",
                     f"f32[1,{g},{rep},{t}]"):
        assert declared in text, declared


#: what ``chipbench/kernels/flash_*.py`` count FLOPs from and what
#: ``chipbench/trace_reduce.kernel_roofline`` matches trace events by: family
#: -> (kernel, contractions, plain operands, results).  The benchmark's files
#: are not a kernel PR's to edit, so a kernel that changes its name, its
#: operands or their order, or its results silently leaves the benchmark.
_BH = B * H
_TILE, _ROW = f"bf16[{_BH},{T},{D}]", f"f32[{_BH},{T},1]"
FLASH_FAMILIES = {
    "flash_fwd": ("_flash_kernel", 2, [_TILE] * 3, [_TILE, _ROW]),
    "flash_dq": ("_dq_kernel", 3, [_TILE] * 4 + [_ROW] * 2, [_TILE]),
    "flash_dkv": ("_dkv_kernel", 4, [_TILE] * 4 + [_ROW] * 2, [_TILE] * 2),
}


@pytest.mark.parametrize("causal,bias", [(True, False), (False, True)])
def test_flash_signatures_are_the_benchmarks(topo, causal, bias):
    """Forward + backward at the cell's shapes, lowered for the described
    chip: the three kernels' names, operand and result types are what the
    benchmark's family files expect (3 / 6 / 6 plain operands, ``[b*h, t,
    d]`` first, the key bias last), and their FLOP counts read them so."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench import hlo
    from chipbench.plugins import load

    fn, shapes = _flash_bwd(causal, bias)
    chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    calls = {c.kernel: c for c in
             hlo.custom_calls(jax.jit(fn).lower(*args).as_text())}
    assert sorted(calls) == sorted(k for k, *_ in FLASH_FAMILIES.values())
    key_bias = [f"f32[{B},1,{T}]"] if bias else []
    for family, (kernel, matmuls, operands, results) in \
            FLASH_FAMILIES.items():
        module, call = load("kernels", family), calls[kernel]
        assert module.KERNEL == kernel
        assert hlo.signature(call) == \
            ",".join(results) + "<-" + ",".join(operands + key_bias), family
        assert module.flops(call.operands, call.results) == \
            2.0 * matmuls * _BH * T * T * D / (2 if causal else 1), family


def test_blockdiff_signatures_are_the_benchmarks(topo):
    """``sdar_30b_a3b_chat``'s attention with its backward, lowered for the
    described chip: three kernels with names of their own, the walk's
    table the first operand ([5, 80] for a query head, [5, 640] for a
    key-value head of a group of 8: one column a live tile), q [b*hq, 2L,
    d] the second: what ``chipbench/kernels/blockdiff_flash_*.py`` count
    their pairs from, ``L (L + 1)`` a head."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench import hlo
    from chipbench.plugins import load

    fn, shapes = _blockdiff_flash()
    chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    calls = {c.kernel: c for c in
             hlo.custom_calls(jax.jit(fn).lower(*args).as_text())}
    families = {"blockdiff_flash_fwd": 2, "blockdiff_flash_dq": 3,
                "blockdiff_flash_dkv": 4}
    assert sorted(calls) == sorted(families)
    signatures = set()
    for name, matmuls in families.items():
        call, module = calls[name], load("kernels", name)
        steps = 640 if name.endswith("dkv") else 80
        assert call.operands[0] == ((5, steps), "i32"), name
        assert call.operands[1] == ((32, 8192, 128), "bf16"), name
        assert module.KERNEL == name
        assert module.flops(call.operands, call.results) == \
            2.0 * matmuls * 32 * 4096 * 4097 * 128, name
        signatures.add(hlo.signature(call))
    assert len(signatures) == 3     # an event is matched to ONE family


@pytest.mark.parametrize("selected,hq,hkv,d", [
    (True, 32, 4, 128), (False, 16, 16, 128), (False, 16, 2, 256)])
def test_causal_grids_are_the_live_tiles_and_q_stands_first(
        topo, selected, hq, hkv, d):
    """The causal kernels at a cell's shapes (Keye's under its selection,
    Instella's group of one, Qwen3-Next's width of 256), lowered for the
    described chip: each grid is its heads by the folded triangle, 8 pairs
    of 17 steps at 16 tiles (a group's query heads in turn under dK/dV),
    136 steps a query head and none dead; and no table stands before q,
    whose declared shape ``chipbench/kernels/sparse_flash_*.py`` count the
    causal half from."""
    import re
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench import hlo
    from chipbench.plugins import load

    fn, shapes = _sparse_flash(selected, 0, 8192, hkv, d, hq)
    chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, ty, sharding=chip) for s, ty in shapes]
    text = jax.jit(fn).lower(*args).as_text()
    calls = hlo.custom_calls(text)
    grids = [[int(i) for i in re.search(
        r"iteration_bounds = array<i64: ([\d, ]+)>", body).group(1).split(",")]
        for body in _mosaic_bodies(text)]
    group = hq // hkv
    want = {"sparse_flash_fwd": (2, [hq, 8, 17]),
            "sparse_flash_dq": (3, [hq, 8, 17]),
            "sparse_flash_dkv": (4, [hkv, 8, 17 * group])}
    assert sorted(c.kernel for c in calls) == sorted(want)
    for call, grid in zip(calls, grids):
        matmuls, bounds = want[call.kernel]
        assert grid == bounds, call.kernel
        assert call.operands[0] == ((hq, 8192, d), "bf16"), call.kernel
        assert (call.operands[-1] == ((1, 8192, 8192), "i8")) == selected
        assert load("kernels", call.kernel).flops(
            call.operands, call.results) == \
            2.0 * matmuls * hq * 8192 * 8192 * d / 2, call.kernel


@pytest.mark.parametrize("cell", sorted(GROUPED_CELLS))
def test_grouped_signatures_are_the_benchmarks(topo, monkeypatch, cell):
    """An expert layer's product with its backward at a cell's shapes,
    lowered for the described chip: the two kernels' names, three scalar-
    prefetch tables first (int32: the groups' first rows, then a row tile
    and a group for each of ``tiles + G - 1`` steps), the rows and the
    weights (or the cotangent) after them and one result are what
    ``chipbench/kernels/grouped_matmul*.py`` count FLOPs from and what the
    trace's events are matched by; three signatures, none shared."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench import hlo
    from chipbench.plugins import load
    from paddle_tpu.parallel import moe

    m, d, f, g = GROUPED_CELLS[cell]
    # the backend here is the CPU, which the kernels would answer with
    # interpret mode: steered in the test, as the chip would have it
    monkeypatch.setattr(kernel_choice, "interpret", lambda stated=None: False)

    def fn(rows, w, sizes):
        return jax.value_and_grad(
            lambda a, b: moe.grouped_product(
                a, b, sizes, moe.product_tables(a, b, sizes)).astype(
                    F32).sum(),
            (0, 1))(rows, w)

    chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in
            (((m, d), BF16), ((g, d, f), BF16), ((g,), I32))]
    calls = hlo.custom_calls(jax.jit(fn).lower(*args).as_text())
    steps = m // pallas_grouped.ROW_TILE + g - 1
    tables = f"s32[{g + 1}],s32[{steps}],s32[{steps}]"
    rows, hidden = f"bf16[{m},{d}]", f"bf16[{m},{f}]"
    weights = f"bf16[{g},{d},{f}]"
    assert [(c.kernel, hlo.signature(c)) for c in calls] == [
        ("grouped_matmul", f"{hidden}<-{tables},{rows},{weights}"),
        ("grouped_matmul", f"{rows}<-{tables},{hidden},{weights}"),
        ("grouped_matmul_t", f"{weights}<-{tables},{rows},{hidden}")]
    for call in calls:
        module = load("kernels", call.kernel)
        assert module.KERNEL == call.kernel
        assert module.flops(call.operands, call.results) == 2.0 * m * d * f


#: the column tile of each product, as ``tests/test_pallas_grouped.py``'s
#: ``TILES`` has them (``up``: result as wide as the experts, ``_t``: the
#: weights' gradient).  Keye's all divide their widths and are the parent's:
#: its step must lower byte-equal across a change of the tile rule
GROUPED_TILES = {
    "keye": {"up": 768, "down": 2048, "up_t": 384, "down_t": 1024},
    "trinity": {"up": 512, "down": 1024, "up_t": 256, "down_t": 512},
    "lfm2": {"up": 896, "down": 1024, "up_t": 256, "down_t": 256},
    "instella": {"up": 768, "down": 1024, "up_t": 384, "down_t": 512},
    "qwen3_next": {"up": 512, "down": 2048, "up_t": 256, "down_t": 1024},
    # the first hidden width that is not 2,048: 18 lane rows in two tiles;
    # 896 is 7 lane rows, ragged as 512 + 384 and as 256 x 3 + 128
    "mellum2": {"up": 512, "down": 1152, "up_t": 256, "down_t": 1152},
    "kimi_linear": {"up": 512, "down": 1152, "up_t": 256, "down_t": 768}}


def _mosaic_bodies(stablehlo_text):
    """Every ``tpu_custom_call``'s kernel as MLIR text without source
    locations (the ``body`` of its ``backend_config`` is bytecode that
    carries them)."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        return [ir.Module.parse(base64.b64decode(body)).operation.get_asm(
                    enable_debug_info=False)
                for body in re.findall(r'body\\22: \\22([^\\]*)\\22',
                                       stablehlo_text)]


@pytest.mark.parametrize("form,which,key", [
    ("plain", "up", "up"), ("plain", "down", "down"),
    ("transposed", "up", "down"), ("transposed", "down", "up"),
    ("weights_gradient", "up", "up_t"), ("weights_gradient", "down",
                                         "down_t")])
@pytest.mark.parametrize("cell", sorted(GROUPED_CELLS))
def test_grouped_grid_is_the_tiles(topo, cell, form, which, key):
    """The kernel each of a cell's six forms lowers to: ``cdiv(width,
    tile)`` column tiles by ``tiles + G - 1`` steps, the result's block one
    row tile by one column tile, ragged or not."""
    import re

    m, d, f, g = GROUPED_CELLS[cell]
    tn = GROUPED_TILES[cell][key]
    width = f if key.startswith("up") else d
    fn, shapes = _grouped(cell, form, which)
    chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in shapes]
    body, = _mosaic_bodies(jax.jit(fn).lower(*args).as_text())
    ints = lambda found: [int(i) for i in found.split(",")]  # noqa: E731
    assert ints(re.search(r"iteration_bounds = array<i64: ([\d, ]+)>",
                          body).group(1)) == \
        [-(-width // tn), m // pallas_grouped.ROW_TILE + g - 1]
    blocks = [ints(b) for b in
              re.findall(r"window_bounds = array<i64: ([\d, ]+)>", body)]
    contraction = d if key.startswith("up") else f
    assert blocks[-1] == ([1, contraction, tn] if form == "weights_gradient"
                          else [pallas_grouped.ROW_TILE, tn])


@pytest.mark.parametrize("cell,rows,width,dtype,eps", [
    ("transformer", R, V, BF16, 0.1),        # 64 x 256 tokens, smoothed
    ("keye", 8192, 18992, BF16, 0.0),        # a ragged last column block
    ("lfm2", 8192, 16384, BF16, 0.0),
])
def test_the_loss_op_and_its_grad_op_hold_one_kernel_each(
        topo, monkeypatch, cell, rows, width, dtype, eps):
    """``softmax_with_cross_entropy`` and its registered grad at a cell's
    head, lowered and compiled for the described chip: the forward kernel
    ONCE (under the generic grad the step held it twice), the backward
    kernel once on the forward's ``Lse``, in the signatures the benchmark's
    ``xent_fwd`` / ``xent_bwd`` families count their bytes from."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chipbench import hlo

    monkeypatch.setenv("PADDLE_TPU_FUSED", "1")
    monkeypatch.setattr(kernel_choice, "interpret", lambda stated=None: False)
    fwd = registry.get_op_def("softmax_with_cross_entropy")
    attrs = {"smooth_epsilon": eps} if eps else {}

    def head(logits, label, dloss):
        ins = {"Logits": [logits], "Label": [label]}
        outs = fwd.fn(registry.ExecContext(fwd.type, dict(ins), {}, attrs))
        ins.update(Loss=[outs["Loss"]], Lse=[outs["Lse"]],
                   **{"Loss@GRAD": [dloss]})
        grads = fwd.grad_fn(registry.ExecContext(
            fwd.type + "_grad", ins, {"Logits@GRAD": ["dx"]}, attrs))
        return outs["Loss"], grads["Logits@GRAD"]

    chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in
            (((rows, width), dtype), ((rows, 1), jnp.int64),
             ((rows, 1), F32))]
    lowered = jax.jit(head).lower(*args)
    x, col = f"bf16[{rows},{width}]", f"f32[{rows},1]"
    label = f"s32[{rows},1]"
    assert [(c.kernel, hlo.signature(c))
            for c in hlo.custom_calls(lowered.as_text())] == [
        ("_xent_partial_kernel",
         ",".join([col] * (4 if eps else 3)) + f"<-{x},{label}"),
        ("_xent_bwd_kernel", f"{x}<-{x},{label},{col},{col},{col}")]
    assert lowered.compile().as_text().count(
        'custom_call_target="tpu_custom_call"') == 2


def _momentum_op(p, g, v, lr):
    """The ``momentum`` OP as the executor calls it (gate, suitability,
    kernel or XLA formulas), not the bare kernel."""
    ctx = registry.ExecContext(
        "momentum",
        {"Param": [p], "Grad": [g], "Velocity": [v], "LearningRate": [lr]},
        {"ParamOut": ["w"], "VelocityOut": ["w_velocity"]}, {"mu": 0.9})
    out = registry.get_op_def("momentum").fn(ctx)
    return out["ParamOut"], out["VelocityOut"]


@pytest.mark.parametrize("shape,sweeps", [((512, 512, 3, 3), 0),
                                          ((512, 2048), 1)])
def test_momentum_op_asks_for_no_relayout(topo, monkeypatch, shape, sweeps):
    """The optimizer tail, without a chip: a convolution filter lives on
    the v5e with its channel dims minor (``{1,0,3,2:T(8,128)}``), and a
    2-D view of it for the Pallas sweep is a copy into a row-major layout
    that pads every 3x3 patch to a ``T(4,128)`` tile, 57 times the array,
    for each of five tensors (38 ms a step of ResNet-50 before PR 28).
    So its update is XLA's, in place: no kernel, no such layout, and
    about the five tensors' bytes accessed.  A lane-aligned matrix keeps
    its sweep."""
    # the gates ask the process's backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct(shape, F32, sharding=chip)
    lr = jax.ShapeDtypeStruct((1,), F32, sharding=chip)
    compiled = jax.jit(_momentum_op, donate_argnums=(0, 2)).lower(
        x, x, x, lr).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == sweeps
    if not sweeps:
        assert "T(4,128)" not in text
        five = 5 * 4 * math.prod(shape)
        assert compiled.cost_analysis()["bytes accessed"] < 10 * five


def _adam_op(p, g, m1, m2, lr, b1p, b2p):
    """The ``adam`` OP as the executor calls it, not the bare kernel."""
    ctx = registry.ExecContext(
        "adam",
        {"Param": [p], "Grad": [g], "Moment1": [m1], "Moment2": [m2],
         "LearningRate": [lr], "Beta1Pow": [b1p], "Beta2Pow": [b2p]},
        {"ParamOut": ["w"], "Moment1Out": ["w_m1"], "Moment2Out": ["w_m2"],
         "Beta1PowOut": ["b1p"], "Beta2PowOut": ["b2p"]},
        {"beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8})
    out = registry.get_op_def("adam").fn(ctx)
    return out["ParamOut"], out["Moment1Out"], out["Moment2Out"]


@pytest.mark.parametrize("shape,sweeps", [((16, 2048, 768), 1),
                                          ((2048, 18992), 0),
                                          ((8, 2048, 1024), 1),
                                          ((2048, 25024), 0),
                                          ((8, 2048, 1792), 1),
                                          ((8, 1792, 2048), 1),
                                          ((16384, 2048), 1)])
def test_adam_op_asks_for_no_relayout(topo, monkeypatch, shape, sweeps):
    """The optimizer tail of the decoder cells, without a chip.  The stacked
    expert weight ``[16, 2048, 768]`` collapses to ``[32768, 768]`` as a
    view (768 lanes, 2048 sublanes a slab) and keeps its Pallas sweep, as
    do ``[8, 2048, 1024]``, ``[8, 2048, 1792]`` and ``[8, 1792, 2048]``
    (1792 = 14 * 128) and the tied embedding ``[16384, 2048]``, which is
    swept once though the step uses it twice; the untied heads ``[2048, 18992]`` and
    ``[2048, 25024]`` have a ragged last dim (18992 = 148 * 128 + 48, 25024
    = 195 * 128 + 64), so their update is XLA's, in place.  None asks for a
    copy into
    another layout: about the seven tensors' bytes are accessed (param,
    grad and two moments read; param and two moments written)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct(shape, F32, sharding=chip)
    one = jax.ShapeDtypeStruct((1,), F32, sharding=chip)
    compiled = jax.jit(_adam_op, donate_argnums=(0, 2, 3)).lower(
        x, x, x, x, one, one, one).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == sweeps
    assert "T(4,128)" not in text
    # scalars (the beta powers) may be copied into on-chip memory; nothing
    # of the tensor's size is copied or transposed
    import re

    for dims in re.findall(r"= f32\[([0-9,]+)\][^=\n]* (?:copy|transpose)\(",
                           text):
        assert math.prod(int(d) for d in dims.split(",")) <= 128, dims
    seven = 7 * 4 * math.prod(shape)
    assert compiled.cost_analysis()["bytes accessed"] < 1.5 * seven


def _dropout_op(x, key):
    """The ``dropout`` OP as the executor calls it, the RNG thread's split
    included."""
    box = [key]
    ctx = registry.ExecContext(
        "dropout", {"X": [x]}, {"Out": ["o"], "Mask": ["m"]},
        {"dropout_prob": 0.1, "is_test": False}, rng_box=box)
    out = registry.get_op_def("dropout").fn(ctx)
    return out["Out"], out["Mask"], box[0]


def test_dropout_op_draws_its_mask_in_32_bits(topo):
    """One of the Transformer-base step's 32 dropout ops, ``[64, 256, 512]``
    bf16, lowered for the v5e (threefry unrolled, as the chip gets it): no
    float64 and no 64-bit type of the mask's size in what the compiler is
    handed.  Until PR 31 the op was ``bernoulli(p: f64)``: 25 ``ui64`` and
    15 ``f64`` tensor types of that size, all emulated on the chip (23.9 ms
    of a 155 ms step).  Left: the key split of the RNG thread, which counts
    its two keys in uint64."""
    import re

    chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((B, T, 512), BF16, sharding=chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    lowered = jax.jit(_dropout_op).lower(x, key)
    wide = set(re.findall(r"tensor<((?:[0-9?]+x)*)(f64|i64|ui64)>",
                          lowered.as_text()))
    assert wide <= {("", "ui64"), ("2x", "ui64")}, wide
    five = 5 * 2 * B * T * 512      # X, Out, Mask and the two halves' bools
    assert lowered.compile().cost_analysis()["bytes accessed"] < five


def test_keep_mask_partitions_without_traffic(topo):
    """The mask's counters are sums of iotas, so under a mesh each chip
    draws its own shard of what one chip would draw: sharded along the
    batch or the sequence axis, the compiled program has no collective."""
    import numpy as np

    from paddle_tpu.ops.random_ops import keep_mask

    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    for spec in (P("dp"), P(None, "dp")):
        text = jax.jit(
            lambda k: keep_mask(k, 0.9, (B, T, 512)),
            out_shardings=NamedSharding(mesh, spec)).lower(key).compile().as_text()
        for collective in ("all-gather", "all-reduce", "all-to-all",
                           "collective-permute"):
            assert collective not in text, (spec, collective)


@pytest.mark.parametrize("soft", [True, False])
def test_sharded_xent_compiles_under_2x2_mesh(topo, soft):
    """The shard_map lowering of the fused loss head on the four-chip mesh
    ``chip_smoke.py --chips 4`` builds: rows over dp, the vocabulary over
    tp (15,000 a shard — ragged against the 512-wide block), and the
    cross-shard logsumexp exchange the compiler has to place.  A
    distribution kept, and the step's own form: int32 labels smoothed by
    ``smooth_epsilon`` over the global width."""
    import numpy as np

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))

    def fn(x, lab):
        def loss(a):
            return pallas_fused.softmax_xent_sharded(
                a, lab, mesh, soft, -100, 256, 512, False,
                0.0 if soft else 0.1)[0].sum()

        return jax.value_and_grad(loss)(x)

    spec = NamedSharding(mesh, P("dp", "tp"))
    args = [jax.ShapeDtypeStruct((R, V), F32, sharding=spec)] * 2
    if not soft:
        args[1] = jax.ShapeDtypeStruct(
            (R, 1), I32, sharding=NamedSharding(mesh, P("dp", None)))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "all-reduce" in text
