"""Attention ops — including sequence-parallel ring attention, a
first-class TPU capability the reference lacks (SURVEY.md §5.7: SP/CP
"Absent"; its sequence story is LoD packing on one device).

``ring_attention`` is mesh-aware: traced under a ShardedTrainStep whose
mesh has an "sp" axis, it runs the ppermute ring (parallel/ring_attention
.py) over ICI; traced single-device (plain Executor) it degrades to the
mathematically identical full-softmax attention, so programs are portable
across places — the same portability contract the reference gives ops via
per-place kernels (op_registry.h OpKernelType).
"""

from __future__ import annotations

from . import kernel_choice
from .registry import register_op


@register_op("ring_attention")
def ring_attention_op(ctx):
    q, k, v = ctx.input("Q"), ctx.input("K"), ctx.input("V")  # [B, H, T, D]
    bias = ctx.input("Bias") if ctx.has_input("Bias") else None
    causal = ctx.attr("causal", False)
    sp_axis = ctx.attr("sp_axis", "sp")
    scale = ctx.attr("scale", 0.0) or None
    from ..parallel import ring_attention as ra
    from ..parallel import spmd

    mesh = spmd.active_mesh()
    if mesh is not None and sp_axis in mesh.axis_names \
            and mesh.shape[sp_axis] > 1:
        out = ra.ring_attention(q, k, v, mesh, sp_axis, causal, scale,
                                bias=bias)
    elif kernel_choice.gate("flash"):
        from . import pallas_fused
        from .pallas_flash import bias_supported, flash_attention

        if bias_supported(bias, q.shape[0], k.shape[2]):
            if mesh is not None:
                # tp-sharded lowering: heads stay sharded through the
                # kernel (GSPMD cannot partition an opaque pallas_call —
                # a mesh-less wrap would all-gather q/k/v around it)
                out = pallas_fused.flash_attention_sharded(
                    q, k, v, bias, scale, causal, mesh,
                    pallas_fused.flash_tp_axis(q, mesh))
            else:
                out = flash_attention(q, k, v, bias, scale, causal)
                pallas_fused.note_flash(q, k, v)
        else:
            out = ra.full_attention(q, k, v, causal, scale, bias=bias)
    else:
        out = ra.full_attention(q, k, v, causal, scale, bias=bias)
    return {"Out": out}
