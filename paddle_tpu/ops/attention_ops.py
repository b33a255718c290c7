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

import jax.numpy as jnp

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

    flash_req = int(ctx.attr("flash", -1))
    mesh = spmd.active_mesh()
    if mesh is not None and sp_axis in mesh.axis_names \
            and mesh.shape[sp_axis] > 1:
        out = ra.ring_attention(q, k, v, mesh, sp_axis, causal, scale,
                                bias=bias)
    elif _flash_decision(flash_req):
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


def _flash_decision(flash_req: int = -1) -> bool:
    """Pallas flash-attention kernel gate.

    Precedence: the PADDLE_TPU_FLASH env kill-switch wins over everything
    (=0 forces OFF even for models built with flash=True; =1 forces ON),
    then the per-op attr (1 on / 0 off), then AUTO: on when the backend
    is a TPU (the kernels compile for the chip and stream K/V through
    VMEM — ops/pallas_flash.py), off on CPU/GPU (interpret mode is a
    correctness tool, not a fast path).  Read through the declared env
    contract (fluid.envcontract) like every other knob."""
    import jax

    from ..fluid import envcontract

    v = envcontract.get("PADDLE_TPU_FLASH")
    if v in ("0", "false"):
        return False
    if v in ("1", "true"):
        return True
    if flash_req != -1:
        return bool(flash_req)
    return jax.default_backend() == "tpu"


def _use_flash() -> bool:
    """AUTO-mode gate (no per-op request) — see _flash_decision."""
    return _flash_decision(-1)
