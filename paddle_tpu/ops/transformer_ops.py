"""Stacked transformer encoder/decoder ops (parallel/transformer_stack.py).

Mesh-aware like ring_attention/gpipe_mlp_stack: traced under a mesh the
stack runs GPipe over "pp", Megatron TP over "mp" and ring attention over
"sp"; single-device it is a lax.scan over layers — mathematically identical,
so programs are portable across places (the portability contract the
reference gives ops via per-place kernels, op_registry.h OpKernelType).

Gradients: the forward consumes threaded RNG (residual dropout), so the
generic vjp (registry.py) cannot replay it.  The forward therefore emits the
key it used as an extra output (RngKey) and the explicit grad impl re-runs
the stack under jax.vjp with that exact key — same masks, exact gradients;
XLA CSEs the recomputed forward away.  (Same pattern as dropout's saved
Mask, ref dropout_op.h DropoutGradKernel, scaled up to a whole block.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_op, register_grad


def _collect(ctx, slots):
    return {s: ctx.input(s) for s in slots}


def _stack_args(ctx, decoder):
    from ..parallel import spmd
    from ..parallel import transformer_stack as ts

    from . import kernel_choice

    slots = ts.DECODER_SLOTS if decoder else ts.ENCODER_SLOTS
    params = _collect(ctx, slots)
    return dict(
        kind="dec" if decoder else "enc",
        enc=ctx.input("EncOut") if decoder else None,
        bias=ctx.input("Bias") if ctx.has_input("Bias") else None,
        params=params,
        n_head=int(ctx.attr("n_head")),
        dropout=float(ctx.attr("dropout", 0.0)),
        is_test=bool(ctx.attr("is_test", False)),
        n_micro=int(ctx.attr("n_microbatches", 4)),
        recompute=bool(ctx.attr("recompute", False)),
        flash=kernel_choice.gate("flash"),
        mesh=spmd.active_mesh(),
    )


def _forward(ctx, decoder):
    from ..parallel import transformer_stack as ts

    a = _stack_args(ctx, decoder)
    x = ctx.input("X")
    if a["dropout"] and not a["is_test"]:
        key = ctx.rng()
    else:
        key = jnp.zeros((2,), jnp.uint32)
    out = ts.stack_apply(a["kind"], x, a["enc"], a["bias"], a["params"],
                         key, n_head=a["n_head"], dropout=a["dropout"],
                         is_test=a["is_test"], n_micro=a["n_micro"],
                         mesh=a["mesh"], recompute=a["recompute"],
                         flash=a["flash"])
    return {"Out": out, "RngKey": key}


def _backward(ctx, decoder):
    from ..parallel import transformer_stack as ts

    a = _stack_args(ctx, decoder)
    x = ctx.input("X")
    key = ctx.input("RngKey")
    gout = ctx.input("Out@GRAD")

    if decoder:
        def f(xx, ee, pp):
            return ts.stack_apply(a["kind"], xx, ee, a["bias"], pp, key,
                                  n_head=a["n_head"], dropout=a["dropout"],
                                  is_test=a["is_test"], n_micro=a["n_micro"],
                                  mesh=a["mesh"], recompute=a["recompute"],
                                  flash=a["flash"])

        _, vjp = jax.vjp(f, x, a["enc"], a["params"])
        gx, genc, gparams = vjp(gout)
        res = {"X@GRAD": gx, "EncOut@GRAD": genc}
    else:
        def f(xx, pp):
            return ts.stack_apply(a["kind"], xx, None, a["bias"], pp, key,
                                  n_head=a["n_head"], dropout=a["dropout"],
                                  is_test=a["is_test"], n_micro=a["n_micro"],
                                  mesh=a["mesh"], recompute=a["recompute"],
                                  flash=a["flash"])

        _, vjp = jax.vjp(f, x, a["params"])
        gx, gparams = vjp(gout)
        res = {"X@GRAD": gx}
    for slot, g in gparams.items():
        res[slot + "@GRAD"] = g
    return res


@register_op("transformer_encoder_stack", stateful=True,
             no_grad_inputs=("Bias",))
def transformer_encoder_stack_op(ctx):
    return _forward(ctx, decoder=False)


@register_grad("transformer_encoder_stack")
def transformer_encoder_stack_grad(ctx):
    return _backward(ctx, decoder=False)


@register_op("transformer_decoder_stack", stateful=True,
             no_grad_inputs=("Bias",))
def transformer_decoder_stack_op(ctx):
    return _forward(ctx, decoder=True)


@register_grad("transformer_decoder_stack")
def transformer_decoder_stack_grad(ctx):
    return _backward(ctx, decoder=True)
