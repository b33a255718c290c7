"""Random / fill / assign ops (ref: uniform_random_op.*, gaussian_random_op.*,
fill_constant_op.cc, fill_zeros_like_op, assign_op, dropout_op, random_crop).

RNG design: the reference seeds cuRAND per op; here randomness is a threefry
key threaded through the traced program as hidden state (@RNG_STATE@), so a
Program with random_seed set replays identically — the determinism contract
the reference's OpTest relies on (SURVEY.md hard part #6).  An op with an
explicit nonzero ``seed`` attr uses its own fixed key instead.

Dropout's keep mask is ``keep_mask``: 32 threefry bits per element against an
integer threshold made on the host, with the counters made here in uint32, so
no float64 and no uint64 reaches the device.  (The package runs jax in x64
mode, where ``jax.random.bernoulli`` with a Python float draws 64-bit words
and compares float64 uniforms, and jax's own partitionable ``random_bits``
counts in uint64 and spends a whole threefry block per 32-bit word; the v5e
emulates all of that.)  Replay on one tree is unchanged, on one chip or
sharded; the mask VALUES for a given seed differ from trees before PR 31,
which drew other words of the same key's stream.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend.random import threefry2x32_p

from .registry import register_op, register_grad


def _np_dtype(ctx, attr="dtype", default="float32"):
    from ..fluid import core as _core

    return _core.np_dtype(ctx.attr(attr, default))


def _key(ctx):
    seed = ctx.attr("seed", 0)
    if seed:
        return jax.random.PRNGKey(seed)
    return ctx.rng()


def keep_mask(key, keep_prob, shape):
    """Boolean mask, True with probability ``round(keep_prob * 2**32) / 2**32``
    (within 1.2e-10 of ``keep_prob``, a Python float): 32 threefry bits per
    element below a uint32 threshold.  The ends are decided here without a
    draw, since a threshold of 2**32 does not fit a uint32."""
    threshold = round(float(keep_prob) * 2**32)
    if threshold <= 0:
        return jnp.zeros(shape, jnp.bool_)
    if threshold >= 2**32:
        return jnp.ones(shape, jnp.bool_)
    # One threefry2x32 block per PAIR of elements: the block counted by
    # (index along axis 0, flat index over the other axes) of the first half
    # of the last axis gives that element its first word and the element half
    # an axis further its second.  The counters are sums of uint32 iotas, so
    # they depend on the position alone (a sharded run draws what one chip
    # draws) and partition along every axis but the last without traffic.
    last = shape[-1] if shape else 1
    half = tuple(shape[:-1]) + (-(-last // 2),)
    if math.prod(half[1:]) >= 2**32:
        raise NotImplementedError(
            f"keep mask of shape {shape}: the counters past axis 0 overflow 32 bits")
    flat, stride = jnp.zeros(half, jnp.uint32), 1
    for axis in range(len(half) - 1, 0, -1):
        flat = flat + lax.broadcasted_iota(jnp.uint32, half, axis) * np.uint32(stride)
        stride *= half[axis]
    words = threefry2x32_p.bind(key[0], key[1],
                                lax.broadcasted_iota(jnp.uint32, half, 0), flat)
    keep = jnp.concatenate([w < np.uint32(threshold) for w in words], axis=-1)
    return keep[..., :last].reshape(shape)


@register_op("fill_constant")
def fill_constant(ctx):
    dt = _np_dtype(ctx)
    shape = tuple(ctx.attr("shape", []))
    value = ctx.attr("value", 0.0)
    # Always a host (numpy) value: constants fold into the trace either way,
    # and host-ness keeps loop counters / conditions concrete under jit so
    # while sub-blocks can unroll (the role force_cpu plays in the
    # reference; here it is the default).  jnp consumers auto-promote.
    return {"Out": np.full(shape, value, dt)}


@register_op("fill_constant_batch_size_like")
def fill_constant_batch_size_like(ctx):
    x = ctx.input("Input")
    shape = list(ctx.attr("shape"))
    in_idx = ctx.attr("input_dim_idx", 0)
    out_idx = ctx.attr("output_dim_idx", 0)
    shape[out_idx] = x.shape[in_idx]
    return {"Out": jnp.full(tuple(shape), ctx.attr("value", 0.0), _np_dtype(ctx))}


@register_op("fill_zeros_like")
def fill_zeros_like(ctx):
    return {"Out": jnp.zeros_like(ctx.input("X"))}


@register_op("fill_any_like")
def fill_any_like(ctx):
    return {"Out": jnp.full_like(ctx.input("X"), ctx.attr("value", 0.0))}


@register_op("assign")
def assign(ctx):
    return {"Out": ctx.input("X")}


@register_op("assign_value")
def assign_value(ctx):
    dt = _np_dtype(ctx)
    vals = ctx.attr("fp32_values") or ctx.attr("int32_values") or ctx.attr("values")
    # Host (numpy) value like fill_constant above: a jnp constant would
    # become a traced op under jit, and ops that need static values
    # (sequence_slice Offset/Length, loop bounds) could no longer consume
    # an assigned constant.  jnp consumers auto-promote.
    return {"Out": np.array(vals, dt).reshape(ctx.attr("shape"))}


@register_op("uniform_random", stateful=True)
def uniform_random(ctx):
    dt = _np_dtype(ctx)
    lo, hi = ctx.attr("min", -1.0), ctx.attr("max", 1.0)
    shape = tuple(ctx.attr("shape"))
    return {"Out": jax.random.uniform(_key(ctx), shape, dt, lo, hi)}


@register_op("uniform_random_batch_size_like", stateful=True)
def uniform_random_batch_size_like(ctx):
    x = ctx.input("Input")
    shape = list(ctx.attr("shape"))
    shape[ctx.attr("output_dim_idx", 0)] = x.shape[ctx.attr("input_dim_idx", 0)]
    lo, hi = ctx.attr("min", -1.0), ctx.attr("max", 1.0)
    return {"Out": jax.random.uniform(_key(ctx), tuple(shape), _np_dtype(ctx), lo, hi)}


@register_op("gaussian_random", stateful=True)
def gaussian_random(ctx):
    dt = _np_dtype(ctx)
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    shape = tuple(ctx.attr("shape"))
    return {"Out": mean + std * jax.random.normal(_key(ctx), shape, dt)}


@register_op("gaussian_random_batch_size_like", stateful=True)
def gaussian_random_batch_size_like(ctx):
    x = ctx.input("Input")
    shape = list(ctx.attr("shape"))
    shape[ctx.attr("output_dim_idx", 0)] = x.shape[ctx.attr("input_dim_idx", 0)]
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    return {"Out": mean + std * jax.random.normal(_key(ctx), tuple(shape), _np_dtype(ctx))}


@register_op("truncated_gaussian_random", stateful=True)
def truncated_gaussian_random(ctx):
    dt = _np_dtype(ctx)
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    shape = tuple(ctx.attr("shape"))
    out = jax.random.truncated_normal(_key(ctx), -2.0, 2.0, shape, dt)
    return {"Out": mean + std * out}


@register_op("sampling_id", stateful=True, no_grad_inputs=("X",))
def sampling_id(ctx):
    x = ctx.input("X")  # [N, C] probabilities
    key = _key(ctx)
    return {"Out": jax.random.categorical(key, jnp.log(jnp.maximum(x, 1e-20)), axis=-1)
            .astype(jnp.int64)}


@register_op("dropout", stateful=True)
def dropout(ctx):
    x = ctx.input("X")
    p = ctx.attr("dropout_prob", 0.5)
    is_test = ctx.attr("is_test", False)
    impl = ctx.attr("dropout_implementation", "downgrade_in_infer")
    if is_test:
        if impl == "upscale_in_train":
            return {"Out": x, "Mask": jnp.ones_like(x)}
        return {"Out": x * (1.0 - p), "Mask": jnp.ones_like(x)}
    keep = keep_mask(_key(ctx), 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        mask = keep.astype(x.dtype) / max(1.0 - p, 1e-12)
    else:
        mask = keep.astype(x.dtype)
    return {"Out": x * mask, "Mask": mask}


@register_grad("dropout")
def dropout_grad(ctx):
    """Backward reuses the saved mask — the one place generic vjp can't apply
    (fresh rng would decorrelate); ref: dropout_op.h DropoutGradKernel."""
    mask = ctx.input("Mask")
    dout = ctx.input("Out@GRAD")
    return {"X@GRAD": dout * mask}


@register_op("shuffle_channel")
def shuffle_channel(ctx):
    x = ctx.input("X")
    g = ctx.attr("group", 1)
    n, c, h, w = x.shape
    return {"Out": x.reshape(n, g, c // g, h, w).swapaxes(1, 2).reshape(n, c, h, w)}


@register_op("range", no_grad_inputs=("Start", "End", "Step"))
def range_op(ctx):
    s = ctx.input("Start").reshape(())
    e = ctx.input("End").reshape(())
    st = ctx.input("Step").reshape(())
    # static shapes required: assume python scalars were baked via attrs if present
    n = ctx.attr("_static_len", None)
    if n is None:
        raise NotImplementedError("range op requires static length on TPU")
    return {"Out": s + st * jnp.arange(n, dtype=s.dtype)}
