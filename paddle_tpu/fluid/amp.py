"""Mixed-precision compute mode: bf16 matmuls/convs with fp32 master weights.

TPU-native equivalent of the reference's float16 transpiler
(ref: paddle/contrib/float16/float16_transpiler.py, which rewrites a program
so inference runs in fp16).  The reference rewrites the *program* because its
kernels are dtype-monomorphic; here the op library itself is polymorphic, so
mixed precision is an execution mode: when enabled, the matmul-class ops
(mul/matmul/fc, conv2d/3d and friends) cast fp32 operands to the compute
dtype and accumulate in fp32 via ``preferred_element_type``.

This is exactly the TPU-idiomatic recipe: parameters, optimizer state,
normalizations and reductions stay fp32 (master weights), while the
MXU-bound contractions run in the low dtype.  The contraction itself
executes entirely in that dtype (the MXU accumulates bf16 products in fp32
*in hardware*; there is no explicit preferred_element_type — its vjp rules
reject mixed cotangent/operand dtypes for convs).  Consequences:

 - "bfloat16" (recommended, the default): same exponent range as fp32, no
   loss scaling needed; hardware fp32 accumulation makes operand rounding
   the only precision loss.
 - "float16": the contraction accumulates in fp16 with fp16's narrow
   exponent range — usable for TRAINING because enabling it arms a
   **dynamic loss scaler** by default: the backward seed is multiplied by
   a persistable scale (so fp16 intermediate grads sit in representable
   range), the raw grads are divided back by the scale before clip and
   update (``clip.append_unscale_ops``), and the guarded executor step
   (``fluid.guardian``) grows the scale x2 every ``growth_interval``
   overflow-free steps, shrinks it /2 and SKIPS the update (device-side,
   bit-exact revert) on overflow.  The reference's fp16 transpiler
   targets *inference* (float16_benchmark.md); this is the training
   story it lacked.

Enable programmatically::

    import paddle_tpu.fluid as fluid
    fluid.amp.enable("bfloat16")          # or fluid.amp.amp_guard(...)

or via the environment: ``PADDLE_TPU_AMP=bfloat16``.
"""

from __future__ import annotations

import contextlib
import os

_SUPPORTED = ("bfloat16", "float16")

#: persistable scope vars carrying the dynamic loss-scale state; created by
#: Optimizer.minimize (via create_loss_scaling_vars) when scaling is active
#: at build time, updated device-side by guardian.fold_health every step
LOSS_SCALE_VAR = "@LOSS_SCALE@"
LOSS_SCALE_GOOD_VAR = "@LOSS_SCALE_GOOD@"

_state = {"dtype": None, "keep": False, "dynamic_scaling": None,
          "init_loss_scale": 2.0 ** 15, "scale_growth_interval": 1000}


def enable(dtype: str = "bfloat16", keep_activations=None,
           dynamic_loss_scaling=None, init_loss_scale=None,
           growth_interval=None) -> None:
    """Enable mixed precision.

    ``keep_activations=True`` selects the pure-low-precision activation
    regime: contraction outputs STAY in the compute dtype instead of being
    cast back to fp32, so inter-layer activations (the dominant HBM
    traffic of conv nets at scale) move at half the bytes.  Numerics keep
    the master-fp32 discipline everywhere it matters: parameters,
    optimizer state and gradients stay fp32 (the cast's transpose upcasts
    cotangents), batch_norm/layer_norm compute statistics in fp32, and
    softmax/cross-entropy upcast at the loss boundary.  This is the
    standard production-TPU training recipe; what it buys on the attached
    chip is not measured yet (root PERF.md).
    Default: the PADDLE_TPU_AMP_KEEP env var, else False.
    """
    if dtype not in _SUPPORTED:
        raise ValueError(f"amp dtype must be one of {_SUPPORTED}, got {dtype!r}")
    _state["dtype"] = dtype
    if keep_activations is None:
        from . import envcontract

        keep_activations = bool(envcontract.get("PADDLE_TPU_AMP_KEEP"))
    _state["keep"] = bool(keep_activations)
    # dynamic loss scaling: None = auto (on for float16, pointless for
    # bfloat16 whose exponent range matches fp32); True/False force it.
    # Scaling is a BUILD-time decision — it threads scale vars and
    # seed/unscale ops through Optimizer.minimize — so set it before
    # building the train program.
    _state["dynamic_scaling"] = dynamic_loss_scaling
    if init_loss_scale is not None:
        _state["init_loss_scale"] = float(init_loss_scale)
    if growth_interval is not None:
        _state["scale_growth_interval"] = max(1, int(growth_interval))


def disable() -> None:
    _state["dtype"] = None
    _state["keep"] = False
    _state["dynamic_scaling"] = None


def dynamic_scaling_active() -> bool:
    """True when programs built NOW should carry dynamic loss scaling."""
    ds = _state["dynamic_scaling"]
    if ds is not None:
        return bool(ds) and _state["dtype"] is not None
    return _state["dtype"] == "float16"


def scaling_config():
    """(init_loss_scale, growth_interval) for the scaler being built."""
    return _state["init_loss_scale"], _state["scale_growth_interval"]


def create_loss_scaling_vars(program, startup_program):
    """Create (or reuse) the persistable loss-scale state vars in
    ``program`` and record them on it for the guarded executor step.
    Returns the scale Variable (read by the seed/unscale ops)."""
    from .framework import program_guard
    from .layers import tensor as _tensor

    block = program.global_block()
    with program_guard(program, startup_program):
        if block.has_var(LOSS_SCALE_VAR):
            scale = block.var(LOSS_SCALE_VAR)
        else:
            scale = _tensor.create_global_var(
                shape=[1], value=_state["init_loss_scale"], dtype="float32",
                persistable=True, name=LOSS_SCALE_VAR)
            _tensor.create_global_var(
                shape=[1], value=0, dtype="int32",
                persistable=True, name=LOSS_SCALE_GOOD_VAR)
    program._loss_scale_vars = (LOSS_SCALE_VAR, LOSS_SCALE_GOOD_VAR)
    program._loss_scale_growth = _state["scale_growth_interval"]
    return scale


def is_enabled() -> bool:
    return _state["dtype"] is not None


def compute_dtype():
    """The active low-precision compute dtype name, or None."""
    return _state["dtype"]


def keep_low_activations() -> bool:
    """True when AMP is on in the pure-low-activation regime."""
    return _state["dtype"] is not None and _state["keep"]


def is_low_float(dtype) -> bool:
    """True for sub-32-bit float dtypes (bf16/fp16) — THE predicate ops use
    to decide 'compute this norm/loss internally in fp32'.  Centralized so
    the regime's dtype policy has one definition."""
    import jax.numpy as jnp

    return jnp.issubdtype(dtype, jnp.floating) and jnp.finfo(dtype).bits < 32


@contextlib.contextmanager
def amp_guard(dtype: str = "bfloat16", keep_activations=None):
    prev = dict(_state)
    enable(dtype, keep_activations=keep_activations)
    try:
        yield
    finally:
        _state.update(prev)


def matmul(a, b):
    """``a @ b`` in the AMP compute dtype; identity when AMP is off.  The
    result is restored to fp32 in the default regime, or LEFT in the
    compute dtype under keep_activations.  The shared helper for code that
    contracts OUTSIDE the op library (stacked transformer, ring
    attention) — one policy, every path."""
    a2, b2, back = cast_operands(a, b)
    return restore_astype(a2 @ b2, back)


def einsum(spec, a, b):
    """Two-operand einsum under the same AMP recipe (and keep_activations
    behavior) as :func:`matmul`."""
    import jax.numpy as jnp

    a2, b2, back = cast_operands(a, b)
    return restore_astype(jnp.einsum(spec, a2, b2), back)


def cast_operands(*arrays):
    """Cast fp32 contraction operands to the AMP dtype.

    Returns ``(arrays..., restore_dtype)``.  Default regime: when AMP is
    off (or any operand is not fp32) the operands pass through unchanged
    and restore_dtype is None; otherwise the caller computes the
    contraction in the low dtype and casts its result back with
    ``restore_astype`` — NOT via ``preferred_element_type``, whose vjp
    rules reject mixed cotangent/operand dtypes for convs.  On the MXU
    this costs nothing: bf16 matmuls accumulate in fp32 internally.

    keep_activations regime: operands may arrive fp32 (params/feeds) or
    already in the compute dtype (upstream activations); fp32 ones are
    cast down, restore_dtype is None, and the result STAYS low — the
    whole point of the regime (half the inter-layer HBM bytes).
    """
    import jax.numpy as jnp

    d = _state["dtype"]
    if d is None:
        return (*arrays, None)
    cd = jnp.bfloat16 if d == "bfloat16" else jnp.float16
    if _state["keep"]:
        # pure-low-activation regime: operands may arrive fp32 (params,
        # feeds) or already in the compute dtype (upstream activations);
        # cast the fp32 ones down and DON'T restore — the contraction
        # result stays low so downstream layers read half the bytes.
        if any(a is None or a.dtype not in (jnp.float32, cd)
               for a in arrays):
            return (*arrays, None)
        return (*(a.astype(cd) if a.dtype == jnp.float32 else a
                  for a in arrays), None)
    if any(a is None or a.dtype != jnp.float32 for a in arrays):
        return (*arrays, None)
    return (*(a.astype(cd) for a in arrays), jnp.float32)


def restore_astype(out, restore_dtype):
    """Cast a contraction result back to the pre-AMP dtype (no-op when
    cast_operands passed through)."""
    return out if restore_dtype is None else out.astype(restore_dtype)


# environment bridge (ref: python/paddle/fluid/__init__.py:121-140 reads
# FLAGS from env at import time)
_env = os.environ.get("PADDLE_TPU_AMP", "").strip().lower()
if _env in ("bf16", "bfloat16", "1", "true"):
    enable("bfloat16")
elif _env in ("fp16", "float16"):
    enable("float16")
_env_scale = os.environ.get("PADDLE_TPU_AMP_INIT_SCALE", "").strip()
if _env_scale:
    _state["init_loss_scale"] = float(_env_scale)
_env_interval = os.environ.get("PADDLE_TPU_AMP_SCALE_INTERVAL", "").strip()
if _env_interval:
    _state["scale_growth_interval"] = max(1, int(_env_interval))
