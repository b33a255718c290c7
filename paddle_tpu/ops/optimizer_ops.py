"""Optimizer update ops (ref: sgd_op.*, momentum_op.*, adam_op.*, adagrad_op.*,
adamax_op.*, adadelta_op.*, rmsprop_op.*, decayed_adagrad_op.*, ftrl_op.*).

Each is a pure function from (param, grad, accumulators, lr) to new values; the
Executor's SSA rebinding makes them in-place on device (donated buffers)."""

from __future__ import annotations

import jax.numpy as jnp

from .registry import register_op


def _lr(ctx):
    return ctx.input("LearningRate").reshape(())


def _grad(ctx, p):
    """Dense view of the Grad input.  A SelectedRows grad (sparse embedding
    backward) is folded by scatter-add; moment-carrying optimizers then run
    exact dense semantics.  (Deviation from the reference's row-lazy sparse
    adam/adagrad — ref adam_op.h SelectedRows branch skips moment decay on
    untouched rows — is deliberate: dense decay is the mathematically
    standard update and XLA fuses the scatter, so there is no kernel-launch
    saving to chase on TPU.  The latency-critical sparse path is sgd, which
    stays truly sparse below.)"""
    from ..fluid.selected_rows import SelectedRows

    g = ctx.input("Grad")
    if isinstance(g, SelectedRows):
        return g.to_dense(p.shape[0]).astype(p.dtype)
    return g


@register_op("sgd", no_grad_inputs=("Param", "Grad", "LearningRate"))
def sgd(ctx):
    from ..fluid.selected_rows import SelectedRows

    p, g = ctx.input("Param"), ctx.input("Grad")
    if isinstance(g, SelectedRows):
        # touch only the looked-up rows; duplicates fold in the scatter-add
        # (ref: sgd_op.h SelectedRows branch)
        return {"ParamOut": g.scatter_sub_into(p, _lr(ctx))}
    return {"ParamOut": p - _lr(ctx) * g}


def _fused_opt_ok(ctx, kind, p, g, out_slots):
    """Route this update through the single-sweep Pallas kernel?  Gate +
    static suitability (an open gate that the tensor does not suit is
    counted as ``ops.fused.declined{kind,why}``; ``why=layout``: no 2-D
    view of it is free, ``pallas_fused._sweep_view``) + (under a mesh)
    spec alignment of param and accumulators — ZeRO-1-diverged updates
    keep the unfused lowering."""
    from . import kernel_choice, pallas_fused

    if not kernel_choice.gate("fused"):
        return False
    names = [(ctx.outputs_spec.get(s) or [None])[0] for s in out_slots]
    why = pallas_fused.opt_declined(p, g, names[0])
    if why is not None:
        pallas_fused._note("declined", kind=kind, why=why)
        return False
    return pallas_fused.opt_specs_aligned(names)


@register_op("momentum", no_grad_inputs=("Param", "Grad", "Velocity", "LearningRate"))
def momentum(ctx):
    p, v = ctx.input("Param"), ctx.input("Velocity")
    g = _grad(ctx, p)
    mu = ctx.attr("mu")
    lr = _lr(ctx)
    if _fused_opt_ok(ctx, "momentum", p, g, ("ParamOut", "VelocityOut")):
        from . import pallas_fused

        p_out, v_out = pallas_fused.fused_momentum(
            p, g, v, lr, mu, ctx.attr("use_nesterov", False),
            var_name=(ctx.outputs_spec.get("ParamOut") or [None])[0])
        return {"ParamOut": p_out, "VelocityOut": v_out}
    v_out = mu * v + g
    if ctx.attr("use_nesterov", False):
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {"ParamOut": p_out, "VelocityOut": v_out}


@register_op("adam", no_grad_inputs=("Param", "Grad", "LearningRate", "Moment1",
                                     "Moment2", "Beta1Pow", "Beta2Pow"))
def adam(ctx):
    p = ctx.input("Param")
    g = _grad(ctx, p)
    m1, m2 = ctx.input("Moment1"), ctx.input("Moment2")
    b1p, b2p = ctx.input("Beta1Pow").reshape(()), ctx.input("Beta2Pow").reshape(())
    b1 = ctx.attr("beta1", 0.9)
    b2 = ctx.attr("beta2", 0.999)
    eps = ctx.attr("epsilon", 1e-8)
    lr = _lr(ctx) * jnp.sqrt(1.0 - b2p) / (1.0 - b1p)
    if _fused_opt_ok(ctx, "adam", p, g,
                     ("ParamOut", "Moment1Out", "Moment2Out")):
        from . import pallas_fused

        # the bias-corrected lr and beta-pow counters are [1]-shaped
        # scalar math; the sweep fuses the four big buffers
        po, m1o, m2o = pallas_fused.fused_adam(
            p, g, m1, m2, lr, b1, b2, eps,
            var_name=(ctx.outputs_spec.get("ParamOut") or [None])[0])
        return {"ParamOut": po, "Moment1Out": m1o, "Moment2Out": m2o,
                "Beta1PowOut": (b1p * b1).reshape(1),
                "Beta2PowOut": (b2p * b2).reshape(1)}
    m1o = b1 * m1 + (1.0 - b1) * g
    m2o = b2 * m2 + (1.0 - b2) * g * g
    po = p - lr * m1o / (jnp.sqrt(m2o) + eps)
    return {"ParamOut": po, "Moment1Out": m1o, "Moment2Out": m2o,
            "Beta1PowOut": (b1p * b1).reshape(1), "Beta2PowOut": (b2p * b2).reshape(1)}


@register_op("adagrad", no_grad_inputs=("Param", "Grad", "Moment", "LearningRate"))
def adagrad(ctx):
    p, m = ctx.input("Param"), ctx.input("Moment")
    g = _grad(ctx, p)
    eps = ctx.attr("epsilon", 1e-6)
    mo = m + g * g
    return {"ParamOut": p - _lr(ctx) * g / (jnp.sqrt(mo) + eps), "MomentOut": mo}


@register_op("adamax", no_grad_inputs=("Param", "Grad", "LearningRate", "Moment",
                                       "InfNorm", "Beta1Pow"))
def adamax(ctx):
    p = ctx.input("Param")
    g = _grad(ctx, p)
    m, inf = ctx.input("Moment"), ctx.input("InfNorm")
    b1p = ctx.input("Beta1Pow").reshape(())
    b1 = ctx.attr("beta1", 0.9)
    b2 = ctx.attr("beta2", 0.999)
    eps = ctx.attr("epsilon", 1e-8)
    mo = b1 * m + (1.0 - b1) * g
    info = jnp.maximum(b2 * inf, jnp.abs(g))
    lr = _lr(ctx) / (1.0 - b1p)
    return {"ParamOut": p - lr * mo / (info + eps), "MomentOut": mo,
            "InfNormOut": info}


@register_op("adadelta", no_grad_inputs=("Param", "Grad", "AvgSquaredGrad",
                                         "AvgSquaredUpdate"))
def adadelta(ctx):
    p = ctx.input("Param")
    g = _grad(ctx, p)
    asg, asu = ctx.input("AvgSquaredGrad"), ctx.input("AvgSquaredUpdate")
    rho = ctx.attr("rho", 0.95)
    eps = ctx.attr("epsilon", 1e-6)
    asg_o = rho * asg + (1.0 - rho) * g * g
    upd = -jnp.sqrt((asu + eps) / (asg_o + eps)) * g
    asu_o = rho * asu + (1.0 - rho) * upd * upd
    return {"ParamOut": p + upd, "AvgSquaredGradOut": asg_o,
            "AvgSquaredUpdateOut": asu_o}


@register_op("rmsprop", no_grad_inputs=("Param", "Grad", "MeanSquare", "Moment",
                                        "LearningRate"))
def rmsprop(ctx):
    p = ctx.input("Param")
    g = _grad(ctx, p)
    ms, mom = ctx.input("MeanSquare"), ctx.input("Moment")
    eps = ctx.attr("epsilon", 1e-10)
    decay = ctx.attr("decay", 0.9)
    mu = ctx.attr("momentum", 0.0)
    ms_o = decay * ms + (1.0 - decay) * g * g
    mom_o = mu * mom + _lr(ctx) * g / jnp.sqrt(ms_o + eps)
    return {"ParamOut": p - mom_o, "MeanSquareOut": ms_o, "MomentOut": mom_o}


@register_op("decayed_adagrad", no_grad_inputs=("Param", "Grad", "Moment",
                                                "LearningRate"))
def decayed_adagrad(ctx):
    p, m = ctx.input("Param"), ctx.input("Moment")
    g = _grad(ctx, p)
    decay = ctx.attr("decay", 0.95)
    eps = ctx.attr("epsilon", 1e-6)
    mo = decay * m + (1.0 - decay) * g * g
    return {"ParamOut": p - _lr(ctx) * g / (jnp.sqrt(mo) + eps), "MomentOut": mo}


@register_op("ftrl", no_grad_inputs=("Param", "Grad", "SquaredAccumulator",
                                     "LinearAccumulator", "LearningRate"))
def ftrl(ctx):
    p = ctx.input("Param")
    g = _grad(ctx, p)
    sq, lin = ctx.input("SquaredAccumulator"), ctx.input("LinearAccumulator")
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    lr_power = ctx.attr("lr_power", -0.5)
    lr = _lr(ctx)
    new_sq = sq + g * g
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
    else:
        sigma = (jnp.power(new_sq, -lr_power) - jnp.power(sq, -lr_power)) / lr
    new_lin = lin + g - sigma * p
    if lr_power == -0.5:
        denom = jnp.sqrt(new_sq) / lr + 2.0 * l2
    else:
        denom = jnp.power(new_sq, -lr_power) / lr + 2.0 * l2
    x = l1 * jnp.sign(new_lin) - new_lin
    p_out = jnp.where(jnp.abs(new_lin) > l1, x / denom, jnp.zeros_like(p))
    return {"ParamOut": p_out, "SquaredAccumOut": new_sq, "LinearAccumOut": new_lin}


@register_op("proximal_gd", no_grad_inputs=("Param", "Grad",
                                             "LearningRate"))
def proximal_gd(ctx):
    """ref: proximal_gd_op.* — SGD step followed by the proximal operator
    for l1/l2 regularization: soft-threshold then shrink."""
    p = ctx.input("Param")
    g = ctx.input("Grad")
    lr = ctx.input("LearningRate").reshape(()).astype(p.dtype)
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    prox = p - lr * g
    out = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)         / (1.0 + lr * l2)
    return {"ParamOut": out.astype(p.dtype)}


@register_op("proximal_adagrad", no_grad_inputs=("Param", "Grad", "Moment",
                                                 "LearningRate"))
def proximal_adagrad(ctx):
    """ref: proximal_adagrad_op.* — adagrad-scaled step + proximal l1/l2."""
    p = ctx.input("Param")
    g = ctx.input("Grad")
    m = ctx.input("Moment")
    lr = ctx.input("LearningRate").reshape(()).astype(p.dtype)
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    m_out = m + g * g
    prox = p - lr * g / jnp.sqrt(m_out + 1e-10)
    # threshold/shrink with the SCALAR lr (ref proximal_adagrad_op.h) —
    # a per-element effective lr would decay the l1 threshold to zero
    out = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0) \
        / (1.0 + lr * l2)
    return {"ParamOut": out.astype(p.dtype), "MomentOut": m_out}


@register_op("average_accumulates",
             no_grad_inputs=("param", "in_sum_1", "in_sum_2", "in_sum_3",
                             "in_num_accumulates", "in_old_num_accumulates",
                             "in_num_updates"))
def average_accumulates(ctx):
    """ModelAverage support (ref: average_accumulates_op.*)."""
    param = ctx.input("param")
    s1, s2, s3 = ctx.input("in_sum_1"), ctx.input("in_sum_2"), ctx.input("in_sum_3")
    na = ctx.input("in_num_accumulates").reshape(())
    ona = ctx.input("in_old_num_accumulates").reshape(())
    nu = ctx.input("in_num_updates").reshape(())
    avg_window = ctx.attr("average_window", 0.0)
    max_avg = ctx.attr("max_average_window", 10000)
    min_avg = ctx.attr("min_average_window", 10000)
    k_max_acc = 16384  # ref: kMaxNumAccumulates in average_accumulates_op.h
    na = na + 1
    nu = nu + 1
    s1 = s1 + param
    # periodic fold of sum_1 into sum_2 to bound fp accumulation error
    fold = (nu % k_max_acc) == 0
    s2 = jnp.where(fold, s2 + s1, s2)
    s1 = jnp.where(fold, jnp.zeros_like(s1), s1)
    # window trigger: snapshot sums into sum_3 and restart the window
    trigger = (na >= min_avg) & \
        (na >= jnp.minimum(float(max_avg), avg_window * nu))
    s3 = jnp.where(trigger, s1 + s2, s3)
    s1 = jnp.where(trigger, jnp.zeros_like(s1), s1)
    s2 = jnp.where(trigger, jnp.zeros_like(s2), s2)
    ona = jnp.where(trigger, na, ona)
    na = jnp.where(trigger, jnp.zeros_like(na), na)
    idt = ctx.input("in_num_accumulates").dtype
    return {"out_sum_1": s1, "out_sum_2": s2, "out_sum_3": s3,
            "out_num_accumulates": na.reshape(1).astype(idt),
            "out_old_num_accumulates": ona.reshape(1).astype(idt),
            "out_num_updates": nu.reshape(1).astype(idt)}
