"""Loss ops (ref: cross_entropy_op.*, softmax_with_cross_entropy_op.*,
sigmoid_cross_entropy_with_logits_op, huber_loss_op, smooth_l1_loss_op,
log_loss_op, hinge_loss_op, rank_loss_op, margin_rank_loss_op,
squared_l2_norm_op, squared_l2_distance_op)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_grad, register_op


def _hard_xent(probs, label, ignore_index=-100):
    if label.ndim == probs.ndim and label.shape[-1] == 1:
        label = label.reshape(label.shape[:-1])
    li = label.astype(jnp.int32)
    picked = jnp.take_along_axis(probs, li[..., None], axis=-1)
    loss = -jnp.log(jnp.maximum(picked, 1e-20))
    if ignore_index >= 0:
        loss = jnp.where((li == ignore_index)[..., None], 0.0, loss)
    return loss


@register_op("cross_entropy", no_grad_inputs=("Label",))
def cross_entropy(ctx):
    from ..fluid import amp

    x = ctx.input("X")  # probabilities [N, C]
    if amp.is_low_float(x.dtype):
        x = x.astype(jnp.float32)  # log() at the loss boundary is fp32
    label = ctx.input("Label")
    if ctx.attr("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, 1e-20)), -1, keepdims=True)
        return {"Y": loss}
    return {"Y": _hard_xent(x, label, ctx.attr("ignore_index", -100))}


def _xent_operands(ctx):
    """(logits, label, soft, eps, kernels): ``eps`` is the uniform
    smoothing of hard labels, ``(1 - eps) * onehot + eps / V``, from the
    label column alone (set by layers.softmax_with_cross_entropy);
    ``kernels`` whether this instance takes the streaming Pallas kernels.
    The forward and the grad op both ask here, so they cannot disagree."""
    from . import kernel_choice, pallas_fused

    logits, label = ctx.input("Logits"), ctx.input("Label")
    soft = ctx.attr("soft_label", False)
    eps = 0.0 if soft else float(ctx.attr("smooth_epsilon", 0.0))
    kernels = kernel_choice.gate("fused") \
        and pallas_fused.xent_fusable(logits, label, soft)
    return logits, label, soft, eps, kernels


@register_op("softmax_with_cross_entropy", no_grad_inputs=("Label",))
def softmax_with_cross_entropy(ctx):
    """Loss ``[..., 1]`` of the softmax of Logits ``[..., V]`` against
    Label: an integer column (``ignore_index``; smoothed uniformly by the
    attr ``smooth_epsilon``) or, with ``soft_label``, a distribution.
    Softmax is the probabilities.  Lse (float32, ``[..., 1]``) is the rows'
    log-sum-exp: the kernels' own (``m + log(l)``) on the Pallas path, the
    true ``logsumexp`` on the XLA path, where nothing reads it and XLA
    drops it.  It is there for the grad op (below)."""
    from ..fluid import amp
    from . import pallas_fused

    logits, label, soft, eps, kernels = _xent_operands(ctx)
    if kernels:
        # streaming Pallas lowering: the [batch, vocab] probability matrix
        # never materializes in HBM; backward recomputes P per tile from
        # the saved logsumexp (ops/pallas_fused.py)
        return pallas_fused.softmax_xent_op(
            logits, label, soft, ctx.attr("ignore_index", -100), eps)

    in_dtype = logits.dtype
    if amp.is_low_float(in_dtype):
        logits = logits.astype(jnp.float32)  # fp32 at the loss boundary
    sm = jax.nn.softmax(logits, axis=-1).astype(in_dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    if soft:
        loss = -jnp.sum(label * logp, -1, keepdims=True)
    else:
        li = label
        if li.ndim == logits.ndim and li.shape[-1] == 1:
            li = li.reshape(li.shape[:-1])
        li = li.astype(jnp.int32)
        loss = -jnp.take_along_axis(logp, li[..., None], axis=-1)
        if eps:
            loss = (1.0 - eps) * loss - (eps / logp.shape[-1]) * jnp.sum(
                logp, -1, keepdims=True)
        ignore = ctx.attr("ignore_index", -100)
        if ignore >= 0:
            loss = jnp.where((li == ignore)[..., None], 0.0, loss)
    return {"Softmax": sm, "Loss": loss, "Lse": lse.astype(jnp.float32)}


@register_grad("softmax_with_cross_entropy")
def softmax_with_cross_entropy_grad(ctx):
    """The backward kernel alone, from the forward's own Lse: the generic
    vjp would trace, and the chip would run, the whole forward kernel a
    second time to get those ``[..., 1]`` numbers back.  Taken where the
    forward took the kernels, the label is a column (hard or smoothed), the
    op has an Lse output and nothing differentiates Softmax.  Anywhere else
    the generic vjp: the XLA path, a soft label (its residual also needs
    ``sum(y)``), a program built or saved without the Lse slot.  Counted as
    ``ops.softmax_xent.grad_calls{path="from_lse"|"generic"}``."""
    from . import pallas_fused, registry
    from .decoder_ops import _count

    logits, label, soft, eps, kernels = _xent_operands(ctx)
    lse = ctx.input("Lse")
    from_lse = kernels and not soft and lse is not None \
        and ctx.input("Softmax@GRAD") is None
    _count("ops.softmax_xent.grad_calls",
           path="from_lse" if from_lse else "generic")
    if not from_lse:
        return registry.run_grad_generic(
            registry.get_op_def("softmax_with_cross_entropy"), ctx)
    return {"Logits@GRAD": pallas_fused.softmax_xent_grad(
        logits, label, lse, ctx.input("Loss@GRAD"),
        ctx.attr("ignore_index", -100), eps)}


@register_op("sigmoid_cross_entropy_with_logits", no_grad_inputs=("Label",))
def sigmoid_ce(ctx):
    x = ctx.input("X")
    label = ctx.input("Label")
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = ctx.attr("ignore_index", -100)
    if ignore >= 0:
        loss = jnp.where(label == ignore, 0.0, loss)
    return {"Out": loss}


@register_op("huber_loss", no_grad_inputs=("Y",))
def huber_loss(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    d = ctx.attr("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= d, 0.5 * r * r, d * (ar - 0.5 * d))
    return {"Out": loss, "Residual": r}


@register_op("smooth_l1_loss", no_grad_inputs=("Y",))
def smooth_l1_loss(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    sigma = ctx.attr("sigma", 1.0)
    s2 = sigma * sigma
    iw = ctx.input("InsideWeight")
    ow = ctx.input("OutsideWeight")
    d = x - y
    if iw is not None:
        d = d * iw
    ad = jnp.abs(d)
    val = jnp.where(ad < 1.0 / s2, 0.5 * d * d * s2, ad - 0.5 / s2)
    if ow is not None:
        val = val * ow
    out = jnp.sum(val.reshape(val.shape[0], -1), axis=1, keepdims=True)
    return {"Out": out, "Diff": d}


@register_op("log_loss", no_grad_inputs=("Labels",))
def log_loss(ctx):
    p = ctx.input("Predicted")
    y = ctx.input("Labels")
    eps = ctx.attr("epsilon", 1e-4)
    out = -y * jnp.log(p + eps) - (1.0 - y) * jnp.log(1.0 - p + eps)
    return {"Loss": out}


@register_op("hinge_loss", no_grad_inputs=("Labels",))
def hinge_loss(ctx):
    logits = ctx.input("Logits")
    y = ctx.input("Labels")
    return {"Loss": jnp.maximum(1.0 - (2.0 * y - 1.0) * logits, 0.0)}


@register_op("rank_loss", no_grad_inputs=("Label",))
def rank_loss(ctx):
    label = ctx.input("Label")
    left, right = ctx.input("Left"), ctx.input("Right")
    d = left - right
    return {"Out": jnp.log1p(jnp.exp(d)) - label * d}


@register_op("margin_rank_loss", no_grad_inputs=("Label",))
def margin_rank_loss(ctx):
    label = ctx.input("Label")
    x1, x2 = ctx.input("X1"), ctx.input("X2")
    m = ctx.attr("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + m)
    return {"Out": out, "Activated": (out > 0).astype(x1.dtype)}


@register_op("squared_l2_norm")
def squared_l2_norm(ctx):
    x = ctx.input("X")
    return {"Out": jnp.sum(x * x).reshape(1)}


@register_op("squared_l2_distance", no_grad_inputs=())
def squared_l2_distance(ctx):
    x, y = ctx.input("X"), ctx.input("Y")
    d = x - y
    return {"Out": jnp.sum(d * d, axis=tuple(range(1, d.ndim)), keepdims=False)
            .reshape(-1, 1), "sub_result": d}


@register_op("bpr_loss", no_grad_inputs=("Label",))
def bpr_loss(ctx):
    x = ctx.input("X")  # [N, C] logits
    label = ctx.input("Label")
    if label.ndim == x.ndim and label.shape[-1] == 1:
        label = label.reshape(label.shape[:-1])
    li = label.astype(jnp.int32)
    pos = jnp.take_along_axis(x, li[..., None], axis=-1)
    # mean of -log(sigmoid(pos - neg)) over the C-1 true negatives
    # (ref: bpr_loss_op.h excludes j == label)
    lls = jax.nn.log_sigmoid(pos - x)
    mask = jax.nn.one_hot(li, x.shape[-1], dtype=x.dtype)
    n_neg = x.shape[-1] - 1
    loss = -jnp.sum(lls * (1.0 - mask), axis=-1, keepdims=True) / n_neg
    return {"Y": loss}


@register_op("kldiv_loss", no_grad_inputs=("Target",))
def kldiv_loss(ctx):
    x = ctx.input("X")  # log-probs
    t = ctx.input("Target")
    loss = t * (jnp.log(jnp.maximum(t, 1e-20)) - x)
    red = ctx.attr("reduction", "mean")
    if red == "mean":
        return {"Loss": jnp.mean(loss)}
    if red == "sum":
        return {"Loss": jnp.sum(loss)}
    if red == "batchmean":
        return {"Loss": jnp.sum(loss) / x.shape[0]}
    return {"Loss": loss}
