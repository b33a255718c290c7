"""Gradient / error clipping (ref: python/paddle/fluid/clip.py — ErrorClip,
ClipByValue, ClipByNorm, ClipByGlobalNorm :212)."""

from __future__ import annotations

import functools

from .framework import NAME_SCOPE_ATTR, OPTIMIZER_SCOPE, OpRole, \
    default_main_program, name_scope_at, param_name_scope

__all__ = ["ErrorClipByValue", "GradientClipByValue", "GradientClipByNorm",
           "GradientClipByGlobalNorm", "append_gradient_clip_ops",
           "append_unscale_ops", "error_clip_callback", "set_gradient_clip"]


class BaseErrorClipAttr:
    def _append_clip_op(self, block, grad_name):
        raise NotImplementedError


class ErrorClipByValue(BaseErrorClipAttr):
    def __init__(self, max, min=None):
        max = float(max)
        self.max = max
        self.min = float(min) if min is not None else -max

    def _append_clip_op(self, block, grad_name):
        block.append_op(type="clip", inputs={"X": [grad_name]},
                        outputs={"Out": [grad_name]},
                        attrs={"min": self.min, "max": self.max,
                               OpRole.KEY: OpRole.Backward})


def error_clip_callback(block, context):
    op = context["__current_op_desc__"]
    for grad_n in op.output_arg_names:
        if not grad_n.endswith("@GRAD"):
            continue
        fwd_var_name = grad_n[: -len("@GRAD")]
        if not block._has_var_recursive(fwd_var_name):
            continue
        fwd_var = block._var_recursive(fwd_var_name)
        error_clip = getattr(fwd_var, "error_clip", None)
        if error_clip is not None:
            with name_scope_at(op.attr(NAME_SCOPE_ATTR, "")):
                error_clip._append_clip_op(block, grad_n)


class BaseGradientClipAttr:
    def _process_context(self, context, param, grad):
        raise NotImplementedError

    def _create_operators(self, param, grad):
        raise NotImplementedError


class NullGradientClipAttr(BaseGradientClipAttr):
    def _process_context(self, context, param, grad):
        pass

    def _create_operators(self, param, grad):
        return param, grad


class GradientClipByValue(BaseGradientClipAttr):
    def __init__(self, max, min=None):
        max = float(max)
        self.max = max
        self.min = float(min) if min is not None else -max

    def _process_context(self, context, param, grad):
        pass

    def _create_operators(self, param, grad):
        from .layers import nn as _nn

        new_grad = _nn.clip(x=grad, min=self.min, max=self.max)
        return param, new_grad


class GradientClipByNorm(BaseGradientClipAttr):
    def __init__(self, clip_norm):
        self.clip_norm = clip_norm

    def _process_context(self, context, param, grad):
        pass

    def _create_operators(self, param, grad):
        from .layers import nn as _nn

        new_grad = _nn.clip_by_norm(x=grad, max_norm=self.clip_norm)
        return param, new_grad


class GradientClipByGlobalNorm(BaseGradientClipAttr):
    def __init__(self, clip_norm, group_name="default_group"):
        self.clip_norm = clip_norm
        self.group_name = group_name

    def _process_context(self, context, param, grad):
        if self.group_name not in context:
            context[self.group_name] = []
            context[self.group_name + "_clip_value"] = self.clip_norm
        elif context[self.group_name + "_clip_value"] != self.clip_norm:
            raise ValueError("all parameters in a group should share clip_norm")
        from .layers import nn as _nn

        with param_name_scope(param):
            local_norm = _nn.reduce_sum(_nn.elementwise_mul(grad, grad))
        context[self.group_name].append(local_norm)
        self.context = context

    def _create_operators(self, param, grad):
        from .layers import nn as _nn, ops as _ops, tensor as _tensor

        group_scale_name = self.group_name + "_scale"
        if group_scale_name not in self.context:
            # the group's own ops are no parameter's
            with name_scope_at(OPTIMIZER_SCOPE):
                group_norm = _tensor.sums(
                    input=self.context[self.group_name])
                group_norm = _ops.sqrt(group_norm)
                clip_var = _tensor.fill_constant(shape=[1], dtype="float32",
                                                 value=self.clip_norm)
                group_scale = _nn.elementwise_div(
                    clip_var, _nn.elementwise_max(clip_var, group_norm))
            self.context[group_scale_name] = group_scale
        new_grad = _nn.elementwise_mul(grad, self.context[group_scale_name])
        return param, new_grad


def set_gradient_clip(clip, param_list=None, program=None):
    program = program or default_main_program()
    if param_list is None:
        param_list = program.global_block().all_parameters()
    param_list = [program.global_block()._var_recursive(p) if isinstance(p, str)
                  else p for p in param_list]
    for param in param_list:
        param.gradient_clip_attr = clip


def append_unscale_ops(params_grads, loss_scale_var):
    """Divide every raw grad by the dynamic loss scale (fluid.amp fp16
    training).  Sits between append_backward and the clip ops, so norms
    and clip thresholds see TRUE gradient magnitudes — the scale only
    ever exists inside the backward pass.  Returns fresh (param, grad)
    pairs; the raw (scaled) grads stay in ``program._params_grads``,
    which is exactly what the guardian's overflow check wants to see."""
    from .framework import program_guard
    from .layers import nn as _nn

    res = []
    for p, g in params_grads:
        if g is None:
            res.append((p, g))
            continue
        block = p.block
        with program_guard(block.program), param_name_scope(p):
            new_grad = _nn.elementwise_div(g, loss_scale_var)
        # backward role: for_test clones and inference pruning must drop
        # the unscale ops together with the rest of the backward graph
        block.ops[-1].attrs[OpRole.KEY] = OpRole.Backward
        res.append((p, new_grad))
    return res


def append_gradient_clip_ops(param_grad):
    context = {}
    for p, g in param_grad:
        clip_attr = getattr(p, "gradient_clip_attr", None) or \
            NullGradientClipAttr()
        clip_attr._process_context(context=context, param=p, grad=g)
    res = []
    for p, g in param_grad:
        clip_attr = getattr(p, "gradient_clip_attr", None) or \
            NullGradientClipAttr()
        with param_name_scope(p):
            res.append(clip_attr._create_operators(param=p, grad=g))
    return res
