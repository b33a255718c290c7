"""Learning-rate decay schedules (ref: layers/learning_rate_scheduler.py —
exponential/natural_exp/inverse_time/polynomial/piecewise/noam decay).

Each schedule is a small in-graph expression over the auto-incremented global
step counter, so it compiles into the same XLA program as the train step.
"""

from __future__ import annotations

import math

from .nn import autoincreased_step_counter, elementwise_div, elementwise_min, \
    elementwise_max
from .tensor import cast, fill_constant
from . import ops as _ops

__all__ = ["exponential_decay", "natural_exp_decay", "inverse_time_decay",
           "polynomial_decay", "piecewise_decay", "noam_decay",
           "cosine_decay", "append_LARS"]


def _decayed_lr_var(value):
    from ..layer_helper import LayerHelper

    helper = LayerHelper("learning_rate_decay")
    lr = helper.create_global_variable(
        name=helper.name + ".lr", shape=[1], dtype="float32",
        persistable=True)
    return lr


def _global_step():
    counter = autoincreased_step_counter(begin=1)
    return cast(counter, "float32")


def noam_decay(d_model, warmup_steps):
    global_step = _global_step()
    a = global_step ** -0.5
    b = (warmup_steps ** -1.5) * global_step
    return (d_model ** -0.5) * elementwise_min(a, b)


def exponential_decay(learning_rate, decay_steps, decay_rate, staircase=False):
    global_step = _global_step()
    div_res = global_step / float(decay_steps)
    if staircase:
        div_res = _ops.floor(div_res)
    return learning_rate * (float(decay_rate) ** div_res)


def natural_exp_decay(learning_rate, decay_steps, decay_rate, staircase=False):
    global_step = _global_step()
    div_res = global_step / float(decay_steps)
    if staircase:
        div_res = _ops.floor(div_res)
    return learning_rate * _ops.exp(div_res * float(-decay_rate))


def inverse_time_decay(learning_rate, decay_steps, decay_rate, staircase=False):
    global_step = _global_step()
    div_res = global_step / float(decay_steps)
    if staircase:
        div_res = _ops.floor(div_res)
    return learning_rate / (div_res * float(decay_rate) + 1.0)


def polynomial_decay(learning_rate, decay_steps, end_learning_rate=0.0001,
                     power=1.0, cycle=False):
    global_step = _global_step()
    if cycle:
        div_res = _ops.ceil(global_step / float(decay_steps))
        # at step 0 paddle forces one cycle
        decay_steps_var = div_res * float(decay_steps)
        p = global_step / decay_steps_var
    else:
        p = elementwise_min(global_step / float(decay_steps),
                            fill_constant([1], "float32", 1.0))
    return (learning_rate - end_learning_rate) * ((1.0 - p) ** power) \
        + end_learning_rate


def piecewise_decay(boundaries, values):
    """lr = values[i] for step in (boundaries[i-1], boundaries[i]].

    Branch-free: a sum of masked constants (TPU-friendly; no lax.cond)."""
    if len(values) - len(boundaries) != 1:
        raise ValueError("len(values) must be len(boundaries) + 1")
    global_step = _global_step()
    lr = fill_constant([1], "float32", values[-1])
    prev_bound = None
    for i, b in enumerate(boundaries):
        below = cast(global_step <= float(b), "float32")
        if prev_bound is not None:
            above = cast(global_step > float(prev_bound), "float32")
            mask = below * above
        else:
            mask = below
        lr = lr + mask * (values[i] - values[-1])
        prev_bound = b
    return lr


def cosine_decay(learning_rate, step_each_epoch, epochs):
    global_step = _global_step()
    cur_epoch = _ops.floor(global_step / float(step_each_epoch))
    return learning_rate * 0.5 * (
        _ops.cos(cur_epoch * (math.pi / float(epochs))) + 1.0)


def append_LARS(params_grads, learning_rate, weight_decay):
    """LARS — layer-wise adaptive rate scaling (ref layers/
    learning_rate_scheduler.py append_LARS): per parameter,
    lr = global_lr * ||param|| / (||grad|| + weight_decay * ||param||),
    stored back on param.optimize_attr for _create_param_lr to pick up."""
    from . import nn as _nn
    from . import ops as _ops
    from ..framework import param_name_scope

    def _balanced_weight(param_norm, grad_norm):
        if weight_decay == 1.0:
            return _nn.elementwise_add(grad_norm, param_norm)
        return _nn.elementwise_add(
            grad_norm, _nn.scale(param_norm, scale=float(weight_decay)))

    for param, grad in params_grads:
        if grad is None:
            continue
        with param_name_scope(param):
            attr = param.optimize_attr or {}
            param_lr = attr.get("learning_rate", 1.0)
            param_norm = _ops.sqrt(_nn.reduce_sum(_ops.square(param)))
            grad_norm = _ops.sqrt(_nn.reduce_sum(_ops.square(grad)))
            if isinstance(param_lr, (int, float)):
                scaled = learning_rate if param_lr == 1.0 else \
                    _nn.scale(learning_rate, scale=float(param_lr))
            else:  # a Variable (e.g. a prior LARS pass): compose, like the ref
                scaled = _nn.elementwise_mul(learning_rate, param_lr)
            decayed = _nn.elementwise_div(
                _nn.elementwise_mul(scaled, param_norm),
                _balanced_weight(param_norm, grad_norm))
            attr["learning_rate"] = decayed
            param.optimize_attr = attr
