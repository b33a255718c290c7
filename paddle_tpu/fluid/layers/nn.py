"""NN layers (ref: python/paddle/fluid/layers/nn.py — ~110 layers).

Layers build IR ops; they do best-effort static shape propagation (batch dims
stay -1) so downstream layers can size their parameters, mirroring the
reference's compile-time InferShape.
"""

from __future__ import annotations

import copy
import math

import numpy as np

from .. import core
from ..framework import Variable
from ..initializer import ConstantInitializer, NormalInitializer
from ..layer_helper import LayerHelper

__all__ = [
    "fc", "embedding", "conv2d", "conv3d", "conv2d_transpose",
    "conv3d_transpose", "pool2d",
    "batch_norm", "layer_norm", "group_norm", "dropout", "softmax",
    "cross_entropy", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "square_error_cost", "mean", "mul",
    "matmul", "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min", "elementwise_pow",
    "scale", "reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
    "reduce_prod", "reshape", "transpose", "split", "topk", "one_hot", "lrn",
    "l2_normalize", "clip", "clip_by_norm", "label_smooth", "smooth_l1",
    "gather", "scatter", "pad", "pad2d", "pad_constant_like", "squeeze",
    "unsqueeze", "stack", "unstack", "expand", "slice", "shape", "flatten",
    "im2sequence", "maxout", "relu", "log", "crop", "mean_iou",
    "image_resize", "resize_bilinear", "autoincreased_step_counter",
    "lod_reset", "prelu", "dice_loss", "log_loss", "huber_loss",
    "ring_attention", "moe_ffn", "gpipe_mlp_stack",
    "rms_norm", "rotary_embedding", "sparse_indexer", "sparse_attention",
    "weighted_mean",
    "moe_experts", "moe_bias_update", "short_conv", "gated_delta_rule",
    "ssd_scan",
    "kv_cache_update", "kv_cache_scatter", "token_select",
    "paged_attention", "spec_accept",
    "transformer_encoder_stack", "transformer_decoder_stack", "cos_sim",
    "multiplex", "pool3d", "random_crop", "rank_loss",
    "image_resize_short", "Print", "load",
    "linear_chain_crf", "crf_decoding", "nce", "hsigmoid", "warpctc",
    "edit_distance", "ctc_greedy_decoder", "sequence_erase",
]


def _dim_or(v, default=-1):
    return default if v is None else v


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None):
    """ref: layers/nn.py fc — emitted as mul(+sum)+elementwise_add+act, the
    same decomposition the reference uses; XLA fuses it back into one GEMM."""
    helper = LayerHelper("fc", **locals())
    dtype = helper.input_dtype()
    mul_results = []
    for input_var, param_attr_ in helper.iter_inputs_and_params():
        in_shape = input_var.shape
        param_shape = [int(np.prod(in_shape[num_flatten_dims:]))] + [size]
        w = helper.create_parameter(attr=param_attr_, shape=param_shape,
                                    dtype=dtype)
        tmp = helper.create_variable_for_type_inference(dtype)
        tmp.shape = tuple(in_shape[:num_flatten_dims]) + (size,)
        helper.append_op(
            type="mul", inputs={"X": [input_var], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        pre_bias.shape = mul_results[0].shape
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    helper = LayerHelper("embedding", **locals())
    w = helper.create_parameter(attr=helper.param_attr, shape=size,
                                dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    ids_shape = input.shape
    if ids_shape and ids_shape[-1] == 1:
        out.shape = tuple(ids_shape[:-1]) + (size[1],)
    else:
        out.shape = tuple(ids_shape or ()) + (size[1],)
    helper.append_op(
        type="lookup_table", inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={"is_sparse": is_sparse, "is_distributed": is_distributed,
               "padding_idx": -1 if padding_idx is None else padding_idx})
    return out


def _conv_out_dim(size, k, pad, stride, dilation=1):
    if size in (-1, None):
        return -1
    return (size + 2 * pad - (dilation * (k - 1) + 1)) // stride + 1


def _to_list(v, n):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    helper = LayerHelper("conv2d", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    filter_size = _to_list(filter_size, 2)
    stride = _to_list(stride, 2)
    padding = _to_list(padding, 2)
    dilation = _to_list(dilation, 2)
    filter_shape = [num_filters, num_channels // groups] + filter_size

    def _std(shape):
        fan_in = num_channels * shape[2] * shape[3] // groups
        return (2.0 / fan_in) ** 0.5

    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=NormalInitializer(0.0, _std(filter_shape)))
    out = helper.create_variable_for_type_inference(dtype)
    n, c, h, wd = input.shape
    out.shape = (n, num_filters,
                 _conv_out_dim(h, filter_size[0], padding[0], stride[0], dilation[0]),
                 _conv_out_dim(wd, filter_size[1], padding[1], stride[1], dilation[1]))
    helper.append_op(
        type="conv2d", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": stride, "paddings": padding, "dilations": dilation,
               "groups": groups, "use_cudnn": use_cudnn})
    pre_act = helper.append_bias_op(out, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    helper = LayerHelper("conv3d", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    filter_size = _to_list(filter_size, 3)
    stride = _to_list(stride, 3)
    padding = _to_list(padding, 3)
    dilation = _to_list(dilation, 3)
    filter_shape = [num_filters, num_channels // groups] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape,
                                dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    dims = input.shape
    out.shape = (dims[0], num_filters) + tuple(
        _conv_out_dim(dims[2 + i], filter_size[i], padding[i], stride[i],
                      dilation[i]) for i in range(3))
    helper.append_op(
        type="conv3d", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": stride, "paddings": padding, "dilations": dilation,
               "groups": groups})
    pre_act = helper.append_bias_op(out, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper("conv2d_transpose", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    stride = _to_list(stride, 2)
    padding = _to_list(padding, 2)
    dilation = _to_list(dilation, 2)
    if filter_size is None:
        if output_size is None:
            raise ValueError("need filter_size or output_size")
        output_size = _to_list(output_size, 2)
        h, w_ = input.shape[2], input.shape[3]
        filter_size = [
            (output_size[0] - (h - 1) * stride[0] + 2 * padding[0] - 1) //
            dilation[0] + 1,
            (output_size[1] - (w_ - 1) * stride[1] + 2 * padding[1] - 1) //
            dilation[1] + 1]
    else:
        filter_size = _to_list(filter_size, 2)
    filter_shape = [num_channels, num_filters // groups] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape,
                                dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    n, c, h, wd = input.shape

    def _out_dim(size, k, pad, s, d):
        if size in (-1, None):
            return -1
        return (size - 1) * s - 2 * pad + d * (k - 1) + 1

    out.shape = (n, num_filters,
                 _out_dim(h, filter_size[0], padding[0], stride[0], dilation[0]),
                 _out_dim(wd, filter_size[1], padding[1], stride[1], dilation[1]))
    helper.append_op(
        type="conv2d_transpose", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": stride, "paddings": padding, "dilations": dilation,
               "groups": groups})
    pre_act = helper.append_bias_op(out, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False, name=None,
           exclusive=True):
    helper = LayerHelper("pool2d", **locals())
    pool_size = _to_list(pool_size, 2)
    pool_stride = _to_list(pool_stride, 2)
    pool_padding = _to_list(pool_padding, 2)
    out = helper.create_variable_for_type_inference(helper.input_dtype())
    n, c, h, w = input.shape
    if global_pooling:
        out.shape = (n, c, 1, 1)
    else:
        def _po(size, k, pad, s):
            if size in (-1, None):
                return -1
            if ceil_mode:
                return (size - k + 2 * pad + s - 1) // s + 1
            return (size - k + 2 * pad) // s + 1
        out.shape = (n, c, _po(h, pool_size[0], pool_padding[0], pool_stride[0]),
                     _po(w, pool_size[1], pool_padding[1], pool_stride[1]))
    helper.append_op(
        type="pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"pooling_type": pool_type, "ksize": pool_size,
               "global_pooling": global_pooling, "strides": pool_stride,
               "paddings": pool_padding, "ceil_mode": ceil_mode,
               "exclusive": exclusive})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               fuse_with_relu=False):
    helper = LayerHelper("batch_norm", **locals())
    dtype = helper.input_dtype()
    input_shape = input.shape
    if data_layout == "NCHW":
        channel_num = input_shape[1]
    else:
        channel_num = input_shape[-1]
    param_shape = [channel_num]

    scale = helper.create_parameter(attr=helper.param_attr, shape=param_shape,
                                    dtype=dtype,
                                    default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(attr=helper.bias_attr, shape=param_shape,
                                   dtype=dtype, is_bias=True)
    from .. import unique_name
    # moving stats must have stable saveable names — an anonymous @TEMP@
    # persistable cannot round-trip through save/load_inference_model
    mean = helper.create_global_variable(
        name=moving_mean_name or unique_name.generate(
            helper.name + ".w_mean"),
        dtype=dtype, shape=param_shape, persistable=True)
    helper.set_variable_initializer(mean, ConstantInitializer(0.0))
    variance = helper.create_global_variable(
        name=moving_variance_name or unique_name.generate(
            helper.name + ".w_variance"),
        dtype=dtype, shape=param_shape,
        persistable=True)
    helper.set_variable_initializer(variance, ConstantInitializer(1.0))

    saved_mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    saved_variance = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = input_shape
    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [variance],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_variance]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_layout": data_layout})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5,
               param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("layer_norm", **locals())
    dtype = helper.input_dtype()
    input_shape = input.shape
    param_shape = [int(np.prod(input_shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(attr=helper.param_attr, shape=param_shape,
                                    dtype=dtype,
                                    default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(attr=helper.bias_attr, shape=param_shape,
                                    dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = input_shape
    helper.append_op(type="layer_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean_out],
                              "Variance": [var_out]},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    helper = LayerHelper("group_norm", **locals())
    dtype = helper.input_dtype()
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        s = helper.create_parameter(attr=helper.param_attr, shape=[c],
                                    dtype=dtype,
                                    default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if bias_attr is not False:
        b = helper.create_parameter(attr=helper.bias_attr, shape=[c],
                                    dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = input.shape
    helper.append_op(type="group_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean_out],
                              "Variance": [var_out]},
                     attrs={"epsilon": epsilon, "groups": groups})
    return helper.append_activation(out)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    mask = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    mask.shape = x.shape
    helper.append_op(
        type="dropout", inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={"dropout_prob": dropout_prob, "is_test": is_test,
               "seed": seed if seed is not None else 0,
               "dropout_implementation": dropout_implementation})
    return out


def softmax(input, use_cudnn=True, name=None):
    helper = LayerHelper("softmax", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    out.shape = input.shape
    helper.append_op(type="softmax", inputs={"X": [input]},
                     outputs={"Out": [out]})
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    if input.shape:
        out.shape = tuple(input.shape[:-1]) + (1,)
    helper.append_op(type="cross_entropy",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index})
    return out


def _uniform_smoothing(block, logits, label):
    """``(ids, eps)`` where the soft ``label`` is, in ``block``,
    ``scale(one_hot(ids, V), 1 - eps, eps / V)`` over the ``V`` classes of
    ``logits`` (what ``label_smooth`` with no ``prior_dist`` appends), else
    None.  The loss then needs ``ids`` and ``eps``, not the distribution.
    (The two forms part only on an id outside ``[0, V)``, which the
    reference's ``one_hot`` refuses: here it made a row of zeros.)"""
    if not logits.shape:
        return None
    made = {}                      # var name -> indices of the ops writing it
    for i, op in enumerate(block.ops):
        for n in op.output_arg_names:
            made.setdefault(n, []).append(i)

    def only_maker(name, op_type):
        at = made.get(name, ())
        if len(at) == 1 and block.ops[at[0]].type == op_type:
            return at[0]
        return None

    at = only_maker(label.name, "scale")
    if at is None:
        return None
    smooth = block.ops[at]
    at = only_maker(smooth.input("X")[0], "one_hot")
    if at is None:
        return None
    hot = block.ops[at]
    width = logits.shape[-1]
    k, bias = smooth.attrs["scale"], smooth.attrs["bias"]
    ids = hot.input("X")[0]
    if hot.attrs["depth"] != width or not 0.0 <= k < 1.0 \
            or not smooth.attrs.get("bias_after_scale", True) \
            or not math.isclose(bias * width, 1.0 - k, rel_tol=1e-9) \
            or not block.has_var(ids) \
            or not str(block.var(ids).dtype).startswith(("int", "uint")) \
            or any(i > at for i in made.get(ids, ())):
        return None
    return block.var(ids), 1.0 - k


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False):
    helper = LayerHelper("softmax_with_cross_entropy", **locals())
    softmax_out = helper.create_variable_for_type_inference(logits.dtype)
    softmax_out.shape = logits.shape
    loss = helper.create_variable_for_type_inference(logits.dtype)
    # the rows' log-sum-exp, kept for the kernels' backward
    lse = helper.create_variable_for_type_inference("float32",
                                                    stop_gradient=True)
    if logits.shape:
        loss.shape = lse.shape = tuple(logits.shape[:-1]) + (1,)
    attrs = {"soft_label": soft_label, "ignore_index": ignore_index}
    smoothing = soft_label and _uniform_smoothing(
        helper.main_program.current_block(), logits, label)
    if smoothing:
        # hard labels again, smoothed inside the loss: the one_hot and the
        # scale stay in the block with no reader (a soft label knows no
        # ignore_index, so the hard form is given none)
        label, eps = smoothing
        attrs = {"soft_label": False, "ignore_index": -100,
                 "smooth_epsilon": eps}
    helper.append_op(type="softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Softmax": [softmax_out], "Loss": [loss],
                              "Lse": [lse]},
                     attrs=attrs)
    if return_softmax:
        return loss, softmax_out
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type="sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x], "Label": [label]},
                     outputs={"Out": [out]},
                     attrs={"ignore_index": ignore_index})
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost", **locals())
    minus_out = helper.create_variable_for_type_inference(input.dtype)
    minus_out.shape = input.shape
    helper.append_op(type="elementwise_sub",
                     inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [minus_out]})
    square_out = helper.create_variable_for_type_inference(input.dtype)
    square_out.shape = input.shape
    helper.append_op(type="square", inputs={"X": [minus_out]},
                     outputs={"Out": [square_out]})
    return square_out


def mean(x, name=None):
    helper = LayerHelper("mean", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = (1,)
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    if x.shape and y.shape:
        out.shape = tuple(x.shape[:x_num_col_dims]) + tuple(y.shape[y_num_col_dims:])
    helper.append_op(type="mul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    if x.shape and y.shape:
        xs = list(x.shape)
        ys = list(y.shape)
        if transpose_x and len(xs) >= 2:
            xs[-1], xs[-2] = xs[-2], xs[-1]
        if transpose_y and len(ys) >= 2:
            ys[-1], ys[-2] = ys[-2], ys[-1]
        if len(xs) >= 2 and len(ys) >= 2:
            out.shape = tuple(xs[:-1]) + (ys[-1],)
    helper.append_op(type="matmul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"transpose_X": transpose_x,
                            "transpose_Y": transpose_y, "alpha": alpha})
    return out


def _binary_layer(op_type):
    def layer(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, name=name, act=act)
        out = helper.create_variable_for_type_inference(x.dtype)
        out.shape = x.shape
        helper.append_op(type=op_type, inputs={"X": [x], "Y": [y]},
                         outputs={"Out": [out]}, attrs={"axis": axis})
        return helper.append_activation(out)
    layer.__name__ = op_type
    return layer


elementwise_add = _binary_layer("elementwise_add")
elementwise_sub = _binary_layer("elementwise_sub")
elementwise_mul = _binary_layer("elementwise_mul")
elementwise_div = _binary_layer("elementwise_div")
elementwise_max = _binary_layer("elementwise_max")
elementwise_min = _binary_layer("elementwise_min")
elementwise_pow = _binary_layer("elementwise_pow")


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type="scale", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"scale": float(scale), "bias": float(bias),
                            "bias_after_scale": bias_after_scale})
    return helper.append_activation(out)


def _reduce_layer(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_variable_for_type_inference(input.dtype)
        if input.shape is not None:
            s = list(input.shape)
            dims = dim if dim is not None else list(range(len(s)))
            if isinstance(dims, int):
                dims = [dims]
            dims = [d % len(s) for d in dims]
            if keep_dim:
                ns = [1 if i in dims else v for i, v in enumerate(s)]
            else:
                ns = [v for i, v in enumerate(s) if i not in dims]
            out.shape = tuple(ns) if ns else (1,)
        helper.append_op(
            type=op_type, inputs={"X": [input]}, outputs={"Out": [out]},
            attrs={"dim": dim if isinstance(dim, (list, tuple)) or dim is None
                   else [dim],
                   "keep_dim": keep_dim, "reduce_all": dim is None})
        return out
    layer.__name__ = op_type
    return layer


reduce_sum = _reduce_layer("reduce_sum")
reduce_mean = _reduce_layer("reduce_mean")
reduce_max = _reduce_layer("reduce_max")
reduce_min = _reduce_layer("reduce_min")
reduce_prod = _reduce_layer("reduce_prod")


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    if x.shape is not None and -1 not in x.shape:
        total = int(np.prod(x.shape))
        s = [x.shape[i] if v == 0 else v for i, v in enumerate(shape)]
        if -1 in s:
            known = int(np.prod([v for v in s if v != -1]))
            s[s.index(-1)] = total // known
        out.shape = tuple(s)
    else:
        out.shape = tuple(shape)
    helper.append_op(type="reshape", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"shape": list(shape)})
    return helper.append_activation(out)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    if x.shape is not None:
        out.shape = tuple(x.shape[p] for p in perm)
    helper.append_op(type="transpose", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"axis": list(perm)})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", **locals())
    input_shape = input.shape
    dim_ = dim % len(input_shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
        sizes = [input_shape[dim_] // num] * num if input_shape[dim_] not in (-1, None) else [-1] * num
    else:
        sections = list(num_or_sections)
        num = 0
        sizes = sections
    outs = []
    for sz in sizes:
        o = helper.create_variable_for_type_inference(input.dtype)
        s = list(input_shape)
        s[dim_] = sz
        o.shape = tuple(s)
        outs.append(o)
    helper.append_op(type="split", inputs={"X": [input]},
                     outputs={"Out": outs},
                     attrs={"num": num, "sections": sections, "axis": dim_})
    return outs


def topk(input, k, name=None):
    helper = LayerHelper("top_k", **locals())
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64",
                                                        stop_gradient=True)
    if input.shape is not None:
        s = tuple(input.shape[:-1]) + (k,)
        values.shape = s
        indices.shape = s
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    values.stop_gradient = True
    return values, indices


def one_hot(input, depth):
    helper = LayerHelper("one_hot", **locals())
    out = helper.create_variable_for_type_inference("float32")
    if input.shape is not None:
        s = list(input.shape)
        if s and s[-1] == 1:
            s = s[:-1]
        out.shape = tuple(s) + (depth,)
    helper.append_op(type="one_hot", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"depth": depth})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype,
                                                    stop_gradient=True)
    out.shape = input.shape
    helper.append_op(type="lrn", inputs={"X": [input]},
                     outputs={"Out": [out], "MidOut": [mid]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    from . import ops as _ops

    if axis < 0:
        axis = len(x.shape) + axis
    sq = elementwise_mul(x, x)
    ssum = reduce_sum(sq, dim=axis, keep_dim=True)
    norm = _ops.sqrt(scale(ssum, bias=epsilon))
    return elementwise_div(x, norm)


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type="clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": min, "max": max})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type="clip_by_norm", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"max_norm": max_norm})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    if epsilon > 1.0 or epsilon < 0.0:
        raise ValueError("epsilon must be in [0, 1]")
    n_classes = label.shape[-1]
    smoothed = scale(label, scale=1.0 - epsilon,
                     bias=epsilon / n_classes if prior_dist is None else 0.0)
    if prior_dist is not None:
        smoothed = elementwise_add(smoothed, scale(prior_dist, scale=epsilon))
    return smoothed


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1", **locals())
    diff = helper.create_variable_for_type_inference(x.dtype,
                                                     stop_gradient=True)
    diff.shape = x.shape
    loss = helper.create_variable_for_type_inference(x.dtype)
    if x.shape is not None:
        loss.shape = (x.shape[0], 1)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(type="smooth_l1_loss", inputs=inputs,
                     outputs={"Diff": [diff], "Out": [loss]},
                     attrs={"sigma": sigma if sigma is not None else 1.0})
    return loss


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", **locals())
    loss = helper.create_variable_for_type_inference(input.dtype)
    loss.shape = input.shape
    helper.append_op(type="log_loss",
                     inputs={"Predicted": [input], "Labels": [label]},
                     outputs={"Loss": [loss]}, attrs={"epsilon": epsilon})
    return loss


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss", **locals())
    residual = helper.create_variable_for_type_inference(input.dtype,
                                                         stop_gradient=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    residual.shape = input.shape
    out.shape = input.shape
    helper.append_op(type="huber_loss",
                     inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out], "Residual": [residual]},
                     attrs={"delta": delta})
    return out


def dice_loss(input, label, epsilon=1e-5):
    label = one_hot(label, depth=input.shape[-1])
    reduce_dims = list(range(1, len(input.shape)))
    inse = reduce_sum(elementwise_mul(input, label), dim=reduce_dims)
    dice_denominator = elementwise_add(reduce_sum(input, dim=reduce_dims),
                                       reduce_sum(label, dim=reduce_dims))
    dice_score = scale(elementwise_div(
        scale(inse, scale=2.0),
        scale(dice_denominator, bias=epsilon)), scale=-1.0, bias=1.0)
    return reduce_mean(dice_score)


def gather(input, index):
    helper = LayerHelper("gather", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    if input.shape is not None and index.shape is not None:
        out.shape = (index.shape[0],) + tuple(input.shape[1:])
    helper.append_op(type="gather",
                     inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    out.shape = input.shape
    helper.append_op(type="scatter",
                     inputs={"X": [input], "Ids": [index],
                             "Updates": [updates]},
                     outputs={"Out": [out]}, attrs={"overwrite": overwrite})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    if x.shape is not None:
        out.shape = tuple(
            -1 if d in (-1, None) else d + paddings[2 * i] + paddings[2 * i + 1]
            for i, d in enumerate(x.shape))
    helper.append_op(type="pad", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings),
                            "pad_value": float(pad_value)})
    return out


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    if input.shape is not None:
        n, c, h, w = input.shape
        if data_format == "NCHW":
            out.shape = (n, c,
                         -1 if h in (-1, None) else h + paddings[0] + paddings[1],
                         -1 if w in (-1, None) else w + paddings[2] + paddings[3])
    helper.append_op(type="pad2d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "mode": mode,
                            "pad_value": float(pad_value),
                            "data_format": data_format})
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", **locals())
    out = helper.create_variable_for_type_inference(y.dtype)
    out.shape = x.shape
    helper.append_op(type="pad_constant_like",
                     inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
                     attrs={"pad_value": float(pad_value)})
    return out


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    if input.shape is not None:
        s = [d for i, d in enumerate(input.shape)
             if not (i in [a % len(input.shape) for a in axes] and d == 1)] \
            if axes else [d for d in input.shape if d != 1]
        out.shape = tuple(s)
    helper.append_op(type="squeeze", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axes": axes})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    if input.shape is not None:
        s = list(input.shape)
        for a in sorted(axes):
            s.insert(a, 1)
        out.shape = tuple(s)
    helper.append_op(type="unsqueeze", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axes": axes})
    return out


def stack(x, axis=0):
    helper = LayerHelper("stack")
    if isinstance(x, Variable):
        x = [x]
    out = helper.create_variable_for_type_inference(x[0].dtype)
    if x[0].shape is not None:
        s = list(x[0].shape)
        s.insert(axis % (len(s) + 1), len(x))
        out.shape = tuple(s)
    helper.append_op(type="stack", inputs={"X": x}, outputs={"Y": [out]},
                     attrs={"axis": axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    if num is None:
        num = x.shape[axis]
    outs = []
    s = list(x.shape)
    del s[axis % len(s)]
    for _ in range(num):
        o = helper.create_variable_for_type_inference(x.dtype)
        o.shape = tuple(s)
        outs.append(o)
    helper.append_op(type="unstack", inputs={"X": [x]}, outputs={"Y": outs},
                     attrs={"axis": axis, "num": num})
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    if x.shape is not None:
        out.shape = tuple(-1 if d in (-1, None) else d * t
                          for d, t in zip(x.shape, expand_times))
    helper.append_op(type="expand", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"expand_times": list(expand_times)})
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    if input.shape is not None:
        s = list(input.shape)
        for a, st, e in zip(axes, starts, ends):
            if s[a] in (-1, None):
                continue
            st_ = st + s[a] if st < 0 else min(st, s[a])
            e_ = e + s[a] if e < 0 else min(e, s[a])
            s[a] = max(e_ - st_, 0)
        out.shape = tuple(s)
    helper.append_op(type="slice", inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends)})
    return out


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_variable_for_type_inference("int32",
                                                    stop_gradient=True)
    out.shape = (len(input.shape),)
    helper.append_op(type="shape", inputs={"Input": [input]},
                     outputs={"Out": [out]})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    if x.shape is not None:
        lead = x.shape[:axis]
        rest = x.shape[axis:]
        l = -1 if any(d in (-1, None) for d in lead) else int(np.prod(lead)) if lead else 1
        r = -1 if any(d in (-1, None) for d in rest) else int(np.prod(rest)) if rest else 1
        out.shape = (l, r)
    helper.append_op(type="flatten", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    helper = LayerHelper("im2sequence", **locals())
    filter_size = _to_list(filter_size, 2)
    stride = _to_list(stride, 2)
    padding = _to_list(padding, 2)
    if len(padding) == 2:
        padding = padding * 2
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="im2sequence", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"kernels": filter_size, "strides": stride,
                            "paddings": padding})
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    if x.shape is not None:
        n, c, h, w = x.shape
        out.shape = (n, c // groups, h, w)
    helper.append_op(type="maxout", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"groups": groups})
    return out


def relu(x, name=None):
    helper = LayerHelper("relu", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type="relu", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def log(x, name=None):
    helper = LayerHelper("log", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type="log", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper("crop", **locals())
    if isinstance(shape, Variable):
        raise NotImplementedError("dynamic crop shape not supported on TPU")
    offsets = offsets or [0] * len(x.shape)
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = tuple(shape)
    helper.append_op(type="crop", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"shape": list(shape), "offsets": list(offsets)})
    return out


def mean_iou(input, label, num_classes):
    helper = LayerHelper("mean_iou", **locals())
    out_mean_iou = helper.create_variable_for_type_inference("float32",
                                                             stop_gradient=True)
    out_wrong = helper.create_variable_for_type_inference("float32",
                                                          stop_gradient=True)
    out_correct = helper.create_variable_for_type_inference("float32",
                                                            stop_gradient=True)
    helper.append_op(type="mean_iou",
                     inputs={"Predictions": [input], "Labels": [label]},
                     outputs={"OutMeanIou": [out_mean_iou],
                              "OutWrong": [out_wrong],
                              "OutCorrect": [out_correct]},
                     attrs={"num_classes": num_classes})
    return out_mean_iou, out_wrong, out_correct


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR"):
    helper = LayerHelper("image_resize", **locals())
    if out_shape is None:
        out_shape = [int(input.shape[2] * scale), int(input.shape[3] * scale)]
    op_type = "bilinear_interp" if resample == "BILINEAR" else "nearest_interp"
    out = helper.create_variable_for_type_inference(input.dtype)
    out.shape = (input.shape[0], input.shape[1], out_shape[0], out_shape[1])
    helper.append_op(type=op_type, inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"out_h": out_shape[0], "out_w": out_shape[1]})
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, name, "BILINEAR")


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", **locals())
    if mode not in ("all", "channel", "element"):
        raise ValueError("mode must be all|channel|element")
    alpha_shape = [1]
    if mode == "channel":
        alpha_shape = [x.shape[1]]
    elif mode == "element":
        alpha_shape = [int(np.prod(x.shape[1:]))]
    alpha = helper.create_parameter(
        attr=helper.param_attr, shape=alpha_shape, dtype="float32",
        is_bias=False, default_initializer=ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type="prelu", inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    return out


def lod_reset(x, y=None, target_lod=None):
    """ref: lod_reset_op.cc — replace x's LoD from y or target_lod."""
    helper = LayerHelper("lod_reset")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    out.shape = x.shape
    inputs = {"X": [x]}
    if y is not None:
        inputs["Y"] = [y]
    helper.append_op(type="lod_reset", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"target_lod": list(target_lod or [])})
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    helper = LayerHelper("global_step_counter")
    counter_name = counter_name or "@STEP_COUNTER@"
    counter = helper.create_global_variable(
        name=counter_name, dtype="int64", shape=[1], persistable=True)
    helper.set_variable_initializer(counter,
                                    ConstantInitializer(begin - 1))
    helper.main_program.global_block().append_op(
        type="increment", inputs={"X": [counter]}, outputs={"Out": [counter]},
        attrs={"step": float(step)})
    counter.stop_gradient = True
    return counter


# ---------------------------------------------------------------------------
# structured losses (ref: layers/nn.py linear_chain_crf/crf_decoding/nce/
# hsigmoid/warpctc/edit_distance/ctc_greedy_decoder)
# ---------------------------------------------------------------------------


def linear_chain_crf(input, label, param_attr=None):
    """ref: layers/nn.py linear_chain_crf — emission + learned transition
    ([start; end; A] rows, crf_decoding_op.cc doc)."""
    helper = LayerHelper("linear_chain_crf", **locals())
    size = input.shape[1]
    transition = helper.create_parameter(attr=helper.param_attr,
                                         shape=[size + 2, size],
                                         dtype=input.dtype)
    alpha = helper.create_variable_for_type_inference(dtype=input.dtype)
    emission_exps = helper.create_variable_for_type_inference(
        dtype=input.dtype)
    transition_exps = helper.create_variable_for_type_inference(
        dtype=input.dtype)
    log_likelihood = helper.create_variable_for_type_inference(
        dtype=input.dtype)
    helper.append_op(
        type="linear_chain_crf",
        inputs={"Emission": [input], "Transition": [transition],
                "Label": [label]},
        outputs={"Alpha": [alpha], "EmissionExps": [emission_exps],
                 "TransitionExps": [transition_exps],
                 "LogLikelihood": [log_likelihood]})
    return log_likelihood


def crf_decoding(input, param_attr, label=None):
    """ref: layers/nn.py crf_decoding."""
    helper = LayerHelper("crf_decoding", **locals())
    transition = helper.get_parameter(param_attr.name)
    viterbi_path = helper.create_variable_for_type_inference(dtype="int64")
    inputs = {"Emission": [input], "Transition": [transition]}
    if label is not None:
        inputs["Label"] = [label]
    helper.append_op(type="crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": [viterbi_path]})
    return viterbi_path


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=None, name=None,
        seed=0):
    """ref: layers/nn.py nce."""
    helper = LayerHelper("nce", **locals())
    if sample_weight is not None:
        raise NotImplementedError("nce: sample_weight is not supported")
    dim = input.shape[1]
    num_neg_samples = int(num_neg_samples or 10)
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=input.dtype)
    b = helper.create_parameter(attr=helper.bias_attr,
                                shape=[num_total_classes, 1],
                                dtype=input.dtype, is_bias=True)
    cost = helper.create_variable_for_type_inference(dtype=input.dtype)
    sample_logits = helper.create_variable_for_type_inference(
        dtype=input.dtype)
    sample_labels = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="nce",
        inputs={"Input": [input], "Label": [label], "Weight": [w],
                "Bias": [b]},
        outputs={"Cost": [cost], "SampleLogits": [sample_logits],
                 "SampleLabels": [sample_labels]},
        attrs={"num_total_classes": num_total_classes,
               "num_neg_samples": num_neg_samples, "seed": seed})
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None):
    """ref: layers/nn.py hsigmoid (hierarchical sigmoid over a complete
    binary class tree)."""
    helper = LayerHelper("hierarchical_sigmoid", **locals())
    dim = input.shape[1]
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_classes - 1, dim],
                                dtype=input.dtype)
    b = helper.create_parameter(attr=helper.bias_attr,
                                shape=[1, num_classes - 1],
                                dtype=input.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    pre_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="hierarchical_sigmoid",
        inputs={"X": [input], "W": [w], "Label": [label], "Bias": [b]},
        outputs={"Out": [out], "PreOut": [pre_out]},
        attrs={"num_classes": num_classes})
    return out


def warpctc(input, label, blank=0, norm_by_times=False):
    """ref: layers/nn.py warpctc (CTC loss on lod logits/labels)."""
    helper = LayerHelper("warpctc", **locals())
    loss_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    grad_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="warpctc", inputs={"Logits": [input], "Label": [label]},
        outputs={"WarpCTCGrad": [grad_out], "Loss": [loss_out]},
        attrs={"blank": blank, "norm_by_times": norm_by_times})
    return loss_out


def sequence_erase(input, tokens=None, name=None):
    """Remove listed token values from a LoD sequence tensor (ref:
    layers/nn.py sequence_erase, sequence_erase_op.cc).  Output rows are
    data-dependent, so the op executes as an eager host island."""
    helper = LayerHelper("sequence_erase", **locals())
    out = helper.create_variable_for_type_inference(
        dtype=helper.input_dtype())
    helper.append_op(
        type="sequence_erase", inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"tokens": [int(t) for t in (tokens or [])]})
    return out


def edit_distance(input, label, normalized=True, ignored_tokens=None):
    """ref: layers/nn.py edit_distance (ignored tokens are erased from
    both hypotheses and references first, via sequence_erase)."""
    helper = LayerHelper("edit_distance", **locals())
    if ignored_tokens:
        input = sequence_erase(input, tokens=ignored_tokens)
        label = sequence_erase(label, tokens=ignored_tokens)
    edit_distance_out = helper.create_variable_for_type_inference(
        dtype="float32")
    sequence_num = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="edit_distance", inputs={"Hyps": [input], "Refs": [label]},
        outputs={"Out": [edit_distance_out], "SequenceNum": [sequence_num]},
        attrs={"normalized": normalized})
    return edit_distance_out, sequence_num


def ctc_greedy_decoder(input, blank, name=None):
    """ref: layers/nn.py ctc_greedy_decoder = argmax + ctc_align."""
    helper = LayerHelper("ctc_greedy_decoder", **locals())
    _, topk_indices = topk(input, k=1)
    ctc_out = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="ctc_align", inputs={"Input": [topk_indices]},
        outputs={"Output": [ctc_out]},
        attrs={"merge_repeated": True, "blank": blank})
    return ctc_out


def moe_ffn(input, num_experts, hidden_size, top_k=2, capacity_factor=1.25,
            activation="relu", param_attr=None, name=None):
    """Mixture-of-experts feed-forward with expert parallelism (TPU-native
    capability beyond the reference — SURVEY.md §2.6 lists MoE/EP "Absent";
    see parallel/moe.py).  input: [..., D].  Returns (out [..., D],
    aux_loss scalar) — callers add the Switch load-balancing ``aux_loss``
    (weighted ~1e-2) to their training loss and usually wrap ``out`` in a
    residual connection (dropped-overflow tokens output zero).

    Expert weights carry ``dist_hint="ep"``: under a mesh with an "ep" axis
    the expert dimension shards across it and GSPMD lowers the dispatch
    einsums to all-to-alls over ICI."""
    if top_k > num_experts:
        raise ValueError(
            f"moe_ffn: top_k={top_k} exceeds num_experts={num_experts}")
    from ..initializer import XavierInitializer

    helper = LayerHelper("moe_ffn", **locals())
    dtype = helper.input_dtype()
    d = int(input.shape[-1])
    # each create_parameter mutates attr.name — every param needs its own
    # copy or they all collapse onto one var
    _pa = lambda: copy.deepcopy(param_attr)
    gate_w = helper.create_parameter(attr=_pa(), shape=[d, num_experts],
                                     dtype=dtype)
    # stacked expert weights need PER-EXPERT fans — the default fan
    # convention would read the expert dim as part of the receptive field
    w1 = helper.create_parameter(attr=_pa(),
                                 shape=[num_experts, d, hidden_size],
                                 dtype=dtype,
                                 default_initializer=XavierInitializer(
                                     fan_in=d, fan_out=hidden_size))
    b1 = helper.create_parameter(attr=_pa(),
                                 shape=[num_experts, hidden_size],
                                 dtype=dtype, is_bias=True)
    w2 = helper.create_parameter(attr=_pa(),
                                 shape=[num_experts, hidden_size, d],
                                 dtype=dtype,
                                 default_initializer=XavierInitializer(
                                     fan_in=hidden_size, fan_out=d))
    b2 = helper.create_parameter(attr=_pa(), shape=[num_experts, d],
                                 dtype=dtype, is_bias=True)
    for p in (w1, b1, w2, b2):
        p.dist_hint = "ep"
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = tuple(input.shape)
    aux = helper.create_variable_for_type_inference(dtype)
    aux.shape = ()
    helper.append_op(
        type="moe_ffn",
        inputs={"X": [input], "GateW": [gate_w], "W1": [w1], "B1": [b1],
                "W2": [w2], "B2": [b2]},
        outputs={"Out": [out], "AuxLoss": [aux]},
        attrs={"top_k": int(top_k), "capacity_factor": float(capacity_factor),
               "activation": activation})
    return out, aux


def gpipe_mlp_stack(input, n_layers, act="relu", n_microbatches=4,
                    pp_axis="pp", param_attr=None, name=None):
    """A stack of ``n_layers`` equal-width fc layers run as a GPipe
    pipeline when the active mesh has a "pp" axis (TPU-native capability —
    SURVEY.md §2.6 lists PP "Absent in Fluid"; see parallel/pipeline.py).
    Single-device the layers apply sequentially: identical math, portable
    programs.  input: [N, D]; weights are stacked [L, D, D] with
    ``dist_hint="pp"`` so each pipeline stage holds only its own layers."""
    from ..initializer import XavierInitializer

    helper = LayerHelper("gpipe_mlp_stack", **locals())
    dtype = helper.input_dtype()
    d = int(input.shape[-1])
    w = helper.create_parameter(attr=copy.deepcopy(param_attr),
                                shape=[n_layers, d, d],
                                dtype=dtype,
                                default_initializer=XavierInitializer(
                                    fan_in=d, fan_out=d))
    b = helper.create_parameter(attr=copy.deepcopy(param_attr),
                                shape=[n_layers, d],
                                dtype=dtype, is_bias=True)
    w.dist_hint = "pp"
    b.dist_hint = "pp"
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = tuple(input.shape)
    helper.append_op(
        type="gpipe_mlp_stack",
        inputs={"X": [input], "W": [w], "B": [b]},
        outputs={"Out": [out]},
        attrs={"act": act, "n_microbatches": int(n_microbatches),
               "pp_axis": pp_axis})
    return out


def ring_attention(q, k, v, causal=False, scale=None, sp_axis="sp",
                   bias=None, name=None):
    """Fused attention (TPU-native capability beyond the reference — see
    parallel/ring_attention.py + ops/pallas_flash.py).  q, k, v:
    [B, H, T, D].  Under a mesh with an `sp` axis the sequence dim shards
    across devices and K/V rotate the ICI ring; single-device the op runs
    the Pallas flash kernel (fwd + bwd VMEM streaming) or XLA full softmax:
    the kernel on a TPU backend, XLA elsewhere (ops/kernel_choice.py; no
    argument of a layer chooses).  ``bias``, if given, is an additive
    [B, 1, 1, T] key bias (padding mask)."""
    helper = LayerHelper("ring_attention", **locals())
    out = helper.create_variable_for_type_inference(helper.input_dtype("q"))
    out.shape = tuple(q.shape)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        inputs["Bias"] = [bias]
    helper.append_op(
        type="ring_attention", inputs=inputs,
        outputs={"Out": [out]},
        attrs={"causal": causal, "scale": float(scale or 0.0),
               "sp_axis": sp_axis})
    return out

def rms_norm(input, epsilon=1e-6, param_attr=None, name=None, groups=1):
    """x * rsqrt(mean(x^2) + epsilon) * scale over the LAST axis, with one
    scale of that width (init 1): the per-row norm of a [B, T, D] stream and
    the per-head norm of [B, T, H, Dh] alike.  Statistics in float32.
    ``groups`` n > 1: the mean runs over each of the last axis's n equal
    groups of columns by itself; the scale stays one of the whole width."""
    helper = LayerHelper("rms_norm", **locals())
    dtype = helper.input_dtype()
    if int(groups) < 1 or int(input.shape[-1]) % int(groups):
        raise ValueError(f"rms_norm: {input.shape[-1]} columns do not "
                         f"divide into {groups} groups")
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[int(input.shape[-1])], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = tuple(input.shape)
    # only what departs from the norm over the whole axis is written
    attrs = {"epsilon": float(epsilon)}
    if int(groups) > 1:
        attrs["groups"] = int(groups)
    helper.append_op(type="rms_norm", inputs={"X": [input], "Scale": [scale]},
                     outputs={"Y": [out]}, attrs=attrs)
    return out


def rotary_embedding(input, theta=10000.0, start=0, dims=0, interleaved=False,
                     inv_freq=None, period=0, name=None):
    """Rotary positions on [B, T, H, D]: position t, the index along axis
    1, rotates the pair (i, i + n/2) of the head's ``n = dims`` columns
    from ``start`` on (``dims`` 0: to the head's end; the defaults are the
    whole head) by t * theta^(-2i/n), the rotate-half form.
    ``interleaved``: the pair is (2i, 2i + 1).  ``inv_freq``: n/2
    frequencies in ``theta``'s place, for a table that a scaling rule made
    (``models.decoder_lm.yarn_inv_freq``).  Columns outside the part pass
    unchanged.  ``period`` p > 0: axis 1 holds copies of a sequence of p
    tokens side by side, and index i has position i mod p."""
    helper = LayerHelper("rotary_embedding", **locals())
    out = helper.create_variable_for_type_inference(helper.input_dtype())
    out.shape = tuple(input.shape)
    # only what departs from the whole head, rotate-half, theta's table is
    # written into the op
    attrs = {"theta": float(theta)}
    if start:
        attrs["start"] = int(start)
    if dims:
        attrs["dims"] = int(dims)
    if interleaved:
        attrs["interleaved"] = True
    if inv_freq is not None:
        attrs["inv_freq"] = [float(f) for f in inv_freq]
    if period:
        attrs["period"] = int(period)
    helper.append_op(type="rotary_embedding", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def _named_attrs(param_attr, name, suffixes):
    """One ParamAttr per parameter of a layer that makes several: copies of
    ``param_attr``, named ``<name>_<suffix>`` where the layer has a name."""
    from ..param_attr import ParamAttr

    out = []
    for suffix in suffixes:
        attr = copy.deepcopy(ParamAttr._to_attr(param_attr))
        if name is not None:
            attr.name = f"{name}_{suffix}"
        out.append(attr)
    return out


def sparse_indexer(input, num_heads, head_dim, topk, theta=10000.0,
                   param_attr=None, name=None):
    """The indexer of a learned sparse attention and its top-k (ops/
    decoder_ops.py).  input: [B, T, D].  ``num_heads`` index query heads of
    ``head_dim`` and ONE index key head, both rotated, and a weight per
    head: ``I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s]) / sqrt(head_dim)``.
    Returns the selection [B, T, T] int8 (1 where query t attends key s):
    the ``topk`` keys ``s <= t`` of largest ``I[t,s]``, every such key while
    ``t < topk``; the form ``sparse_attention`` takes.  The selection is
    piecewise constant, so the three weights (``<name>_q_w``, ``_k_w``,
    ``_w_w``) get a gradient of exactly zero through it."""
    helper = LayerHelper("sparse_indexer", **locals())
    dtype = helper.input_dtype()
    d = int(input.shape[-1])
    aq, ak, aw = _named_attrs(param_attr, name, ("q_w", "k_w", "w_w"))
    wq = helper.create_parameter(attr=aq, shape=[d, num_heads * head_dim],
                                 dtype=dtype)
    wk = helper.create_parameter(attr=ak, shape=[d, head_dim], dtype=dtype)
    ww = helper.create_parameter(attr=aw, shape=[d, num_heads], dtype=dtype)
    sel = helper.create_variable_for_type_inference("int8")
    sel.shape = (input.shape[0], input.shape[1], input.shape[1])
    helper.append_op(
        type="sparse_indexer",
        inputs={"X": [input], "WQ": [wq], "WK": [wk], "WW": [ww]},
        outputs={"Sel": [sel]},
        attrs={"num_heads": int(num_heads), "topk": int(topk),
               "theta": float(theta)})
    return sel


def sparse_attention(q, k, v, selection=None, scale=None, window=0,
                     block_rule=None, name=None):
    """Grouped-query attention, causal unless ``block_rule`` is given,
    optionally over a per-query
    selection of keys (a sibling of ``ring_attention``; ops/decoder_ops.py
    + ops/pallas_sparse_flash.py).  q: [B, Hq, T, D]; k: [B, Hkv, T, D];
    v: [B, Hkv, T, Dv], and the result [B, Hq, T, Dv] (the kernels take
    ``Dv = D`` only, the XLA path any),
    with Hq a multiple of Hkv (query head h reads head h // (Hq / Hkv));
    ``selection``: [B, T, T] int8 from ``sparse_indexer`` or None (every
    key s <= t).  ``window``: 0, or a causal window: key s counts for query
    t iff ``0 <= t - s < window`` (a static band that the kernels' grids
    are cut to; never a [B, T, T] mask).  ``block_rule``: None, or (tokens
    a copy L, block length): T = 2L holds a clean copy of a sequence and
    then a noised one, and with ``B(i) = (i mod L) // block`` a clean query
    counts the clean keys of blocks ``<= B(t)`` (its own block whole), a
    noised one the clean keys of blocks ``< B(t)`` and the noised keys of
    block ``B(t)``, in one softmax; static, with neither a selection nor a
    window.  Kernel or twin as for
    ``ring_attention``: the Pallas kernels (the selection as a mask inside
    them) or the blocked XLA path; nothing [Hq, T, T] reaches HBM either
    way."""
    helper = LayerHelper("sparse_attention", **locals())
    out = helper.create_variable_for_type_inference(helper.input_dtype("q"))
    out.shape = tuple(q.shape[:3]) + (v.shape[3],)
    # the kernels' log-sum-exp, kept for their backward
    lse = helper.create_variable_for_type_inference("float32",
                                                    stop_gradient=True)
    lse.shape = tuple(q.shape[:3]) + (1,)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    topk = 0
    if selection is not None:
        inputs["Sel"] = [selection]
        # the label of the call's counter: the attr of the op that made it
        topk = next((op.attr("topk", 0) for op in selection.block.ops
                     if selection.name in op.output_arg_names), 0)
    # refused beside a selection or a window by the op and its infer rule
    rule = {} if block_rule is None else {
        "copy_tokens": int(block_rule[0]), "block": int(block_rule[1])}
    helper.append_op(
        type="sparse_attention", inputs=inputs,
        outputs={"Out": [out], "Lse": [lse]},
        attrs={"scale": float(scale or 0.0), "topk": int(topk),
               **({"window": int(window)} if window else {}), **rule})
    return out


def weighted_mean(x, weight, name=None):
    """``sum(x * weight) / x.size`` as a [1] tensor: a mean over ALL of
    ``x`` in which every value counts by its weight (0: not at all), as a
    diffusion step's loss over its masked tokens does.  ``weight`` has
    ``x``'s size and gets no gradient.  The op publishes how many values
    bear weight (``ops.weighted_mean.live_rows`` of ``.rows``, step
    gauges)."""
    helper = LayerHelper("weighted_mean", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = (1,)
    helper.append_op(type="weighted_mean",
                     inputs={"X": [x], "Weight": [weight]},
                     outputs={"Out": [out]})
    return out


def moe_experts(input, num_routed, experts_held, hidden_size, top_k,
                expert_offset=0, norm_topk=True, param_attr=None, name=None,
                score="softmax", select_bias=False, norm_eps=0.0,
                route_scale=1.0, gated=True):
    """The share of a routed expert layer that ``experts_held`` of its
    ``num_routed`` experts give (parallel/moe.py ``routed_experts``): the
    router is ``num_routed`` wide and every token picks its ``top_k`` over
    all of them; the experts ``[expert_offset, expert_offset + held)`` live
    here, SiLU-gated with no bias (``<name>_w1`` gate and ``_w3`` up
    [held, D, hidden], ``_w2`` down [held, hidden, D], ``_router_w``
    [D, num_routed]).  No capacity and no dropped assignment; what absent
    experts would add is left out.  ``held = num_routed`` is the whole
    layer.  Not ``gated``: experts of TWO matrices about a squared ReLU,
    ``W2 relu(W1 m)^2``, and no ``_w3``.  Unlike ``moe_ffn`` (dense [N, E, C] dispatch with a capacity
    that drops, ReLU experts with biases, every expert held) this sorts the
    assignments by expert and multiplies them as grouped products.

    ``score``: ``softmax`` over the router's outputs, or ``sigmoid`` of
    each; the chosen scores are renormalized where ``norm_topk`` (over
    their sum + ``norm_eps``) and multiplied by ``route_scale``.
    ``select_bias``: the router also holds ``<name>_route_bias``
    ([num_routed] float32, zeros, persistable, NOT trainable), added to the
    scores for the choice of the ``top_k`` only, never to the weights.  The
    layer then returns ``(out, bias, counts)``, ``counts`` [num_routed]
    int32 being the step's assignments to every routed expert:
    ``moe_bias_update(bias, counts, coeff)``, appended after
    ``optimizer.minimize``, is the rule that moves the bias."""
    from ..initializer import XavierInitializer

    helper = LayerHelper("moe_experts", **locals())
    dtype = helper.input_dtype()
    d = int(input.shape[-1])
    ar, a1, a3, a2 = _named_attrs(param_attr, name,
                                  ("router_w", "w1", "w3", "w2"))
    router = helper.create_parameter(attr=ar, shape=[d, num_routed],
                                     dtype=dtype)
    up = XavierInitializer(fan_in=d, fan_out=hidden_size)
    ups = {slot: helper.create_parameter(
        attr=attr, shape=[experts_held, d, hidden_size], dtype=dtype,
        default_initializer=up)
        for slot, attr in (("W1", a1), ("W3", a3)) if gated or slot == "W1"}
    w2 = helper.create_parameter(
        attr=a2, shape=[experts_held, hidden_size, d], dtype=dtype,
        default_initializer=XavierInitializer(fan_in=hidden_size, fan_out=d))
    for p in (*ups.values(), w2):
        p.dist_hint = "ep"
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = tuple(input.shape)
    inputs = {"X": [input], "RouterW": [router],
              **{slot: [w] for slot, w in ups.items()}, "W2": [w2]}
    outputs = {"Out": [out]}
    attrs = {"num_routed": int(num_routed),
             "experts_held": int(experts_held),
             "expert_offset": int(expert_offset), "top_k": int(top_k),
             "norm_topk": bool(norm_topk)}
    # only what departs from the softmax router is written into the op
    if score != "softmax":
        attrs["score"] = str(score)
    if norm_eps:
        attrs["norm_eps"] = float(norm_eps)
    if route_scale != 1.0:
        attrs["route_scale"] = float(route_scale)
    bias = counts = None
    if select_bias:
        from . import tensor

        bias = tensor.create_global_var(
            [num_routed], 0.0, "float32", persistable=True,
            name=None if name is None else f"{name}_route_bias")
        bias.stop_gradient = True
        counts = helper.create_variable_for_type_inference(
            "int32", stop_gradient=True)
        counts.shape = (num_routed,)
        inputs["Bias"], outputs["Counts"] = [bias], [counts]
    helper.append_op(type="moe_experts", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return (out, bias, counts) if select_bias else out


def moe_bias_update(bias, counts, coeff, name=None):
    """The balancing rule of a router's selection bias (ops/decoder_ops.py
    ``moe_bias_update``), in place: ``bias += coeff * sign(mean(counts) -
    counts)``: up for an expert that got fewer assignments this step than
    the mean, down for one that got more.  ``bias`` and ``counts`` are what
    ``moe_experts(select_bias=True)`` returned.  Append it AFTER
    ``optimizer.minimize``: it is state moved by a rule and has no
    gradient."""
    helper = LayerHelper("moe_bias_update", **locals())
    helper.append_op(type="moe_bias_update",
                     inputs={"Bias": [bias], "Counts": [counts]},
                     outputs={"BiasOut": [bias]},
                     attrs={"coeff": float(coeff)})
    return bias


def short_conv(input, taps, param_attr=None, name=None, gated=True,
               bias_attr=None):
    """A short convolution over the sequence (ops/decoder_ops.py
    ``short_conv``): one causal filter of ``taps`` weights a channel
    (``w`` [channels, taps], no bias), zero before position 0.  ``gated``
    (the default), the token mixer of a layer without attention: for
    ``input`` = [B | C | u] ([batch, T, 3 * channels], three chunks in this
    order, as one projection makes them)
    ``out[t] = C[t] * sum_j w[:, j] * (B * u)[t - (taps - 1) + j]``.  Not
    ``gated``: ``out = SiLU(filter(input))`` over ``input``'s own channels,
    the form in front of a linear attention (``gated_delta_rule``) or a
    state-space scan (``ssd_scan``), with ``bias_attr`` (a ParamAttr, or
    True) a bias a channel, zeros at first, added before the SiLU.  Unlike
    ``row_conv`` it looks back and never ahead and takes a dense
    [batch, T, ...] tensor: every row of the batch is a sequence of its
    own and nothing crosses from one to the next."""
    helper = LayerHelper("short_conv", **locals())
    dtype = helper.input_dtype()
    width = int(input.shape[-1])
    channels = width // 3 if gated else width
    if int(taps) < 1 or (gated and 3 * channels != width):
        raise ValueError(f"short_conv: {taps} taps over an input "
                         f"{tuple(input.shape)} that is not 3 * channels "
                         f"wide")
    if gated and bias_attr:
        raise ValueError("short_conv: the gated form has no bias")
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[channels, int(taps)], dtype=dtype)
    inputs = {"X": [input], "Filter": [w]}
    if bias_attr:
        inputs["Bias"] = [helper.create_parameter(
            attr=helper.bias_attr, shape=[channels], dtype=dtype,
            is_bias=True)]
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = tuple(input.shape[:-1]) + (channels,)
    # only what departs from the gated form is written into the op
    helper.append_op(type="short_conv", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs=None if gated else {"gated": False})
    return out


def gated_delta_rule(q, k, v, g, beta, chunk=64, scale=None, norm_eps=0.0,
                     name=None):
    """The gated delta rule (ops/decoder_ops.py ``gated_delta_rule``,
    ops/delta_rule.py), a linear attention: every value head keeps a
    [dk, dv] state that each token decays by ``exp(g_t)``, corrects towards
    its value along its key with the step ``beta_t`` and reads with its
    query.  q, k: [B, T, Hk, dk]; v: [B, T, Hv, dv], Hv a multiple of Hk
    (key head j serves the value heads ``j * Hv / Hk`` on); g (<= 0) and
    beta: [B, T, Hv].  Returns [B, T, Hv, dv].  ``chunk``: the tokens worked
    at once between two steps of the state; ``scale`` multiplies q (None:
    ``dk ** -0.5``); ``norm_eps`` > 0 l2-norms q and k per head first.
    Every row of the batch starts from a zero state.  A ``g`` [B, T, Hv,
    dk] decays every key channel (the state's rows) by a number of its
    own."""
    helper = LayerHelper("gated_delta_rule", **locals())
    out = helper.create_variable_for_type_inference(helper.input_dtype("v"))
    out.shape = tuple(v.shape)
    attrs = {"chunk": int(chunk), "scale": float(scale or 0.0)}
    if norm_eps:
        attrs["norm_eps"] = float(norm_eps)
    helper.append_op(
        type="gated_delta_rule",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]},
        outputs={"Out": [out]}, attrs=attrs)
    return out


def ssd_scan(u, delta, a, b, c, d, chunk=128, groups=1, name=None):
    """A selective state-space scan (ops/decoder_ops.py ``ssd_scan``,
    ops/ssd.py), the recurrence of a Mamba-2 mixer: every head keeps a
    [P, N] state that each token decays by ``exp(delta_t a)``, adds
    ``delta_t u_t b_t^T`` to and reads along ``c_t``, and ``d u_t`` passes
    beside it.  u: [B, T, H, P]; delta (> 0): [B, T, H]; a (< 0) and d:
    [H]; b, c: [B, T, groups * N], head h reading group ``h // (H //
    groups)``.  Returns [B, T, H, P].  ``chunk``: the tokens worked at once
    between two steps of the state.  Every row of the batch starts from a
    zero state."""
    helper = LayerHelper("ssd_scan", **locals())
    out = helper.create_variable_for_type_inference(helper.input_dtype("u"))
    out.shape = tuple(u.shape)
    helper.append_op(
        type="ssd_scan",
        inputs={"U": [u], "Delta": [delta], "A": [a], "B": [b], "C": [c],
                "D": [d]},
        outputs={"Out": [out]},
        attrs={"chunk": int(chunk), "groups": int(groups)})
    return out


def paged_attention(q, cache_k, cache_v, page_table, bias, scale=1.0,
                    name=None):
    """One decode step of attention over a PAGED K/V cache
    (serving/kvpool, ops/decode_ops.py + ops/pallas_paged.py).  q:
    [slots, 1, d_model]; cache_k/cache_v: [num_pages + 1, page_size,
    d_model] page pools (the last row is the trash page); page_table:
    [slots, pages_per_slot] int (unmapped entries point at the trash
    page); bias: [slots, 1, pages_per_slot * page_size] additive
    validity bias with exact ``-inf`` past each slot's live length.
    The Pallas scalar-prefetch gather kernel on a TPU backend, the XLA
    ``take`` twin elsewhere (ops/kernel_choice.py).  Returns
    [slots, 1, d_model]."""
    helper = LayerHelper("paged_attention", **locals())
    out = helper.create_variable_for_type_inference(helper.input_dtype("q"))
    out.shape = tuple(q.shape)
    helper.append_op(
        type="paged_attention",
        inputs={"Q": [q], "CacheK": [cache_k], "CacheV": [cache_v],
                "PageTable": [page_table], "Bias": [bias]},
        outputs={"Out": [out]},
        attrs={"scale": float(scale)})
    return out


def kv_cache_update(cache, new, slots, pos, name=None):
    """Scatter ``new`` [n, w, ...] into rows of the persistable KV cache
    ``cache`` [max_slots, max_len, ...] at per-row destinations: row j
    lands at ``cache[slots[j], pos[j]:pos[j]+w]`` (continuous-batching
    decode, ops/decode_ops.py).  The op writes the cache var IN PLACE
    (its output is ``cache`` itself), so the executor commits it as
    persistent device state after the dispatch — with
    ``program._donate_state`` the buffer is donated and aliased
    window-over-window.  Returns ``cache``.  Callers guarantee
    ``pos + w <= max_len``."""
    helper = LayerHelper("kv_cache_update", **locals())
    helper.append_op(
        type="kv_cache_update",
        inputs={"Cache": [cache], "New": [new], "Slots": [slots],
                "Pos": [pos]},
        outputs={"Out": [cache]})
    return cache


def kv_cache_scatter(cache, new, rows, offs, name=None):
    """Scatter per-token K/V rows ``new`` [n, ...] into the persistable
    cache ``cache`` [rows, width, ...] at explicit destinations: token j
    lands at ``cache[rows[j], offs[j]]`` (speculative verify step,
    ops/decode_ops.py).  Dense caches pass (slot, absolute position);
    paged caches pass (page, in-page offset).  Out-of-range rows are
    scatter-dropped — the dense trash slot.  In-place by name like
    ``kv_cache_update``; returns ``cache``."""
    helper = LayerHelper("kv_cache_scatter", **locals())
    helper.append_op(
        type="kv_cache_scatter",
        inputs={"Cache": [cache], "New": [new], "Rows": [rows],
                "Offs": [offs]},
        outputs={"Out": [cache]})
    return cache


def spec_accept(logits, draft, mask=None, end_id=0, name=None):
    """Greedy speculative acceptance (serving/specdec): given verify
    logits [slots, k+1, vocab] and the k drafted tokens [slots, k],
    return ``(tokens, num_accept)`` — tokens [slots, k+1] int64 is the
    target argmax at every scored position, num_accept [slots] int64 the
    longest draft==argmax prefix.  The engine consumes
    ``tokens[s, :n+1]``, all target argmaxes, so speculative output is
    bitwise identical to sequential greedy decode.  Inactive slots
    (mask == 0) emit ``end_id`` and accept 0."""
    helper = LayerHelper("spec_accept", **locals())
    toks = helper.create_variable_for_type_inference(
        core.convert_dtype("int64"), stop_gradient=True)
    toks.shape = tuple(logits.shape[:-1])
    nacc = helper.create_variable_for_type_inference(
        core.convert_dtype("int64"), stop_gradient=True)
    nacc.shape = (logits.shape[0],)
    inputs = {"Logits": [logits], "Draft": [draft]}
    if mask is not None:
        inputs["Mask"] = [mask]
    helper.append_op(type="spec_accept", inputs=inputs,
                     outputs={"Tokens": [toks], "NumAccept": [nacc]},
                     attrs={"end_id": int(end_id)})
    return toks, nacc


def token_select(logits, mask=None, end_id=0, name=None):
    """Greedy per-slot next-token choice for the compiled decode step:
    ``argmax(logits, -1)`` where ``mask`` is truthy, ``end_id``
    otherwise (inactive/free slots emit inert pad tokens).  logits:
    [slots, vocab]; mask: optional [slots].  Returns [slots] int64."""
    helper = LayerHelper("token_select", **locals())
    out = helper.create_variable_for_type_inference(
        core.convert_dtype("int64"), stop_gradient=True)
    out.shape = tuple(logits.shape[:-1])
    inputs = {"Logits": [logits]}
    if mask is not None:
        inputs["Mask"] = [mask]
    helper.append_op(type="token_select", inputs=inputs,
                     outputs={"Out": [out]}, attrs={"end_id": int(end_id)})
    return out


def _stack_params(helper, dtype, n_layer, d_model, d_inner, decoder,
                  param_attr):
    """Create the stacked [L, ...] parameters of a transformer layer stack,
    tagged with per-dim ``dist_spec`` mesh hints (parallel/transformer_stack
    .dist_spec_for) so pp shards layers and mp shards the Megatron dims."""
    from ...parallel import transformer_stack as ts
    from ..initializer import ConstantInitializer, XavierInitializer

    table = ts.DECODER_SLOTS if decoder else ts.ENCODER_SLOTS
    shapes = {
        "WQ": [n_layer, d_model, d_model], "WK": [n_layer, d_model, d_model],
        "WV": [n_layer, d_model, d_model], "WO": [n_layer, d_model, d_model],
        "FFN1W": [n_layer, d_model, d_inner], "FFN1B": [n_layer, d_inner],
        "FFN2W": [n_layer, d_inner, d_model], "FFN2B": [n_layer, d_model],
        "LN1S": [n_layer, d_model], "LN1B": [n_layer, d_model],
        "LN2S": [n_layer, d_model], "LN2B": [n_layer, d_model],
    }
    if decoder:
        shapes.update({
            "CQ": [n_layer, d_model, d_model], "CK": [n_layer, d_model, d_model],
            "CV": [n_layer, d_model, d_model], "CO": [n_layer, d_model, d_model],
            "LN3S": [n_layer, d_model], "LN3B": [n_layer, d_model],
        })
    params = {}
    for slot, shape in shapes.items():
        if slot.endswith(("S",)) and slot.startswith("LN"):
            init = ConstantInitializer(1.0)
        elif slot.endswith("B") or len(shape) == 2:
            init = ConstantInitializer(0.0)
        else:
            # stacked weights need PER-LAYER fans: the default fan
            # convention would read the layer dim as receptive field
            init = XavierInitializer(fan_in=shape[1], fan_out=shape[2])
        p = helper.create_parameter(attr=copy.deepcopy(param_attr),
                                    shape=shape, dtype=dtype,
                                    default_initializer=init)
        p.dist_spec = ts.dist_spec_for(slot, len(shape), decoder)
        params[slot] = p
    return params


def transformer_encoder_stack(input, bias=None, n_layer=2, n_head=4,
                              d_inner=None, dropout=0.0, is_test=False,
                              n_microbatches=4, recompute=False,
                              param_attr=None, name=None):
    """A full transformer ENCODER stack as one mesh-aware op (TPU-native
    capability — see parallel/transformer_stack.py).  input: [N, T, D];
    bias: optional [N, 1, 1, T] additive key bias (padding mask).

    Single-device this is a lax.scan over the stacked layer params; under a
    mesh it composes pipeline ("pp"), Megatron tensor ("mp") and ring-
    attention sequence ("sp") parallelism with data parallelism ("dp") —
    the same program runs on every mesh shape.  Residual dropout only (see
    transformer_stack module docstring)."""
    helper = LayerHelper("transformer_encoder_stack", **locals())
    dtype = helper.input_dtype()
    d = int(input.shape[-1])
    params = _stack_params(helper, dtype, n_layer, d, d_inner or 4 * d,
                           False, param_attr)
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = tuple(input.shape)
    rng_key = helper.create_variable_for_type_inference("int32")
    rng_key.shape = (2,)
    rng_key.stop_gradient = True
    inputs = {"X": [input]}
    if bias is not None:
        inputs["Bias"] = [bias]
    inputs.update({slot: [p] for slot, p in params.items()})
    helper.append_op(
        type="transformer_encoder_stack", inputs=inputs,
        outputs={"Out": [out], "RngKey": [rng_key]},
        attrs={"n_head": int(n_head), "dropout": float(dropout),
               "is_test": bool(is_test),
               "n_microbatches": int(n_microbatches),
               "recompute": bool(recompute)})
    return out


def transformer_decoder_stack(input, enc_out, src_bias=None, n_layer=2,
                              n_head=4, d_inner=None, dropout=0.0,
                              is_test=False, n_microbatches=4,
                              recompute=False, param_attr=None, name=None):
    """A full transformer DECODER stack (causal self-attn + cross-attn +
    FFN per layer) as one mesh-aware op; see transformer_encoder_stack.
    input: [N, Tt, D]; enc_out: [N, Ts, D]; src_bias: [N, 1, 1, Ts]."""
    helper = LayerHelper("transformer_decoder_stack", **locals())
    dtype = helper.input_dtype()
    d = int(input.shape[-1])
    params = _stack_params(helper, dtype, n_layer, d, d_inner or 4 * d,
                           True, param_attr)
    out = helper.create_variable_for_type_inference(dtype)
    out.shape = tuple(input.shape)
    rng_key = helper.create_variable_for_type_inference("int32")
    rng_key.shape = (2,)
    rng_key.stop_gradient = True
    inputs = {"X": [input], "EncOut": [enc_out]}
    if src_bias is not None:
        inputs["Bias"] = [src_bias]
    inputs.update({slot: [p] for slot, p in params.items()})
    helper.append_op(
        type="transformer_decoder_stack", inputs=inputs,
        outputs={"Out": [out], "RngKey": [rng_key]},
        attrs={"n_head": int(n_head), "dropout": float(dropout),
               "is_test": bool(is_test),
               "n_microbatches": int(n_microbatches),
               "recompute": bool(recompute)})
    return out


def cos_sim(X, Y, name=None):
    """Cosine similarity per row (ref: layers/nn.py cos_sim, cos_sim_op.*)."""
    helper = LayerHelper("cos_sim", **locals())
    dtype = helper.input_dtype("X")
    out = helper.create_variable_for_type_inference(dtype)
    xn = helper.create_variable_for_type_inference(dtype)
    yn = helper.create_variable_for_type_inference(dtype)
    out.shape = (X.shape[0], 1)
    helper.append_op(type="cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]})
    return out


def multiplex(inputs, index):
    """Row-wise select across candidate tensors (ref multiplex_op.*)."""
    helper = LayerHelper("multiplex", **locals())
    out = helper.create_variable_for_type_inference(
        helper.input_dtype("inputs"))
    out.shape = tuple(inputs[0].shape)
    helper.append_op(type="multiplex",
                     inputs={"X": list(inputs), "Ids": [index]},
                     outputs={"Out": [out]})
    return out


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True):
    """3-D pooling (ref pool_op.* 3-D registration)."""
    helper = LayerHelper("pool3d", **locals())
    pool_size = _to_list(pool_size, 3)
    pool_stride = _to_list(pool_stride, 3)
    pool_padding = _to_list(pool_padding, 3)
    out = helper.create_variable_for_type_inference(helper.input_dtype())
    dims = input.shape

    def _po(size, k, pad, st):
        if size in (-1, None):
            return -1
        if ceil_mode:
            return (size - k + 2 * pad + st - 1) // st + 1
        return (size - k + 2 * pad) // st + 1

    if global_pooling:
        out.shape = tuple(dims[:2]) + (1, 1, 1)
    else:
        out.shape = tuple(dims[:2]) + tuple(
            _po(dims[2 + i], pool_size[i], pool_padding[i], pool_stride[i])
            for i in range(3))
    helper.append_op(
        type="pool3d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"pooling_type": pool_type, "ksize": pool_size,
               "strides": pool_stride, "paddings": pool_padding,
               "global_pooling": global_pooling, "ceil_mode": ceil_mode,
               "exclusive": exclusive})
    return out


def random_crop(x, shape, seed=None):
    """Per-instance random crops of the trailing dims (ref
    random_crop_op.*)."""
    helper = LayerHelper("random_crop", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    lead = len(x.shape) - len(shape)
    out.shape = tuple(x.shape[:lead]) + tuple(shape)
    seed_out = helper.create_variable_for_type_inference("int64")
    helper.append_op(type="random_crop", inputs={"X": [x]},
                     outputs={"Out": [out], "SeedOut": [seed_out]},
                     attrs={"shape": list(shape),
                            "startup_seed": seed or 0})
    return out


def rank_loss(label, left, right, name=None):
    """RankNet pairwise loss (ref rank_loss_op.*)."""
    helper = LayerHelper("rank_loss", **locals())
    out = helper.create_variable_for_type_inference("float32")
    out.shape = tuple(label.shape)
    helper.append_op(type="rank_loss",
                     inputs={"Label": [label], "Left": [left],
                             "Right": [right]},
                     outputs={"Out": [out]})
    return out


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """Resize so the SHORT side equals out_short_len, keeping aspect
    (ref layers/nn.py image_resize_short)."""
    in_shape = input.shape
    if len(in_shape) != 4:
        raise ValueError("image_resize_short expects NCHW input")
    h, w = in_shape[2], in_shape[3]
    # pin the SHORT side exactly; round the long side half-up (ref
    # layers/nn.py image_resize_short)
    if h <= w:
        out_shape = [out_short_len, int(w * out_short_len / h + 0.5)]
    else:
        out_shape = [int(h * out_short_len / w + 0.5), out_short_len]
    return image_resize(input, out_shape=out_shape, resample=resample)


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=True,
          print_phase="both"):
    """Debug-print a tensor during execution (ref print_op.cc; runs as a
    host callback in the eager island path)."""
    helper = LayerHelper("Print", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    out.shape = tuple(input.shape)
    helper.append_op(
        type="print", inputs={"In": [input]}, outputs={"Out": [out]},
        attrs={"first_n": first_n, "message": message or "",
               "summarize": summarize,
               "print_tensor_name": print_tensor_name,
               "print_tensor_dtype": print_tensor_type,
               "print_tensor_shape": print_tensor_shape})
    return out


def load(out, file_path, load_as_fp16=False):
    """In-graph load of one variable from disk (ref load_op.cc:24)."""
    helper = LayerHelper("load", **locals())
    helper.append_op(type="load", inputs={}, outputs={"Out": [out]},
                     attrs={"file_path": file_path,
                            "load_as_fp16": load_as_fp16})
    return out

def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    """3-D transposed convolution (ref conv3d_transpose registration in
    conv_transpose_op.*)."""
    helper = LayerHelper("conv3d_transpose", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    stride = _to_list(stride, 3)
    padding = _to_list(padding, 3)
    dilation = _to_list(dilation, 3)
    if filter_size is None:
        if output_size is None:
            raise ValueError("need filter_size or output_size")
        output_size = _to_list(output_size, 3)
        dims_in = input.shape
        filter_size = [
            (output_size[i] - (dims_in[2 + i] - 1) * stride[i]
             + 2 * padding[i] - 1) // dilation[i] + 1 for i in range(3)]
    else:
        filter_size = _to_list(filter_size, 3)
    filter_shape = [num_channels, num_filters // groups] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape,
                                dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    dims = input.shape

    def _out_dim(size, k, pad, st, d):
        if size in (-1, None):
            return -1
        return (size - 1) * st - 2 * pad + d * (k - 1) + 1

    out.shape = (dims[0], num_filters) + tuple(
        _out_dim(dims[2 + i], filter_size[i], padding[i], stride[i],
                 dilation[i]) for i in range(3))
    helper.append_op(
        type="conv3d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": stride, "paddings": padding,
               "dilations": dilation, "groups": groups})
    pre_act = helper.append_bias_op(out, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)

