"""FLOPs the forward and backward passes NEED, from the program's own shapes.

Walks the ops of a built ``Program`` and counts two operations per
multiply-accumulate of every contraction in the forward pass: ``mul`` (fc),
``matmul``, ``conv2d`` and the attention op (``ring_attention``, the fused
attention op's name in the IR).  The backward pass of a contraction is two
contractions of the same size (one for the input, one for the weight), so a
training step needs 3x the forward count.  Recomputation inside a kernel or
by the compiler is NOT counted; elementwise work, normalisation, softmax and
the optimizer are not counted either.  Causal attention needs half the
score and value contractions and is counted as half.

The count is per SAMPLE (one sequence pair, one image): shapes whose batch
dim is dynamic (-1) are read with batch 1.

The walk knows four contractions and nothing else, so it may only be trusted
on a program whose every op type it has been told about: ``COUNTED`` (the
four) or ``NO_CONTRACTION`` (the other op types of the two programs the
benchmark was accepted with, read from their built ``Program``s; none holds
a contraction), each with its ``_grad``.  ``uncounted_op_types`` names the
rest, and where there is any, ``mfu_pct`` is withheld: a program whose
contractions sit in an op the walk does not know (routed experts, a scan)
would otherwise report an undercount as its MFU.  A configuration with such
ops states its FLOPs itself, in ``configs/<name>/flops.py``
(``train_flops_per_sample(sizes)``), which then replaces the walk.
"""

from __future__ import annotations

import math


COUNTED = frozenset({"mul", "matmul", "conv2d", "ring_attention"})
NO_CONTRACTION = frozenset({
    "accuracy", "adam", "assign_value", "batch_norm", "cast",
    "cross_entropy", "dropout", "elementwise_add", "elementwise_div",
    "elementwise_mul", "equal", "fill_any_like", "fill_constant",
    "fill_constant_batch_size_like", "layer_norm", "logical_not",
    "lookup_table", "mean", "momentum", "one_hot", "pool2d", "reduce_sum",
    "relu", "reshape", "scale", "softmax", "softmax_with_cross_entropy",
    "sum", "top_k", "transpose"})


def uncounted_op_types(program) -> list:
    """The op types of ``program`` (block 0) that the walk neither counts
    nor knows to hold no contraction."""
    known = COUNTED | NO_CONTRACTION
    return sorted({op.type for op in program.global_block().ops}
                  - known - {t + "_grad" for t in known})


def _shape(block, name, batch=1):
    shp = block._var_recursive(name).shape
    return [batch if (d is None or d < 0) else int(d) for d in shp]


def _prod(xs):
    return int(math.prod(xs)) if xs else 1


def mul_flops(x_shape, y_shape, x_num_col_dims=1, y_num_col_dims=1):
    """fluid ``mul``: X flattened to [M, K] at ``x_num_col_dims``, Y to
    [K, N]; 2*M*K*N."""
    m = _prod(x_shape[:x_num_col_dims])
    k = _prod(x_shape[x_num_col_dims:])
    n = _prod(y_shape[y_num_col_dims:])
    return 2 * m * k * n


def matmul_flops(x_shape, y_shape, transpose_x=False, transpose_y=False):
    """Batched matmul over the leading dims of X."""
    xm, xk = (x_shape[-1], x_shape[-2]) if transpose_x else x_shape[-2:]
    yn = y_shape[-2] if transpose_y else y_shape[-1]
    return 2 * _prod(x_shape[:-2]) * xm * xk * yn


def conv2d_flops(out_shape, filter_shape, groups=1):
    """NCHW conv: every output element is one dot product over
    Cin/groups * kh * kw inputs.  ``filter_shape`` is [Cout, Cin/groups,
    kh, kw]."""
    del groups  # filter_shape[1] is already Cin / groups
    return 2 * _prod(out_shape) * _prod(filter_shape[1:])


def attention_flops(q_shape, k_shape, causal=False):
    """softmax(q k^T) v for q [b, h, lq, d], k/v [b, h, lk, d]: two
    contractions of b*h*lq*lk*d multiply-accumulates each."""
    b, h, lq, d = q_shape
    lk = k_shape[2]
    full = 2 * 2 * b * h * lq * lk * d
    return full // 2 if causal else full


def forward_flops(program) -> int:
    """Needed forward FLOPs of one sample of ``program`` (block 0)."""
    blk = program.global_block()
    total = 0
    for op in blk.ops:
        t = op.type
        if t == "mul":
            total += mul_flops(_shape(blk, op.inputs["X"][0]),
                               _shape(blk, op.inputs["Y"][0]),
                               op.attrs.get("x_num_col_dims", 1),
                               op.attrs.get("y_num_col_dims", 1))
        elif t == "matmul":
            total += matmul_flops(_shape(blk, op.inputs["X"][0]),
                                  _shape(blk, op.inputs["Y"][0]),
                                  op.attrs.get("transpose_X", False),
                                  op.attrs.get("transpose_Y", False))
        elif t == "conv2d":
            total += conv2d_flops(_shape(blk, op.outputs["Output"][0]),
                                  _shape(blk, op.inputs["Filter"][0]))
        elif t == "ring_attention":
            total += attention_flops(_shape(blk, op.inputs["Q"][0]),
                                     _shape(blk, op.inputs["K"][0]),
                                     bool(op.attrs.get("causal", False)))
    return total


def train_flops_per_sample(program) -> int:
    """Forward + backward: 3x the forward contractions."""
    return 3 * forward_flops(program)
