"""Op library: importing this package registers every op implementation."""

from . import registry  # noqa: F401
from .registry import register_op, register_grad, is_registered, get_op_def  # noqa: F401

from . import (  # noqa: F401
    math_ops,
    activation_ops,
    reduce_ops,
    shape_ops,
    random_ops,
    nn_ops,
    loss_ops,
    optimizer_ops,
    metric_ops,
    sequence_ops,
    rnn_ops,
    array_ops,
    struct_loss_ops,
    detection_ops,
    quant_ops,
    attention_ops,
    misc_ops,
    rcnn_ops,
    moe_ops,
    pipeline_ops,
    transformer_ops,
    decode_ops,
    decoder_ops,
)
from . import infer_rules  # noqa: F401,E402  (static infer rules, after impls)
