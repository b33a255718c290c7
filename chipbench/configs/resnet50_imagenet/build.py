"""ResNet-50 through the program's normal path (``models/resnet.build``).

There is no dropout, so the step compared with the reference is the training
program itself on a smaller batch; batch norm stays in training mode.
"""

import numpy as np


def build(fluid, sizes, deterministic=False):
    from paddle_tpu.models import resnet

    del deterministic  # nothing random in the step
    hw = sizes["image_size"]
    opt = sizes["optimizer"]
    _, _, _, loss, _ = resnet.build(
        class_dim=sizes["num_classes"], depth=sizes["depth"],
        image_shape=(3, hw, hw), lr=opt["lr"], with_momentum=True)
    return {"loss": loss, "units_per_sample": 1}


def make_feed(sizes, batch, rng):
    hw = sizes["image_size"]
    return {"img": rng.normal(size=(batch, 3, hw, hw)).astype(np.float32),
            "label": rng.randint(0, sizes["num_classes"],
                                 size=(batch, 1)).astype(np.int64)}


def trainable_names(program):
    return [p.name for p in program.global_block().all_parameters()
            if getattr(p, "trainable", True)]
