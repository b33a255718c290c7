"""The two control cells' training steps, lowered, against a pinned digest.

``transformer_base_wmt.resident`` and ``resnet50_imagenet.resident`` hold no
``moe_experts`` op, and a PR that works on the decoders' layers claims that
they "do not move".  The chip says so after forty minutes; this says it in
seconds: each configuration is built as its cell builds it (bf16 AMP with low
activations, ``optimizer.minimize``) at its ``tiny`` sizes with the Pallas
kernels interpreted, as ``chipbench/run.py --rehearse`` does, and the text of
the step handed to the backend (``Executor.lower_step(...).as_text()``, byte-
equal between two trees that lower the same program; .claude/skills/verify)
is hashed.

The digests are of the tree PR 39 started from (15bda75) and were equal on
PR 39's; the Transformer's is PR 50's, which meant to change that step (its
loss's grad op takes the forward's ``Lse`` and holds the forward kernel no
second time; PR 49's, ``a8d0f4d3...``, was of the loss that reads the labels
and ``smooth_epsilon``).  A PR that MEANS to change one of these steps (an op's lowering, a
pass, the optimizer) replaces the digest with the one this test prints and
says so in CHANGES.md; one that does not has moved a control cell.
"""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import plugins  # noqa: E402

STEPS = {
    "transformer_base_wmt":
        "4ebf1dc18b6cb71e197b8d94b91e273c0f36cf6db38c62a08f0e67e5888cc225",
    "resnet50_imagenet":
        "b91426762a53d56bcd839804385d83ffaee7d988dd6e31e946c93b9835721a83",
}


@pytest.mark.parametrize("config", sorted(STEPS))
def test_a_control_cells_lowered_step_is_the_pinned_one(monkeypatch, config):
    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")      # Pallas, interpreted
    monkeypatch.setenv("PADDLE_TPU_FUSED", "1")
    sizes = json.load(open(os.path.join(ROOT, "chipbench", "configs", config,
                                        "config.json")))
    sizes = {**sizes, **sizes["tiny"]}
    builder = plugins.load(os.path.join("configs", config), "build")
    fluid.amp.enable("bfloat16", keep_activations=True)
    try:
        loss = builder.build(fluid, sizes)["loss"]
        main = fluid.default_main_program()
        main.random_seed = fluid.default_startup_program().random_seed = 7
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        feed = builder.make_feed(sizes, sizes["batch_per_chip"],
                                 np.random.RandomState(0))
        text = exe.lower_step(main, feed, [loss]).as_text()
    finally:
        fluid.amp.disable()
    assert "stablehlo" in text and len(text) > 100_000
    assert hashlib.sha256(text.encode()).hexdigest() == STEPS[config]
