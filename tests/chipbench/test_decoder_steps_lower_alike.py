"""The seven decoder configurations that the benchmark had before
``nemotron_twotower_30b_a3b`` lower, at their ``tiny`` sizes under the
harness's bf16 AMP, to the text they lowered to on the commit before it
(PR 57's tree, ``0041527``): the expert layer with three matrices, the
filter without a bias, the norm over the whole axis and the attention with
head norms are untouched where the new switches (``w3`` None, ``Bias``,
``groups``, ``qk_norm``, ``sub_blocks``, the ``ssm`` kind) are off.  CPU
only, the XLA paths (no kernel switch set); the digests were taken with
this file's own code in a checkout of that commit, twice, in two
processes."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import plugins  # noqa: E402

#: config -> (characters, sha256) of ``Executor.lower_step(...).as_text()``
STEPS_BEFORE = {
    "keye_vl_2_0_30b_a3b": (416371, "3725a053027be07d5e7f9ee1c0726d02"
                                    "70b16abb1dbaad8f89c44d456e03608c"),
    "trinity_mini": (958020, "5c31f7e869461dcc27bd3a57ac8271c5"
                             "1efabf0d8e48a6afe78ca3fc34a9d732"),
    "lfm2_8b_a1b": (602589, "652079ea86abb5a6fadd579abc3158ef"
                            "b4f32a7723fed0ed97fb83de4b87035d"),
    "instella_moe_16b_a3b": (988512, "53fee940a4b26166a043b8662adc274d"
                                     "5737e641842c2e57a50b91c58d4dcd7c"),
    "qwen3_next_80b_a3b": (1074917, "9f5f9428252ba2f6acbe26191c41bd88"
                                    "ba4242993c7c4eda53853049c5ac3ddb"),
    "mellum2_12b_a2_5b": (658155, "c7bac078f9207d40fdb4bda1ede3bc50"
                                  "a340c042a99a8944a4de1ef01424e262"),
    "kimi_linear_48b_a3b": (1357732, "37afaa849c66463586d21dea5f73a6db"
                                     "4c51aa6f9fc3811a97cbae7aac0b2ae6"),
}


@pytest.mark.parametrize("config", sorted(STEPS_BEFORE))
def test_the_step_lowers_to_the_text_it_lowered_to_before(config):
    sizes = json.load(open(os.path.join(ROOT, "chipbench", "configs", config,
                                        "config.json")))
    sizes = {**sizes, **sizes["tiny"]}
    build = plugins.load(f"configs/{config}", "build")
    fluid.amp.enable("bfloat16", keep_activations=True)
    try:
        built = build.build(fluid, sizes)
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        feed = build.make_feed(sizes, 1, np.random.RandomState(0))
        text = exe.lower_step(fluid.default_main_program(), feed,
                              [built["loss"]]).as_text()
    finally:
        fluid.amp.disable()
    assert "ssd_scan" not in text
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) \
        == STEPS_BEFORE[config]


def test_a_layer_in_slabs_lowers_both_passes_to_the_text_before():
    """No ``tiny`` size walks in slabs, so the loop's body is held apart: 2
    of 128 experts under a bias, 256 tokens, both passes (``jax.grad`` of
    the layer) lowered on XLA's grouped product.  In the backward's body
    the weights' gradients come first behind a barrier and the rows'
    cotangents read the BARRIER's results: with that wiring lost the step
    still computed the same and Trinity's read 0.4% faster and 65 MB
    smaller on the chip (my chip runs, PR 58, call 4), which is not what
    the parent lowers."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    n, k, held, routed, width = 256, 2, 2, 128, 16
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(n, width), jnp.float32)
    wr = jnp.asarray(rng.randn(width, routed), jnp.float32)
    w1, w3, w2 = (jnp.asarray(0.2 * rng.randn(held, width, width),
                              jnp.float32) for _ in range(3))
    bias = jnp.zeros(routed, jnp.float32)
    assert moe.walk_of(x, wr, w1, w2, k, bias)[2] < n * k

    def loss(x, wr, w1, w3, w2):
        return jnp.sum(moe.routed_experts(
            x, wr, w1, w3, w2, top_k=k, expert_offset=8, bias=bias) ** 2)

    text = jax.jit(jax.grad(loss, range(5))).lower(x, wr, w1, w3,
                                                   w2).as_text()
    assert text.count("stablehlo.while") == 2
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) == (
        84284, "b51344ec17172779d85afb9d20ff2f7b"
               "e030551de29549e07655bbc3ee05f77e")
