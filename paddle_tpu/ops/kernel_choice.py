"""Kernel or twin: the one rule that says whether a family of Pallas
kernels runs or its XLA twin does.

A family's kernel runs where its group's gate is open AND the kernel
module's own check of the operands gives no reason against.  A group's gate
is its environment switch where that is set (``0`` / ``false`` closed, ``1``
/ ``true`` open, whatever the platform), else the platform: open on a TPU
backend, closed elsewhere.  ``flash`` (``PADDLE_TPU_FLASH``) is
``ring_attention``, the transformer stacks' attention,
``sparse_attention``, ``gated_delta_rule`` under either kind of decay
(``ops/pallas_delta_rule.py``) and ``ssd_scan`` (``ops/pallas_ssd.py``:
linear attentions both, so the attention kernels' gate); ``fused`` (``PADDLE_TPU_FUSED``) is softmax
cross-entropy, the Adam and momentum sweeps and ``paged_attention``.  The
switches are for tests and the benchmark's rehearsal on the CPU; nothing
above ``ops/`` has a say, no layer, model or op attribute.  The expert
layer's grouped products have no gate: their path follows from the operands
alone (``parallel/moe.py`` ``product_path``).  Off the TPU a kernel that
does run is interpreted (a correctness tool, not a fast path).  Read live,
through ``fluid.envcontract`` like every other knob.
"""

from __future__ import annotations

import jax

SWITCHES = {"flash": "PADDLE_TPU_FLASH", "fused": "PADDLE_TPU_FUSED"}


def gate(group: str) -> bool:
    """Whether the kernels of ``group`` ('flash' or 'fused') may run."""
    from ..fluid import envcontract

    v = envcontract.get(SWITCHES[group])
    if v in ("0", "false"):
        return False
    if v in ("1", "true"):
        return True
    return jax.default_backend() == "tpu"


def interpret(stated=None) -> bool:
    """A ``pallas_call``'s ``interpret``: what the caller ``stated``, else
    interpreted everywhere but on a TPU."""
    return jax.default_backend() != "tpu" if stated is None else stated


def switches() -> dict:
    """The two switches as the environment has them now ('' where unset),
    by group: what a compiled step's cache key holds of this module."""
    from ..fluid import envcontract

    return {group: envcontract.get_raw(name)
            for group, name in SWITCHES.items()}
