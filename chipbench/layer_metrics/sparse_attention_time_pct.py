"""Attention over the selection, forward and backward: the share of the
device's busy time under the op type ``sparse_attention`` (its XLA path,
and the XLA glue of its kernels: ``op:sparse_attention``,
``op:sparse_attention_grad``) and inside its three Pallas families
(``kernel:sparse_flash_fwd`` / ``_dq`` / ``_dkv``)."""

from chipbench import op_time


def value(run):
    s = op_time.share(run, ("op:sparse_attention", "kernel:sparse_flash_"))
    return None if s is None else 100.0 * s
