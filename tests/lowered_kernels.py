"""What a lowered step holds of the loss kernels on the CPU, where a Pallas
kernel is interpreted and leaves no custom call behind: one ``while`` over
its grid, under the name of the op that made it."""

import re

from jax._src.lib.mlir import ir

_LOSS_OP = re.compile(r"jit\(fn\)/(softmax_with_cross_entropy(?:_grad)?)/")


def loss_kernel_calls(lowered):
    """``[(op type, kernel)]`` for every call of a loss kernel in a lowered
    step, in program order.  ``_xent_partial_kernel`` carries a running
    row maximum across its column blocks (a ``reduce_max`` in the loop's
    body); ``_xent_bwd_kernel`` carries nothing and reduces nothing."""
    calls = []

    def inside(op):
        found = []

        def note(nested):
            found.append(str(nested.location))
            return ir.WalkResult.ADVANCE

        op.operation.walk(note)
        return found

    def visit(op):
        made_by = _LOSS_OP.search(str(op.location))
        if op.name == "stablehlo.while" and made_by:
            forward = any("reduce_max" in where for where in inside(op))
            calls.append((made_by.group(1), "_xent_partial_kernel"
                          if forward else "_xent_bwd_kernel"))
        return ir.WalkResult.ADVANCE

    for func in lowered.compiler_ir("stablehlo").body.operations:
        func.operation.walk(visit)
    return calls
