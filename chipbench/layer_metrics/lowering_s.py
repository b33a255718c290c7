"""Seconds jax spent tracing to jaxprs and lowering them to MLIR modules,
from process start to the window's first stamp: the union of the program's
``fluid.compile.trace`` and ``fluid.compile.lower`` spans."""

from chipbench import program_spans


def value(run):
    return program_spans.compile_seconds(
        run, ("fluid.compile.trace", "fluid.compile.lower"))
