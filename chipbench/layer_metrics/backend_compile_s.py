"""Seconds in backend compiles or loads from jax's persistent cache, from
process start to the window's first stamp: the union of the program's
``fluid.compile.backend`` spans."""

from chipbench import program_spans


def value(run):
    return program_spans.compile_seconds(run, ("fluid.compile.backend",))
