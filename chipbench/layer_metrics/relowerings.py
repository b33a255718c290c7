"""The program's ``executor.relowerings`` counter: lowerings jax made
under a step call although the executor's own cache held the entry.  Each
is a set-up cost users pay again (the uncommitted RNG key: PERF.md)."""

from chipbench import program_spans


def value(run):
    return program_spans.relowerings(run)
