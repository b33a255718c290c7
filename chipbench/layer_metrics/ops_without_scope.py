"""Ops of the main program that carry no ``fluid.name_scope`` path (a
count of the program, so a rehearsal reports it too).  None where no op
carries one: the program was built without name scopes."""

from chipbench import scope_time


def value(run):
    del run
    try:
        return scope_time.ops_without_scope()
    except Exception:
        return None
