"""The coverage of the names: busy time whose instruction carries a
``fluid.name_scope`` path over busy time.  What is left (``op:unjoined``,
argument copies, whatever a pass made without a path) is printed by op type
in the ``scopes:`` table.  None where no instruction carries a path."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.scoped_share(run))
