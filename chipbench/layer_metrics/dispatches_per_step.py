"""``executor.dispatches`` counter delta over the steps dispatched in the
window: 1 for a per-step ``Executor.run``.  ``BENCHMARK.json`` lists the
cells whose entry point counts one dispatch a step."""


def value(run):
    if not run["dispatches"]:
        return None
    return run["dispatches"] / run["dispatched_steps"]
