"""The measured loop, one implementation for every cell.

``make_step`` turns a traffic mix (a data file under ``chipbench/traffic/``)
into two callables over the program's normal entry points: ``dispatch()``
enqueues one training step without waiting for it and returns a handle,
``finish(handle)`` waits for that step's loss and returns it as a float.

``run_window`` keeps ``lookahead`` steps in flight: it dispatches step n+1,
then waits for step n and stamps the host clock.  Where the host keeps up
the device queue never drains, and every step has a completion time.  The
window opens at the first completion stamp and closes at the first stamp at
or after ``seconds``.
"""

from __future__ import annotations

import collections
import math
import time

import numpy as np


def make_step(fluid, traffic, program, loss, feed, chips):
    """(dispatch, finish, lower) for one traffic mix.  ``lower()`` gives
    the (StableHLO, optimized HLO) texts of the training step that ran and
    the compiler's account of its memory."""
    import jax

    def texts_and_memory(lowered):
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        memory = {k: int(getattr(ma, k + "_size_in_bytes"))
                  for k in ("argument", "output", "temp", "alias")
                  if hasattr(ma, k + "_size_in_bytes")}
        return lowered.as_text(), compiled.as_text(), memory

    entry = traffic["entry"]
    if entry == "executor":
        if chips != 1:
            raise ValueError("fluid.Executor drives one chip")
        place = fluid.TPUPlace()
        exe = fluid.Executor(place)
        if traffic["feed"] == "device":
            from paddle_tpu.fluid import core

            dev = core.get_jax_device(place)
            feed = {k: jax.device_put(v, dev) for k, v in feed.items()}

        def dispatch(program=program, feed=feed, fetch=(loss,)):
            return exe.run(program, feed=feed, fetch_list=list(fetch),
                           return_numpy=False)

        def lower():
            return texts_and_memory(exe.lower_step(program, feed, [loss]))

    elif entry == "parallel_executor":
        from paddle_tpu.fluid.parallel_executor import ParallelExecutor

        executors = {}

        def executor_for(prog, loss_name):
            pe = executors.get(id(prog))
            if pe is None:
                pe = executors[id(prog)] = ParallelExecutor(
                    loss_name=loss_name, main_program=prog,
                    mesh=traffic["mesh"])
                if pe.device_count != chips:
                    raise ValueError(
                        f"mesh {traffic['mesh']!r} spans {pe.device_count} "
                        f"devices, the cell {chips}")
            return pe

        exe = executor_for(program, loss.name)

        def dispatch(program=program, feed=feed, fetch=(loss,)):
            first = fetch[0]
            pe = executor_for(program, getattr(first, "name", first))
            return pe.run(list(fetch), feed=feed, return_numpy=False)

        def lower():
            # that entry point has no lower_step of its own yet: its one
            # cached step is lowered again with the arrays it places
            (step,) = exe._cache.values()
            arrays = {k: np.asarray(v) for k, v in feed.items()}
            return texts_and_memory(step._fn.lower(
                step.place_feed(arrays),
                step.place_state(fluid.global_scope())))

    else:
        raise ValueError(f"traffic entry {entry!r}: executor or "
                         "parallel_executor")

    def finish(handle):
        return float(np.asarray(handle[0]).reshape(-1)[0])

    return dispatch, finish, lower


def device_arrays(handles):
    """What ``dispatch`` returned, as the arrays behind it, still on the
    device.  ``Executor.run(return_numpy=False)`` wraps each fetch in a
    ``LoDTensor`` that offers only a host copy (``__array__``, which also
    lets go of the device array); the wrapper has no accessor for the
    array itself yet (PERF.md, Open questions)."""
    return [getattr(h, "_data", h) for h in handles]


def run_window(dispatch, finish, seconds=None, steps=None, lookahead=1,
               clock=time.perf_counter):
    """Run until ``seconds`` have passed since the window opened, or for
    exactly ``steps`` completions after it opened.  Returns the stamps, the
    losses, the host time inside each dispatch call, and the counts."""
    from jax.profiler import TraceAnnotation

    pending = collections.deque()
    stamps, losses, dispatch_s = [], [], []
    attempted = failed = 0

    def one_dispatch():
        nonlocal attempted
        attempted += 1
        t0 = clock()
        with TraceAnnotation("bench.dispatch"):
            h = dispatch()
        dispatch_s.append(clock() - t0)
        pending.append(h)

    def one_finish():
        nonlocal failed
        with TraceAnnotation("bench.fetch"):
            v = finish(pending.popleft())
        if not math.isfinite(v):
            failed += 1
        return v

    for _ in range(lookahead):
        one_dispatch()
    while True:
        one_dispatch()
        v = one_finish()
        stamps.append(clock())
        losses.append(v)
        done = len(stamps) - 1
        if (steps is not None and done >= steps) or \
                (seconds is not None and stamps[-1] - stamps[0] >= seconds):
            break
    while pending:             # drain what is still in flight, unstamped
        losses.append(one_finish())
    return {"stamps": stamps, "losses": losses, "dispatch_s": dispatch_s,
            "attempted": attempted, "failed": failed}
