"""What the program says of its own device values: ``observe.step_gauges``.

Since PR 54 an op's lowering can publish a value computed ON the device
inside the step (``paddle_tpu.observe.step_gauge``); it leaves the step in one
small extra output that the dispatch keeps unread, and
``observe.step_gauges(wait=True)`` returns what has been materialised, oldest
first, as ``(step, span_id, t, {rendered name: value})`` with ``t`` the
``time.perf_counter`` at the step's call: the clock of ``run["stamps"]``.
This file is the one reader of it, as ``program_spans.py`` is of the ring;
the three ``layer_metrics/moe_*`` files added with it are one call each into
it.  Where the program has no such function (a commit before PR 54) or no
routed layer published anything, every reader returns None and the metric is
left out of the line.

The first publisher is the routed expert layer (``parallel/moe.py``), a layer
and step, labelled with the op's ``fluid.name_scope`` path as ``scope``:

- ``ops.moe.live_rows``: the assignments that chose an expert held here;
- ``ops.moe.rows``: the ``N * top_k`` rows the layer walks;
- ``ops.moe.fullest_group``: the assignments of the fullest held expert.

``load(run)`` takes the entries whose ``t`` lies in the unprofiled window
(``stamps[0]`` .. ``stamps[-1]``, the rule of ``program_spans.run_split``),
makes the one call, and prints one line a cell.
"""

from __future__ import annotations

import re
import statistics
import time
from typing import Dict, List, Optional

LIVE, ROWS, FULLEST = ("ops.moe.live_rows", "ops.moe.rows",
                       "ops.moe.fullest_group")
_RENDERED = re.compile(r'^([^{]+)(?:\{(.*)\})?$')
_LABEL = re.compile(r'([^=,]+)="([^"]*)"')


def entries(since: Optional[float] = None):
    """The program's materialised step gauges, those still in flight waited
    for; None where the program has no such reader."""
    try:
        from paddle_tpu import observe

        read = observe.step_gauges
    except (ImportError, AttributeError):
        return None
    return read(since=since, wait=True)


def split(rendered: str):
    """``(name, {label: value})`` of a rendered gauge name."""
    name, inner = _RENDERED.match(rendered).groups()
    return name, dict(_LABEL.findall(inner or ""))


def by_scope(values: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """One step's ``ops.moe.*`` gauges as ``{scope: {name: value}}``."""
    out: Dict[str, Dict[str, float]] = {}
    for rendered, v in values.items():
        name, labels = split(rendered)
        if name in (LIVE, ROWS, FULLEST):
            scope = labels.get("scope", "") + (
                "#" + labels["call"] if "call" in labels else "")
            out.setdefault(scope, {})[name] = v
    return out


def window_of(found, t_open: float, t_close: float) -> Optional[dict]:
    """The routed layers' load over the steps whose call lies in [t_open,
    t_close]: per step the share of walked rows that is work, and per scope
    the live rows at the first step, their median and at the last, the rows
    walked and the fullest group at the middle step.  None when no step of
    the window has an ``ops.moe.rows`` gauge."""
    steps = [by_scope(e[3]) for e in found if t_open <= e[2] <= t_close]
    steps = [s for s in steps if any(ROWS in g for g in s.values())]
    if not steps:
        return None
    shares = []
    for s in steps:
        rows = sum(g.get(ROWS, 0.0) for g in s.values())
        shares.append(sum(g.get(LIVE, 0.0) for g in s.values()) / rows)
    scopes: Dict[str, dict] = {}
    for scope in sorted({k for s in steps for k in s}):
        live = [s[scope].get(LIVE, 0.0) for s in steps if scope in s]
        middle = steps[len(steps) // 2].get(scope, {})
        scopes[scope] = {"first": live[0],
                         "median": statistics.median(live),
                         "last": live[-1],
                         "rows": middle.get(ROWS),
                         "fullest": middle.get(FULLEST)}
    return {"steps": len(steps), "shares": shares, "scopes": scopes}


def load(run) -> Optional[dict]:
    """The window of this run, made and printed once: kept in the run's own
    record, which three metrics read in turn."""
    if "step_gauges" in run:
        return run["step_gauges"]
    t0 = time.perf_counter()
    found = entries(since=run["stamps"][0])
    took = time.perf_counter() - t0
    got = run["step_gauges"] = None if not found else window_of(
        found, run["stamps"][0], run["stamps"][-1])
    if got is not None:
        print(line(got) + f"; step_gauges(wait=True) took {1e3 * took:.3f} "
              f"ms for {len(found)} entries", flush=True)
    return got


def line(got: dict) -> str:
    shares = got["shares"]
    return (f"expert layer load over {got['steps']} steps of the window: "
            f"live share first {100 * shares[0]:.3f}% median "
            f"{100 * statistics.median(shares):.3f}% last "
            f"{100 * shares[-1]:.3f}% (min {100 * min(shares):.3f}% max "
            f"{100 * max(shares):.3f}%); per scope live rows first/median/"
            "last of rows walked, fullest group at the middle step: "
            + ", ".join(
                f"{scope or '(none)'} {g['first']:.0f}/{g['median']:.0f}/"
                f"{g['last']:.0f} of {_num(g['rows'])}, fullest "
                f"{_num(g['fullest'])}"
                for scope, g in got["scopes"].items()))


def _num(v) -> str:
    return "?" if v is None else f"{v:.0f}"


def live_rows_pct(run) -> Optional[float]:
    got = load(run)
    return None if got is None else 100.0 * statistics.median(got["shares"])


def live_rows_range_pct(run) -> Optional[float]:
    got = load(run)
    return None if got is None else \
        100.0 * (max(got["shares"]) - min(got["shares"]))


def gauged_layers(run) -> Optional[float]:
    got = load(run)
    return None if got is None else float(len(got["scopes"]))
