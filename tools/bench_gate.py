"""Bench regression gate: newest BENCH_*.json vs the previous round.

The BENCH trajectory (BENCH_r01.json, BENCH_r02.json, ...) records each
round's headline throughputs; this tool diffs the two newest rounds and
exits non-zero when any shared metric regressed by more than
``--threshold`` percent.  TIER-1 (ISSUE 11, ROADMAP item 2):
``tests/test_bench_gate.py`` runs it as a blocking test at 20%, so a
flat-regression round fails a PR instead of landing silently; with
fewer than two rounds in the directory (the committed tree today) it
reports ``skipped`` and passes.  Tighter thresholds remain available
for pre-merge hooks and by-hand runs.  Every BENCH line since
ISSUE 12 also records the active kernel config (``flash``/``fused``), so
a gate trip is attributable to the kernel change that caused it.

Metric extraction: every line of a round's ``tail`` that parses as JSON
with ``metric``/``value`` keys contributes (the per-model lines AND the
combined final line; later lines win on duplicate metric names), plus
the ``parsed`` dict as a fallback for single-line rounds.  Error lines
(``value == 0`` with an ``error`` field) are skipped on BOTH sides, so a
model that crashed in one round neither gates nor masks.

Usage::

    python tools/bench_gate.py [--dir .] [--threshold 25] [--json]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def find_rounds(dir_path: str):
    """[(round_number, path)] sorted ascending."""
    out = []
    for path in glob.glob(os.path.join(dir_path, "BENCH_r*.json")):
        m = _ROUND_RE.search(os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def extract_metrics(path: str) -> dict:
    """{metric_name: value} from one BENCH round file."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    metrics = {}

    def _take(rec):
        if not isinstance(rec, dict):
            return
        name, value = rec.get("metric"), rec.get("value")
        if not name or not isinstance(value, (int, float)):
            return
        if rec.get("error") or value <= 0:
            return  # crashed/degenerate lines neither gate nor mask
        metrics[name] = float(value)
        # the combined final line carries the transformer number inline
        tm, tv = rec.get("transformer_metric"), \
            rec.get("transformer_tokens_per_sec_chip")
        if tm and isinstance(tv, (int, float)) and tv > 0:
            metrics[tm] = float(tv)

    for line in (doc.get("tail") or "").splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            _take(json.loads(line))
        except ValueError:
            continue
    _take(doc.get("parsed"))
    return metrics


def compare(prev: dict, cur: dict, threshold_pct: float) -> dict:
    """Diff two metric dicts; a regression is a drop > threshold_pct."""
    rows = []
    regressions = []
    for name in sorted(set(prev) & set(cur)):
        p, c = prev[name], cur[name]
        change_pct = (c - p) / p * 100.0 if p else 0.0
        row = {"metric": name, "prev": p, "cur": c,
               "change_pct": round(change_pct, 2)}
        rows.append(row)
        if change_pct < -threshold_pct:
            regressions.append(row)
    return {"compared": rows, "regressions": regressions,
            "only_prev": sorted(set(prev) - set(cur)),
            "only_cur": sorted(set(cur) - set(prev))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Gate on BENCH_*.json regressions (newest vs "
                    "previous round).")
    ap.add_argument("--dir", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="where the BENCH files live")
    ap.add_argument("--threshold", type=float, default=25.0,
                    help="max tolerated drop, percent (default 25)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report only")
    args = ap.parse_args(argv)

    rounds = find_rounds(args.dir)
    if len(rounds) < 2:
        print(json.dumps({"ok": True, "skipped": True,
                          "note": f"need 2+ BENCH rounds under "
                                  f"{args.dir}, found {len(rounds)}"}))
        return 0
    (n_prev, p_prev), (n_cur, p_cur) = rounds[-2], rounds[-1]
    prev, cur = extract_metrics(p_prev), extract_metrics(p_cur)
    result = compare(prev, cur, args.threshold)
    ok = not result["regressions"]
    report = {"ok": ok, "prev_round": n_prev, "cur_round": n_cur,
              "threshold_pct": args.threshold, **result}
    if args.json:
        print(json.dumps(report))
    else:
        print(json.dumps(report, indent=1))
        for r in result["regressions"]:
            print(f"REGRESSION {r['metric']}: {r['prev']} -> {r['cur']} "
                  f"({r['change_pct']}%)", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
