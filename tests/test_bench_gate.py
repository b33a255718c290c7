"""Tier-1 BENCH regression gate (ROADMAP item 2 / ISSUE 11 satellite).

``tools/bench_gate.py`` was opt-in since PR 9; this test promotes it to a
blocking tier-1 check: the two newest committed ``BENCH_r*.json`` rounds
are diffed and any shared headline metric that dropped by more than the
threshold FAILS the suite — a flat-regression round lands as a red test,
not silently.

Threshold: 20%.  The records it was tuned on were not from the attached
chip and were deleted (PR 23), so on the committed tree the gate finds
no rounds and reports ``skipped`` — the honest answer until the
benchmark of ROADMAP S1 writes new ones.  The gate itself is exercised
against synthetic rounds (clear regression → exit 1) so a
silently-broken gate cannot pass vacuously.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: tier-1 tolerated drop, percent — ratchet DOWN as BENCH stabilizes
TIER1_THRESHOLD_PCT = 20.0


def _run_gate(args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_gate.py"),
         "--json"] + args,
        capture_output=True, text=True, timeout=120, cwd=REPO)


def test_bench_gate_blocks_tier1():
    """The committed BENCH history must clear the tier-1 threshold: a
    future round regressing any shared metric past it fails the suite."""
    r = _run_gate(["--threshold", str(TIER1_THRESHOLD_PCT)])
    report = json.loads(r.stdout)
    assert r.returncode == 0, (
        f"BENCH regression past {TIER1_THRESHOLD_PCT}% between rounds "
        f"r{report.get('prev_round')} and r{report.get('cur_round')}: "
        f"{report.get('regressions')}")
    # the gate actually compared something (it is not passing vacuously
    # on an empty metric intersection)
    assert report.get("skipped") or report["compared"], report


def test_bench_gate_catches_seeded_regression(tmp_path):
    """A synthetic 50% throughput drop between rounds must exit 1 and
    name the regressed metric — the gate has teeth, not just wiring."""
    for n, value in ((1, 100.0), (2, 50.0)):
        tail = json.dumps({"metric": "m_train_cpu", "value": value})
        with open(tmp_path / f"BENCH_r{n:02d}.json", "w") as f:
            json.dump({"tail": tail}, f)
    r = _run_gate(["--dir", str(tmp_path), "--threshold", "25"])
    assert r.returncode == 1, r.stdout
    report = json.loads(r.stdout)
    assert report["regressions"][0]["metric"] == "m_train_cpu"
    # and an improvement passes
    with open(tmp_path / "BENCH_r03.json", "w") as f:
        json.dump({"tail": json.dumps(
            {"metric": "m_train_cpu", "value": 80.0})}, f)
    r2 = _run_gate(["--dir", str(tmp_path), "--threshold", "25"])
    assert r2.returncode == 0, r2.stdout
