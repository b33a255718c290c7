"""tools/stallwatch.py over a stand-in checkout: a loop whose one slow step
sits in ``finish``, and nothing of jax."""

import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LOOP = """
import time


def run_window(dispatch, finish, seconds=None, lookahead=1):
    stamps = []
    for i in range(12):
        h = dispatch()
        finish((h, i))
        stamps.append(time.perf_counter())
    return {"stamps": stamps}
"""

RUN = """
import sys
import time

from chipbench import loop

slow = int(sys.argv[sys.argv.index("--slow") + 1])
loop.run_window(lambda: None, lambda h: None, seconds=0.01)   # a warm-up
window = loop.run_window(
    lambda: time.sleep(0.002),
    lambda h: time.sleep(0.3 if h[1] == slow else 0.02), seconds=34)
print("steps", len(window["stamps"]))
"""


def test_a_late_interval_is_split_by_phase_and_seen_by_both_watchers(tmp_path):
    bench = tmp_path / "chipbench"
    bench.mkdir()
    (bench / "__init__.py").write_text("")
    (bench / "loop.py").write_text(textwrap.dedent(LOOP))
    (bench / "run.py").write_text(textwrap.dedent(RUN))
    out = tmp_path / "watch.json"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "stallwatch.py"),
         "--out", str(out), "--root", str(tmp_path), "--", "--slow", "7"],
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    assert lines[0] == "steps 12"
    assert lines[1].startswith("stallwatch: 11 intervals") \
        and " 1 late;" in lines[1]
    late, = [json.loads(line[len("stallwatch LATE "):]) for line in lines
             if line.startswith("stallwatch LATE ")]
    record = json.loads(out.read_text())
    assert record["late"] == [late]
    # stamp 6 -> stamp 7: the slow step's dispatch and its finish
    assert late["step"] == 6 and 0.3 < late["seconds"] < 0.6
    took = {kind: seconds for kind, _, seconds in late["phases"]}
    assert took["finish"] > 0.29 > 0.05 > took["dispatch"]
    # the main thread slept in finish; the sampler saw it there and was
    # not stopped itself; nothing was collected
    assert late["sampler_ticks"] >= 5
    assert "stallwatch.py" in late["stacks"][0][0]
    assert late["collections"] == []
    # the heartbeat process is gone with the run
    assert os.path.exists(str(out) + ".heartbeat")
    running = subprocess.run(["ps", "-eo", "args"], capture_output=True,
                             text=True).stdout
    assert str(out) + ".heartbeat" not in running
