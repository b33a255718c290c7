"""The readers of the program's own spans (``chipbench/program_spans.py``)
and the ten per-layer metrics of PR 27, on a hand-made ring, on hand-made
device events and on a recording; and both cells' rehearsals printing
``relowerings``.  CPU only."""

import collections
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import program_spans as ps  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.plugins import load  # noqa: E402

Sp = collections.namedtuple("Sp", "name t0 t1 span_id parent_id tid step")
MS = 1e-3


def root_with(rid, t0, children, step=0):
    """One ``fluid.run`` root and its children laid end to end from t0:
    children is [(name, ms, [(grandchild name, ms), ...]), ...]."""
    out, t = [], t0
    for i, (name, ms, grand) in enumerate(children):
        cid = f"{rid}.{i}"
        g0 = t
        for j, (gname, gms) in enumerate(grand):
            out.append(Sp(gname, g0, g0 + gms * MS, f"{cid}.{j}", cid, 0,
                          step))
            g0 += gms * MS
        out.append(Sp("fluid.run." + name, t, t + ms * MS, cid, rid, 0,
                      step))
        t += ms * MS
    out.append(Sp("fluid.run", t0, t + 0.25 * MS, rid, None, 0, step))
    return out


def hand_made_ring():
    ring = []
    # set-up, before the window opens at t = 100: a first call that traces,
    # lowers and compiles, an eager operation inside the trace (counted
    # once: a union), and a second call that lowers again
    ring += [Sp("fluid.compile.trace", 10.0, 12.0, "c1", "x", 0, None),
             Sp("fluid.compile.trace", 10.5, 11.0, "c1e", "x", 0, None),
             Sp("fluid.compile.lower", 12.0, 20.0, "c2", "x", 0, None),
             Sp("fluid.compile.backend", 20.0, 23.0, "c3", "x", 0, None),
             Sp("fluid.compile.lower", 30.0, 38.0, "c4", "y", 0, None),
             Sp("fluid.compile.backend", 38.0, 39.5, "c5", "y", 0, None)]
    ring += root_with("warm", 50.0, [("feed", 9, []), ("call", 9, [])])
    # three roots in the window; medians are the middle one's
    for k, scale in enumerate((1.0, 2.0, 3.0)):
        ring += root_with(f"r{k}", 100.0 + k, [
            ("feed", 0.1 * scale, []),
            ("lookup", 2.0 * scale, [("fluid.run.build", 1.5 * scale)]),
            ("feed", 0.4 * scale, []),            # ParallelExecutor's second
            ("state", 1.0 * scale, []),
            ("call", 10.0 * scale, [("fluid.compile.lower", 6.0 * scale),
                                    ("fluid.compile.backend", 1.0 * scale)]),
            ("commit", 0.5 * scale, []),
            ("observe", 0.7 * scale, []),
            ("fetch", 5.0 * scale, [])], step=k)
    # a compile inside the window is no set-up
    ring.append(Sp("fluid.compile.lower", 101.0, 101.006, "c6", "z", 0, 1))
    ring += root_with("after", 200.0, [("feed", 9, []), ("call", 9, [])])
    return ring


RUN = {"workload": "hand.made", "stamps": [100.0, 101.0, 102.5],
       "dispatch_s": [0.0295, 0.0297, 0.0299],
       "times": {"startup_s": 5.0, "reference_check_s": 20.0,
                 "first_call_s": 12.0, "warmup_s": 8.0}}

EXPECTED = {  # the middle root (scale 2): self times
    "run_feed_ms": 2 * (0.1 + 0.4), "run_lookup_ms": 2 * 0.5,
    "run_state_ms": 2 * 1.0, "run_call_ms": 2 * 3.0,
    "run_commit_ms": 2 * 0.5, "run_observe_ms": 2 * 0.7,
    "lowering_s": 2.0 + 8.0 + 8.0, "backend_compile_s": 3.0 + 1.5}


def test_split_of_takes_self_times_of_the_roots_that_begin_in_the_window():
    split = ps.split_of(hand_made_ring(), 100.0, 102.5)
    assert split["roots"] == 3
    for c in ps.CHILDREN:
        assert split[c + "_ms"] == pytest.approx(EXPECTED[f"run_{c}_ms"])
    assert split["other_children_ms"] == pytest.approx(10.0)   # the fetch
    assert split["root_ms"] == pytest.approx(2 * 19.7 + 0.25)
    assert ps.split_of(hand_made_ring(), 300.0, 400.0) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_layer_metric_on_a_hand_made_ring(name, monkeypatch, capsys):
    monkeypatch.setattr(ps, "ring", hand_made_ring)
    assert load("layer_metrics", name).value(dict(RUN)) == \
        pytest.approx(EXPECTED[name])
    line = capsys.readouterr().out
    if name.startswith("run_"):
        # printed once per run, with the root and the reading from outside
        assert "run() host split" in line and "root 39.6500" in line
        assert "host_dispatch_ms) 29.7000" in line
        assert "longest interval: 1500.000 ms, of which 99.000 ms" in line
    else:
        assert "fluid.compile.lower x2 16.000 s" in line
        # the comparison's seconds are no set-up: it runs after the window
        assert "startup + first_call + warmup 25.000 s" in line


def test_longest_interval_says_how_much_of_it_was_inside_run():
    # stamps 100, 101, 102.5: the longest is 101 .. 102.5, and holds the
    # root r1 from 101 on (its 39.65 ms) and all of r2 (3 x 19.7 + 0.25 ms)
    a, b, inside = ps.longest_interval(hand_made_ring(), RUN["stamps"])
    assert (a, b) == (101.0, 102.5)
    assert inside == pytest.approx((39.65 + 59.35) * MS)


def test_relowerings_reads_the_programs_counter(monkeypatch, capsys):
    from paddle_tpu import observe

    monkeypatch.setattr(ps, "ring", hand_made_ring)
    mod = load("layer_metrics", "relowerings")
    assert mod.value(dict(RUN)) == 0.0
    observe.registry().inc("executor.relowerings")
    observe.registry().inc("compile.lowerings", 3)
    assert mod.value(dict(RUN)) == 1.0
    out = capsys.readouterr().out.splitlines()[-1]
    assert "5 fluid.run roots (3 begin in the window)" in out
    assert "lowerings 3" in out and "relowerings 1" in out


NEW = ["run_feed_ms", "run_lookup_ms", "run_state_ms", "run_call_ms",
       "run_commit_ms", "run_observe_ms", "idle_under_run_pct",
       "lowering_s", "backend_compile_s", "relowerings"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_leaves_the_metric_out(name, monkeypatch):
    """The parent commit has no ring and writes no ``fluid.*`` span: each
    reader returns None there and does not raise."""
    monkeypatch.setattr(ps, "ring", lambda: None)
    monkeypatch.setattr(ps, "newest_trace", lambda workload: None)
    run = dict(RUN, trace={"busy_s": 1.0, "window_s": 1.0})
    assert load("layer_metrics", name).value(run) is None


def test_the_ring_reader_finds_the_programs_ring():
    from paddle_tpu.observe import trace

    with trace.span("fluid.run"):
        pass
    assert [s.name for s in ps.ring()] == ["fluid.run"]


def test_entries_are_the_ten_and_read_from_program_spans():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    got = {m["name"]: m for m in bench["per_layer"]}
    # in their order, wherever later entries were appended after them
    assert [m["name"] for m in bench["per_layer"] if m["name"] in NEW] == NEW
    for name in NEW:
        assert got[name]["better"] == "lower"
        assert "workloads" not in got[name]
        src = open(os.path.join(ROOT, "chipbench", "layer_metrics",
                                name + ".py")).read()
        assert "program_spans." in src
    assert {got[n]["moves"] for n in NEW[:7]} == {"step_ms_p95"}
    assert {got[n]["moves"] for n in NEW[7:]} == {"setup_s"}


# -- the trace reader on hand-made events --------------------------------

def ev(start, dur, name="op"):
    return tr.Event(name, float(start), float(dur))


def span(name, start, end):
    return ps.HostSpan(name, float(start), float(end), "python3")


HOST = sorted([
    span("bench.dispatch", 0, 1000), span("fluid.run", 100, 900),
    span("fluid.run.feed", 110, 200), span("fluid.run.call", 300, 700),
    span("bench.fetch", 1000, 5000),
    span("bench.dispatch", 5000, 6000), span("fluid.run", 5100, 5900),
    span("fluid.run.observe", 5500, 5800)],
    key=lambda s: (s.start_ns, -s.end_ns))


def test_innermost_span_is_the_latest_to_have_started():
    got = ps.innermost_at(HOST, [50, 150, 250, 400, 950, 3000, 5600, 7000])
    assert got == [("bench.dispatch", False), ("fluid.run.feed", True),
                   ("fluid.run", True), ("fluid.run.call", True),
                   ("bench.dispatch", False), ("bench.fetch", False),
                   ("fluid.run.observe", True), ("host:none", False)]


def test_gaps_are_those_idle_gaps_finds():
    events = [ev(0, 100), ev(50, 100), ev(200, 100), ev(300, 50),
              ev(500, 10)]
    assert ps.gaps_of(events) == [(175.0, 50.0), (425.0, 150.0)]
    assert sum(g for _, g in ps.gaps_of(events)) / 1e9 == pytest.approx(
        sum(s for _, s in tr.idle_gaps(events, [])))


def test_idle_under_run_is_the_worst_devices_share():
    quiet = [ev(0, 3000), ev(3000, 4000)]                       # no gap
    # gaps: 380..420 (mid 400, under fluid.run.call), 2000..4000 (mid 3000,
    # under bench.fetch), 5590..5610 (mid 5600, under fluid.run.observe)
    gappy = [ev(0, 380), ev(420, 1580), ev(4000, 1590), ev(5610, 1390)]
    got = ps.idle_by_span({"/device:TPU:0": quiet, "/device:TPU:1": gappy},
                          HOST)
    assert got["device"] == "/device:TPU:1"
    assert got["window_s"] == pytest.approx(7000e-9)
    assert got["under_run_s"] == pytest.approx(60e-9)
    assert got["pct"] == pytest.approx(100 * 60 / 7000)
    assert got["by_span"] == [("bench.fetch", pytest.approx(2000e-9)),
                              ("fluid.run.call", pytest.approx(40e-9)),
                              ("fluid.run.observe", pytest.approx(20e-9))]


def test_clock_check_orders_call_first_device_event_and_fetch():
    assert ps.clock_check([350.0, 400.0], HOST)["holds"]
    assert not ps.clock_check([250.0, 400.0], HOST)["holds"]   # before call
    assert not ps.clock_check([5200.0], HOST)["holds"]         # after fetch
    assert ps.clock_check([350.0], [s for s in HOST
                                    if s.name != "bench.fetch"]) is None


# -- a recording ----------------------------------------------------------

RECORDED = os.path.join(ROOT, "chipbench", "testdata",
                        "run_spans_two_steps.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return ps.read_trace(RECORDED)


# The first two steps of the traced stretch of
# ``transformer_base_wmt.resident`` on the v5e (my chip run, PR 27), cut
# with the xplane proto to the host spans of the program, the benchmark
# and the runtime's launch, and to the device operations of the first 3 ms
# after the drain and of 1.5 ms either side of the two step boundaries.

def test_recording_is_small_and_has_the_programs_spans(recorded):
    assert os.path.getsize(RECORDED) < 500_000
    devices, host, clock = recorded
    assert list(devices) == ["/device:TPU:0"]
    names = {s.name for s in host}
    assert {"fluid.run", "bench.dispatch", "bench.fetch"} <= names
    assert {"fluid.run." + c for c in ps.CHILDREN} <= names


def test_recording_shows_the_devices_clock_behind_the_hosts(recorded):
    """As numbers the two bases of ``trace_reduce.from_profile`` agree to
    under a nanosecond; as clocks they do not: unmoved, the first device
    event after the drain precedes the opening of the call that launched
    it.  ``read_trace`` moves the device events to follow the runtime's
    enqueue, and the fetch still closes after its step."""
    devices, host, clock = recorded
    dev = "/device:TPU:0"
    assert abs(clock["number_offset_ns"][dev]) < 1.0
    assert clock["anchor"] == "DoEnqueueProgram"
    # from the first step's module to the runtime's enqueue of it
    skew = clock["skew_ns"][dev]
    assert skew == pytest.approx(56667318 - 54990768.75, abs=2.0)
    call = next(s for s in host if s.name == "fluid.run.call")
    first = devices[dev][0].start_ns
    assert first - skew < call.start_ns < first      # before / after the move
    raw = tr.read(RECORDED).devices[dev]
    assert raw[0].start_ns == pytest.approx(first - skew, abs=1.0)


def test_plane_clock_takes_the_first_step_not_a_small_program_before_it():
    Stat = collections.namedtuple("Ev", "start_ns duration_ns stats name")
    Line = collections.namedtuple("Line", "name events")
    Plane = collections.namedtuple("Plane", "name lines")

    def module(start_us, dur_us):
        return Stat(0.0, 0.0, [("device_offset_ps", start_us * 1e6),
                               ("device_duration_ps", dur_us * 1e6)], "m")

    ops = [Stat(1058.5, 1.0, [("device_offset_ps", 1e6)], "op")]
    plane = Plane("/device:TPU:0", [
        Line("XLA Ops", ops),
        Line("XLA Modules", [module(1, 40), module(70000, 150000),
                             module(230000, 150000)])])
    # start_ns lies 58.5 ns ahead of the device base on this plane, and
    # the 40 us program (a feed's re-sharding) is not the step
    assert ps.plane_clock(plane) == (58.5, 70000e3)
    assert ps.plane_clock(Plane("/device:TPU:0", [Line("XLA Ops", ops)])) \
        == (58.5, None)


def test_device_skew_takes_the_latest_launch_anchor_there_is():
    launches = {"PJRT_LoadedExecutable_Execute": 700.0,
                "DoEnqueueProgram": 1600.0}
    assert ps.device_skew_ns(100.0, launches, 50.0) == (
        1500.0, "DoEnqueueProgram")
    assert ps.device_skew_ns(100.0, {"tpu::System::Execute": 900.0},
                             50.0) == (800.0, "tpu::System::Execute")
    # no event of the runtime in the trace: the call's own opening
    assert ps.device_skew_ns(100.0, {}, 150.0) == (50.0, "fluid.run.call")
    # the order already holds: nothing is moved
    assert ps.device_skew_ns(2000.0, launches, 50.0) == (
        0.0, "DoEnqueueProgram")
    assert ps.device_skew_ns(100.0, {}, None) == (0.0, None)


def test_every_bench_dispatch_of_the_recording_holds_one_root(recorded):
    _, host, _ = recorded
    roots = [s for s in host if s.name == "fluid.run"]
    dispatches = [s for s in host if s.name == "bench.dispatch"]
    assert len(dispatches) >= 2
    for d in dispatches:
        inside = [r for r in roots
                  if d.start_ns <= r.start_ns and r.end_ns <= d.end_ns]
        assert len(inside) == 1, (d, inside)
        kids = [s.name for s in host
                if s.name.startswith("fluid.run.")
                and s.name.count(".") == 2
                and inside[0].start_ns <= s.start_ns
                and s.end_ns <= inside[0].end_ns]
        assert kids == ["fluid.run." + c for c in ps.CHILDREN]


def test_recording_names_each_gap_by_the_innermost_span(recorded):
    devices, host, clock = recorded
    got = ps.idle_by_span(devices, host)
    events = devices[got["device"]]
    gaps = ps.gaps_of(events)
    # the same idle seconds as trace_reduce finds, named more finely
    assert sum(s for _, s in got["by_span"]) == pytest.approx(
        sum(g for _, g in gaps) / 1e9)
    roots = [s for s in host if s.name == "fluid.run"]
    under = sum(g for m, g in gaps
                if any(r.start_ns <= m <= r.end_ns for r in roots))
    assert got["under_run_s"] == pytest.approx(under / 1e9)
    assert got["pct"] == pytest.approx(
        100 * under / 1e9 / tr.busy_and_window(events)[1])
    assert all(n.startswith(("fluid.", "bench.", "host:none"))
               for n, _ in got["by_span"])
    assert ps.clock_check(list(clock["step_start_ns"].values()),
                          host)["holds"]


# -- both cells, rehearsed -------------------------------------------------

def rehearse(tmp_path, workload, seed):
    """The one command, rehearsed from a copy of the benchmark's files
    beside the program: a rehearsal writes its trace under its checkout's
    ``.cache/``, and ``test_harness.py`` rehearses the same cell from the
    repository itself, on another worker at the same time."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "paddle_tpu"),
               os.path.join(root, "paddle_tpu"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    return lines, json.loads(lines[-1])


@pytest.mark.parametrize("workload,expected", [
    # the startup program's arrays are not committed to a device and the
    # first step's are, so the SECOND call lowers the step again (in the
    # Transformer the RNG key too, in the same call).  Until PR 57 the
    # comparison's step ran first and took that on itself: ResNet read 0.
    ("transformer_base_wmt.resident", 1.0),
    ("resnet50_imagenet.resident", 1.0)])
def test_rehearsal_prints_relowerings(tmp_path, workload, expected):
    lines, last = rehearse(tmp_path, workload, seed=2147483777)
    assert last["correct"]
    assert last["metrics"]["relowerings"] == {"value": expected,
                                              "unit": "count"}
    # a CPU gives counts only: none of the nine timed metrics is printed
    assert not set(last["metrics"]) & set(NEW[:9])
    (counts,) = [l for l in lines if l.startswith("program spans:")]
    assert f"relowerings {int(expected)}" in counts
