"""bench.py output-channel and device contract.

The BENCH driver parses stdout: EVERY stdout line is a clean metric JSON
line (the last one the combined record) that names the device it ran on,
and anything else goes to stderr.  The bench runs on the CPU only when
the process was pinned there on purpose; it never concedes to it.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402  (repo-root module)


def test_bench_fails_without_accelerator_when_cpu_not_asked(monkeypatch,
                                                            capsys):
    """jax sees only the CPU and nobody pinned the process to it: the
    bench exits non-zero and prints no metric line."""
    import jax

    monkeypatch.setattr(sys, "argv", ["bench.py", "--model", "mnist"])
    old = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(SystemExit) as exc:
            bench.main()
    finally:
        jax.config.update("jax_platforms", old)
    assert exc.value.code not in (0, None)
    assert "no accelerator" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_bench_lines_name_the_device():
    line = bench.result_line("m", 1.0, "images/sec/chip", "mnist")
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": 8}


@pytest.mark.slow
def test_bench_stdout_every_line_parses(tmp_path):
    """Regression: run the real driver (tiny CPU mnist) and parse every
    stdout line as JSON — the driver's tail capture must never see a
    non-JSON or diagnostic line again."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "BENCH_MODEL": "mnist",
                "BENCH_MNIST_STEPS": "3", "BENCH_MNIST_BS": "16"})
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=420, cwd=str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    lines = [l for l in r.stdout.splitlines() if l.strip()]
    assert lines, "no stdout at all"
    parsed = [json.loads(l) for l in lines]  # every line must parse
    last = parsed[-1]
    assert last.get("metric", "").startswith("mnist")
    assert last.get("value", 0) > 0
    assert last["device"]["platform"] == "cpu"
