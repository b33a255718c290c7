"""The control of the comparison that decides ``correct``, at a size a test
run can hold: the plain reference with float8 (e4m3) contraction inputs,
put in the program's place, has to come out NOT correct under the limits
the configuration's file states, and well above what the program reads."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import check  # noqa: E402


CONFIGS = {"transformer_base_wmt": "1,2147489999", "resnet50_imagenet": "1"}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def readings(request):
    """(limits, program rows, control rows) of one configuration."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "check_seeds.py"),
         "--workload", request.param + ".resident", "--rehearse",
         "--seeds", CONFIGS[request.param], "--control-seeds", "1,2,3"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    rows = [json.loads(l) for l in p.stdout.splitlines()
            if l.startswith("{")]
    path = os.path.join(ROOT, "chipbench", "configs", request.param,
                        "config.json")
    sizes = json.load(open(path))
    # a rehearsal reads the configuration's ``tiny`` sizes over the others,
    # its limits among them where the tiny size needs its own
    return ({**sizes, **sizes["tiny"]}["limits"],
            [r for r in rows if r["kind"] == "program"],
            [r for r in rows if r["kind"] == "control_fp8"])


def test_the_control_is_not_correct(readings):
    limits, _, control = readings
    assert len(control) == 3
    for row in control:
        assert check.decide(row, limits) is False, row


def test_the_program_is_correct_at_the_rehearsal_size(readings):
    limits, program, _ = readings
    assert program
    for row in program:
        assert check.decide(row, limits) is True, row


def test_the_control_reads_well_above_the_program(readings):
    _, program, control = readings
    assert program
    worst = max(r["grad_rel"] for r in program)
    assert min(r["grad_rel"] for r in control) > 3 * worst


def test_a_number_without_a_limit_is_not_correct():
    row = {"grad_rel": 0.0, "update_rel": 0.0}
    assert check.decide(row, {"grad_rel": 1.0}) is False
    assert check.decide(row, dict.fromkeys(row, 1e-9)) is True
    assert check.decide(dict(row, grad_rel=float("nan")),
                        dict.fromkeys(row, 1.0)) is False
