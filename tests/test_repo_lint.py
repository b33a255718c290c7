"""Runtime-contract repo linter (ISSUE 8 satellite; tier-1 CI).

The tree itself must be clean, seeded defects in a scratch tree must be
flagged, and docs/ENV.md must match the envcontract generator.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import repo_lint  # noqa: E402


def test_repo_is_clean():
    findings = repo_lint.run()
    assert findings == [], "\n".join(
        f"{k}:{p}:{l}: {m}" for k, p, l, m in findings)


def test_repo_lint_cli_exit_zero():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "repo_lint.py")],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "clean" in out.stdout


def test_seeded_racy_dict_flagged(tmp_path):
    bad = tmp_path / "racy.py"
    bad.write_text(textwrap.dedent("""
        _CACHE = {}

        def put(key, value):
            _CACHE[key] = value  # unlocked read-modify-write
    """))
    findings = repo_lint.run(str(tmp_path))
    assert any(k == "racy-dict" for k, _, _, _ in findings), findings


def test_locked_and_import_time_writes_pass(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text(textwrap.dedent("""
        import threading

        _CACHE = {}
        _lock = threading.Lock()
        _CACHE["seed"] = 1  # import time: fine

        def put(key, value):
            with _lock:
                _CACHE[key] = value
    """))
    findings = repo_lint.run(str(tmp_path))
    assert findings == [], findings


def test_seeded_undeclared_env_key_flagged(tmp_path):
    bad = tmp_path / "knob.py"
    bad.write_text(textwrap.dedent("""
        import os

        def read():
            return os.environ.get("PADDLE_TOTALLY_NEW_KNOB", "")
    """))
    findings = repo_lint.run(str(tmp_path))
    assert any(k == "undeclared-env" and "PADDLE_TOTALLY_NEW_KNOB" in m
               for k, _, _, m in findings), findings


def test_declared_env_keys_pass(tmp_path):
    ok = tmp_path / "knob.py"
    ok.write_text(textwrap.dedent("""
        import os

        def read():
            a = os.environ.get("PADDLE_TPU_MESH", "")
            b = os.environ.get("PADDLE_FAULT_WHATEVER_NEW", "")  # family
            return a, b
    """))
    findings = repo_lint.run(str(tmp_path))
    assert findings == [], findings


def test_seeded_bare_reference_flagged(tmp_path):
    call = "REF.loss_and_" + "grads(weights, feed, sizes)\n"  # not flagged
    (tmp_path / "test_new_decoder.py").write_text("loss, grads = " + call)
    (tmp_path / "decoder_reference.py").write_text("return " + call)
    assert [(k, p, l) for k, p, l, _ in
            repo_lint.check_bare_references(str(tmp_path))] == [
        ("bare-reference", "tests/test_new_decoder.py", 1)]


def test_env_md_matches_generator():
    from paddle_tpu.fluid import envcontract

    with open(os.path.join(REPO, "docs", "ENV.md")) as f:
        assert f.read().strip() == envcontract.generate_markdown().strip(), \
            "docs/ENV.md is stale: regenerate with " \
            "`python -m paddle_tpu.fluid.envcontract > docs/ENV.md`"


def test_envcontract_typed_reads(monkeypatch):
    from paddle_tpu.fluid import envcontract

    monkeypatch.setenv("PADDLE_TPU_SPD", "4")
    assert envcontract.get("PADDLE_TPU_SPD") == 4
    monkeypatch.setenv("PADDLE_TPU_DONATE", "off")
    assert envcontract.get("PADDLE_TPU_DONATE") is False
    monkeypatch.delenv("PADDLE_TPU_VERIFY", raising=False)
    assert envcontract.get("PADDLE_TPU_VERIFY") == "warn"
    monkeypatch.setenv("PADDLE_TPU_VERIFY", "STRICT")
    assert envcontract.get("PADDLE_TPU_VERIFY") == "strict"
    monkeypatch.setenv("PADDLE_TPU_VERIFY", "bogus")
    assert envcontract.get("PADDLE_TPU_VERIFY") == "warn"  # enum default
    try:
        envcontract.get("PADDLE_NOT_DECLARED")
        assert False, "undeclared read must raise"
    except KeyError:
        pass
    assert envcontract.declared("PADDLE_FAULT_ANYTHING_AT_ALL")
    assert not envcontract.declared("PADDLE_NOT_DECLARED")
