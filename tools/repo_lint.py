#!/usr/bin/env python
"""Runtime-contract repo linter (tier-1 CI; tests/test_repo_lint.py).

AST-walks ``paddle_tpu/`` and fails on two defect classes this codebase
has actually shipped, plus doc drift:

 1. **racy-dict** — a subscript/augmented write to a module-level (or
    class-level) mutable dict from function scope with no enclosing
    ``with <...lock...>:`` block.  This is the PR 5 profiler-race class:
    unlocked read-modify-write on shared module state drops updates under
    serving/guardian/trainer concurrency.  Import-time writes (module or
    class body, decorator-driven registries called during import) are
    exempt; reviewed exceptions live in ``ALLOWLIST`` with justification.

 2. **undeclared-env** — any ``PADDLE_*`` string literal (env knob name)
    not declared in ``paddle_tpu/fluid/envcontract.py``.  Every knob must
    be declared (name/type/default/subsystem) so docs/ENV.md and the
    verifier's env contract stay exhaustive.

 3. **env-doc-drift** — ``docs/ENV.md`` differs from the generator
    output (``python -m paddle_tpu.fluid.envcontract``).

 4. **bare-reference** — a file under ``tests/`` (outside
    ``tests/chipbench/``, the benchmark's) calls a configuration's
    ``loss_and_grads(`` itself.  ``tests/decoder_reference.py`` is the one
    place that does, under ``jax.jit`` and once for each (reference, sizes,
    operands): called bare, a reference dispatches primitive by primitive
    and compiles each, which is how tier-1 outgrew its time limit (PR 63;
    docs/COVERAGE.md).

Exit 0 = clean, 1 = findings (printed one per line as
``<class>:<file>:<line>: <message>``).
"""

from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from typing import Dict, List, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ENV_KEY_RE = re.compile(r"^PADDLE_[A-Z0-9_]*$")

#: (path relative to repo, dict name) -> justification.  Reviewed
#: exceptions ONLY; a new unlocked write needs a lock or an entry here.
ALLOWLIST: Dict[Tuple[str, str], str] = {
    ("paddle_tpu/fluid/layers/io.py", "_READERS"):
        "reader registration happens on the build thread before any "
        "consumer starts; readers are keyed by unique var name",
    ("paddle_tpu/ops/registry.py", "REGISTRY"):
        "op registration is import-time only (ops/__init__ imports every "
        "module once under the import lock)",
    ("paddle_tpu/ops/registry.py", "INFER_REGISTRY"):
        "same import-time registration as REGISTRY",
    ("paddle_tpu/fluid/ir.py", "_passes"):
        "pass registration is decorator-driven at import time",
    ("paddle_tpu/fluid/envcontract.py", "REGISTRY"):
        "knob declaration is module-body-driven at import time",
    ("paddle_tpu/fluid/amp.py", "_state"):
        "execution-mode toggles are set during single-threaded model "
        "build (enable/disable), read-only during traced execution",
    ("paddle_tpu/fluid/core.py", "GLOBAL_FLAGS"):
        "init_gflags runs at process startup before any worker thread",
}


class _FileLint(ast.NodeVisitor):
    def __init__(self, relpath: str, tree: ast.Module):
        self.relpath = relpath
        self.findings: List[Tuple[str, int, str]] = []
        # module-level and class-level names bound to mutable dicts
        self.dicts: Set[str] = set()
        for node in tree.body:
            self._collect_dicts(node, self.dicts)
            if isinstance(node, ast.ClassDef):
                for sub in node.body:
                    self._collect_dicts(sub, self.dicts)
        self._func_depth = 0
        self._with_lock_depth = 0

    @staticmethod
    def _collect_dicts(node, out: Set[str]) -> None:
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            return
        value = node.value
        is_dict = isinstance(value, ast.Dict) or (
            isinstance(value, ast.Call)
            and isinstance(value.func, ast.Name)
            and value.func.id in ("dict", "OrderedDict", "defaultdict"))
        if not is_dict:
            return
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for t in targets:
            if isinstance(t, ast.Name):
                out.add(t.id)

    # -- lock / function scope tracking --
    @staticmethod
    def _mentions_lock(expr: ast.expr) -> bool:
        for sub in ast.walk(expr):
            name = None
            if isinstance(sub, ast.Name):
                name = sub.id
            elif isinstance(sub, ast.Attribute):
                name = sub.attr
            if name and "lock" in name.lower():
                return True
        return False

    def visit_With(self, node: ast.With):
        locked = any(self._mentions_lock(item.context_expr)
                     for item in node.items)
        if locked:
            self._with_lock_depth += 1
        self.generic_visit(node)
        if locked:
            self._with_lock_depth -= 1

    def visit_FunctionDef(self, node):
        self._func_depth += 1
        self.generic_visit(node)
        self._func_depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- check 1: racy dict writes --
    def _dict_name(self, target) -> str:
        """The shared-dict name a subscript write hits, or ''."""
        if not isinstance(target, ast.Subscript):
            return ""
        base = target.value
        if isinstance(base, ast.Name) and base.id in self.dicts:
            return base.id
        if isinstance(base, ast.Attribute) and base.attr in self.dicts:
            return base.attr
        return ""

    def _check_write(self, node, target) -> None:
        name = self._dict_name(target)
        if not name:
            return
        if self._func_depth == 0 or self._with_lock_depth > 0:
            return  # import-time or lock-protected
        if (self.relpath, name) in ALLOWLIST:
            return
        self.findings.append((
            "racy-dict", node.lineno,
            f"unlocked write to shared module dict '{name}' from function "
            f"scope — hold a lock (with <..lock..>:) or add a reviewed "
            f"ALLOWLIST entry in tools/repo_lint.py"))

    def visit_Assign(self, node: ast.Assign):
        for t in node.targets:
            self._check_write(node, t)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        self._check_write(node, node.target)
        self.generic_visit(node)

    # -- check 2: undeclared PADDLE_* env keys --
    def visit_Constant(self, node: ast.Constant):
        if isinstance(node.value, str) and _ENV_KEY_RE.match(node.value):
            self.findings.append(("env-key", node.lineno, node.value))
        self.generic_visit(node)


def lint_file(path: str, declared) -> List[Tuple[str, str, int, str]]:
    relpath = os.path.relpath(path, REPO)
    with open(path, "r") as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError as e:
            return [("syntax", relpath, e.lineno or 0, str(e))]
    v = _FileLint(relpath, tree)
    v.visit(tree)
    out = []
    for kind, lineno, msg in v.findings:
        if kind == "env-key":
            if relpath.endswith("fluid/envcontract.py") or declared(msg):
                continue
            out.append((
                "undeclared-env", relpath, lineno,
                f"env knob {msg!r} is not declared in "
                f"paddle_tpu/fluid/envcontract.py — declare it (name, "
                f"type, default, subsystem) so docs/ENV.md stays "
                f"exhaustive"))
        else:
            out.append((kind, relpath, lineno, msg))
    return out


def check_env_doc() -> List[Tuple[str, str, int, str]]:
    from paddle_tpu.fluid import envcontract

    path = os.path.join(REPO, "docs", "ENV.md")
    want = envcontract.generate_markdown().strip()
    try:
        with open(path) as f:
            have = f.read().strip()
    except OSError:
        have = ""
    if have != want:
        return [("env-doc-drift", "docs/ENV.md", 0,
                 "stale — regenerate with `python -m "
                 "paddle_tpu.fluid.envcontract > docs/ENV.md`")]
    return []


def check_fault_doc() -> List[Tuple[str, str, int, str]]:
    """docs/FAULTS.md must match the chaos-schedule generator — a new
    PADDLE_FAULT_* hook cannot ship undocumented or invisible to the
    seeded drills (ISSUE 18)."""
    # the submodule directly: the chaos package __init__ pulls in the
    # drill runner, which the linter has no business importing
    from paddle_tpu.chaos import schedule as chaos_schedule

    path = os.path.join(REPO, "docs", "FAULTS.md")
    want = chaos_schedule.generate_fault_table().strip()
    try:
        with open(path) as f:
            have = f.read().strip()
    except OSError:
        have = ""
    if have != want:
        return [("fault-doc-drift", "docs/FAULTS.md", 0,
                 "stale — regenerate with `python -m paddle_tpu.chaos "
                 "faults --write`")]
    uncovered = chaos_schedule.uncovered_knobs()
    if uncovered:
        return [("fault-catalog-gap", "paddle_tpu/chaos/schedule.py", 0,
                 f"fault knob(s) {uncovered} are declared in envcontract "
                 f"but neither samplable in the chaos catalog nor "
                 f"explicitly exempt/excluded — add a CATALOG entry or "
                 f"an exclusion rationale")]
    return []


def check_bare_references(tests_dir: str = None
                          ) -> List[Tuple[str, str, int, str]]:
    tests_dir = tests_dir or os.path.join(REPO, "tests")
    out = []
    for fn in sorted(os.listdir(tests_dir)):
        if not fn.endswith(".py") or fn == "decoder_reference.py":
            continue
        with open(os.path.join(tests_dir, fn)) as f:
            for lineno, line in enumerate(f, 1):
                if "loss_and_grads(" in line:
                    out.append((
                        "bare-reference", f"tests/{fn}", lineno,
                        "a configuration's reference is called here: take "
                        "`reference_step` of tests/decoder_reference.py"))
    return out


def run(root: str = None) -> List[Tuple[str, str, int, str]]:
    sys.path.insert(0, REPO)
    from paddle_tpu.fluid import envcontract

    root = root or os.path.join(REPO, "paddle_tpu")
    findings: List[Tuple[str, str, int, str]] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                findings.extend(lint_file(os.path.join(dirpath, fn),
                                          envcontract.declared))
    if os.path.abspath(root) == os.path.join(REPO, "paddle_tpu"):
        findings.extend(check_env_doc())
        findings.extend(check_fault_doc())
        findings.extend(check_bare_references())
    return findings


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--root", default=None,
                   help="tree to lint (default: <repo>/paddle_tpu)")
    args = p.parse_args(argv)
    findings = run(args.root)
    for kind, relpath, lineno, msg in findings:
        print(f"{kind}:{relpath}:{lineno}: {msg}")
    if findings:
        print(f"repo_lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    print("repo_lint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
