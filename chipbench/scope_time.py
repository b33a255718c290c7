"""The traced step by model block: the second level of the device's names.

The executor pushes two ``jax.named_scope``s around every op it traces: the
fluid op type, and behind it the op's ``fluid.name_scope`` path in ONE
segment that starts with ``MARK``, so an instruction of the compiled step
reads ``op_name="jit(fn)/conv2d_grad/~stage2.block1/transpose(jvp(..))/.."``.
``chipbench/hlo.py`` takes the first segment (the op type); this file takes
the marked one, and gives the device's self time by path:

- ``paths_of(optimized_hlo)``: instruction -> (op type, path); path ``""``
  where the instruction carries no marked segment (an argument's copy, an
  instruction with no metadata, a program built without name scopes).
- ``reduce(optimized_hlo, events)``: self time of one device's events by
  instruction (``trace_reduce.time_by_label``; an event is named by its
  instruction's HLO text, a Pallas call's too), joined to the paths.
- ``table(run)``: the same for a traced run, from the newest trace of the
  cell (read again, as ``moe_time_pct`` does) and ``run["optimized_hlo"]``;
  made and PRINTED once a run, kept in ``run``.  None where nothing was
  traced, or no instruction carries a path (the parent of the PR that
  made ``fluid.name_scope`` real).
- ``share(run, patterns)``: time under the paths ÷ ``labelled_busy_s``.
- ``needed_flops(patterns)``: 3 x the forward contractions of the ops of
  ``fluid.default_main_program()`` (the harness's ``Built.main``) under the
  paths, counted by ``chipbench/flops.py``'s own walk, per sample.
- ``mfu(run, patterns)``: needed FLOPs x samples a step x steps traced ÷
  (seconds under the paths x chips x the chip's peak).  NEEDED work over
  the time booked under the name: it passes 1 only where XLA books a
  fusion's time under another block's name (a fusion is named by its root).

A pattern is a path's leading segments, each an ``fnmatch`` pattern:
``stage1`` takes ``stage1.block3`` and not ``stage10``; ``layer*.mixer``
takes every layer's mixer.  All of it returns None, and never raises, on a
program that has no name scopes.
"""

from __future__ import annotations

import fnmatch
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

#: what the executor puts before the path (``fluid/framework.py``
#: ``NAME_SCOPE_MARK``; XLA takes a segment that starts with ``@`` out)
MARK = "~"
#: the op attr that carries the path (``framework.NAME_SCOPE_ATTR``)
ATTR = "op_namescope"
UNJOINED = "unjoined"


class Table(NamedTuple):
    by: Dict[Tuple[str, str], float]      # (op type, path) -> seconds
    instructions: Dict[str, Tuple[str, str, float, str]]
    # instruction -> (op type, path, seconds, result shape)


def under(path: str, pattern: str) -> bool:
    want, have = pattern.split("."), path.split(".")
    return len(have) >= len(want) and all(
        fnmatch.fnmatchcase(h, w) for w, h in zip(want, have))


def matches(path: str, patterns: Sequence[str]) -> bool:
    return bool(path) and any(under(path, p) for p in patterns)


def paths_of(optimized_hlo: str) -> Dict[str, Tuple[str, str]]:
    from chipbench import hlo

    out = {}
    for line in optimized_hlo.splitlines():
        at = line.find('op_name="')
        eq = line.find(" = ")
        if at < 0 or eq < 0 or eq > at:
            continue
        op_name = line[at + 9:line.find('"', at + 9)]
        op_type = hlo.scope_of(op_name)
        if not op_type:
            continue
        path = next((s[len(MARK):] for s in op_name.split("/")
                     if s.startswith(MARK)), "")
        out[line[:eq].split()[-1].lstrip("%")] = (op_type, path)
    return out


def result_shape(event_name: str) -> str:
    """``%fusion.12 = bf16[256,64,56,56]{..} fusion(..)`` -> the result."""
    text = event_name.partition(" = ")[2]
    if not text.startswith("("):
        return text.partition(" ")[0]
    depth = 0                     # a tuple; its layouts hold brackets too
    for i, c in enumerate(text):
        depth += (c == "(") - (c == ")")
        if depth == 0:
            return text[:i + 1]
    return text


def reduce(optimized_hlo: str, events) -> Optional[Table]:
    from chipbench import hlo, trace_reduce

    where = paths_of(optimized_hlo)
    if not any(path for _, path in where.values()):
        return None
    by_inst = trace_reduce.time_by_label(
        events, lambda ev: hlo.instruction_name(ev.name))
    shapes = {}
    for ev in events:
        shapes.setdefault(hlo.instruction_name(ev.name),
                          result_shape(ev.name))
    by, instructions = {}, {}
    for inst, seconds in by_inst.items():
        key = where.get(inst, (UNJOINED, ""))
        by[key] = by.get(key, 0.0) + seconds
        instructions[inst] = key + (seconds, shapes[inst])
    return Table(by, instructions)


def table(run) -> Optional[Table]:
    if "scope_time" in run:
        return run["scope_time"]
    run["scope_time"] = found = _read(run)
    if found is not None:
        print(report(run, found), flush=True)
    return found


def _read(run) -> Optional[Table]:
    import time

    from chipbench import program_spans, trace_reduce

    if not run.get("labelled_busy_s") or not run.get("optimized_hlo"):
        return None
    pb = program_spans.newest_trace(run["workload"])
    if pb is None:
        return None
    t0 = time.perf_counter()
    devices = trace_reduce.read(pb).devices
    found = reduce(run["optimized_hlo"], devices[sorted(devices)[0]])
    print(f"scope_time: read the trace again and joined it to the paths in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    return found


def seconds_under(found: Table, patterns) -> Optional[float]:
    mine = [v for (_, path), v in found.by.items() if matches(path, patterns)]
    return sum(mine) if mine else None


def share(run, patterns) -> Optional[float]:
    found = table(run)
    if found is None:
        return None
    seconds = seconds_under(found, patterns)
    return None if seconds is None else seconds / run["labelled_busy_s"]


def scoped_share(run) -> Optional[float]:
    """Busy time whose instruction carries a path ÷ busy time."""
    return share(run, ("*",))


class _Under:
    """The ops of a program's block 0 under some paths, as
    ``flops.forward_flops`` walks a program."""

    def __init__(self, program, patterns):
        block = program.global_block()
        self.ops = [op for op in block.ops
                    if matches(op.attrs.get(ATTR, ""), patterns)]
        self._var_recursive = block._var_recursive

    def global_block(self):
        return self


def _main_program():
    import paddle_tpu.fluid as fluid

    return fluid.default_main_program()


def needed_flops(patterns, program=None) -> Optional[int]:
    from chipbench import flops

    mine = _Under(program or _main_program(), patterns)
    return 3 * flops.forward_flops(mine) if mine.ops else None


def uncounted_under(patterns, program=None) -> list:
    from chipbench import flops

    return flops.uncounted_op_types(_Under(program or _main_program(),
                                           patterns))


def mfu(run, patterns, program=None) -> Optional[float]:
    from chipbench.peaks import peaks_for

    found = table(run)
    if found is None:
        return None
    seconds = seconds_under(found, patterns)
    needed = needed_flops(patterns, program)
    if not seconds or not needed:
        return None
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return needed * run["samples_per_step"] * run["steps_traced"] \
        / (seconds * run["chips"] * peak)


def pct(x):
    return None if x is None else 100.0 * x


def ops_without_scope(program=None) -> Optional[int]:
    """Ops of the main program (every block) with no path; None where no op
    has one: ``fluid.name_scope`` does nothing there."""
    ops = list((program or _main_program()).all_ops())
    bare = [op.type for op in ops if not op.attrs.get(ATTR)]
    if len(bare) == len(ops):
        return None
    print(f"ops_without_scope {len(bare)} of the main program's {len(ops)} "
          "ops" + (f": {sorted(set(bare))}" if bare else ""), flush=True)
    return len(bare)


# -- the printed tables -----------------------------------------------------

def report(run, found: Table, program=None) -> str:
    """(a) every first-level path with ms a step, share and, where it holds
    contractions the walk counts, MFU; (b) each split by op type; (c) the
    five longest instructions of the three heaviest paths; what carries no
    path, by op type."""
    steps, busy = run["steps_traced"], run["labelled_busy_s"]

    def ms(seconds):
        return 1e3 * seconds / steps

    first = {}
    for (op, path), v in found.by.items():
        top = path.partition(".")[0]
        first.setdefault(top, {})
        first[top][op] = first[top].get(op, 0.0) + v
    named = sorted((k for k in first if k),
                   key=lambda k: -sum(first[k].values()))
    lines = [f"scopes: ({steps} steps traced; ms a step, % of busy time, "
             "MFU of the contractions chipbench/flops.py counts)"]
    starred = set()
    for top in named:
        total = sum(first[top].values())
        line = f"  {top:<12} {ms(total):9.3f} ms {100 * total / busy:6.2f}%"
        try:
            m = mfu(run, (top,), program)
            unknown = uncounted_under((top,), program) if m else []
        except Exception as e:          # a table is no reason to fail a run
            m, unknown = None, []
            line += f"  (no MFU: {type(e).__name__}: {e})"
        if m is not None:
            line += f"  MFU {100 * m:6.2f}%" + ("*" if unknown else "")
            starred.update(unknown)
        lines.append(line)
    bare = first.get("", {})
    lines.append(f"  {'(no path)':<12} {ms(sum(bare.values())):9.3f} ms "
                 f"{100 * sum(bare.values()) / busy:6.2f}%  " + ", ".join(
                     f"{op} {ms(v):.3f}" for op, v in
                     sorted(bare.items(), key=lambda kv: -kv[1])[:8]))
    if starred:
        lines.append("  * the path also holds op types the walk does not "
                     f"know, so the MFU is a floor: {sorted(starred)}")
    lines.append("scopes x op types (ms a step):")
    for top in named:
        lines.append(f"  {top:<12} " + ", ".join(
            f"{op} {ms(v):.3f}" for op, v in
            sorted(first[top].items(), key=lambda kv: -kv[1])[:8]))
    by_path = {}
    for (_, path), v in found.by.items():
        if path:
            by_path[path] = by_path.get(path, 0.0) + v
    lines.append("longest instructions of the three heaviest paths "
                 "(ms a step):")
    for path in sorted(by_path, key=lambda k: -by_path[k])[:3]:
        lines.append(f"  {path} {ms(by_path[path]):.3f}")
        mine = sorted(((s, inst, op, shape) for inst, (op, p, s, shape)
                       in found.instructions.items() if p == path),
                      reverse=True)[:5]
        lines += [f"    {ms(s):8.3f} {op:<22} {inst:<28} {shape[:70]}"
                  for s, inst, op, shape in mine]
    return "\n".join(lines)
