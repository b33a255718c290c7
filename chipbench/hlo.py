"""What the lowered step declares: its Pallas custom calls and their shapes.

``custom_calls(text)`` reads the StableHLO text of a lowered step
(``Lowered.as_text()``) and returns, for every ``tpu_custom_call``, the
kernel's name and the operand and result tensor types the call itself
declares.  Bytes are counted from those declarations and from nothing else,
so a change to what a kernel reads or writes moves the count with it, and a
count can never include bytes no call of that name moves.
"""

from __future__ import annotations

import re
from typing import List, NamedTuple, Tuple

_ITEMSIZE = {"f64": 8, "i64": 8, "ui64": 8, "f32": 4, "i32": 4, "ui32": 4,
             "bf16": 2, "f16": 2, "i16": 2, "ui16": 2, "i8": 1, "ui8": 1,
             "i1": 1, "f8E4M3FN": 1, "f8E5M2": 1}

Tensor = Tuple[Tuple[int, ...], str]          # (shape, element type)


class CustomCall(NamedTuple):
    kernel: str
    operands: Tuple[Tensor, ...]
    results: Tuple[Tensor, ...]


#: StableHLO element type -> the name the same type has in HLO text
_HLO_NAME = {"i64": "s64", "i32": "s32", "i16": "s16", "i8": "s8",
             "ui64": "u64", "ui32": "u32", "ui16": "u16", "ui8": "u8",
             "i1": "pred", "f8E4M3FN": "f8e4m3fn", "f8E5M2": "f8e5m2"}

_CALL = re.compile(
    r"stablehlo\.custom_call @tpu_custom_call\((?P<args>[^)]*)\)\s*\{"
    r"(?P<attrs>.*?)\}\s*:\s*\((?P<ins>[^)]*)\)\s*->\s*(?P<outs>[^\n]*)")
_KERNEL = re.compile(r'kernel_name = "([^"]+)"')
_TENSOR = re.compile(r"tensor<((?:\d+x)*)([A-Za-z0-9]+)>")


def parse_tensors(text: str) -> Tuple[Tensor, ...]:
    """``tensor<32x256x64xbf16>, tensor<f32>`` -> shapes and types."""
    return tuple((tuple(int(d) for d in dims.split("x") if d), ty)
                 for dims, ty in _TENSOR.findall(text))


def tensor_bytes(t: Tensor) -> int:
    shape, ty = t
    n = 1
    for d in shape:
        n *= d
    try:
        return n * _ITEMSIZE[ty]
    except KeyError:
        raise KeyError(f"element type {ty!r} has no size in chipbench/hlo.py"
                       ) from None


def declared_bytes(call: CustomCall) -> int:
    """Every operand read once and every result written once."""
    return sum(tensor_bytes(t) for t in call.operands + call.results)


def signature(call: CustomCall) -> str:
    """What a trace event of this call shows of it: result and operand
    shapes and types, in HLO's spelling (``bf16[32,256,64]``).  The kernel's
    name is not in the compiled program or the trace; its signature is."""
    def one(t):
        shape, ty = t
        return f"{_HLO_NAME.get(ty, ty)}[{','.join(map(str, shape))}]"

    return ",".join(map(one, call.results)) + "<-" + \
        ",".join(map(one, call.operands))


_HLO_TENSOR = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9]m[0-9](?:fn)?)?)"
                         r"\[([0-9,]*)\](?:\{([^}]*)\})?")
_HLO_SIZE = {_HLO_NAME.get(ty, ty): n for ty, n in _ITEMSIZE.items()}


def event_call(instruction_text: str):
    """(signature, HBM bytes) of a ``tpu_custom_call`` instruction as the
    profiler names its event (the instruction's HLO text), or None for any
    other event.  HBM bytes leave out the operands and results that the
    compiled program keeps in on-chip memory (a memory space ``S(n)`` in
    the layout): the kernel moves nothing over HBM for those."""
    if 'custom_call_target="tpu_custom_call"' not in instruction_text:
        return None
    head, _, rest = instruction_text.partition(" custom-call(")
    results = _HLO_TENSOR.findall(head.partition(" = ")[2])
    operands = _HLO_TENSOR.findall(
        rest.partition("), custom_call_target=")[0])
    hbm = 0
    for ty, dims, layout in results + operands:
        if "S(" in layout:
            continue
        n = _HLO_SIZE[ty]
        for d in dims.split(","):
            if d:
                n *= int(d)
        hbm += n

    def many(tensors):
        return ",".join(f"{ty}[{dims}]" for ty, dims, _ in tensors)

    return many(results) + "<-" + many(operands), hbm


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def custom_calls(stablehlo_text: str) -> List[CustomCall]:
    calls = []
    for line in stablehlo_text.splitlines():
        if "@tpu_custom_call" not in line:
            continue
        m = _CALL.search(line)
        k = _KERNEL.search(line)
        if not m or not k:
            raise ValueError("a tpu_custom_call line chipbench/hlo.py cannot "
                             "read: " + line[:120] + " ... " + line[-200:])
        calls.append(CustomCall(k.group(1), parse_tensors(m.group("ins")),
                                parse_tensors(m.group("outs"))))
    return calls


def instruction_scopes(optimized_hlo_text: str) -> dict:
    """HLO instruction name -> the fluid op type whose ``jax.named_scope``
    the executor pushed around it (first scope after the ``jit(..)``
    prefix of the instruction's ``op_name`` metadata)."""
    out = {}
    for line in optimized_hlo_text.splitlines():
        at = line.find('op_name="')
        eq = line.find(" = ")
        if at < 0 or eq < 0 or eq > at:
            continue
        scope = scope_of(line[at + 9:line.find('"', at + 9)])
        if scope:
            out[line[:eq].split()[-1].lstrip("%")] = scope
    return out


def scope_of(op_name: str) -> str:
    parts = [p for p in op_name.split("/") if p]
    while parts and (parts[0].startswith("jit(") or
                     parts[0].startswith("pjit") or
                     parts[0].startswith("shard_map")):
        parts = parts[1:]
    # an argument's own name (mut_state['conv2d_45.w_0']) marks the copies
    # the compiler makes of it at the program's edge: one label for all
    return parts[0].partition("[")[0] if parts else ""
