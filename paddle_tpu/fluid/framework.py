"""Program IR: Variable / Operator / Block / Program.

TPU-native re-design of the reference's graph-builder layer
(ref: python/paddle/fluid/framework.py:207 Variable, :496 Operator, :923 Block,
:1407 Program, over C++ ProgramDesc protobufs in framework.proto:24-194).

Differences from the reference, by design:
 - The IR lives in Python (plain objects, cheaply clonable/serializable); there
   is no mutable C++ desc mirror because execution does not interpret the IR
   op-by-op — the Executor traces a whole block into ONE jitted XLA program
   (see executor.py), so the IR only needs to be a faithful build-time record.
 - Shapes may contain -1 (batch); concrete shapes are bound at trace time from
   the fed arrays, which is what makes one Program servable at many batch
   sizes (one XLA executable per shape signature).
"""

from __future__ import annotations

import contextlib
import copy
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from . import core, unique_name

GRAD_VAR_SUFFIX = "@GRAD"
TEMP_VAR_NAME = "@TEMP@"
RNG_STATE_VAR = "@RNG_STATE@"


class OpRole:
    """Op role attr consumed by transpilers/parallel pass (ref: op_proto_maker.h)."""

    Forward = 0
    Backward = 1
    Optimize = 2
    RPC = 3
    Dist = 4
    LRSched = 16
    Loss = 256

    KEY = "op_role"
    VAR_KEY = "op_role_var"


def grad_var_name(name: str) -> str:
    return name + GRAD_VAR_SUFFIX


class Variable:
    """A named value in a Block (ref: framework.py:207).

    Dense LoD tensors carry an optional host-side LoD (list of offset lists);
    on device everything is a static-shape array.
    """

    def __init__(self, block, name=None, shape=None, dtype="float32",
                 lod_level=0, persistable=False, stop_gradient=False,
                 is_data=False, type=core.VarType.LOD_TENSOR, error_clip=None,
                 **kwargs):
        self.block = block
        if name is None:
            name = unique_name.generate(TEMP_VAR_NAME)
        self.name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = core.convert_dtype(dtype) if type == core.VarType.LOD_TENSOR else dtype
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.type = type
        self.error_clip = error_clip

    # -- paddle API parity helpers --
    @property
    def grad_name(self):
        return grad_var_name(self.name)

    def astype(self, dtype):
        from .layers import tensor as tensor_layers

        return tensor_layers.cast(self, dtype)

    def to_string(self, throw_on_error=False, with_details=False):
        return (f"var {self.name} : shape{self.shape} dtype={self.dtype} "
                f"persistable={self.persistable} stop_gradient={self.stop_gradient}")

    __repr__ = __str__ = lambda self: self.to_string()

    def _clone_into(self, block):
        v = copy.copy(self)
        v.block = block
        return v


class Parameter(Variable):
    """Trainable persistable variable (ref: framework.py:2029)."""

    def __init__(self, block, shape, dtype, **kwargs):
        if shape is None or dtype is None:
            raise ValueError("Parameter needs shape and dtype")
        kwargs.setdefault("persistable", True)
        kwargs.setdefault("stop_gradient", False)
        super().__init__(block, shape=shape, dtype=dtype, **kwargs)
        self.trainable = kwargs.get("trainable", True)
        self.optimize_attr = kwargs.get("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.get("regularizer", None)
        self.gradient_clip_attr = kwargs.get("gradient_clip_attr", None)
        self.do_model_average = kwargs.get("do_model_average", None)
        # where its update ops go (optimizer.minimize)
        self.name_scope = current_name_scope()


class Operator:
    """One op in a block: type + named input/output slots + attrs
    (ref: framework.py:496 over OpDesc, framework.proto:42)."""

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        self.inputs: Dict[str, List[str]] = _normalize_slot_map(inputs)
        self.outputs: Dict[str, List[str]] = _normalize_slot_map(outputs)
        self.attrs: Dict[str, object] = dict(attrs or {})
        self.attrs.setdefault(OpRole.KEY, OpRole.Forward)

    def input(self, slot) -> List[str]:
        return self.inputs.get(slot, [])

    def output(self, slot) -> List[str]:
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self):
        return [n for ns in self.inputs.values() for n in ns]

    @property
    def output_arg_names(self):
        return [n for ns in self.outputs.values() for n in ns]

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def _set_attr(self, name, val):
        self.attrs[name] = val
        self.block.program._bump_version()

    set_attr = _set_attr

    def has_attr(self, name):
        return name in self.attrs

    def _rename_input(self, old, new):
        for slot, names in self.inputs.items():
            self.inputs[slot] = [new if n == old else n for n in names]
        self.block.program._bump_version()

    def _rename_output(self, old, new):
        for slot, names in self.outputs.items():
            self.outputs[slot] = [new if n == old else n for n in names]
        self.block.program._bump_version()

    def to_string(self, throw_on_error=False):
        ins = ", ".join(f"{k}={v}" for k, v in sorted(self.inputs.items()))
        outs = ", ".join(f"{k}={v}" for k, v in sorted(self.outputs.items()))
        sig_attrs = {k: v for k, v in self.attrs.items()
                     if k not in (OpRole.KEY, OpRole.VAR_KEY)}
        return f"{{{outs}}} = {self.type}(inputs=[{ins}], attrs={sig_attrs})"

    __repr__ = __str__ = lambda self: self.to_string()


def _normalize_slot_map(m) -> Dict[str, List[str]]:
    out: Dict[str, List[str]] = OrderedDict()
    if not m:
        return out
    for slot, vals in m.items():
        if vals is None:
            out[slot] = []
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        names = []
        for v in vals:
            if v is None:
                continue
            names.append(v.name if isinstance(v, Variable) else str(v))
        out[slot] = names
    return out


class Block:
    """Ordered ops + var table; blocks nest for control flow
    (ref: framework.py:923, BlockDesc framework.proto:177)."""

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = OrderedDict()
        self.ops: List[Operator] = []
        # forward-block link used by grad ops of sub-blocks
        self.forward_block_idx = -1

    @property
    def parent_block(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    # ---- vars ----
    def create_var(self, **kwargs) -> Variable:
        name = kwargs.get("name")
        if name is not None and name in self.vars:
            return self.vars[name]
        v = Variable(self, **kwargs)
        self.vars[v.name] = v
        self.program._bump_version()
        return v

    def create_parameter(self, **kwargs) -> Parameter:
        p = Parameter(self, **kwargs)
        # parameters always live in the outermost (global) block
        gb = self.program.global_block()
        p.block = gb
        gb.vars[p.name] = p
        self.program._bump_version()
        return p

    def var(self, name: str) -> Variable:
        v = self.vars.get(name)
        if v is None:
            raise ValueError(f"var {name} not in block {self.idx}")
        return v

    def has_var(self, name: str) -> bool:
        return name in self.vars

    def _var_recursive(self, name: str) -> Variable:
        b = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent_block
        raise ValueError(f"var {name} not found from block {self.idx} upward")

    def _has_var_recursive(self, name: str) -> bool:
        b = self
        while b is not None:
            if name in b.vars:
                return True
            b = b.parent_block
        return False

    def _remove_var(self, name: str):
        self.vars.pop(name, None)
        self.program._bump_version()

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # ---- ops ----
    def _new_op(self, type, inputs, outputs, attrs) -> Operator:
        """An op of this block, under the name scope that is open (unless
        ``attrs`` already say under which: a grad op's are its forward
        op's)."""
        op = Operator(self, type=type, inputs=inputs, outputs=outputs, attrs=attrs)
        path = current_name_scope()
        if path:
            op.attrs.setdefault(NAME_SCOPE_ATTR, path)
        self.program._bump_version()
        return op

    def append_op(self, type=None, inputs=None, outputs=None, attrs=None) -> Operator:
        op = self._new_op(type, inputs, outputs, attrs)
        self.ops.append(op)
        return op

    def _prepend_op(self, type=None, inputs=None, outputs=None, attrs=None) -> Operator:
        op = self._new_op(type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        return op

    def _insert_op(self, index, type=None, inputs=None, outputs=None, attrs=None) -> Operator:
        op = self._new_op(type, inputs, outputs, attrs)
        self.ops.insert(index, op)
        return op

    def _remove_op(self, index):
        del self.ops[index]
        self.program._bump_version()

    def to_string(self, throw_on_error=False, with_details=False):
        lines = [f"-- block {self.idx} (parent {self.parent_idx}) --"]
        for v in self.vars.values():
            lines.append("  " + v.to_string())
        for op in self.ops:
            lines.append("  " + op.to_string())
        return "\n".join(lines)


class Program:
    """A whole computation: list of blocks (ref: framework.py:1407).

    ``_version`` is bumped on every mutation; the Executor keys its
    trace/compile cache on (program, version, shape signature).
    """

    _token_counter = 0

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._version = 0
        # monotonic process-wide identity token: Executor caches key on this
        # instead of id(program), which a freed clone's recycled id could
        # alias into a stale compiled entry (ADVICE r5).  clone()/_prune()
        # build fresh Programs, so derived programs get their own token.
        Program._token_counter += 1
        self._cache_token = Program._token_counter
        self._seed_counter = 0
        # set by optimizer.minimize / append_backward for transpilers
        self._params_grads = None
        self._is_test = False

    # ---- structure ----
    def global_block(self) -> Block:
        return self.blocks[0]

    def block(self, idx) -> Block:
        return self.blocks[idx]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def _create_block(self, parent_idx=None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        self._bump_version()
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def _bump_version(self):
        self._version += 1

    def next_seed(self) -> int:
        """Deterministic per-op seed stream derived from random_seed."""
        self._seed_counter += 1
        return self._seed_counter

    # ---- iteration helpers ----
    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def all_ops(self):
        for b in self.blocks:
            yield from b.ops

    # ---- clone / prune ----
    def clone(self, for_test=False) -> "Program":
        p = Program()
        p.random_seed = self.random_seed
        p._seed_counter = self._seed_counter
        p.blocks = []
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            nb.forward_block_idx = b.forward_block_idx
            for v in b.vars.values():
                nb.vars[v.name] = v._clone_into(nb)
            for op in b.ops:
                nop = Operator(nb, op.type, copy.deepcopy(op.inputs),
                               copy.deepcopy(op.outputs), copy.deepcopy(op.attrs))
                if for_test and "is_test" in _TEST_MODE_OPS.get(op.type, ()):
                    nop.attrs["is_test"] = True
                nb.ops.append(nop)
            p.blocks.append(nb)
        p.current_block_idx = 0
        p._is_test = for_test
        if for_test:
            for b in p.blocks:
                b.ops = [op for op in b.ops
                         if op.attr(OpRole.KEY, OpRole.Forward) & OpRole.Backward == 0
                         and op.attr(OpRole.KEY, OpRole.Forward) != OpRole.Optimize]
        return p

    def _prune(self, targets, drop_roles=()) -> "Program":
        """Keep only ops needed to produce target vars (ref: prune.cc).
        ``drop_roles``: op-role values removed before slicing (the
        reference's pruning skips backward/optimize ops the same way)."""
        target_names = set()
        for t in targets:
            target_names.add(t.name if isinstance(t, Variable) else str(t))
        drop = 0
        for r in drop_roles:
            drop |= int(r)
        p = self.clone()
        gb = p.global_block()
        needed = set(target_names)
        kept = []
        for op in reversed(gb.ops):
            role = int(op.attrs.get(OpRole.KEY, OpRole.Forward))
            if drop and (role & drop):
                continue
            if any(n in needed for n in op.output_arg_names):
                kept.append(op)
                needed.update(op.input_arg_names)
        gb.ops = list(reversed(kept))
        return p

    def inference_optimize(self) -> "Program":
        p = self.clone(for_test=True)
        return p

    # ---- serialization (ref: ProgramDesc proto round-trip —
    # framework.proto:190; the on-wire format here is a versioned pickle,
    # which save/load_inference_model already uses for __model__) ----
    SERIAL_VERSION = 1

    def serialize_to_string(self) -> bytes:
        import pickle

        return pickle.dumps({"version": self.SERIAL_VERSION,
                             "program": self})

    @staticmethod
    def parse_from_string(data: bytes) -> "Program":
        import pickle

        payload = pickle.loads(data)
        if isinstance(payload, Program):  # pre-versioned blobs
            return payload
        if payload.get("version") != Program.SERIAL_VERSION:
            raise ValueError(
                f"program blob version {payload.get('version')} != "
                f"{Program.SERIAL_VERSION}")
        return payload["program"]

    def to_string(self, throw_on_error=False, with_details=False):
        return "\n".join(b.to_string() for b in self.blocks)

    __repr__ = __str__ = lambda self: self.to_string()


# Ops that behave differently under test mode.
_TEST_MODE_OPS = {
    "dropout": ("is_test",),
    "batch_norm": ("is_test",),
}


# ---------------------------------------------------------------------------
# default programs & guards (ref: framework.py:2047-2158)
# ---------------------------------------------------------------------------

_main_program_ = Program()
_startup_program_ = Program()


def default_main_program() -> Program:
    return _main_program_


def default_startup_program() -> Program:
    return _startup_program_


def switch_main_program(program: Program) -> Program:
    global _main_program_
    old = _main_program_
    _main_program_ = program
    return old


def switch_startup_program(program: Program) -> Program:
    global _startup_program_
    old = _startup_program_
    _startup_program_ = program
    return old


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


# ---------------------------------------------------------------------------
# name scopes (ref: the later framework.py's name_scope / op_namescope attr)
# ---------------------------------------------------------------------------

#: the ONE string attr that carries an op's name-scope path (``stage2.block1``)
NAME_SCOPE_ATTR = "op_namescope"
#: what the executor puts before the path in the compiled program's metadata
#: (``jit(fn)/conv2d_grad/~stage2.block1/..``): no segment jax makes starts
#: so.  Not ``@``: XLA takes a segment that starts with it out of ``op_name``
#: (it reads there as a call site), on the CPU and on the TPU
NAME_SCOPE_MARK = "~"
#: where ``Optimizer.minimize`` puts the ops that belong to no parameter
OPTIMIZER_SCOPE = "optimizer"

_name_scope_stack: List[str] = []


def current_name_scope() -> str:
    """The open name scopes joined with ``.``; ``""`` where none is open."""
    return ".".join(_name_scope_stack)


@contextlib.contextmanager
def name_scope(prefix=None):
    """Every op appended inside carries the path of the open scopes as its
    ``op_namescope`` attr; the executor pushes it into the compiled
    program's metadata beneath the op type (``run_op``), so a device trace
    reads by model block.  ``prefix`` None or empty opens nothing."""
    if not prefix:
        yield
        return
    depth = len(_name_scope_stack)
    _name_scope_stack.append(str(prefix))
    try:
        yield
    finally:
        del _name_scope_stack[depth:]


@contextlib.contextmanager
def name_scope_at(path):
    """The scope ``path`` whatever is open now: ops made later FOR something
    named earlier (a parameter's update, a pass's replacement) go where it
    is.  An empty path is no scope."""
    saved = _name_scope_stack[:]
    _name_scope_stack[:] = [path] if path else []
    try:
        yield
    finally:
        _name_scope_stack[:] = saved


def param_name_scope(param):
    """The scope a parameter's own update, clip and decay ops go under: the
    one it was created in, else the optimizer's."""
    return name_scope_at(getattr(param, "name_scope", "") or OPTIMIZER_SCOPE)


def fresh_session():
    """Reset ALL build-session globals: default programs, unique-name
    counters, global scope.  The single place that knows the full list —
    used by the test fixture, driver entry points, and scripts that build
    several models in one process."""
    from . import executor as _executor
    from . import unique_name as _unique_name

    switch_main_program(Program())
    switch_startup_program(Program())
    del _name_scope_stack[:]
    _unique_name.switch()
    _executor._global_scope = _executor.Scope()
