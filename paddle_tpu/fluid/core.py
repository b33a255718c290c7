"""Core runtime primitives: places, dtypes, device resolution.

TPU-native analogue of the reference's ``paddle/fluid/platform/place.h`` and the
pybind ``core`` module (ref: pybind/pybind.cc:443-455).  Instead of a C++
``boost::variant<CUDAPlace, CPUPlace, ...>`` dispatching to per-device kernels,
a Place here selects a JAX/PJRT device set; all compute lowers to XLA.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

# ---------------------------------------------------------------------------
# dtypes
# ---------------------------------------------------------------------------


class VarType:
    """Mirror of the reference's framework.proto VarType (framework.proto:104).

    Values are stable small ints so programs can be serialized.
    """

    BOOL = 0
    INT16 = 1
    INT32 = 2
    INT64 = 3
    FP16 = 4
    FP32 = 5
    FP64 = 6
    UINT8 = 7
    INT8 = 8
    BF16 = 9
    # non-pod types
    LOD_TENSOR = 20
    SELECTED_ROWS = 21
    FEED_MINIBATCH = 22
    FETCH_LIST = 23
    STEP_SCOPES = 24
    LOD_RANK_TABLE = 25
    LOD_TENSOR_ARRAY = 26
    READER = 28
    RAW = 30


_STR_TO_NP = {
    "bool": np.bool_,
    "int16": np.int16,
    "int32": np.int32,
    "int64": np.int64,
    "float16": np.float16,
    "float32": np.float32,
    "float64": np.float64,
    "uint8": np.uint8,
    "int8": np.int8,
    # bfloat16 resolved lazily through ml_dtypes (always present with jax)
}

_STR_TO_VARTYPE = {
    "bool": VarType.BOOL,
    "int16": VarType.INT16,
    "int32": VarType.INT32,
    "int64": VarType.INT64,
    "float16": VarType.FP16,
    "float32": VarType.FP32,
    "float64": VarType.FP64,
    "uint8": VarType.UINT8,
    "int8": VarType.INT8,
    "bfloat16": VarType.BF16,
}

_VARTYPE_TO_STR = {v: k for k, v in _STR_TO_VARTYPE.items()}


def convert_dtype(dtype) -> str:
    """Normalize any dtype spec (string, numpy dtype, VarType int) to a string."""
    if dtype is None:
        return "float32"
    if isinstance(dtype, str):
        if dtype in _STR_TO_VARTYPE:
            return dtype
        # allow numpy-style names like "float" / "double"
        return np.dtype(dtype).name
    if isinstance(dtype, int):
        if dtype in _VARTYPE_TO_STR:
            return _VARTYPE_TO_STR[dtype]
        raise ValueError(f"unknown VarType enum {dtype}")
    try:
        name = np.dtype(dtype).name
        if name in _STR_TO_VARTYPE:
            return name
    except TypeError:
        pass
    # ml_dtypes bfloat16 etc.
    name = getattr(dtype, "name", None) or str(dtype)
    if name in _STR_TO_VARTYPE:
        return name
    raise ValueError(f"cannot convert dtype {dtype!r}")


def np_dtype(dtype) -> np.dtype:
    name = convert_dtype(dtype)
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(_STR_TO_NP[name])


# ---------------------------------------------------------------------------
# Places
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Place:
    device_type: str  # "cpu" | "tpu" | "gpu"
    device_id: int = 0

    def __repr__(self):  # pragma: no cover - cosmetic
        return f"{self.device_type.upper()}Place({self.device_id})"


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


class CUDAPlace(Place):
    """Accepted for API parity; resolves to whatever accelerator JAX has."""

    def __init__(self, device_id: int = 0):
        super().__init__("gpu", device_id)


class CUDAPinnedPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


def _jax():
    import jax

    return jax


def get_jax_device(place: Place):
    """Resolve a Place to a concrete jax.Device.

    Always a process-LOCAL device: under jax.distributed the global device
    list starts with process 0's devices, and committing feeds to another
    process's device would make every fetch non-addressable here (the
    local-SGD runner hit exactly that).

    An accelerator place with no such accelerator raises, naming the
    platforms jax does see — a trainer asked for the TPU never trains on
    the host unnoticed.  The one exception is a process explicitly pinned
    to the CPU (``JAX_PLATFORMS=cpu`` / ``jax_platforms == "cpu"``, which
    is how the tests and ``--device CPU`` run): there the place resolves
    onto the virtual host devices."""
    jax = _jax()
    kind = place.device_type
    local = jax.local_devices()
    if kind == "cpu":
        devs = [d for d in local if d.platform == "cpu"] or jax.devices("cpu")
    else:
        # CUDAPlace is accepted for API parity: it takes whatever
        # accelerator jax has
        devs = [d for d in local if d.platform == kind
                or (kind == "gpu" and d.platform != "cpu")]
        if not devs:
            if jax.config.jax_platforms != "cpu":
                seen = sorted({d.platform for d in local})
                raise RuntimeError(
                    f"{place!r} needs a {kind} device, but jax sees only "
                    f"{seen}; pin the process to the CPU "
                    f"(JAX_PLATFORMS=cpu) to run there on purpose")
            devs = local
    return devs[place.device_id % len(devs)]


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    try:
        return any(d.platform == "tpu" for d in _jax().devices())
    except RuntimeError:  # pragma: no cover
        return False


def get_device_count(kind: str = None) -> int:
    jax = _jax()
    try:
        return len(jax.devices(kind)) if kind else len(jax.devices())
    except RuntimeError:
        return 0


# gflags-style runtime flags (ref: python/paddle/fluid/__init__.py:121-140
# imports gflags from env via core.init_gflags, pybind.cc:517 InitGflags).
# A plain dict; init_gflags supports the reference's two arg forms:
# "--tryfromenv=a,b,c" (import FLAGS_<name> from the environment) and
# direct "--name=value" assignment.
def _flag_value(raw):
    """Parse a flag's textual value preserving its type: numerics stay
    numeric ('1' -> 1, not True — gflags int flags like --rpc_retry_times=1
    must survive round-trips), only true/false-style literals become bools,
    and anything else stays a string (so a flag legitimately valued 'on'
    would be the bool True but e.g. 'ON_DEMAND' stays text)."""
    if isinstance(raw, bool):
        return raw
    s = str(raw).strip()
    try:
        return int(s)
    except ValueError:
        pass
    try:
        return float(s)
    except ValueError:
        pass
    if s.lower() in ("true", "yes", "on"):
        return True
    if s.lower() in ("false", "no", "off", ""):
        return False
    return s


GLOBAL_FLAGS = {
    "check_nan_inf": _flag_value(os.environ.get("FLAGS_check_nan_inf", "0")),
    "benchmark": _flag_value(os.environ.get("FLAGS_benchmark", "0")),
}


def init_gflags(args=None):
    """ref: platform/init.cc:36 InitGflags via pybind.cc:517."""
    for arg in (args or []):
        if not isinstance(arg, str) or not arg.startswith("--"):
            continue
        body = arg[2:]
        if body.startswith("tryfromenv="):
            for name in body[len("tryfromenv="):].split(","):
                name = name.strip()
                if not name:
                    continue
                env = os.environ.get(f"FLAGS_{name}")
                if env is not None:
                    GLOBAL_FLAGS[name] = _flag_value(env)
        elif "=" in body:
            name, _, val = body.partition("=")
            GLOBAL_FLAGS[name.strip()] = _flag_value(val)
    return True


def init_devices():
    return True


class EOFException(Exception):
    """Raised when a reader's queue is exhausted (ref: the C++ executor
    throws EOFException from the read op; users catch fluid.core.
    EOFException around their train loop)."""


# host-side LoDTensor lives in fluid.lod_tensor; re-export for the pybind
# parity surface (ref exposes core.LoDTensor, pybind.cc:160)
from .lod_tensor import LoDTensor  # noqa: E402,F401


def __getattr__(attr):
    # ref pybind.cc:345 exposes core.Scope; ours lives in fluid.executor
    # (imported lazily here — executor imports core at module load)
    if attr == "Scope":
        from .executor import Scope

        return Scope
    raise AttributeError(attr)
