"""Base utilities: errors, dtype registry, naming.

TPU-native re-implementation of the roles played by the reference's
``python/mxnet/base.py`` (ctypes plumbing, ``MXNetError``, ``check_call``)
and mshadow's dtype switch machinery (``mshadow/base.h :: kFloat32`` etc.).
There is no C ABI boundary here yet: the compute core is JAX/XLA, so the
"library handle" is the in-process JAX runtime.
"""
from __future__ import annotations

import os
import threading

import numpy as _np

__all__ = [
    "MXNetError",
    "NotSupportedForSparseNDArray",
    "string_types",
    "numeric_types",
    "integer_types",
    "dtype_np_to_id",
    "dtype_id_to_np",
    "name_manager",
]


class MXNetError(RuntimeError):
    """Framework-level error (reference: ``python/mxnet/base.py :: MXNetError``)."""


class NotSupportedForSparseNDArray(MXNetError):
    def __init__(self, function, alias, *args):
        super().__init__(
            f"Function {function.__name__}"
            f" (alias: {alias}) is not supported for SparseNDArray."
        )


string_types = (str,)
numeric_types = (float, int, _np.generic)
integer_types = (int, _np.integer)

# dtype id table mirrors mshadow's TypeFlag ordering so that serialized
# .params files and symbol.json attrs keep the same integer codes
# (reference: mshadow/base.h :: kFloat32=0, kFloat64=1, kFloat16=2,
# kUint8=3, kInt32=4, kInt8=5, kInt64=6, kBool=7, plus bf16 extension).
_DTYPE_NP_TO_ID = {
    _np.dtype("float32"): 0,
    _np.dtype("float64"): 1,
    _np.dtype("float16"): 2,
    _np.dtype("uint8"): 3,
    _np.dtype("int32"): 4,
    _np.dtype("int8"): 5,
    _np.dtype("int64"): 6,
    _np.dtype("bool"): 7,
    _np.dtype("int16"): 8,
    _np.dtype("uint16"): 9,
    _np.dtype("uint32"): 10,
    _np.dtype("uint64"): 11,
    # bfloat16 is TPU-first-class; id 12 matches mshadow's bfloat16 slot.
    "bfloat16": 12,
}

_DTYPE_ID_TO_NP = {v: k for k, v in _DTYPE_NP_TO_ID.items()}


def dtype_np_to_id(dtype) -> int:
    import ml_dtypes

    if dtype == ml_dtypes.bfloat16 or str(dtype) == "bfloat16":
        return 12
    return _DTYPE_NP_TO_ID[_np.dtype(dtype)]


def dtype_id_to_np(type_id: int):
    if type_id == 12:
        import ml_dtypes

        return _np.dtype(ml_dtypes.bfloat16)
    return _DTYPE_ID_TO_NP[type_id]


class _NameManager(threading.local):
    """Automatic unique-name assignment.

    Reference: ``python/mxnet/name.py :: NameManager``.
    """

    def __init__(self):
        super().__init__()
        self._counter = {}

    def get(self, name, hint):
        if name is not None:
            return name
        idx = self._counter.get(hint, 0)
        self._counter[hint] = idx + 1
        return f"{hint}{idx}"

    def reset(self):
        self._counter = {}


name_manager = _NameManager()


def classproperty(func):
    class _Descriptor:
        def __get__(self, obj, owner):
            return func(owner)

    return _Descriptor()


# ---------------------------------------------------------------------------
# execution-platform plumbing
# ---------------------------------------------------------------------------
import contextlib as _contextlib
import contextvars as _contextvars


def cpu_only_process() -> bool:
    """``JAX_PLATFORMS`` restricts this process to the CPU — the test
    and rehearsal environment, where accelerator contexts stand in on
    CPU devices and the persistent compile cache defaults off."""
    toks = [t.strip()
            for t in os.environ.get("JAX_PLATFORMS", "").split(",")]
    toks = [t for t in toks if t]
    return bool(toks) and all(t == "cpu" for t in toks)

_exec_platform = _contextvars.ContextVar("mxnet_tpu_exec_platform",
                                         default=None)


@_contextlib.contextmanager
def execution_platform(platform):
    """Declare the platform ops are being traced/lowered for.

    The framework's jit entry points (per-op eager cache, CachedOp,
    TrainStep) set this from the devices they will actually run on, so
    kernel-eligibility checks inside a trace (e.g. the Pallas flash
    attention dispatch) don't have to guess from the default backend — a
    CPU-context op must not take the Pallas path just because a TPU exists
    in the process.
    """
    token = _exec_platform.set(platform)
    try:
        yield
    finally:
        _exec_platform.reset(token)


def current_execution_platform(sample=None):
    """Execution platform for `sample` (concrete array, tracer, or None)."""
    override = _exec_platform.get()
    if override is not None:
        return override
    import jax

    if sample is not None and not isinstance(sample, jax.core.Tracer):
        try:
            return next(iter(sample.devices())).platform
        except Exception:
            pass
    try:
        return jax.devices()[0].platform
    except Exception:
        return "none"
