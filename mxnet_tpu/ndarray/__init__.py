"""The ``mx.nd`` namespace: NDArray + generated op wrappers.

Reference: ``python/mxnet/ndarray/register.py`` — at import time MXNet
enumerates C-registered operators and code-generates Python wrappers into
``mx.nd.*``. Here the registry is the pure-JAX op table
(``mxnet_tpu/ops/registry.py``) and wrappers are generated the same way, so
``dir(mx.nd)`` shows the operator surface and each wrapper accepts tensors
positionally or by name, attrs as keywords, plus ``out=`` / ``ctx=``.
"""
from __future__ import annotations

import sys
import types
from typing import Optional

import numpy as _np

from ..base import numeric_types
from ..context import Context, current_context, cpu, gpu, tpu
from ..ops import registry as _registry
from ..ops.registry import get_op, list_ops
# import op implementation modules to populate the registry
from ..ops import elemwise as _elemwise  # noqa: F401
from ..ops import tensor as _tensor  # noqa: F401
from ..ops import nn as _nn  # noqa: F401
from ..ops import random as _random_ops  # noqa: F401
from ..ops import optimizer_op as _optimizer_op  # noqa: F401
from ..ops import contrib as _contrib_ops  # noqa: F401
from ..ops import rnn as _rnn_ops  # noqa: F401
from ..ops import attention as _attention_ops  # noqa: F401
from ..ops import fused_loss as _fused_loss_ops  # noqa: F401
from ..ops import spatial as _spatial_ops  # noqa: F401
from ..ops import multibox as _multibox_ops  # noqa: F401
from ..ops import deformable as _deformable_ops  # noqa: F401
from ..ops import custom as _custom_ops  # noqa: F401
from ..ops import ssm as _ssm_ops  # noqa: F401
from ..ops import diffusion as _diffusion_ops  # noqa: F401
from ..ops import linear_attention as _linear_attention_ops  # noqa: F401
from ..ops import diff_attention as _diff_attention_ops  # noqa: F401

from .ndarray import NDArray, array, empty, imperative_invoke, waitall, _wrap_jax
from .serialization import save, load, loads

__all__ = ["NDArray", "array", "empty", "save", "load", "waitall", "zeros",
           "ones", "full", "arange", "concat", "random", "contrib", "linalg"]


def _make_wrapper(opname: str):
    opdef = get_op(opname)

    def wrapper(*args, **kwargs):
        out = kwargs.pop("out", None)
        kwargs.pop("name", None)
        ctx = kwargs.pop("ctx", None)
        if isinstance(ctx, str):
            ctx = Context(ctx)
        tensors = []
        attrs = {}
        if opdef.variadic:
            tensors = [a for a in args]
            for k, v in kwargs.items():
                attrs[k] = v
        elif opdef.tensor_params:
            named = {}
            pos = list(args)
            # positional args fill tensor slots first
            tensors = [None] * len(opdef.tensor_params)
            for i, a in enumerate(pos):
                if i < len(tensors):
                    tensors[i] = a
                else:
                    # overflow positionals map onto attr params in order
                    # (MXNet parity: e.g. nd.clip(x, 0, 6))
                    j = i - len(tensors)
                    if j < len(opdef.attr_params):
                        attrs[opdef.attr_params[j]] = a
                    else:
                        raise TypeError(
                            f"{opname}: too many positional arguments")
            for k, v in kwargs.items():
                if k in opdef.tensor_params:
                    tensors[opdef.tensor_params.index(k)] = v
                elif k in attrs:
                    raise TypeError(
                        f"{opname}() got multiple values for argument "
                        f"{k!r}")
                else:
                    attrs[k] = v
            # trim trailing unset optional tensors
            while tensors and tensors[-1] is None:
                tensors.pop()
        else:
            # creation-style op: positional args map onto attrs in order
            for i, a in enumerate(args):
                if i < len(opdef.attr_params):
                    attrs[opdef.attr_params[i]] = a
            attrs.update(kwargs)
        tensors = [
            t if (t is None or isinstance(t, NDArray) or isinstance(t, numeric_types))
            else array(t, ctx=ctx)
            for t in tensors
        ]
        return imperative_invoke(opdef, tensors, attrs, out=out, ctx=ctx)

    wrapper.__name__ = opname
    wrapper.__qualname__ = f"nd.{opname}"
    from ..ops.registry import render_attr_docs

    wrapper.__doc__ = (opdef.fn.__doc__ or f"{opname} operator.") \
        + render_attr_docs(opdef)
    return wrapper


_this = sys.modules[__name__]
random = types.ModuleType(__name__ + ".random")
contrib = types.ModuleType(__name__ + ".contrib")
linalg = types.ModuleType(__name__ + ".linalg")
image = types.ModuleType(__name__ + ".image")
sys.modules[random.__name__] = random
sys.modules[contrib.__name__] = contrib
sys.modules[linalg.__name__] = linalg
sys.modules[image.__name__] = image

def _refresh_ops():
    """(Re)generate op wrappers from the registry — called at import and
    again by mx.library.load after native ops register."""
    for _name in list_ops():
        if hasattr(_this, _name):
            continue
        _w = _make_wrapper(_name)
        setattr(_this, _name, _w)
        if _name.startswith("_contrib_"):
            setattr(contrib, _name[len("_contrib_"):], _w)
        if _name.startswith("_linalg_"):
            setattr(linalg, _name[len("_linalg_"):], _w)
        if _name.startswith("_image_"):
            setattr(image, _name[len("_image_"):], _w)
        if _name.startswith("_random_"):
            setattr(random, _name[len("_random_"):], _w)
        elif _name.startswith("_sample_"):
            # NDArray-parameterized forms live as random.sample_* (the
            # scalar forms keep the short names, matching mx.nd.random)
            setattr(random, _name[1:], _w)


_refresh_ops()

from . import sparse  # noqa: E402  (mx.nd.sparse)

# higher-order control flow (python-function arguments — not registry ops)
from ..ops import control_flow as _control_flow  # noqa: E402

contrib.foreach = _control_flow.foreach
contrib.while_loop = _control_flow.while_loop
contrib.cond = _control_flow.cond

# mx.nd.random has MXNet names: uniform/normal/... already set above;
# add the multisample aliases whose broadcast-parameter form differs.
random.seed = None  # patched by mxnet_tpu.random module import


def zeros(shape, ctx: Optional[Context] = None, dtype=None, **kwargs) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return imperative_invoke(get_op("_zeros"), [],
                             {"shape": tuple(shape), "dtype": str(_np.dtype(dtype or "float32")) if dtype != "bfloat16" else "bfloat16"},
                             ctx=ctx)


def ones(shape, ctx: Optional[Context] = None, dtype=None, **kwargs) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return imperative_invoke(get_op("_ones"), [],
                             {"shape": tuple(shape), "dtype": str(_np.dtype(dtype or "float32")) if dtype != "bfloat16" else "bfloat16"},
                             ctx=ctx)


def full(shape, val, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return imperative_invoke(get_op("_full"), [],
                             {"shape": tuple(shape), "value": float(val),
                              "dtype": str(_np.dtype(dtype or "float32")) if dtype != "bfloat16" else "bfloat16"},
                             ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx: Optional[Context] = None,
           dtype=None) -> NDArray:
    return imperative_invoke(get_op("_arange"), [],
                             {"start": start, "stop": stop, "step": step,
                              "repeat": repeat,
                              "dtype": str(_np.dtype(dtype or "float32"))},
                             ctx=ctx)


def zeros_like(a, **kw):
    return imperative_invoke(get_op("zeros_like"), [a], {})


def ones_like(a, **kw):
    return imperative_invoke(get_op("ones_like"), [a], {})


def moveaxis(a, source, destination):
    axes = list(range(a.ndim))
    axes.remove(source)
    axes.insert(destination if destination >= 0 else destination + a.ndim, source)
    return a.transpose(axes)


def maximum(lhs, rhs):
    if isinstance(rhs, numeric_types):
        return imperative_invoke(get_op("_maximum_scalar"), [lhs], {"scalar": float(rhs)})
    if isinstance(lhs, numeric_types):
        return imperative_invoke(get_op("_maximum_scalar"), [rhs], {"scalar": float(lhs)})
    return imperative_invoke(get_op("broadcast_maximum"), [lhs, rhs], {})


def minimum(lhs, rhs):
    if isinstance(rhs, numeric_types):
        return imperative_invoke(get_op("_minimum_scalar"), [lhs], {"scalar": float(rhs)})
    if isinstance(lhs, numeric_types):
        return imperative_invoke(get_op("_minimum_scalar"), [rhs], {"scalar": float(lhs)})
    return imperative_invoke(get_op("broadcast_minimum"), [lhs, rhs], {})


def power(lhs, rhs):
    if isinstance(rhs, numeric_types):
        return imperative_invoke(get_op("_power_scalar"), [lhs], {"scalar": float(rhs)})
    if isinstance(lhs, numeric_types):
        return imperative_invoke(get_op("_rpower_scalar"), [rhs], {"scalar": float(lhs)})
    return imperative_invoke(get_op("broadcast_power"), [lhs, rhs], {})


def equal(l, r):
    return l == r


def not_equal(l, r):
    return l != r


def greater(l, r):
    return l > r


def lesser(l, r):
    return l < r


def cast_storage(arr, stype="default"):
    """reference: src/operator/tensor/cast_storage.cc — convert between
    dense/'csr'/'row_sparse' storage. Sparse storage is a Python-level
    facade here (SURVEY.md §7.3.5), so this delegates to ``tostype``."""
    return arr.tostype(stype)
