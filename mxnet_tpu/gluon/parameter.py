"""Gluon Parameter and ParameterDict.

Reference: ``python/mxnet/gluon/parameter.py :: Parameter`` — deferred-shape
parameters, per-context data/grad copies, grad_req, lr_mult/wd_mult — and
``::ParameterDict`` (prefixing, shared params, save/load).

TPU-native notes: a parameter's payload is one NDArray per context for the
MXNet-compatible multi-device API, but the SPMD training path
(kvstore 'tpu_sync' / parallel.Mesh) keeps ONE array with a
`jax.sharding.NamedSharding` — per-device python copies are an anti-pattern
on TPU (SURVEY.md §2.4).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import List, Optional

import numpy as _np

from .. import autograd, initializer
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import NDArray, array as nd_array, zeros as nd_zeros
from ..ndarray import ndarray as _ndarray_mod

__all__ = ["Parameter", "Constant", "ParameterDict",
           "DeferredInitializationError", "abstract_init"]

_ABSTRACT_INIT = [False]


class abstract_init:
    """Context: parameters initialize as zero-cost abstract placeholders.

    For AOT compilation of models too large to materialize on the host
    (e.g. validating an 8B-parameter sharded train step on a laptop-sized
    machine): inside the context, ``_finish_init`` records shape/dtype and
    stores abstract data instead of running the initializer. Such
    parameters cannot be read — only their shapes/dtypes feed
    ``jax.ShapeDtypeStruct``-based lowering (TrainStep.aot_compile).
    """

    def __enter__(self):
        self._prev = _ABSTRACT_INIT[0]
        _ABSTRACT_INIT[0] = True
        return self

    def __exit__(self, *exc):
        _ABSTRACT_INIT[0] = self._prev
        return False


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its deferred shape was resolved
    (reference: parameter.py::DeferredInitializationError)."""


class Parameter:
    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        # grad_stype='row_sparse' routes embedding weights through the
        # lazy row-update path (parallel.sparse_grad); storage itself
        # stays dense-backed (SURVEY.md §7.3.5)
        self.grad_stype = grad_stype
        self._stype = stype
        self._data: Optional[OrderedDict] = None  # Context -> NDArray
        self._grad: Optional[OrderedDict] = None
        self._deferred_init = None  # (init, ctx_list, default_init)
        self._trainer = None

    # ------------------------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        # 0 is an unknown dim on EITHER side (deferred init / shared params
        # e.g. a tied Dense declaring (vocab, 0) over an embedding's
        # (vocab, units)); merge keeping the more specific size.
        if len(self._shape) != len(new_shape) or any(
            s != 0 and n != 0 and s != n
            for s, n in zip(self._shape, new_shape)
        ):
            raise MXNetError(
                f"Parameter {self.name}: cannot overwrite shape {self._shape} "
                f"with incompatible {tuple(new_shape)}")
        self._shape = tuple(s if n == 0 else n
                            for s, n in zip(self._shape, new_shape))

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null")
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._grad = None
                for arr in self._data.values():
                    arr.drop_grad()
            else:
                self._init_grad()

    # ------------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False) -> None:
        """Allocate and initialize on the given context(s)
        (reference: Parameter.initialize / _finish_deferred_init)."""
        if self._data is not None and not force_reinit:
            return
        default_init = default_init or initializer.Uniform()
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if _ABSTRACT_INIT[0]:
            # abstract-AOT mode: defer even known-shape params so their
            # placeholder data is created inside the settle trace, where
            # the zeros are free abstract values (no 2 GB embedding tables
            # materializing on the host). The flag is CAPTURED here so the
            # param stays abstract even if it resolves after the
            # abstract_init context has exited (aot_compile's settle).
            self._deferred_init = (init, list(ctx), default_init, True)
            return
        if self._shape is None or any(s <= 0 for s in self._shape):
            if self.allow_deferred_init:
                self._deferred_init = (init, list(ctx), default_init, False)
                return
            raise MXNetError(
                f"cannot initialize Parameter {self.name} with unknown shape "
                f"{self._shape}; set allow_deferred_init=True or give the shape")
        self._finish_init(init, list(ctx), default_init)

    def _finish_init(self, init, ctx_list, default_init, abstract=False):
        import jax

        if abstract or _ABSTRACT_INIT[0]:
            # abstract placeholder: shape/dtype only, no initializer run —
            # inside a live trace the zeros are a free abstract value, and
            # the payload is only ever used as a slot (make_pure_fn swaps
            # real/traced values in before any read). EAGER resolution
            # (no live trace) would silently materialize dense zeros —
            # multi-GB for the weights this mode exists for, and all-zero
            # checkpoints if saved — so it is an error instead.
            import jax.numpy as jnp

            # live-trace probe: under omnistaging a 0-size zeros is a
            # tracer inside any trace and a concrete array outside
            if not isinstance(jnp.zeros((0,)), jax.core.Tracer):
                raise MXNetError(
                    f"Parameter {self.name} was built under "
                    "abstract_init() and holds no values; it can only be "
                    "used through TrainStep.aot_compile (eager reads "
                    "would materialize meaningless zeros)")
            self._data = OrderedDict(
                (c, NDArray(data=jnp.zeros(self._shape,
                                           dtype=str(self.dtype)), ctx=c))
                for c in ctx_list)
            self._deferred_init = None
            return
        # Deferred init can resolve while a trace is live (TrainStep's
        # eval_shape settle, hybridize tracing). Initializer values are
        # concrete by construction; ensure_compile_time_eval keeps the raw
        # jnp calls inside initializers/__setitem__ from being captured as
        # tracers by the surrounding trace.
        with jax.ensure_compile_time_eval():
            self._finish_init_concrete(init, ctx_list, default_init)

    def _finish_init_concrete(self, init, ctx_list, default_init):
        host = _np.zeros(self._shape, dtype="float32")
        host_nd = nd_array(host, ctx=cpu(0), dtype="float32")
        ini = initializer.create(init) if init is not None else initializer.create(self.init) if self.init is not None else default_init
        ini(initializer.InitDesc(self.name, global_init=ini), host_nd)
        self._data = OrderedDict()
        for c in ctx_list:
            self._data[c] = host_nd.copyto(c).astype(self.dtype, copy=False) \
                if str(self.dtype) != "float32" else host_nd.copyto(c)
        self._deferred_init = None
        if self._grad_req != "null":
            self._init_grad()

    def _finish_deferred_init(self, inferred_shape=None) -> None:
        if self._deferred_init is None:
            return
        if inferred_shape is not None:
            self.shape = inferred_shape
        if self._shape is None or any(s <= 0 for s in self._shape):
            raise DeferredInitializationError(
                f"Parameter {self.name} shape still unknown: {self._shape}")
        deferred = self._deferred_init
        if len(deferred) == 4:
            init, ctx_list, default_init, abstract = deferred
        else:  # legacy 3-tuple
            init, ctx_list, default_init = deferred
            abstract = False
        self._finish_init(init, ctx_list, default_init, abstract=abstract)

    def _init_grad(self):
        self._grad = OrderedDict()
        for c, arr in self._data.items():
            g = nd_zeros(arr.shape, ctx=c, dtype=str(arr.dtype))
            self._grad[c] = g
            autograd.mark_variables([arr], [g], self._grad_req)

    # ------------------------------------------------------------------
    def _check_initialized(self, ctx=None):
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    f"Parameter {self.name} has not been initialized yet "
                    "(deferred shape); run a forward pass first")
            raise MXNetError(
                f"Parameter {self.name} has not been initialized; call "
                ".initialize() first")
        if ctx is not None and ctx not in self._data:
            raise MXNetError(
                f"Parameter {self.name} was not initialized on context {ctx}; "
                f"it lives on {list(self._data)}")

    def data(self, ctx: Optional[Context] = None) -> NDArray:
        if self._data is None and self._deferred_init is not None \
                and self._shape and all(s > 0 for s in self._shape):
            # known-shape deferred param resolves on first touch (covers
            # abstract_init, which defers everything)
            self._finish_deferred_init()
        self._check_initialized(ctx)
        if ctx is None:
            return next(iter(self._data.values()))
        return self._data[ctx]

    def list_data(self) -> List[NDArray]:
        self._check_initialized()
        return list(self._data.values())

    def grad(self, ctx: Optional[Context] = None) -> NDArray:
        self._check_initialized(ctx)
        if self._grad is None:
            raise MXNetError(
                f"Parameter {self.name} has grad_req='null'; no gradient buffer")
        if ctx is None:
            return next(iter(self._grad.values()))
        return self._grad[ctx]

    def list_grad(self) -> List[NDArray]:
        self._check_initialized()
        if self._grad is None:
            return []
        return list(self._grad.values())

    def list_ctx(self) -> List[Context]:
        self._check_initialized()
        return list(self._data.keys())

    def set_data(self, data) -> None:
        if self._data is None and self._deferred_init is not None:
            # setting data resolves a deferred parameter (load_parameters path)
            self.shape = data.shape
            deferred = self._deferred_init
            if _ABSTRACT_INIT[0] or (len(deferred) == 4 and deferred[3]):
                self._finish_deferred_init()
            else:
                # straight from the argument: the initializer's float32
                # host copy would be shipped and overwritten at once
                self._data = OrderedDict(
                    (c, NDArray(data=self._payload(data, c, self.dtype), ctx=c))
                    for c in deferred[1])
                self._deferred_init = None
                if self._grad_req != "null":
                    self._init_grad()
                return
        self._check_initialized()
        if tuple(data.shape) != tuple(self._shape):
            raise MXNetError(
                f"Parameter {self.name}: cannot set data of shape "
                f"{tuple(data.shape)} on parameter of shape {self._shape}")
        for c, arr in self._data.items():
            arr._set_data(self._payload(data, c, arr.dtype))

    @staticmethod
    def _payload(data, ctx, dtype):
        src = data if isinstance(data, NDArray) else nd_array(data, ctx=ctx)
        return src.as_in_context(ctx).astype(str(dtype), copy=False).data

    def zero_grad(self) -> None:
        if self._grad is None:
            return
        for g in self._grad.values():
            g[:] = 0

    def reset_ctx(self, ctx) -> None:
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._check_initialized()
        cur = self.data()
        self._data = OrderedDict((c, cur.copyto(c)) for c in ctx)
        if self._grad_req != "null":
            self._init_grad()

    def cast(self, dtype) -> None:
        self.dtype = dtype
        if self._data is None:
            return
        self._data = OrderedDict(
            (c, arr.astype(dtype)) for c, arr in self._data.items())
        if self._grad is not None:
            self._init_grad()

    def var(self):
        from ..symbol import var

        return var(self.name, shape=self._shape, dtype=self.dtype)

    def __repr__(self):
        return f"Parameter {self.name} (shape={self._shape}, dtype={self.dtype})"


class Constant(Parameter):
    """Non-differentiable constant parameter (reference:
    parameter.py::Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = nd_array(value)
        self.value = value
        super().__init__(
            name, grad_req="null", shape=value.shape, dtype=str(value.dtype),
            init=initializer.Constant(value), differentiable=False)


class ParameterDict:
    """Prefix-scoped dict of Parameters (reference:
    parameter.py::ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def __repr__(self):
        lines = [f"{type(self).__name__} ({self._prefix}"]
        lines += [f"  {v}" for v in self.values()]
        return "\n".join(lines) + ")"

    def get(self, name, **kwargs) -> Parameter:
        """Find (or create) a parameter named prefix+name
        (reference: ParameterDict.get — also resolves shared params)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if k == "shape" and v is not None:
                    param.shape = tuple(v)
                elif k == "init" and v is not None and param.init is None:
                    param.init = v
        return param

    def get_constant(self, name, value=None) -> Constant:
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise MXNetError(f"no constant named {name} and no value given")
            param = Constant(name, value)
            self._params[name] = param
        return param

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared:
            self._params[name] = self._shared[name]
            return self._params[name]
        return None

    def update(self, other) -> None:
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise MXNetError(f"duplicate parameter name {k}")
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False) -> None:
        default = initializer.create(init) if init is not None \
            else initializer.Uniform()
        for p in self.values():
            p.initialize(None, ctx, default_init=default, force_reinit=force_reinit)

    def zero_grad(self):
        for p in self.values():
            p.zero_grad()

    def setattr(self, name, value):
        for p in self.values():
            setattr(p, name, value)

    def reset_ctx(self, ctx):
        for p in self.values():
            p.reset_ctx(ctx)

    def save(self, filename, strip_prefix="") -> None:
        from ..ndarray import serialization

        arg_dict = {}
        for p in self.values():
            name = p.name
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            arg_dict[name] = p.data().as_in_context(cpu(0))
        serialization.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix="", cast_dtype=False,
             dtype_source="current") -> None:
        from ..ndarray import serialization

        loaded = serialization.load(filename)
        if isinstance(loaded, list):
            raise MXNetError("parameter file holds an unnamed list, not a dict")
        data = {}
        for k, v in loaded.items():
            if k.startswith(("arg:", "aux:")):
                k = k[4:]
            data[restore_prefix + k] = v
        if not allow_missing:
            for name in self.keys():
                if name not in data:
                    raise MXNetError(
                        f"Parameter {name} missing in file {filename}; set "
                        "allow_missing=True to skip")
        for name, v in data.items():
            if name not in self._params:
                if not ignore_extra:
                    raise MXNetError(
                        f"file {filename} has extra parameter {name}; set "
                        "ignore_extra=True to skip")
                continue
            p = self._params[name]
            if cast_dtype and dtype_source == "current" and p._data is not None:
                v = v.astype(str(p.dtype))
            elif cast_dtype and dtype_source == "saved":
                p.dtype = str(v.dtype)
            if ctx is not None and p._data is None and p._deferred_init is None:
                p.initialize(ctx=ctx, default_init=initializer.Zero())
            p.set_data(v)
