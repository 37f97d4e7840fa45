"""Gluon Block / HybridBlock.

Reference: ``python/mxnet/gluon/block.py :: Block`` (children tree, param
collection, hooks, initialize, save/load_parameters) and ``:: HybridBlock``
(`hybridize()` → CachedOp, `export()`, deferred shape inference).

TPU-native CachedOp (SURVEY.md §3.3 — "THE lowering seam"): MXNet's
``HybridBlock._build_cache`` traces ``hybrid_forward`` into an nnvm graph
and runs it via ``src/imperative/cached_op.cc`` with static memory planning
and op bulking. Here ``hybridize()`` wraps the block's forward in ONE
``jax.jit`` executable per (input shapes, dtypes, train-flag) key:

* static_alloc ≙ XLA buffer allocation, bulking ≙ XLA fusion — both free;
* parameters enter as executable inputs so autograd can differentiate the
  whole fused step via one ``jax.vjp``;
* in-place aux-state writes during the trace (BatchNorm moving stats) are
  captured by ``mxnet_tpu.mutation`` and returned as extra outputs, then
  written back — the functional re-design of MXNet's mutable aux states;
* random ops draw from a per-call PRNG key input, so one compiled
  executable yields fresh dropout masks per step with zero recompiles.
"""
from __future__ import annotations

import functools
import re
import threading
from collections import OrderedDict
from typing import List, Optional

from .. import autograd, engine, mutation, random_state
from ..base import MXNetError, name_manager
from ..context import Context, cpu, current_context
from ..ndarray import NDArray
from ..ndarray.ndarray import _wrap_jax, imperative_invoke, _LambdaOp
from .parameter import DeferredInitializationError, Parameter, ParameterDict

__all__ = ["Block", "HybridBlock", "SymbolBlock", "nested_flatten_nd",
           "remat_call", "resolve_remat_policy"]


def resolve_remat_policy(policy):
    """Normalize a remat policy name to a ``jax.checkpoint`` policy.

    The single validator behind every remat surface (``remat_call``, the
    model zoo's ``remat=`` kwargs, ``TrainStep(remat=...)``), so a typo
    raises the same ValueError everywhere — eagerly, never from inside a
    trace. Returns the jax policy callable (or None for save-nothing):

      None / "full"  save nothing — recompute the whole span;
      "dots"         ``dots_with_no_batch_dims_saveable`` — matmul
                     outputs SAVED, elementwise/norm/rotary recompute;
      callable       passed through (a raw jax checkpoint policy).
    """
    import jax

    if policy in (None, "full"):
        return None
    if policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if callable(policy):
        return policy
    raise ValueError(f"unknown remat policy {policy!r}")


def remat_call(block, *args, policy=None):
    """Call ``block`` under ``jax.checkpoint`` when inside a live trace.

    Gradient rematerialization for big models (SURVEY.md §7.2 "remat
    policy"): inside a compiled train step the block's activations are
    recomputed in the backward pass instead of saved — HBM for FLOPs, the
    standard trade for transformer trunks. Parameters reach the block as
    closed-over trace inputs and stay saved; only intra-block activations
    are recomputed. Outside a trace (eager) this is a plain call: eager
    autograd replays the graph anyway, so there is nothing to save.

    ``policy``:
      None / "full"  save nothing — recompute the whole block (max memory
                     savings, ~+1 forward of FLOPs per backward);
      "dots"         ``dots_with_no_batch_dims_saveable`` — matmul outputs
                     are SAVED, only elementwise/norm/rotary recompute.
                     The backward re-runs no MXU work, so the remat FLOPs
                     tax ~vanishes for ~the matmul-output bytes per block
                     (the middle ground when full activations don't fit
                     but matmul outputs do — see PERF_HISTORY.md round 4 for the
                     measured policy ladder on the 0.7B proxy).
    """
    import jax

    from ..ndarray import NDArray

    # validate the policy on EVERY call (eager included) so a typo can't
    # hide until the first traced step
    jpolicy = resolve_remat_policy(policy)

    if not args or not isinstance(args[0].data, jax.core.Tracer):
        return block(*args)
    ctx = args[0].context

    def _pure(*vals):
        out = block(*[NDArray(data=v, ctx=ctx) for v in vals])
        flat, tree = nested_flatten_nd(out)
        _pure.tree = tree
        return tuple(o.data for o in flat)

    out_vals = jax.checkpoint(_pure, policy=jpolicy)(*[a.data for a in args])
    out_nd = [NDArray(data=v, ctx=ctx) for v in out_vals]
    return nested_unflatten_nd(_pure.tree, out_nd)


class _BlockScope(threading.local):
    """Name scope for automatic prefixing (reference: block.py::_BlockScope)."""

    def __init__(self):
        super().__init__()
        self.current = None

    @staticmethod
    def create(prefix, params, hint):
        scope = _scope
        if scope.current is None:
            if prefix is None:
                prefix = name_manager.get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, shared=params)
            return prefix, params
        block = scope.current
        if prefix is None:
            prefix = name_manager.get(None, hint) + "_"
        if params is None:
            parent = block._block._params
            params = ParameterDict(parent.prefix + prefix, shared=None)
        else:
            params = ParameterDict(params.prefix, shared=params)
        return block._block.prefix + prefix, params


_scope = _BlockScope()


class _NameScopeCtx:
    """One ctx per Block, REUSED across ``with`` statements — so saved
    outer scopes live on a stack: re-entering the same block's scope
    (e.g. a helper taking ``parent.name_scope()`` while the parent's
    __init__ is already inside it) must not clobber the saved outer
    scope with ``self`` and leak the scope process-wide."""

    def __init__(self, block):
        self._block = block
        self._olds = []

    def __enter__(self):
        self._olds.append(_scope.current)
        _scope.current = self
        return self

    def __exit__(self, *exc):
        _scope.current = self._olds.pop()


class Block:
    """Base building block (reference: gluon/block.py::Block)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _NameScopeCtx(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()
        self._hook_id = 0

    def _alias(self):
        return self.__class__.__name__.lower()

    # ------------------------------------------------------------------
    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self):
        return self._scope

    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pat = re.compile(select)
            ret.update({k: v for k, v in self._params.items() if pat.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    # ------------------------------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block
        return block

    def register_forward_hook(self, hook):
        self._hook_id += 1
        self._forward_hooks[self._hook_id] = hook
        return _HookHandle(self._forward_hooks, self._hook_id)

    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return _HookHandle(self._forward_pre_hooks, self._hook_id)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._params.values():
            p.cast(dtype)

    # ------------------------------------------------------------------
    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        rows = []

        def add_hooks(blk, path):
            hs = []
            for name, child in blk._children.items():
                hs += add_hooks(child, f"{path}.{name}")
            h = blk.register_forward_hook(
                lambda b, i, o, path=path: rows.append(
                    (path, type(b).__name__,
                     getattr(o[0] if isinstance(o, (list, tuple)) else o, "shape", None))))
            hs.append(h)
            return hs

        handles = add_hooks(self, self._name)
        try:
            self(*inputs)
        finally:
            for h in handles:
                h.detach()
        lines = [f"{'Layer':<40}{'Type':<25}{'Output shape'}"]
        lines += [f"{p:<40}{t:<25}{s}" for p, t, s in rows]
        return "\n".join(lines)

    # ------------------------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        """reference: Block.save_parameters — params only, keyed by the
        block-relative name so models are prefix-independent."""
        params = self._collect_params_with_prefix()
        from ..ndarray import serialization

        serialization.save(filename, {k: v.data().as_in_context(cpu(0))
                                      for k, v in params.items()})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        from ..ndarray import serialization

        loaded = serialization.load(filename)
        if isinstance(loaded, list):
            raise MXNetError(f"{filename} holds a list, not a parameter dict")
        # an optimize_for graph holds folded COPIES of the old params; it
        # must not keep serving after a checkpoint restore
        if getattr(self, "_optimized_block", None) is not None:
            self._set_optimized_block(None)
        loaded = {k[4:] if k.startswith(("arg:", "aux:")) else k: v
                  for k, v in loaded.items()}
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    # name the keys the file DOES hold: a prefix mismatch
                    # ('features.0.weight' vs '0.weight') is then obvious
                    # from the error alone instead of a debugger session
                    avail = sorted(loaded)
                    shown = ", ".join(avail[:12]) + \
                        (f", ... ({len(avail) - 12} more)"
                         if len(avail) > 12 else "")
                    raise MXNetError(
                        f"Parameter {name} missing in {filename} "
                        f"(allow_missing=False). The file contains "
                        f"{len(avail)} parameter(s): [{shown}]")
        for name, v in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise MXNetError(
                        f"{filename} contains extra parameter {name} "
                        "(ignore_extra=False)")
                continue
            p = params[name]
            if cast_dtype:
                if dtype_source == "current" and p._data is not None:
                    v = v.astype(str(p.dtype))
                elif dtype_source == "saved":
                    p.dtype = str(v.dtype)
            if p._data is None and p._deferred_init is None:
                p.initialize(ctx=ctx or cpu(0))
            p.set_data(v)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + name: p for name, p in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def __repr__(self):
        s = f"{self.__class__.__name__}(\n"
        for name, child in self._children.items():
            s += f"  ({name}): {repr(child)}\n"
        return s + ")"


class _HookHandle:
    def __init__(self, hooks, hid):
        self._hooks = hooks
        self._id = hid

    def detach(self):
        self._hooks.pop(self._id, None)


def nested_flatten_nd(out):
    """Flatten nested (tuple/list of) NDArray into a flat list + treedef."""
    flat = []

    def walk(o):
        if isinstance(o, NDArray):
            flat.append(o)
            return ("leaf", len(flat) - 1)
        if isinstance(o, (list, tuple)):
            return ("seq", type(o).__name__, [walk(x) for x in o])
        raise MXNetError(f"hybrid forward returned unsupported type {type(o)}")

    tree = walk(out)
    return flat, tree


def nested_unflatten_nd(tree, flat):
    kind = tree[0]
    if kind == "leaf":
        return flat[tree[1]]
    _, tname, children = tree
    seq = [nested_unflatten_nd(c, flat) for c in children]
    return tuple(seq) if tname == "tuple" else seq


def make_pure_fn(block, param_arrays, ctx, training):
    """Build a pure function over a Block's forward.

    Returns ``(pure, cell)`` where ``pure(param_vals, rng, *input_vals) ->
    (out_vals, aux_vals)`` is jax-traceable and ``cell`` carries the output
    treedef plus the aux-state NDArrays mutated during the trace (BatchNorm
    moving stats etc. — see mxnet_tpu.mutation). This is the single lowering
    seam shared by CachedOp (hybridize) and the sharded train step
    (mxnet_tpu.parallel.step); reference: src/imperative/cached_op.cc.
    """

    def pure(param_vals, rng, *input_vals):
        prev_rec = autograd.set_recording(False)
        prev_train = autograd.set_training(training)
        olds = [arr._data for arr in param_arrays]
        with mutation.mutation_scope() as log:
            with random_state.scoped_key(rng):
                try:
                    for arr, v in zip(param_arrays, param_vals):
                        arr._data = v
                        arr._version += 1
                    nd_in = [NDArray(data=v, ctx=ctx) for v in input_vals]
                    out = block._eager_forward(*nd_in)
                    flat, tree = nested_flatten_nd(out)
                    aux_arrays = [a for a in log.arrays]
                    cell["aux_arrays"] = aux_arrays
                    cell["treedef"] = tree
                    cell["n_out"] = len(flat)
                    out_vals = tuple(o.data for o in flat)
                    aux_vals = tuple(a.data for a in aux_arrays)
                    return out_vals, aux_vals
                finally:
                    # restore any concrete payloads clobbered by tracers:
                    # first logged mutations, then the param swaps
                    for a, orig in log.originals:
                        a._data = orig
                        a._version += 1
                    for arr, old in zip(param_arrays, olds):
                        arr._data = old
                        arr._version += 1
                    autograd.set_recording(prev_rec)
                    autograd.set_training(prev_train)

    cell = {"aux_arrays": None, "treedef": None, "n_out": None}
    return pure, cell


class _CachedGraph:
    """One compiled executable per (shapes, dtypes, train-flag) key — the
    jax.jit equivalent of ``src/imperative/cached_op.cc :: CachedOp``.

    Routed through the compilation service: canonical signature keying
    (``compiler.signature``), executables AOT-compiled via
    ``jit(...).lower().compile()`` and deduped across architecturally
    identical blocks through the in-process executable table (replica N
    of a Router reuses replica 0's XLA compile), every build journaled to
    the signature manifest for :func:`mxnet_tpu.compiler.warm_start`.
    """

    def __init__(self, block, flags):
        from ..compiler import service as _csvc

        self.block = block
        self.flags = dict(flags or {})
        self._cache = _csvc.SiteCache("cached_op")
        self._cells = {}     # training-flag -> cell memo (see _build)

    def clear(self):
        self._cache.clear()

    def _key_for(self, args, param_arrays, training):
        from ..compiler import signature

        # trace-time routing knobs (Pallas fused kernels, hash dropout)
        # select different op bodies — they key the cache like shapes do
        return signature(
            "cached_op", id(self.block),
            avals=tuple((tuple(a.shape), str(a.dtype)) for a in args),
            extra=(tuple((tuple(a.shape), str(a.dtype))
                         for a in param_arrays), training))

    def __call__(self, args: List[NDArray]):
        block = self.block
        ctx = args[0].context if args else current_context()
        params = [p for p in block.collect_params().values()]
        # deferred shapes must be settled before tracing
        if any(p._data is None for p in params):
            raise DeferredInitializationError  # caller runs one eager pass
        param_arrays = [p.data(ctx) for p in params]
        training = autograd.is_training()
        key = self._key_for(args, param_arrays, training)
        entry = self._cache.lookup(key)
        if entry is self._cache.MISS:
            entry = self._build(param_arrays, args, ctx, training)
            self._cache.insert(key, entry)
        jitted, cell = entry["jitted"], entry["cell"]
        rng = random_state.get_state_key()

        n_params = len(param_arrays)

        def call_fn(*tensors):
            pvals = tensors[:n_params]
            ivals = tensors[n_params:]
            outs, aux = jitted(tuple(pvals), rng, *ivals)
            return tuple(outs) + tuple(aux)

        results = imperative_invoke(
            _LambdaOp(call_fn, f"CachedOp_{block.name}"),
            list(param_arrays) + list(args), {}, ctx=ctx)
        if not isinstance(results, list):
            results = [results]
        n_out = cell["n_out"]
        out_nd = results[:n_out]
        aux_nd = results[n_out:]
        for arr, v in zip(cell["aux_arrays"], aux_nd):
            arr._set_data(v.data)
        return nested_unflatten_nd(cell["treedef"], out_nd)

    def _build(self, param_arrays, args, ctx, training):
        import jax

        pure, cell = make_pure_fn(self.block, param_arrays, ctx, training)
        # training-mode graphs run under autograd recording (jax.vjp over
        # call_fn) where a Compiled cannot serve — sealing would compile
        # an executable whose every use is the tracer fallback; plain jit
        # traces once and serves both. Inference graphs (the serving warm
        # path) seal through the service.
        if training:
            return {"jitted": jax.jit(pure), "cell": cell}
        jitted = None
        try:
            from .. import compiler
            from ..compiler import service as _csvc

            # AOT through the service's persistence stack: the canonical
            # signature (graph structure + forward bytecode + avals +
            # routing + platform) keys the in-process executable table —
            # replica N of one architecture reuses replica 0's XLA
            # compile — and the exported-StableHLO blob store, so a
            # fresh process skips the trace too. The trace (when one
            # runs) settles `cell`; a blob hit settles it via the
            # cell-shape probe below.
            psds = tuple(jax.ShapeDtypeStruct(tuple(a.shape), a.data.dtype)
                         for a in param_arrays)
            isds = tuple(jax.ShapeDtypeStruct(tuple(a.shape), a.data.dtype)
                         for a in args)
            with random_state.preserved_stream():
                rng = random_state.get_state_key()
            rsds = jax.ShapeDtypeStruct(tuple(rng.shape), rng.dtype)
            graph = compiler.graph_ident(self.block)
            arg_avals = tuple((tuple(a.shape), str(a.data.dtype))
                              for a in args)
            sig_fp = compiler.keys.fingerprint(compiler.keys.encode((
                "cached_op", graph,
                tuple((tuple(a.shape), str(a.data.dtype))
                      for a in param_arrays),
                arg_avals, (tuple(rng.shape), str(rng.dtype)), training,
                compiler.routing_knobs(),
                jax.default_backend(), jax.__version__)))
            jitted = _csvc.seal_executable(
                sig_fp, jax.jit(pure), (psds, rsds) + isds,
                fallback=functools.partial(jax.jit, pure))
            if cell["treedef"] is None:
                # exported-blob hit: nothing traced `pure`, so the cell
                # (output treedef + aux arrays) is still unset — reuse
                # the memo from a sibling signature (structure is a
                # property of the block, not the batch shape), else
                # settle it with one host-side shape probe (no compile)
                memo = self._cells.get(training)
                if memo is not None:
                    cell.update(memo)
                else:
                    jax.eval_shape(pure, psds, rsds, *isds)
            if cell["treedef"] is not None:
                self._cells[training] = {
                    k: cell[k]
                    for k in ("aux_arrays", "treedef", "n_out")}
            compiler.record_signature("cached_op", {
                "graph": graph, "args": arg_avals, "training": training,
                "routing": compiler.routing_knobs()})
        except Exception:
            # AOT lowering is an optimization; blocks whose forward needs
            # concrete values (or exotic placements) keep the trace-at-
            # first-call jit path
            jitted = None
        if jitted is None:
            jitted = jax.jit(pure)
        return {"jitted": jitted, "cell": cell}

    def warm_spec(self, spec) -> str:
        """AOT-compile one recorded ``cached_op`` manifest entry against
        this graph's live block — no real dispatch, just
        ``jit(...).lower().compile()`` through the executable table.
        Returns the warm outcome ("replayed"/"deduped"/"skipped")."""
        from .. import autograd as _ag
        from ..ndarray import zeros as _nd_zeros

        arg_avals = spec.get("args") or ()
        args = [_nd_zeros(tuple(shape), dtype=dtype)
                for shape, dtype in arg_avals]
        if not args:
            return "skipped"
        training = bool(spec.get("training", False))
        block = self.block
        params = [p for p in block.collect_params().values()]
        if any(p._data is None for p in params):
            try:
                with _ag.pause():
                    block._deferred_infer_shape(*args)
            except Exception:
                return "skipped"    # warm cannot settle this graph
            params = [p for p in block.collect_params().values()]
        ctx = args[0].context
        param_arrays = [p.data(ctx) for p in params]
        key = self._key_for(args, param_arrays, training)
        if key in self._cache:
            return "deduped"
        prev = _ag.set_training(training)
        try:
            entry = self._build(param_arrays, args, ctx, training)
        finally:
            _ag.set_training(prev)
        self._cache.insert(key, entry)
        return "replayed"

    def warmup(self, arg_specs, dtype="float32", ctx=None):
        """AOT-compile one cache entry per input signature, ahead of any
        real request (first bite of ROADMAP item 5 — a serving replica
        must start hot, not pay first-request trace+compile latencies).

        ``arg_specs``: iterable of input signatures. Each spec is either
        one shape tuple (single-input block) or a sequence of shape
        tuples (multi-input); ``dtype`` applies to every input, or pass
        ``(shape, dtype)`` pairs inside a multi-input spec-style list to
        mix — shapes whose first element is an ``int`` are treated as a
        single input.

        Drives a real zero-filled call through ``__call__`` per spec
        (inference mode, gradient tape paused), so both the trace cache
        here AND jax's executable cache are warm — a later request with
        that signature is a pure cache hit. A signature already seated
        by an AOT warm (manifest replay, a previous warmup) is skipped
        without dispatching — its executable exists, re-executing it
        would only burn device time per bucket per reload. Returns the
        number of entries newly compiled (0 = everything was already
        warm).
        """
        from .. import autograd as _ag
        from ..ndarray import zeros as _nd_zeros

        before = len(self._cache)
        for spec in arg_specs:
            spec = list(spec) if not (spec and isinstance(spec[0], int)) \
                else [tuple(spec)]
            args = []
            for item in spec:
                if (len(item) == 2 and isinstance(item[0], (tuple, list))
                        and isinstance(item[1], str)):
                    shape, dt = tuple(item[0]), item[1]
                else:
                    shape, dt = tuple(item), dtype
                args.append(_nd_zeros(shape, ctx=ctx, dtype=dt))
            with _ag.pause():
                if self._is_warm(args):
                    continue
                try:
                    self(args)
                except DeferredInitializationError:
                    self.block._deferred_infer_shape(*args)
                    self(args)
        return len(self._cache) - before

    def _is_warm(self, args) -> bool:
        """Whether this exact call signature already has a compiled
        entry (telemetry-silent — a warmup probe is not a serving
        lookup)."""
        params = [p for p in self.block.collect_params().values()]
        if any(p._data is None for p in params):
            return False
        ctx = args[0].context if args else current_context()
        param_arrays = [p.data(ctx) for p in params]
        key = self._key_for(args, param_arrays, autograd.is_training())
        return key in self._cache


def warm_cached_op_spec(block, spec) -> str:
    """``compiler.warm_start``'s cached_op replay hook: seat one recorded
    input signature in ``block``'s graph cache, AOT-compiled. The block
    is hybridized if it is not already (a warm target must serve through
    the compiled path for the warm entry to be the one hit)."""
    if getattr(block, "_active", None) is False:
        block.hybridize()
    if block._cached_graph is None:
        block._cached_graph = _CachedGraph(block, block._flags)
    return block._cached_graph.warm_spec(spec)


class HybridBlock(Block):
    """Block that can be compiled to one XLA executable
    (reference: gluon/block.py::HybridBlock)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = {}
        self._cached_graph = None

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=2, forward_bulk_size=None,
                  backward_bulk_size=None, **kwargs):
        """Compile this block (reference: HybridBlock.hybridize; the
        CachedOpConfig flags map to XLA behaviors — static_alloc/bulking are
        native to XLA, kept for API compat)."""
        self._active = active
        self._flags = {"static_alloc": static_alloc, "static_shape": static_shape}
        self._cached_graph = None
        # drop any optimize_for graph: its params are a folded COPY, so
        # it must not shadow the live params after a re-hybridize
        self._set_optimized_block(None)
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def _clear_cached_op(self):
        self._cached_graph = None

    def warmup(self, input_shapes, dtype="float32", ctx=None):
        """Pre-trace + compile the hybridized graph for every signature
        in ``input_shapes`` (see :meth:`_CachedGraph.warmup`) so no
        real request pays a first-call compile — the serving bucket
        grid's load-time hook. Requires :meth:`hybridize` first; returns
        the number of entries newly compiled."""
        if not self._active:
            raise MXNetError(
                f"{self.name}: warmup() requires hybridize() — only a "
                "compiled block has a graph cache to warm")
        if self._cached_graph is None:
            self._cached_graph = _CachedGraph(self, self._flags)
        return self._cached_graph.warmup(input_shapes, dtype=dtype, ctx=ctx)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes from sample inputs."""
        self._deferred_infer_shape(*args)

    def _deferred_infer_shape(self, *args):
        with autograd.pause():
            self._eager_forward(*args)

    # ------------------------------------------------------------------
    def forward(self, *args):
        from ..symbol import Symbol as _Sym

        if args and isinstance(args[0], _Sym):
            return self._symbolic_forward(*args)
        opt = getattr(self, "_optimized_block", None)
        if opt is not None and args and isinstance(args[0], NDArray):
            # optimize_for swapped in a backend-transformed graph
            return opt(*args)
        if self._active and args and isinstance(args[0], NDArray) \
                and not mutation.is_tracing():
            if self._cached_graph is None:
                self._cached_graph = _CachedGraph(self, self._flags)
            try:
                return self._cached_graph(list(args))
            except DeferredInitializationError:
                self._deferred_infer_shape(*args)
                return self._cached_graph(list(args))
        return self._eager_forward(*args)

    def _symbolic_forward(self, *args):
        """Trace hybrid_forward with Symbol proxies (reference:
        HybridBlock._build_cache's CachedOp graph construction; here it
        serves `export()` → symbol.json). Parameters become variables named
        by their full parameter name, so the exported graph binds against
        the saved .params file."""
        from .. import symbol as sym_mod

        pdata = {}
        for name, p in self._reg_params.items():
            pdata[name] = sym_mod.var(p.name)
        return self.hybrid_forward(sym_mod, *args, **pdata)

    def _eager_forward(self, *args):
        """Un-compiled forward: resolve params and call hybrid_forward."""
        from .. import ndarray as nd_mod

        ctx = None
        for a in args:
            if isinstance(a, NDArray):
                ctx = a.context
                break
        if ctx is None:
            ctx = current_context()
        try:
            pdata = {name: p.data(ctx) for name, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._infer_param_shapes(*args)
            pdata = {name: p.data(ctx) for name, p in self._reg_params.items()}
        return self.hybrid_forward(nd_mod, *args, **pdata)

    def _infer_param_shapes(self, *args):
        """Layer-specific deferred-shape resolution; layers with deferred
        params override (reference: the nnvm infer_shape pass feeding
        _finish_deferred_init)."""
        raise DeferredInitializationError(
            f"{self.name}: parameter shapes are unknown and "
            f"{type(self).__name__} does not implement shape inference; "
            "initialize with explicit shapes")

    def hybrid_forward(self, F, *args, **params):
        raise NotImplementedError

    def export(self, path, epoch=0):
        """Export architecture + params (reference: HybridBlock.export →
        prefix-symbol.json + prefix-%04d.params)."""
        from ..symbol.export import export_hybrid_block

        return export_hybrid_block(self, path, epoch)

    def optimize_for(self, x, backend=None, **kwargs):
        """Apply a subgraph backend to this block (reference:
        HybridBlock.optimize_for). With a backend: symbolically trace,
        run the backend's registered passes (mxnet_tpu.subgraph), and
        swap the block's forward to the transformed graph — the same
        replace-in-place contract as upstream. Without: just hybridize
        (XLA fuses natively).

        The swapped-in graph holds its own (possibly weight-FOLDED)
        parameter copies — an inference artifact. ``hybridize()`` or
        ``load_parameters()`` clears it and reconnects the live params;
        re-run optimize_for afterwards if wanted."""
        self.hybridize()
        if backend is None:
            return self(x)
        from .. import subgraph
        from ..symbol.export import trace_symbol

        sym, arg_params, aux_params = trace_symbol(self)
        sym = subgraph.apply_backend(backend, sym, arg_params, aux_params,
                                     **kwargs)
        opt = SymbolBlock(sym, self._sym_trace_inputs(sym, arg_params,
                                                      aux_params))
        for name, arr in list(arg_params.items()) + list(aux_params.items()):
            p = opt.collect_params()[name]
            p.shape = tuple(arr.shape)
            p.initialize(force_reinit=True)
            p.set_data(arr)
        self._set_optimized_block(opt)
        return self(x)

    def _set_optimized_block(self, blk):
        # bypass __setattr__: the swapped-in graph is an inference
        # artifact, NOT a child (its folded param copies must not appear
        # in collect_params / save_parameters)
        self.__dict__["_optimized_block"] = blk
        self._children.pop("_optimized_block", None)

    @staticmethod
    def _sym_trace_inputs(sym, arg_params, aux_params):
        from ..symbol import var

        return [var(n) for n in sym.list_arguments()
                if n not in arg_params and n not in aux_params]


class SymbolBlock(HybridBlock):
    """Import a symbolic graph as a Block (reference:
    gluon/block.py::SymbolBlock). Completed in mxnet_tpu/symbol."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        self._sym_outputs = outputs
        self._sym_inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        from ..symbol import Symbol

        out = outputs if isinstance(outputs, Symbol) else outputs[0]
        self._out_sym = outputs
        # register params for every non-input argument of the graph
        input_names = {s.name for s in self._sym_inputs}
        for name in out.list_arguments():
            if name not in input_names:
                self._reg_params[name] = self.params.get(
                    name, allow_deferred_init=True)
        for name in out.list_auxiliary_states():
            self._reg_params[name] = self.params.get(
                name, grad_req="null", allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from ..symbol import load as sym_load, var

        sym = sym_load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [var(n) for n in input_names]
        block = SymbolBlock(sym, inputs)
        if param_file is not None:
            block.load_parameters(param_file, ctx=ctx, cast_dtype=True,
                                  allow_missing=False, ignore_extra=False)
        return block

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        return {prefix + name: p for name, p in self._reg_params.items()}

    def hybrid_forward(self, F, *args, **params):
        from ..symbol.executor import eval_symbol

        feed = {s.name: a for s, a in zip(self._sym_inputs, args)}
        feed.update(params)
        out = eval_symbol(self._out_sym, feed)
        return out
