"""Operator registry.

Reference: the nnvm op registry (``3rdparty/tvm/nnvm/include/nnvm/op.h``)
plus MXNet's per-op registration pattern
(``src/operator/... :: NNVM_REGISTER_OP(x).set_attr<FCompute>(...)``).

In the TPU-native build an operator is a **pure JAX function**
``fn(*tensors, **attrs) -> array | tuple`` registered by its MXNet name.
The same registry serves:

* the imperative frontend (``mx.nd.*`` wrappers dispatch here, with an
  eager per-op executable cache — the equivalent of MXNet pushing one op
  to the ThreadedEngine, see §7.3.2 of SURVEY.md);
* the symbolic frontend (``mx.sym.*`` records the op name + attrs into a
  graph; the Executor looks implementations up here at jit time);
* autograd (``jax.vjp`` over the pure function replaces per-op FGradient
  attrs — XLA derives the backward, no hand-written grads needed except
  where MXNet defines *non-mathematical* gradients, e.g. SoftmaxOutput,
  which use ``jax.custom_vjp`` in their impl).

Attr convention: tensor inputs are positional parameters; attributes are
keyword(-only) parameters with defaults. The wrapper generators use
``inspect`` to split the two.
"""
from __future__ import annotations

import functools
import inspect
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

from .. import engine, fault, telemetry
from ..fault import _state as _fault_state
from ..telemetry import _state as _telemetry_state

__all__ = ["OpDef", "AttrSpec", "attr", "register", "get_op", "list_ops",
           "alias", "validate_attrs", "execute_segment",
           "fused_segment_cache_clear"]


class AttrSpec(NamedTuple):
    """Typed operator-attribute declaration.

    The dmlc::Parameter equivalent (reference: ``include/dmlc/parameter.h``
    — typed param structs with range checks whose descriptions flow into
    the generated op docs). Declared per-op at ``register(attrs=[...])``;
    validated on every call; rendered into the ``mx.nd.*`` / ``mx.sym.*``
    wrapper docstrings.
    """

    name: str
    type: object = None          # python type or tuple of types
    doc: str = ""
    low: Optional[float] = None  # inclusive numeric bounds
    high: Optional[float] = None
    choices: Optional[tuple] = None

    def describe(self):
        parts = []
        if self.type is not None:
            ts = self.type if isinstance(self.type, tuple) else (self.type,)
            parts.append("/".join(t.__name__ for t in ts))
        if self.choices is not None:
            parts.append("one of " + ", ".join(map(repr, self.choices)))
        if self.low is not None or self.high is not None:
            lo = "-inf" if self.low is None else self.low
            hi = "inf" if self.high is None else self.high
            parts.append(f"range [{lo}, {hi}]")
        return ", ".join(parts)


def attr(name, type=None, doc="", low=None, high=None, choices=None):
    return AttrSpec(name, type, doc, low, high,
                    tuple(choices) if choices is not None else None)


_COERCIBLE = {
    int: (int,),
    float: (int, float),
    bool: (bool, int),
    str: (str,),
    tuple: (tuple, list, int),
}


def validate_attrs(opdef: "OpDef", attrs: Dict) -> None:
    """Raise a typed MXNetError naming the op, attribute and constraint
    for out-of-spec attribute values. Undeclared attributes pass (specs
    cover the documented surface, not every internal knob)."""
    specs = opdef.attr_specs
    if not specs:
        return
    from ..base import MXNetError

    import numpy as _np

    for k, v in attrs.items():
        spec = specs.get(k)
        if spec is None or v is None:
            continue
        if isinstance(v, (_np.generic,)):
            v = v.item()
        if spec.type is not None:
            want = spec.type if isinstance(spec.type, tuple) else (spec.type,)
            ok = any(isinstance(v, _COERCIBLE.get(t, (t,))) for t in want)
            # bools are ints in python — reject bool where int expected
            if ok and bool not in want and isinstance(v, bool):
                ok = False
            if not ok:
                raise MXNetError(
                    f"{opdef.name}: attribute {k}={v!r} has type "
                    f"{type(v).__name__}; expected {spec.describe()}")
        if spec.choices is not None and v not in spec.choices:
            raise MXNetError(
                f"{opdef.name}: attribute {k}={v!r} must be "
                f"{spec.describe()}")
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if not isinstance(item, (int, float)) or isinstance(item, bool):
                continue
            if spec.low is not None and item < spec.low:
                raise MXNetError(
                    f"{opdef.name}: attribute {k}={v!r} below "
                    f"{spec.describe()}")
            if spec.high is not None and item > spec.high:
                raise MXNetError(
                    f"{opdef.name}: attribute {k}={v!r} above "
                    f"{spec.describe()}")


def render_attr_docs(opdef: "OpDef") -> str:
    """Numpy-style attribute section for generated wrapper docstrings."""
    if not opdef.attr_specs:
        return ""
    lines = ["", "", "Attributes", "----------"]
    for spec in opdef.attr_specs.values():
        head = spec.name
        desc = spec.describe()
        if desc:
            head += f" : {desc}"
        lines.append(head)
        if spec.doc:
            lines.append(f"    {spec.doc}")
    return "\n".join(lines)


class OpDef(NamedTuple):
    name: str
    fn: Callable
    # names of tensor (array) parameters, in order
    tensor_params: tuple
    # tensor params that may be None (optional inputs like bias)
    optional_tensor_params: frozenset
    # attr param names
    attr_params: tuple
    # whether the fn consumes a PRNG key as first argument (random ops)
    needs_rng: bool
    # number of outputs; None = infer from returned tuple
    num_outputs: Optional[int]
    # if True, the imperative wrapper resolves autograd.is_training() and
    # passes it as the `_training` attr
    pass_training_flag: bool
    # accepts variable number of tensor inputs as a leading list
    variadic: bool
    # op must run untraced (dynamic output shapes — e.g. boolean_mask)
    eager_only: bool
    # typed attribute declarations (AttrSpec by name); None = undeclared
    attr_specs: Optional[Dict] = None
    # fn has **kwargs: forward ALL attrs, not just declared attr_params
    # (the `Custom` op's user-defined attribute surface)
    var_attrs: bool = False
    # optional attrs -> bool predicate: draw/consume a PRNG key only when
    # it returns True (ops like sdp_attention that are random only when a
    # dropout attr is set — an unconditional draw would advance the
    # global stream on every eval-mode call, a reproducibility trap).
    # When gated off the fn still receives rng=None positionally.
    rng_gate: Optional[Callable] = None


_REGISTRY: Dict[str, OpDef] = {}


def register(
    name: Optional[str] = None,
    aliases: Sequence[str] = (),
    needs_rng: bool = False,
    num_outputs: Optional[int] = None,
    pass_training_flag: bool = False,
    variadic: bool = False,
    eager_only: bool = False,
    attrs: Sequence[AttrSpec] = (),
    rng_gate: Optional[Callable] = None,
):
    """Decorator registering a pure-JAX op implementation.

    ``attrs``: optional typed AttrSpec declarations (the dmlc::Parameter
    equivalent) — validated on every call, rendered into wrapper docs.
    """

    def deco(fn):
        opname = name or fn.__name__
        sig = inspect.signature(fn)
        tensor_params: List[str] = []
        optional: List[str] = []
        attr_params: List[str] = []
        for pname, p in sig.parameters.items():
            if needs_rng and pname == "rng":
                continue
            if pname == "_training":
                continue
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
                if p.kind == p.POSITIONAL_OR_KEYWORD and p.default is not inspect.Parameter.empty and not _is_tensor_default(p.default):
                    attr_params.append(pname)
                else:
                    tensor_params.append(pname)
                    if p.default is None:
                        optional.append(pname)
            elif p.kind == p.KEYWORD_ONLY:
                attr_params.append(pname)
            elif p.kind == p.VAR_POSITIONAL:
                # variadic tensor inputs (e.g. Concat, add_n)
                tensor_params.append(pname)
        opdef = OpDef(
            name=opname,
            fn=fn,
            tensor_params=tuple(tensor_params),
            optional_tensor_params=frozenset(optional),
            attr_params=tuple(attr_params),
            needs_rng=needs_rng,
            num_outputs=num_outputs,
            pass_training_flag=pass_training_flag,
            variadic=variadic or any(
                p.kind == p.VAR_POSITIONAL for p in sig.parameters.values()
            ),
            eager_only=eager_only,
            attr_specs={s.name: s for s in attrs} if attrs else None,
            var_attrs=any(p.kind == p.VAR_KEYWORD
                          for p in sig.parameters.values()),
            rng_gate=rng_gate,
        )
        _REGISTRY[opname] = opdef
        for a in aliases:
            _REGISTRY[a] = opdef
        fn.__opdef__ = opdef
        return fn

    return deco


def _is_tensor_default(default):
    # positional params whose default is None are optional tensors (bias=None)
    return default is None


def alias(new_name: str, existing: str) -> None:
    _REGISTRY[new_name] = _REGISTRY[existing]


def get_op(name: str) -> OpDef:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise NotImplementedError(
            f"operator {name!r} is not implemented in mxnet_tpu "
            f"(see SURVEY.md §2.1 op families for the porting roadmap)"
        ) from None


def has_op(name: str) -> bool:
    return name in _REGISTRY


def list_ops() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Eager single-op executable cache.
#
# Reference analogue: MXNet's imperative path pays ~µs dispatch per op
# (SURVEY.md §3.1); ours pays a jit-cache lookup. Executables are cached by
# (op name, attr values); XLA itself caches by input shape/dtype underneath.
# Routed through the compilation service (compiler.SiteCache): one
# canonical keying scheme, LRU policy preserved, evictions observable.
# ---------------------------------------------------------------------------

from ..compiler import keys as _ckeys
from ..compiler import manifest as _cmanifest

# canonical name kept: block.py / step.py key their caches with the same
# knobs (the compilation service owns the definition now)
_routing_knobs = _ckeys.routing_knobs

_EAGER_CACHE = None


def _eager_cache():
    global _EAGER_CACHE
    if _EAGER_CACHE is None:
        from ..compiler import service as _csvc

        _EAGER_CACHE = _csvc.shared_cache("eager_op", maxsize=4096)
    return _EAGER_CACHE


def _build_eager(opname: str, attr_items: tuple, has_rng: bool):
    # `platform` keys the cache even though the traced fn only reads it
    # ambiently: op impls dispatch on current_execution_platform() at
    # TRACE time (Pallas kernels, int8 MXU paths), so one executable per
    # platform — otherwise the first-traced platform's body would be
    # served everywhere (round-3 review finding, verified live)
    import jax

    opdef = _REGISTRY[opname]
    attrs = dict(attr_items)

    if has_rng:
        def pure(rng, *tensors):
            return opdef.fn(rng, *tensors, **attrs)
    elif opdef.needs_rng:
        # rng draw gated off (rng_gate): the fn still expects the slot
        def pure(*tensors):
            return opdef.fn(None, *tensors, **attrs)
    else:
        def pure(*tensors):
            return opdef.fn(*tensors, **attrs)

    pure.__name__ = opname
    return jax.jit(pure)


def _eager_executable(opname: str, attr_items: tuple, n_tensors: int,
                      has_rng: bool, platform: str, routing: tuple = (),
                      record: bool = True):
    """(jitted fn, cache hit) through the service's eager_op site cache."""
    cache = _eager_cache()
    key = _ckeys.signature("eager_op", opname, attrs=attr_items,
                           platform=platform, routing=routing,
                           extra=(n_tensors, has_rng))
    fn = cache.lookup(key, record=record)
    if fn is not cache.MISS:
        return fn, True
    fn = _build_eager(opname, attr_items, has_rng)
    cache.insert(key, fn)
    return fn, False


def _cached_call(opname: str, attr_items: tuple, n_tensors: int,
                 has_rng: bool, platform: str, routing: tuple = ()):
    """Compat shim over the service cache (amp and tests call this
    directly); telemetry-silent — the dispatch path records through
    :func:`_eager_executable`."""
    return _eager_executable(opname, attr_items, n_tensors, has_rng,
                             platform, routing, record=False)[0]


def _cached_call_clear():
    _eager_cache().clear()


_cached_call.cache_clear = _cached_call_clear


def _harmonize_devices(tensors):
    """Mixed single-device / mesh-sharded operands: replicate the
    single-device ones onto the sharded operand's mesh.

    This is what lets a model trained by parallel.TrainStep (params laid out
    over a Mesh) be used eagerly afterwards — ``net(x)`` with a host-side
    ``x`` — without the user re-placing anything. The reference's analogue
    is ``as_in_context`` coercion; here the "context" is the mesh layout.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    # In-trace operands are tracers on one logical device set already; and
    # Tracer.sharding raises an AttributeError whose MESSAGE construction
    # walks the whole jaxpr for provenance — profiled at ~70% of total
    # model trace time when this ran per-op (see PERF_HISTORY.md round 3).
    for t in tensors:
        if isinstance(t, jax.core.Tracer):
            return tensors

    mesh = None
    mixed = False
    for t in tensors:
        sh = getattr(t, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.num_devices > 1:
            if mesh is None:
                mesh = sh.mesh
        elif hasattr(t, "sharding"):
            mixed = True
    if mesh is None or not mixed:
        return tensors
    rep = NamedSharding(mesh, PartitionSpec())
    out = []
    for t in tensors:
        sh = getattr(t, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.num_devices > 1:
            out.append(t)
        else:
            out.append(jax.device_put(t, rep))
    return type(tensors)(out) if isinstance(tensors, tuple) else out


def eager_call(opdef: OpDef, tensors, attrs, rng=None):
    """Execute an op eagerly through the per-op executable cache.

    Telemetry (MXNET_TELEMETRY=1): per-op invocation count + host dispatch
    latency; disabled mode costs exactly this one branch. Fault site
    ``engine.dispatch`` (MXNET_FAULT_SPEC): one injection opportunity per
    dispatch — errors here propagate like a failed device op (the
    ThreadedVar ExceptionRef analogue); likewise one branch when off.
    """
    if _fault_state.enabled:
        fault.check("engine.dispatch", opdef.name)
    if _telemetry_state.enabled:
        t0 = time.perf_counter()
        try:
            return _eager_call(opdef, tensors, attrs, rng)
        finally:
            telemetry.record_op_dispatch(
                opdef.name, time.perf_counter() - t0)
    return _eager_call(opdef, tensors, attrs, rng)


def _eager_call(opdef: OpDef, tensors, attrs, rng=None):
    from ..base import current_execution_platform, execution_platform

    if opdef.attr_specs:
        validate_attrs(opdef, attrs)
    scope = engine.current_bulk_scope()
    if scope is not None and not engine.is_naive():
        res = _bulk_record(scope, opdef, tensors, attrs, rng)
        if res is _FLUSH_AND_RUN:
            # non-recordable op (eager-only / unhashable attrs / sparse-
            # grad / tracer input): flush trigger (c), then run eagerly
            scope.flush("unrecordable")
            tensors = [engine.concretize(t) for t in tensors]
        elif res is not _RUN_EAGER:
            return res
    else:
        # no recorder on THIS thread, but an input may be the pending
        # output of another thread's open segment (or of a scope running
        # under NaiveEngine) — materialize before eager dispatch. Scan
        # first: the common no-bulk case must not pay a list rebuild
        for t in tensors:
            if type(t) is engine.PendingValue:
                tensors = [engine.concretize(v)
                           if type(v) is engine.PendingValue else v
                           for v in tensors]
                break
    tensors = _harmonize_devices(tensors)
    attr_items = tuple(sorted(attrs.items(), key=lambda kv: kv[0]))
    try:
        hash(attr_items)
        uncached = opdef.eager_only
    except TypeError:  # unhashable attr (e.g. list) — run uncached
        uncached = True
    if not uncached and attrs.get("_sparse_uid") is not None:
        # row-sparse-grad ops must inline into the SURROUNDING trace:
        # their custom-VJP side channel (parallel.sparse_grad) logs
        # backward tracers, which would escape a per-op jit's scope
        from ..parallel.sparse_grad import sparse_grad_active

        uncached = sparse_grad_active()
    # pin the execution platform from the concrete operands so in-trace
    # kernel dispatch (Pallas flash) targets where the op actually runs
    sample = tensors[0] if tensors else None
    platform = current_execution_platform(sample)
    with execution_platform(platform):
        if uncached:
            if _telemetry_state.enabled:
                telemetry.record_xla_dispatch("eager_uncached")
            if rng is not None:
                return opdef.fn(rng, *tensors, **attrs)
            if opdef.needs_rng:
                return opdef.fn(None, *tensors, **attrs)
            return opdef.fn(*tensors, **attrs)
        routing = _routing_knobs()
        fn, hit = _eager_executable(opdef.name, attr_items, len(tensors),
                                    rng is not None, platform, routing)
        if _telemetry_state.enabled:
            telemetry.record_xla_dispatch("eager_op")
        if not hit and _cmanifest.recorder() is not None:
            _cmanifest.record_signature("eager_op", {
                "op": opdef.name, "attrs": attr_items,
                "avals": tuple((tuple(t.shape), str(t.dtype))
                               if hasattr(t, "shape") else None
                               for t in tensors),
                "has_rng": rng is not None, "platform": platform,
                "routing": routing})
        if rng is not None:
            return fn(rng, *tensors)
        return fn(*tensors)


# ---------------------------------------------------------------------------
# Bulked execution: record-vs-execute fork + fused-segment cache.
#
# Reference analogue: CachedOp — MXNet wins its imperative perf back by
# bulking op sequences into single engine pushes keyed by a graph signature.
# Here an ``engine.bulk`` scope records ops into an ``engine.Segment``; the
# segment lowers to ONE jitted function compiled through ``_FUSED_CACHE``,
# keyed by the full (op, attrs, input shape/dtype, wiring, live-output)
# sequence, so a repeated loop body replays a compiled executable with zero
# retracing. See engine.py for the scope/flush machinery.
# ---------------------------------------------------------------------------

_RUN_EAGER = object()       # don't record; no flush needed (independent op)
_FLUSH_AND_RUN = object()   # non-recordable: flush segment, then run eagerly

_jax_cached = None


def _jax_mod():
    """Cached jax module for the per-recorded-op path (this module keeps
    jax imports lazy, but a sys.modules lookup per recorded op is the same
    per-call overhead class the engine hot-path hoists removed)."""
    global _jax_cached
    if _jax_cached is None:
        import jax

        _jax_cached = jax
    return _jax_cached


def _bulk_record(scope, opdef: OpDef, tensors, attrs, rng):
    """Try to append this op to the thread's open bulk segment.

    Returns the op's result (PendingValue(s)) when recorded, or one of the
    ``_RUN_EAGER`` / ``_FLUSH_AND_RUN`` sentinels when the op must execute
    eagerly.
    """
    _jax = _jax_mod()

    if opdef.eager_only:
        return _FLUSH_AND_RUN
    attr_items = tuple(sorted(attrs.items(), key=lambda kv: kv[0]))
    try:
        hash(attr_items)
    except TypeError:  # unhashable attr (e.g. nested list) — not keyable
        return _FLUSH_AND_RUN
    if attrs.get("_sparse_uid") is not None:
        # row-sparse-grad side channel logs backward tracers that must not
        # cross a fused-segment jit boundary (same rule as the per-op cache)
        from ..parallel.sparse_grad import sparse_grad_active

        if sparse_grad_active():
            return _FLUSH_AND_RUN

    # classify inputs; rng (a concrete PRNG key) is a leading runtime arg
    # but NOT an array input for the creation-op test below — a zero-tensor
    # random sampler is a creation op and must take the _RUN_EAGER path
    raw_inputs = list(tensors)
    n_prefix = 0
    if rng is not None:
        raw_inputs.insert(0, rng)
        n_prefix = 1
    elif opdef.needs_rng:  # gated-off rng: fn still expects the slot
        raw_inputs.insert(0, None)
        n_prefix = 1
    staged = []        # ("r", pv) | ("a", value) | ("s", literal)
    aval_key = []      # hashable per-input descriptors for shape inference
    seg = scope.segment
    has_array_input = False
    for i, t in enumerate(raw_inputs):
        if type(t) is engine.PendingValue:
            c = t._concrete
            if c is not None:
                t = c  # already flushed: plain runtime arg
            elif seg is not None and t.segment is seg:
                has_array_input = True
                staged.append(("r", t))
                aval_key.append(("v", t.aval.shape, t.aval.dtype))
                continue
            else:
                # pending output of ANOTHER segment (cross-thread handoff
                # or pre-nesting leftovers): materialize it
                t = t.force()
        if isinstance(t, _jax.core.Tracer):
            # already inside someone else's trace — recording would leak
            # the tracer into the fused jit's scope
            return _FLUSH_AND_RUN
        if t is None or isinstance(t, (bool, int, float, complex, str)):
            staged.append(("s", t))
            aval_key.append(("s", t))
            continue
        if not hasattr(t, "shape"):
            return _FLUSH_AND_RUN
        sh = getattr(t, "sharding", None)
        if sh is not None and getattr(sh, "num_devices", 1) > 1:
            # multi-device operands keep the eager path (its device
            # harmonization logic); bulking targets single-device chains
            return _FLUSH_AND_RUN
        if i >= n_prefix:
            has_array_input = True
        staged.append(("a", t))
        aval_key.append(("v", tuple(t.shape), t.dtype))
    if not has_array_input:
        # creation-style op (zeros/arange/...): no dataflow into the
        # segment, so nothing to defer — run eagerly WITHOUT flushing
        return _RUN_EAGER

    if seg is not None and not seg.flushed:
        platform = seg.platform
    else:
        from ..base import current_execution_platform

        sample = next((t for k, t in staged
                       if k == "a" and hasattr(t, "devices")), None)
        platform = current_execution_platform(sample)

    try:
        out_avals, out_is_seq = _segment_avals(
            opdef.name, attr_items, tuple(aval_key), platform)
    except Exception:
        # abstract eval failed (value-dependent op, bad shapes, ...): the
        # eager path reproduces the exact per-op error at the right line
        return _FLUSH_AND_RUN

    seg = scope.open_segment(platform)
    with seg._lock:
        if seg.flushed:  # another thread forced a flush mid-record
            seg = scope.open_segment(platform)
        node_index = len(seg.nodes)
        input_specs = []
        sig_inputs = []
        for kind, v in staged:
            if kind == "r" and (v.segment is not seg
                                or v._concrete is not None):
                # the segment was flushed (and reopened) between staging
                # and commit — the dependency is concrete now
                kind, v = "a", (v._concrete if v._concrete is not None
                                else v.force())
            if kind == "r":
                spec = ("r", v.node_index, v.out_index)
                input_specs.append(spec)
                sig_inputs.append(spec)
            elif kind == "a":
                idx = seg.add_const(v)
                input_specs.append(("a", idx))
                sig_inputs.append(("a", idx, tuple(v.shape), str(v.dtype)))
            else:
                input_specs.append(("s", v))
                sig_inputs.append(("s", v))
        sig = (opdef.name, attr_items, tuple(sig_inputs))
        node = engine._SegmentNode(
            opdef.name, opdef.fn, attr_items, tuple(input_specs),
            len(out_avals), out_is_seq, sig)
        seg.nodes.append(node)
        pvs = [engine.PendingValue(seg, node_index, oi,
                                   _jax.ShapeDtypeStruct(shape, dtype))
               for oi, (shape, dtype) in enumerate(out_avals)]
        seg.out_refs.append([engine.weakref.ref(pv) for pv in pvs])
        full = len(seg.nodes) >= scope.max_size
    if full:
        seg.flush("size")  # trigger (b): segment reached bulk(size)
    if out_is_seq:
        return tuple(pvs)
    return pvs[0]


@functools.lru_cache(maxsize=8192)
def _segment_avals(opname: str, attr_items: tuple, aval_key: tuple,
                   platform: str):
    """Output (shape, dtype) sequence of one op via ``jax.eval_shape`` —
    cached so steady-state recording never re-traces. ``aval_key`` entries:
    ``("v", shape, dtype)`` for runtime args, ``("s", literal)`` for
    static scalars/None."""
    import jax

    from ..base import execution_platform

    opdef = _REGISTRY[opname]
    attrs = dict(attr_items)
    avals = [jax.ShapeDtypeStruct(k[1], k[2]) for k in aval_key
             if k[0] == "v"]

    def pure(*arrs):
        it = iter(arrs)
        args = [next(it) if k[0] == "v" else k[1] for k in aval_key]
        return opdef.fn(*args, **attrs)

    with execution_platform(platform):
        out = jax.eval_shape(pure, *avals)
    out_is_seq = isinstance(out, (tuple, list))
    outs = tuple(out) if out_is_seq else (out,)
    return tuple((tuple(o.shape), o.dtype) for o in outs), out_is_seq


# signature -> jitted fused function; LRU-bounded through the service's
# fused_segment site cache. The signature encodes the complete segment
# semantics (per-node op/attrs/static-literals/wiring, runtime-arg
# shapes+dtypes, live-output mask, platform), so a hit replays a compiled
# executable for a structurally identical segment. Evictions are counted
# (mxnet_jit_cache_evictions_total{cache="fused_segment"}) and the evicted
# signature logged at debug — cache thrash used to be silent here.
_FUSED_CACHE_MAX = 1024
_FUSED_CACHE = None


def _fused_cache():
    global _FUSED_CACHE
    if _FUSED_CACHE is None:
        from ..compiler import service as _csvc

        _FUSED_CACHE = _csvc.shared_cache("fused_segment",
                                          maxsize=_FUSED_CACHE_MAX)
    return _FUSED_CACHE


def fused_segment_cache_clear() -> None:
    _fused_cache().clear()


def _build_fused(nodes, live_mask):
    """Lower a recorded segment into one pure function and jit it. The
    closure captures node structure only — everything it captures is part
    of the cache signature, so reuse across segments is sound."""
    import jax

    from ..base import MXNetError

    def fused_segment(*consts):
        env = {}
        for ni, node in enumerate(nodes):
            args = []
            for spec in node.input_specs:
                kind = spec[0]
                if kind == "r":
                    args.append(env[(spec[1], spec[2])])
                elif kind == "a":
                    args.append(consts[spec[1]])
                else:
                    args.append(spec[1])
            try:
                out = node.fn(*args, **dict(node.attr_items))
            except Exception as e:
                # flush-time errors must name the originating op — the
                # user's call site is long gone by now
                raise MXNetError(
                    f"error while executing bulked segment at op #{ni} "
                    f"({node.name!r}): {e}") from e
            outs = out if isinstance(out, (tuple, list)) else (out,)
            for oi, o in enumerate(outs):
                env[(ni, oi)] = o
        return tuple(env[k] for k in live_mask)

    fused_segment.__name__ = "fused_segment"
    return jax.jit(fused_segment)


def execute_segment(seg, reason: str) -> None:
    """Flush one segment: one fused XLA dispatch through the signature-
    keyed cache; resolve live PendingValues. Called (exactly once per
    segment) by ``engine.Segment.flush`` with the segment lock held."""
    from ..base import execution_platform

    t0 = time.perf_counter()
    live = []
    for refs in seg.out_refs:
        for ref in refs:
            pv = ref()
            if pv is not None:
                live.append(pv)
    live_mask = tuple((pv.node_index, pv.out_index) for pv in live)
    node_sigs = tuple(n.sig for n in seg.nodes)
    routing = _routing_knobs()
    cache = _fused_cache()
    key = _ckeys.signature("fused_segment", node_sigs,
                           platform=seg.platform, routing=routing,
                           extra=(live_mask,))
    jitted = cache.lookup(key)
    hit = jitted is not cache.MISS
    if not hit:
        jitted = _build_fused(tuple(seg.nodes), live_mask)
        cache.insert(key, jitted)
        if _cmanifest.recorder() is not None:
            _cmanifest.record_signature("fused_segment", {
                "nodes": node_sigs, "live": live_mask,
                "platform": seg.platform, "routing": routing})
    with execution_platform(seg.platform):
        outs = jitted(*seg.consts)
    if _telemetry_state.enabled:
        telemetry.record_xla_dispatch("fused_segment")
        telemetry.record_bulk_flush(reason, len(seg.nodes),
                                    time.perf_counter() - t0)
    for pv, val in zip(live, outs):
        pv._concrete = val
        engine.track(val)
    from .. import profiler

    if profiler.state() == "run":
        profiler.record_span("Bulk::flush", time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Manifest warm-start replay (compiler.warm_start's op-level sites).
# ---------------------------------------------------------------------------


def _platform_available(platform) -> bool:
    import jax

    if not platform:
        return False
    try:
        return bool(jax.devices(platform))
    except Exception:
        return False


# (op key, avals) fingerprints already driven by warm_eager_spec: a
# reload (or replica N) replaying the same manifest must not re-dispatch
# every recorded op on device — one zero-filled drive per signature per
# process is the whole point
_WARMED_EAGER: set = set()
_warmed_eager_lock = threading.Lock()


def warm_eager_spec(spec: dict) -> str:
    """Replay one ``eager_op`` manifest entry: rebuild the per-op jitted
    executable and drive one zero-filled dispatch at the recorded avals so
    jax's executable cache (and the persistent disk tier) is hot before
    real traffic. Returns the warm outcome ("replayed"/"deduped"/
    "skipped")."""
    import jax.numpy as jnp

    from .. import random_state
    from ..base import execution_platform
    from ..compiler import keys as _keys

    opname = spec.get("op")
    platform = spec.get("platform")
    if opname not in _REGISTRY or not _platform_available(platform):
        return "skipped"
    attr_items = tuple(spec.get("attrs", ()))
    avals = spec.get("avals", ())
    has_rng = bool(spec.get("has_rng"))
    warmed_fp = _keys.fingerprint(_keys.encode(
        (opname, attr_items, avals, has_rng, platform,
         _routing_knobs())))
    with _warmed_eager_lock:
        if warmed_fp in _WARMED_EAGER:
            return "deduped"
    fn, hit = _eager_executable(opname, attr_items, len(avals), has_rng,
                                platform, _routing_knobs(), record=False)
    args = []
    for av in avals:
        if av is None:
            args.append(None)
        else:
            shape, dtype = av
            args.append(jnp.zeros(tuple(shape), dtype=dtype))
    with random_state.preserved_stream():
        rng = random_state.get_state_key() if has_rng else None
        with execution_platform(platform):
            out = fn(rng, *args) if has_rng else fn(*args)
    import jax

    jax.block_until_ready(out)
    # marked warm only AFTER the dispatch succeeds: a failed replay must
    # stay retryable on the next warm_start, not report "deduped" forever
    with _warmed_eager_lock:
        _WARMED_EAGER.add(warmed_fp)
    return "deduped" if hit else "replayed"


def warm_fused_spec(spec: dict) -> str:
    """Replay one ``fused_segment`` manifest entry: rebuild the segment
    program from the registry, AOT-compile it through the service's
    executable table (``jit(...).lower().compile()``) and seat it in the
    fused cache under the exact signature live recording computes — a
    later structurally identical segment flushes straight into the warm
    executable."""
    import jax

    from ..base import execution_platform
    from ..compiler import service as _csvc

    node_sigs = spec.get("nodes")
    live_mask = spec.get("live")
    platform = spec.get("platform")
    if not node_sigs or live_mask is None \
            or not _platform_available(platform):
        return "skipped"
    node_sigs = tuple(node_sigs)
    live_mask = tuple(live_mask)
    cache = _fused_cache()
    key = _ckeys.signature("fused_segment", node_sigs, platform=platform,
                           routing=_routing_knobs(), extra=(live_mask,))
    if key in cache:
        return "deduped"
    nodes = []
    const_avals = {}
    for nsig in node_sigs:
        opname, attr_items, sig_inputs = nsig
        opdef = _REGISTRY.get(opname)
        if opdef is None:
            return "skipped"
        input_specs = []
        for s in sig_inputs:
            if s[0] == "a":
                input_specs.append(("a", s[1]))
                const_avals[s[1]] = (tuple(s[2]), s[3])
            else:
                input_specs.append(tuple(s))
        nodes.append(engine._SegmentNode(
            opname, opdef.fn, tuple(attr_items), tuple(input_specs),
            0, False, nsig))
    nodes = tuple(nodes)
    if sorted(const_avals) != list(range(len(const_avals))):
        return "skipped"    # torn spec: const slots must be dense
    sds = [jax.ShapeDtypeStruct(const_avals[i][0], const_avals[i][1])
           for i in range(len(const_avals))]
    with execution_platform(platform):
        lowered = _build_fused(nodes, live_mask).lower(*sds)
        fp = _csvc.fingerprint_lowered(lowered)
        compiled = _csvc.exec_table.get_or_build(fp, lowered.compile)
    guarded = _csvc.GuardedExec(
        compiled, lambda: _build_fused(nodes, live_mask))
    cache.insert(key, guarded)
    return "replayed"
