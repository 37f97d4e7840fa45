"""Canonical signature keying for every jit-compile cache in the repo.

Before the compilation service, five caches keyed executables five ways
(eager per-op ``lru_cache`` args, the fused-segment node-sig tuple,
``_CachedGraph``'s shape key, ``TrainStep._cache``'s batch key, the symbol
``Executor``'s train flag). A signature here is ONE canonical shape::

    SigKey(site, ident, avals, attrs, shardings, platform, routing, extra)

* ``site``     — which cache family owns the entry (``eager_op``,
  ``fused_segment``, ``cached_op``, ``train_step``, ``executor``);
* ``ident``    — what is being compiled (op name, graph fingerprint, node
  signature tuple);
* ``avals``    — input ``(shape, dtype)`` descriptors, where the site keys
  on them (the eager per-op cache deliberately does not: jax.jit retraces
  per shape underneath one entry);
* ``attrs``    — static attributes baked into the trace;
* ``shardings``— input layout descriptors, where the site shards;
* ``platform`` — the execution platform the body was traced FOR (op impls
  dispatch on it at trace time — Pallas kernels, int8 MXU paths);
* ``routing``  — trace-time routing env knobs (``_routing_knobs``): a knob
  toggle selects a different op body for the same signature, so it must
  key every cache (round-9 review finding);
* ``extra``    — site-specific residue (training flag, has_rng, ...).

Every field is a hashable tree of primitives, so a SigKey is usable as a
dict key directly, and :func:`fingerprint` gives a stable hex digest for
the on-disk signature manifest.
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import NamedTuple, Optional

__all__ = ["SigKey", "signature", "fingerprint", "routing_knobs",
           "graph_ident", "callable_ident", "encode", "decode"]


def routing_knobs() -> tuple:
    """Trace-time routing inputs that select a DIFFERENT op body for
    the same (op, attrs, shapes) signature — they must key every
    executable cache or a toggle would keep replaying the
    previously-traced body. Two env knobs, and what the trace in
    progress holds of the mesh (``parallel.mesh.kernel_routing``: none of
    it, the Pallas kernels giving way, or the kernels over the shards of
    ONE mesh's batch axes): an op first traced off-mesh — a shape probe —
    must not hand its kernel-carrying jaxpr to a step traced for four
    chips, nor a step on ``dp=4`` its ``shard_map`` to one on ``dp=2``."""
    from ..parallel.mesh import kernel_routing

    return (os.environ.get("MXNET_PALLAS_FUSED", "0") == "1",
            os.environ.get("MXNET_TPU_HASH_DROPOUT", "0") == "1",
            kernel_routing())


class SigKey(NamedTuple):
    site: str
    ident: object
    avals: tuple = ()
    attrs: tuple = ()
    shardings: tuple = ()
    platform: Optional[str] = None
    routing: tuple = ()
    extra: tuple = ()


def signature(site: str, ident, avals=(), attrs=(), shardings=(),
              platform=None, routing=None, extra=()) -> SigKey:
    """Build the canonical key. ``routing=None`` means "read the live env
    knobs now" — pass an explicit tuple only when replaying a recorded
    signature."""
    return SigKey(site, ident, tuple(avals), tuple(attrs), tuple(shardings),
                  platform, routing_knobs() if routing is None
                  else tuple(routing), tuple(extra))


# ---------------------------------------------------------------------------
# Tagged JSON codec: SigKeys and replay specs are nested tuples of
# primitives; JSON has no tuple, so tuples are tagged and restored exactly
# (tuple-vs-list identity matters — cache keys compare by ==/hash).
# ---------------------------------------------------------------------------

def _enc(obj):
    if isinstance(obj, tuple):
        return {"t": [_enc(x) for x in obj]}
    if isinstance(obj, list):
        return {"l": [_enc(x) for x in obj]}
    if isinstance(obj, dict):
        return {"d": [[_enc(k), _enc(v)] for k, v in obj.items()]}
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    # dtype objects, np scalars, ... — degrade to their canonical string
    return {"s": str(obj)}


def _dec(obj):
    if isinstance(obj, dict):
        if "t" in obj:
            return tuple(_dec(x) for x in obj["t"])
        if "l" in obj:
            return [_dec(x) for x in obj["l"]]
        if "d" in obj:
            return {_dec(k): _dec(v) for k, v in obj["d"]}
        if "s" in obj:
            return obj["s"]
    return obj


def encode(obj) -> str:
    """Deterministic JSON text for a primitive tree (tuples tagged)."""
    return json.dumps(_enc(obj), sort_keys=True, separators=(",", ":"))


def decode(text: str):
    return _dec(json.loads(text))


def fingerprint(obj) -> str:
    """Stable hex digest of a key / replay spec — the manifest's dedupe
    and lookup handle. Accepts a SigKey, tuple tree, or encoded str."""
    if not isinstance(obj, str):
        obj = encode(tuple(obj) if isinstance(obj, SigKey) else obj)
    return hashlib.sha256(obj.encode()).hexdigest()[:24]


# ---------------------------------------------------------------------------
# Graph identity: a structural fingerprint of a Block (architecture, not
# weights) so manifest entries recorded against replica 0 match replica N
# built from the same factory, and a restarted process can match entries
# against a freshly built net.
# ---------------------------------------------------------------------------

def graph_ident(block) -> str:
    """Structural fingerprint of a gluon Block: class tree + registered
    parameter names/dtypes/grad modes + hybridize flags. Two blocks built
    by the same factory get the same ident; weights don't matter
    (executables take parameter values as runtime inputs), and parameter
    SHAPES are deliberately excluded — a warm target may still carry
    deferred shapes, and the ident is a routing hint for
    :func:`~mxnet_tpu.compiler.warm_start` (the replay always re-lowers
    against the live block, so a loose match costs a compile, never a
    wrong executable)."""
    parts = []

    def walk(b, path):
        cls = type(b)
        parts.append((path, f"{cls.__module__}.{cls.__qualname__}",
                      callable_ident(getattr(cls, "hybrid_forward", None)
                                     or getattr(cls, "forward", None))))
        for name, p in sorted(getattr(b, "_reg_params", {}).items()):
            parts.append((path, name, str(p.dtype),
                          getattr(p, "grad_req", "write"),
                          getattr(p, "grad_stype", "default")))
        for name, child in getattr(b, "_children", {}).items():
            walk(child, f"{path}/{name}")

    walk(block, "")
    # falsy flags are the defaults: a fresh block ({}) and a plain
    # hybridize() ({'static_alloc': False, ...}) must share an ident —
    # warm targets are matched BEFORE the warm path hybridizes them
    flags = tuple(sorted(
        (k, v) for k, v in (getattr(block, "_flags", None) or {}).items()
        if v))
    return fingerprint(encode((tuple(parts), flags)))


def callable_ident(fn) -> str:
    """Behavioral fingerprint of a callable: qualified name + bytecode
    hash (a subclass that overrode forward, or an edited loss lambda,
    must not share a persisted executable with the original)."""
    if fn is None:
        return "none"
    target = getattr(fn, "__func__", fn)
    code = getattr(target, "__code__", None)
    name = f"{getattr(target, '__module__', '')}." \
           f"{getattr(target, '__qualname__', type(fn).__qualname__)}"
    if code is None:
        # callable object: identify by its class's __call__ bytecode
        call = getattr(type(fn), "__call__", None)
        code = getattr(call, "__code__", None)
        if code is None:
            return name
    return name + ":" + hashlib.sha256(code.co_code).hexdigest()[:12]
