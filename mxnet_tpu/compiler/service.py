"""The compilation service: one cache spine for every jit site.

Three pieces:

* :class:`SiteCache` — the shared LRU policy every compile cache routes
  through (eager per-op, fused segments, CachedOp graphs, TrainStep,
  symbol Executor). One keying scheme (:mod:`.keys`), per-site capacity,
  hit/miss telemetry (``mxnet_jit_cache_total{cache,result}``) and —
  new — observable eviction (``mxnet_jit_cache_evictions_total{cache}``
  plus a debug log of the evicted signature), so cache thrash is a
  metric, not a mystery regression.

* :class:`ExecutableTable` — the in-process executable store, keyed by
  lowered-HLO fingerprint with single-flight builds: when N serving
  replicas (or N warm-start threads) race to compile the same program,
  exactly one XLA compile runs; everyone else blocks briefly and shares
  the executable. This is what lets ``Router`` warm replicas
  concurrently without N× compile work.

* :func:`warm_start` — replay a signature manifest (:mod:`.manifest`)
  through ``jax.jit(...).lower().compile()`` BEFORE first traffic, on a
  small thread pool. Generalizes ``HybridBlock.warmup()``: one call
  warms eager-op executables, fused segments, CachedOp graphs (for the
  blocks you pass) and TrainSteps (for the steps you pass), so a serving
  replica, a hot-reload swap, or an elastic rejoiner starts hot.

Cold-start accounting: ``mark_event(name)`` records the first occurrence
of lifecycle milestones (``first_train_step``, ``first_response``,
``warm_start_done``) as seconds since package import, surfaced through
``events()`` and the ``mxnet_coldstart_seconds{event}`` gauge.
"""
from __future__ import annotations

import collections
import logging
import os
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Sequence

from . import keys, manifest as manifest_mod

__all__ = ["SiteCache", "ExecutableTable", "GuardedExec", "exec_table",
           "warm_start", "mark_event", "events", "seconds_since_import",
           "site_caches"]

_log = logging.getLogger(__name__)

_T0 = time.monotonic()          # package-import timestamp: cold-start zero
_events: Dict[str, float] = {}
_events_lock = threading.Lock()


def seconds_since_import() -> float:
    return time.monotonic() - _T0


def mark_event(name: str) -> Optional[float]:
    """Record a cold-start milestone (first occurrence only). Returns the
    seconds-since-import it was recorded at, or None if already marked."""
    with _events_lock:
        if name in _events:
            return None
        t = seconds_since_import()
        _events[name] = t
    try:
        from .. import telemetry
        from ..telemetry import _state as _tstate

        if _tstate.enabled:
            telemetry.record_cold_start(name, t)
    except Exception:
        pass
    return t


def events() -> Dict[str, float]:
    """Cold-start milestones recorded so far: name -> seconds since
    package import."""
    with _events_lock:
        return dict(_events)


# ---------------------------------------------------------------------------
# SiteCache
# ---------------------------------------------------------------------------

_MISS = object()


class SiteCache:
    """Thread-safe LRU over canonical signature keys for one cache site.

    ``maxsize=None`` = unbounded (the CachedOp / TrainStep / Executor
    policy — entries live as long as their owner). Lookups record
    hit/miss telemetry under the site name; evictions are counted and
    the evicted signature logged at debug, so thrash at any of the five
    sites shows up in ``mxnet_jit_cache_evictions_total{cache}``.
    """

    def __init__(self, site: str, maxsize: Optional[int] = None):
        self.site = site
        self.maxsize = maxsize
        self._od: "collections.OrderedDict" = collections.OrderedDict()
        self._lock = threading.Lock()

    def lookup(self, key, record: bool = True):
        """Value for ``key`` (LRU-touched) or the ``MISS`` sentinel;
        records one hit/miss telemetry sample unless ``record=False``."""
        with self._lock:
            val = self._od.get(key, _MISS)
            if val is not _MISS:
                self._od.move_to_end(key)
        if record:
            from .. import telemetry
            from ..telemetry import _state as _tstate

            if _tstate.enabled:
                telemetry.record_cache(self.site, hit=val is not _MISS)
        return val

    MISS = _MISS

    def insert(self, key, value) -> None:
        evicted = []
        with self._lock:
            self._od[key] = value
            self._od.move_to_end(key)
            if self.maxsize is not None:
                while len(self._od) > self.maxsize:
                    evicted.append(self._od.popitem(last=False))
        if evicted:
            from .. import telemetry
            from ..telemetry import _state as _tstate

            if _tstate.enabled:
                telemetry.record_cache_eviction(self.site, len(evicted))
            for k, _ in evicted:
                _log.debug("jit cache %r: evicted signature %r (capacity "
                           "%s)", self.site, k, self.maxsize)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._od

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)

    def clear(self) -> None:
        with self._lock:
            self._od.clear()

    def keys(self) -> list:
        with self._lock:
            return list(self._od)

    def values(self) -> list:
        with self._lock:
            return list(self._od.values())


# the five sites' caches that are process-global (graph-level caches are
# per-object and construct their own SiteCache with site= the same family
# name, so telemetry aggregates per family regardless of instance)
_site_caches: Dict[str, SiteCache] = {}
_site_lock = threading.Lock()


def site_caches() -> Dict[str, SiteCache]:
    with _site_lock:
        return dict(_site_caches)


def shared_cache(site: str, maxsize: Optional[int] = None) -> SiteCache:
    """Process-global SiteCache for ``site`` (created on first use)."""
    with _site_lock:
        c = _site_caches.get(site)
        if c is None:
            c = _site_caches[site] = SiteCache(site, maxsize)
        return c


# ---------------------------------------------------------------------------
# ExecutableTable — single-flight in-process executable dedupe
# ---------------------------------------------------------------------------

class _Pending:
    __slots__ = ("event",)

    def __init__(self):
        self.event = threading.Event()


class ExecutableTable:
    """fingerprint -> compiled executable, with single-flight builds.

    ``get_or_build(fp, build)``: the first caller for a fingerprint runs
    ``build()`` (an XLA compile); concurrent callers for the same
    fingerprint block until it lands and share the result. A failed
    build releases the slot so a later caller can retry. LRU-bounded —
    eviction only drops the dedupe handle, never a live executable (site
    caches hold their own references).
    """

    def __init__(self, maxsize: int = 4096):
        self.maxsize = maxsize
        self._od: "collections.OrderedDict" = collections.OrderedDict()
        self._lock = threading.Lock()
        self.builds = 0          # build() calls that ran
        self.dedup_hits = 0      # calls served from the table
        self.waits = 0           # calls that blocked on another's build

    def get_or_build(self, fp: str, build: Callable):
        while True:
            wait_on = None
            with self._lock:
                entry = self._od.get(fp)
                if entry is None:
                    self._od[fp] = _Pending()
                elif isinstance(entry, _Pending):
                    wait_on = entry.event
                    self.waits += 1
                else:
                    self._od.move_to_end(fp)
                    self.dedup_hits += 1
                    return entry[0]
            if wait_on is not None:
                wait_on.wait()
                continue     # re-read: done (hit) or removed (retry)
            try:
                value = build()
            except BaseException:
                with self._lock:
                    entry = self._od.pop(fp, None)
                if isinstance(entry, _Pending):
                    entry.event.set()
                raise
            evicted = []
            with self._lock:
                pending = self._od.get(fp)
                self._od[fp] = (value,)
                self._od.move_to_end(fp)
                self.builds += 1
                while len(self._od) > self.maxsize:
                    k, v = self._od.popitem(last=False)
                    if isinstance(v, _Pending):   # never evict in-flight
                        self._od[k] = v
                        self._od.move_to_end(k, last=False)
                        break
                    evicted.append(k)
            if isinstance(pending, _Pending):
                pending.event.set()
            return value

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._od), "builds": self.builds,
                    "dedup_hits": self.dedup_hits, "waits": self.waits}

    def clear(self) -> None:
        with self._lock:
            pending = [v for v in self._od.values()
                       if isinstance(v, _Pending)]
            self._od.clear()
        for p in pending:
            p.event.set()


exec_table = ExecutableTable()


class GuardedExec:
    """An AOT-compiled executable with a traceable fallback.

    The compiled path serves the exact avals it was lowered for — the
    overwhelmingly common case after a warm start. Two escape hatches:

    * **tracer operands** (the call sits inside someone else's trace —
      ``jax.vjp`` over a hybridized block under ``autograd.record``): a
      ``Compiled`` cannot be transformed, so the call routes through the
      jit fallback for THAT call only; eager/serving calls keep the
      compiled executable.
    * **aval mismatch** (weak-typed scalar const, layout drift): fall
      back permanently — identical HLO, identical numerics, one retrace.
    """

    __slots__ = ("compiled", "_fallback_factory", "_fallback",
                 "_permanent")

    def __init__(self, compiled, fallback_factory: Callable):
        self.compiled = compiled
        self._fallback_factory = fallback_factory
        self._fallback = None
        self._permanent = False

    def _fb(self):
        if self._fallback is None:
            self._fallback = self._fallback_factory()
        return self._fallback

    def __call__(self, *args):
        if self._permanent:
            return self._fb()(*args)
        import jax

        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(args)):
            return self._fb()(*args)
        try:
            return self.compiled(*args)
        except (TypeError, ValueError) as e:
            _log.debug("AOT executable aval mismatch (%s); falling back "
                       "to jit retrace", e)
            self._permanent = True
            return self._fb()(*args)

    @property
    def __wrapped__(self):
        """The raw pure function, like ``jax.jit``'s ``__wrapped__`` —
        introspection (jaxpr probes in tests) keeps working on sealed
        entries."""
        return self._fb().__wrapped__


def fingerprint_lowered(lowered) -> str:
    """Stable fingerprint of a ``jax.stages.Lowered`` — the
    ExecutableTable key. Uses the lowered StableHLO text: two replicas of
    one architecture lower to byte-identical modules, different programs
    don't."""
    import hashlib

    text = lowered.as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:32]


# ---------------------------------------------------------------------------
# Persistent exported executables: the traced program itself on disk.
#
# The jax persistent cache removes the XLA COMPILE from a warm start, but
# every process still pays the Python trace per signature. jax.export
# serializes the traced+lowered StableHLO module; a warm process
# deserializes it (milliseconds), wraps it in a thin jit, and compiles —
# which is then a persistent-cache disk hit. Net: warm start skips both
# the trace and the compile. Blobs live under
# ``<persistent.base_dir()>/exported/<signature-fp>.shlo``, keyed by the
# CANONICAL signature fingerprint (architecture + aval + routing +
# platform + jax version), never by Python object identity.
# ---------------------------------------------------------------------------

def _exported_path(sig_fp: str) -> Optional[str]:
    from . import persistent

    if not persistent.cache_dir():
        return None
    return os.path.join(persistent.base_dir(), "exported",
                        sig_fp + ".shlo")


def _avals_match(exported, args) -> bool:
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    in_avals = exported.in_avals
    if len(leaves) != len(in_avals):
        return False
    return all(tuple(a.shape) == tuple(l.shape) and a.dtype == l.dtype
               for a, l in zip(in_avals, leaves))


def seal_executable(sig_fp: str, jitted, args, fallback: Callable):
    """AOT-compile ``jitted`` at ``args`` (ShapeDtypeStructs) through the
    full persistence stack: in-process executable table (single-flight,
    keyed by the canonical signature fingerprint), the on-disk exported
    StableHLO module (skips the trace on a warm start), and jax's
    persistent compile cache (skips the XLA compile). Returns a
    :class:`GuardedExec` (or the result of ``fallback()`` if AOT is not
    possible for this program — export unsupported for its features,
    donation active, ...).

    Callers must build ``sig_fp`` from everything that determines the
    traced program (graph identity incl. forward bytecode, every input
    aval, routing knobs, platform, jax version) — the blob store trusts
    it, with an aval cross-check on load as the backstop.
    """
    import jax

    def build():
        from jax import export as jexport

        exported = None
        path = _exported_path(sig_fp)
        if path and os.path.exists(path):
            try:
                with open(path, "rb") as f:
                    exported = jexport.deserialize(f.read())
                if not _avals_match(exported, args):
                    exported = None
            except Exception:
                exported = None
        if exported is None:
            exported = jexport.export(jitted)(*args)
            if path:
                try:
                    from ..checkpoint import atomic_write

                    os.makedirs(os.path.dirname(path), exist_ok=True)
                    atomic_write(path, exported.serialize())
                except Exception:
                    pass    # blob store is best-effort
        return jax.jit(exported.call).lower(*args).compile()

    try:
        compiled = exec_table.get_or_build(sig_fp, build)
    except Exception:
        _log.debug("seal_executable: AOT path failed for %s; using "
                   "fallback jit", sig_fp, exc_info=True)
        return fallback()
    return GuardedExec(compiled, fallback)


# ---------------------------------------------------------------------------
# warm_start
# ---------------------------------------------------------------------------

# Per-provider serialization, PROCESS-GLOBAL: two entries (or two whole
# warm_start calls — N replicas warming concurrently) targeting the SAME
# block or step must not race its parameter settle / state init; the
# interleaved initializer draws would even break bit-identity with a
# cold start. Weak-keyed so provider lifetimes stay the providers' own.
_provider_locks: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_provider_locks_guard = threading.Lock()


def _provider_lock(provider) -> threading.Lock:
    with _provider_locks_guard:
        lock = _provider_locks.get(provider)
        if lock is None:
            lock = _provider_locks[provider] = threading.Lock()
        return lock


def _resolve_entries(manifest) -> List[dict]:
    if manifest is None:
        m = manifest_mod.recorder()
        if m is None:
            m = manifest_mod.Manifest()
        return m.entries()
    if isinstance(manifest, str):
        return manifest_mod.Manifest(manifest).entries()
    if isinstance(manifest, manifest_mod.Manifest):
        return manifest.entries()
    return list(manifest)


def _replay_entry(entry: dict, blocks_by_ident: dict,
                  steps_by_ident: dict) -> str:
    site, spec = entry["site"], entry["spec"]
    if site == "eager_op":
        from ..ops import registry

        return registry.warm_eager_spec(spec)
    if site == "fused_segment":
        from ..ops import registry

        return registry.warm_fused_spec(spec)
    if site == "cached_op":
        block = blocks_by_ident.get(spec.get("graph")) \
            if isinstance(spec, dict) else None
        if block is None:
            return "skipped"
        from ..gluon import block as block_mod

        return block_mod.warm_cached_op_spec(block, spec)
    if site == "train_step":
        step = steps_by_ident.get(spec.get("ident")) \
            if isinstance(spec, dict) else None
        if step is None:
            return "skipped"
        return step.warm_from_spec(spec)
    if site == "optimizer_sweep":
        # needs no provider: the spec fully determines the traced sweep
        # (family + hyperparams + bucket layout), so a fresh process
        # rebuilds and AOT-compiles it before the first Trainer.step
        from ..optimizer import multi_tensor

        return multi_tensor.warm_sweep_spec(spec)
    return "skipped"    # executor: replay needs a bound symbol graph


def warm_start(manifest=None, *, blocks: Sequence = (),
               train_steps: Sequence = (),
               max_workers: Optional[int] = None) -> dict:
    """Replay a signature manifest so this process starts hot.

    ``manifest``: a path, a :class:`~.manifest.Manifest`, a pre-loaded
    entry list, or None (= the active recorder's journal, else the
    default manifest under ``MXNET_XLA_CACHE_DIR``).

    ``blocks``: live HybridBlocks to warm ``cached_op`` entries against,
    matched by structural :func:`~.keys.graph_ident` — pass the model a
    serving replica is about to serve. ``train_steps``: live TrainSteps
    to warm ``train_step`` entries against (an elastic rejoiner's step).
    Op-level entries (``eager_op``, ``fused_segment``) replay with no
    provider.

    Compiles run on a thread pool; signatures another thread (or another
    replica of this process) already built are deduped through the
    in-process :class:`ExecutableTable` — replica N never re-compiles
    what replica 0 compiled. Returns a report dict:
    ``{"replayed", "deduped", "skipped", "failed", "entries", "seconds"}``.
    """
    from concurrent.futures import ThreadPoolExecutor

    t0 = time.perf_counter()
    entries = _resolve_entries(manifest)
    report = {"replayed": 0, "deduped": 0, "skipped": 0, "failed": 0,
              "entries": len(entries), "seconds": 0.0}
    if entries:
        blocks_by_ident = {keys.graph_ident(b): b for b in blocks}
        steps_by_ident = {s.warm_ident(): s for s in train_steps}

        def _provider(entry):
            spec = entry.get("spec")
            if not isinstance(spec, dict):
                return None
            if entry["site"] == "cached_op":
                return blocks_by_ident.get(spec.get("graph"))
            if entry["site"] == "train_step":
                return steps_by_ident.get(spec.get("ident"))
            return None

        def one(entry):
            try:
                prov = _provider(entry)
                if prov is None:
                    return _replay_entry(entry, blocks_by_ident,
                                         steps_by_ident)
                with _provider_lock(prov):
                    return _replay_entry(entry, blocks_by_ident,
                                         steps_by_ident)
            except Exception:
                _log.debug("warm_start: replay failed for site %s",
                           entry.get("site"), exc_info=True)
                return "failed"

        if max_workers is None:
            # auto: XLA:CPU compiles already fan out across every host
            # core, so warm THREADS only contend (measured 6x slower at
            # 4 workers); accelerator compiles are per-device-pipe and
            # overlap well
            import jax

            max_workers = 1 if jax.default_backend() == "cpu" else 4
        n_workers = max(1, min(max_workers, len(entries)))
        if n_workers == 1:
            outcomes = [one(e) for e in entries]
        else:
            with ThreadPoolExecutor(
                    max_workers=n_workers,
                    thread_name_prefix="mx-warm") as pool:
                outcomes = list(pool.map(one, entries))
        for oc in outcomes:
            report[oc if oc in report else "failed"] += 1
    report["seconds"] = time.perf_counter() - t0
    mark_event("warm_start_done")
    try:
        from .. import telemetry
        from ..telemetry import _state as _tstate

        if _tstate.enabled:
            for oc in ("replayed", "deduped", "skipped", "failed"):
                if report[oc]:
                    telemetry.record_warm_start(oc, report[oc])
    except Exception:
        pass
    return report
