"""Execution engine facade + bulked (lazy) imperative execution.

Reference: ``src/engine/threaded_engine.cc :: ThreadedEngine::PushAsync`` —
MXNet's dependency engine makes every op asynchronous: ops are pushed with
read/write variable lists and execute on worker threads; Python blocks only
at explicit sync points (``WaitToRead`` / ``asnumpy`` / ``WaitForAll``).

XLA/PjRt gives the same contract natively: every dispatched computation
returns a future-backed buffer immediately and ordering is guaranteed by
data dependence, so the heavy ThreadedEngine machinery (vars, dependency
counters, per-device worker pools — src/engine/threaded_engine_perdevice.cc)
collapses to a thin facade whose job is:

* the **Naive mode** switch (``MXNET_ENGINE_TYPE=NaiveEngine`` in the
  reference, ``set_engine_type('NaiveEngine')`` / env here): block after
  every op for debugging/de-flaking;
* ``wait_for_all`` / per-array ``wait_to_read`` sync points, which also
  re-raise any exception captured during async execution (reference:
  ThreadedVar ExceptionRef rethrow at WaitToRead);
* the ``bulk`` scope (reference: ``python/mxnet/engine.py :: bulk`` +
  ThreadedEngine op bulking): XLA only fuses *inside* one jit call, and the
  eager path dispatches one single-op ``jax.jit`` per NDArray op. Inside a
  ``bulk(size)`` scope ops are **recorded** into a per-thread segment
  instead of executing; the segment lowers into ONE fused XLA dispatch
  (compiled through a CachedOp-style signature-keyed cache in
  ``ops/registry.py``) when a sync point is hit, the segment reaches
  ``size`` ops, a non-recordable op arrives, or the scope exits.

This module owns the scope plumbing, the per-thread recorder state, the
pending-value placeholder (``PendingValue``) and the flush triggers; the
record-vs-execute fork and the fused-segment compile cache live in
``ops/registry.py``.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
import weakref

import jax

from . import telemetry
from .telemetry import _state as _telemetry_state

__all__ = ["set_engine_type", "engine_type", "is_naive", "wait_for_all",
           "bulk", "PendingValue", "Segment", "current_bulk_scope",
           "in_bulk_scope", "is_pending", "concretize"]

_state = threading.local()
_VALID = ("ThreadedEnginePerDevice", "ThreadedEngine", "NaiveEngine")


def _default_type() -> str:
    env = os.environ.get("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice")
    return env if env in _VALID else "ThreadedEnginePerDevice"


def engine_type() -> str:
    return getattr(_state, "engine_type", None) or _default_type()


def set_engine_type(name: str) -> None:
    if name not in _VALID:
        raise ValueError(f"unknown engine type {name!r}; one of {_VALID}")
    _state.engine_type = name


def is_naive() -> bool:
    return engine_type() == "NaiveEngine"


# Arrays whose async computation may still be in flight.  JAX tracks
# readiness itself; we only keep a registry so wait_for_all() can block on
# everything outstanding (reference: Engine::WaitForAll).
_live_arrays = []
_live_lock = threading.Lock()
_MAX_LIVE = 8192


def track(jax_array) -> None:
    if type(jax_array) is PendingValue:
        # recorded-but-not-executed payload: nothing async exists yet; the
        # concrete output is tracked when the owning segment flushes
        return
    # weak references only: the registry must never pin device buffers
    # (`weakref` import hoisted to module scope — it used to run on every
    # array creation; see PERF_HISTORY.md "engine hot-path imports")
    try:
        ref = weakref.ref(jax_array)
    except TypeError:  # non-weakrefable (plain scalar) — nothing async
        return
    n_evict = 0
    with _live_lock:
        _live_arrays.append(ref)
        if len(_live_arrays) > _MAX_LIVE:
            # compact collected (dead) entries first; halve only if still
            # over — those evictions drop STILL-LIVE refs out of
            # wait_for_all coverage, so they are counted (telemetry:
            # mxnet_engine_live_evictions_total) instead of silent
            _live_arrays[:] = [r for r in _live_arrays if r() is not None]
            if len(_live_arrays) > _MAX_LIVE:
                n_evict = len(_live_arrays) // 2
                del _live_arrays[:n_evict]
        n_live = len(_live_arrays)
    # record outside _live_lock: track() runs on every array creation and
    # telemetry takes its own lock — never nest the two
    if n_evict:
        telemetry.record_live_evictions(n_evict)
    if _telemetry_state.enabled:
        telemetry.set_live_arrays(n_live)


def wait_for_all() -> None:
    """Block until all outstanding async work is done; re-raises any
    exception captured during async execution (reference:
    ThreadedEngine::WaitForAll + exception rethrow). A sync point: flushes
    this thread's open bulk segment first."""
    scope = current_bulk_scope()
    if scope is not None:
        scope.flush("sync")
    # capture the flag ONCE: enable() from another thread mid-wait must
    # not pair an unset t0 with a recording exit (uptime-scale sample)
    rec = _telemetry_state.enabled
    t0 = time.perf_counter() if rec else 0.0
    with _live_lock:
        pending = [r() for r in _live_arrays]
        _live_arrays.clear()
    try:
        for arr in pending:
            # a donated (deleted) buffer has no work outstanding; its
            # array object may outlive it wherever a caller kept one
            if arr is None or (isinstance(arr, jax.Array)
                               and not isinstance(arr, jax.core.Tracer)
                               and arr.is_deleted()):
                continue
            jax.block_until_ready(arr)
    finally:
        if rec:
            telemetry.record_engine_wait(time.perf_counter() - t0)
            # arrays may have been tracked concurrently while we blocked
            with _live_lock:
                n_live = len(_live_arrays)
            telemetry.set_live_arrays(n_live)


# ---------------------------------------------------------------------------
# Bulked execution: per-thread segment recorder (reference: ThreadedEngine
# op bulking / CachedOp forward_bulk_size; design: LazyTensor-style deferral)
# ---------------------------------------------------------------------------


class PendingValue:
    """Placeholder payload for an output of a recorded (not yet executed)
    bulk-segment op. Quacks enough like a jax.Array for NDArray metadata
    (shape/dtype/ndim); any real data access goes through :meth:`force`,
    which flushes the owning segment."""

    __slots__ = ("segment", "node_index", "out_index", "aval", "_concrete",
                 "__weakref__")

    def __init__(self, segment: "Segment", node_index: int, out_index: int,
                 aval):
        self.segment = segment
        self.node_index = node_index
        self.out_index = out_index
        self.aval = aval          # jax.ShapeDtypeStruct
        self._concrete = None     # set by Segment flush

    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def ndim(self):
        return len(self.aval.shape)

    @property
    def size(self):
        n = 1
        for d in self.aval.shape:
            n *= d
        return n

    def force(self):
        """Materialize: flush the owning segment (sync-point trigger) and
        return the concrete jax.Array."""
        c = self._concrete
        if c is None:
            self.segment.flush("sync")
            c = self._concrete
            if c is None:
                from .base import MXNetError

                err = self.segment.error
                if err is not None:
                    # the segment already failed (possibly raised at an
                    # earlier sibling's sync point): re-raise for every
                    # pending output, reference ThreadedVar ExceptionRef
                    raise MXNetError(
                        f"bulk segment execution failed: {err}") from err
                raise MXNetError(  # pragma: no cover - lock-atomic
                    "bulk segment flushed without resolving a pending "
                    "output (engine bug)")
        return c


def is_pending(value) -> bool:
    """True for a PendingValue that has NOT been materialized yet (a
    resolved PendingValue may linger as an NDArray payload until the next
    read swaps it out — that array is no longer pending)."""
    return type(value) is PendingValue and value._concrete is None


def concretize(value):
    """PendingValue -> concrete jax.Array (flushing if needed); everything
    else passes through."""
    if type(value) is PendingValue:
        c = value._concrete
        return c if c is not None else value.force()
    return value


class _SegmentNode:
    """One recorded op: the pure fn, its attrs, and wiring into the segment.

    ``input_specs`` entries:
      ``("r", node_idx, out_idx)``  — output of an earlier node in the segment
      ``("a", const_idx)``          — runtime array argument (Segment.consts)
      ``("s", literal)``            — static python scalar / None
    ``sig`` additionally encodes const shapes/dtypes so it is a complete
    CachedOp-style signature element (op name, attrs, input shape/dtype seq).
    """

    __slots__ = ("name", "fn", "attr_items", "input_specs", "n_out",
                 "out_is_seq", "sig")

    def __init__(self, name, fn, attr_items, input_specs, n_out, out_is_seq,
                 sig):
        self.name = name
        self.fn = fn
        self.attr_items = attr_items
        self.input_specs = input_specs
        self.n_out = n_out
        self.out_is_seq = out_is_seq
        self.sig = sig


class Segment:
    """An open (recording) or flushed bulk segment.

    Thread-safety: the owning thread appends; any thread may force a
    PendingValue (e.g. an array handed across threads), so append and flush
    are serialized on ``_lock``. After flush the segment is immutable.
    """

    __slots__ = ("scope", "platform", "nodes", "consts", "_const_ids",
                 "out_refs", "flushed", "error", "_lock")

    def __init__(self, scope: "_BulkScope", platform: str):
        self.scope = scope
        self.platform = platform
        self.nodes = []         # List[_SegmentNode]
        self.consts = []        # runtime array args, in first-use order
        self._const_ids = {}    # id(value) -> const index (dedup)
        self.out_refs = []      # per node: list[weakref[PendingValue]]
        self.flushed = False
        self.error = None       # set if execution failed (rethrow at force)
        self._lock = threading.RLock()

    def __len__(self):
        return len(self.nodes)

    def add_const(self, value) -> int:
        # caller holds _lock (via record in ops/registry.py)
        idx = self._const_ids.get(id(value))
        if idx is None:
            idx = len(self.consts)
            self.consts.append(value)  # strong ref keeps id() valid
            self._const_ids[id(value)] = idx
        return idx

    def flush(self, reason: str) -> None:
        """Execute all recorded ops as one fused XLA dispatch and resolve
        every live PendingValue. Idempotent; safe from any thread."""
        with self._lock:
            if self.flushed:
                return
            self.flushed = True
            scope = self.scope
            if scope is not None and scope.segment is self:
                scope.segment = None
            if not self.nodes:
                return
            from .ops.registry import execute_segment

            try:
                execute_segment(self, reason)
            except BaseException as e:
                self.error = e
                raise
            finally:
                # resolved (or failed): drop the recorded graph and the
                # strong input refs — resolved PendingValues may outlive
                # the segment (as NDArray payloads until the next read)
                # and must not pin the input device buffers through it
                self.nodes = []
                self.consts = []
                self._const_ids.clear()
                self.out_refs = []


class _BulkScope:
    """Per-thread state for one ``engine.bulk(size)`` scope."""

    __slots__ = ("max_size", "segment")

    def __init__(self, max_size: int):
        self.max_size = max_size
        self.segment = None  # type: Segment | None

    def open_segment(self, platform: str) -> Segment:
        seg = self.segment
        if seg is None or seg.flushed:
            seg = Segment(self, platform)
            self.segment = seg
        return seg

    def flush(self, reason: str) -> None:
        seg = self.segment
        if seg is not None:
            seg.flush(reason)


_bulk_tls = threading.local()


def current_bulk_scope():
    """The innermost active ``bulk`` scope of THIS thread, or None. The
    recorder is strictly thread-local: ops on other threads execute
    eagerly regardless of this thread's scope."""
    return getattr(_bulk_tls, "scope", None)


def in_bulk_scope() -> bool:
    return current_bulk_scope() is not None


@contextlib.contextmanager
def bulk(size: int):
    """Bulked execution scope (reference: mx.engine.bulk / ThreadedEngine
    op bulking). Inside the scope, recordable imperative ops are deferred
    into a segment of at most ``size`` ops and executed as ONE fused XLA
    dispatch at the next flush trigger: a sync point (``asnumpy``,
    ``wait_to_read``, ``item``, printing, ``wait_for_all``), the ``size``
    cap, a non-recordable op (eager-only / unhashable attrs / sparse-grad
    / autograd recording), or scope exit.

    Results are semantically identical to eager execution; ``size`` bounds
    both deferral latency and compiled-segment size. Nesting flushes the
    outer scope's open segment at entry (clean segment boundaries) and the
    inner scope's at exit.
    """
    if isinstance(size, bool) or not isinstance(size, int):
        raise ValueError(
            f"bulk size must be an int >= 1, got {type(size).__name__} "
            f"{size!r}")
    if size < 1:
        raise ValueError(f"bulk size must be >= 1, got {size}")
    prev = current_bulk_scope()
    if prev is not None:
        prev.flush("nested_scope")
    scope = _BulkScope(size)
    _bulk_tls.scope = scope
    try:
        yield
    finally:
        _bulk_tls.scope = prev
        scope.flush("scope_exit")
