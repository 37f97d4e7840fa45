"""``mx.serving.Router`` — overload-safe multi-replica dispatch.

One :class:`~.server.Server` replica batches well (PR 6) but has no
failure story: a wedged or crashing replica takes its queue down with
it, and under overload it queues until every deadline blows. The router
is the serving analogue of the elastic training runtime (PR 8): scale
*as* a robustness layer. It fronts N ``Server`` replicas (one per
device or device group) behind the same ``submit() -> Future`` contract
and owns four concerns the single server cannot:

* **Least-loaded dispatch.** Each request is forwarded to the healthy
  replica with the fewest outstanding router-forwarded requests, so a
  slow replica sheds load to its siblings instead of growing a queue.

* **Health tracking.** A :class:`~.health.CircuitBreaker` per replica:
  ``MXNET_SERVING_BREAKER_FAILURES`` consecutive dispatch failures trip
  it OPEN, and so does a *hung dispatch* — the replica scheduler's
  heartbeat (touched once per loop iteration) going silent past
  ``MXNET_SERVING_DISPATCH_TIMEOUT`` while router requests are in
  flight there (a scheduler patiently filling a batch keeps touching;
  a wedged model dispatch does not). After a cooldown it goes HALF_OPEN
  and exactly one live request is routed through it as a probe —
  success re-admits the replica, failure re-opens it with a doubled
  cooldown. Probes take priority over least-loaded choice so recovery
  is detected under any traffic level.

* **Failover — no future is ever lost.** A failed or hung replica's
  in-flight requests are re-submitted to healthy replicas under a
  bounded retry budget (``MXNET_SERVING_RETRY_BUDGET`` extra
  dispatches, default 2). Every future submitted to the router
  resolves: with a result, or with a typed error
  (:class:`ServerOverloaded` at admission / queued past deadline,
  :class:`FailoverExhausted` when the budget is spent,
  :class:`MXNetError` on stop without drain). The first resolution
  wins; a late result from a replica already declared hung is dropped.

* **Admission control.** The router queue is bounded (``max_queue``)
  and sheds by *predicted deadline miss*: completion timestamps give a
  service-rate estimate, and a request whose predicted queue wait
  exceeds its own deadline is rejected **synchronously** with
  :class:`ServerOverloaded` — at 2x sustainable load the router keeps
  serving at capacity with bounded latency instead of queueing every
  request into a blown deadline.

A scheduler-liveness watchdog (the PR-8 heartbeat pattern, in-process
via :class:`~.health.Heartbeat`) covers the router's own dispatcher
thread: if the loop goes silent past ``MXNET_SERVING_WATCHDOG_TIMEOUT``
the monitor fails every queued future loudly and stops admission — a
wedged dispatcher must not turn into a queue nobody drains.

Fault sites: ``serving.route`` fires on every routing decision (a
transient routing fault costs one unit of the request's retry budget,
not replica health); ``serving.replica`` (and the per-instance
``serving.replica.<index>`` sub-sites) fire inside a replica's dispatch
— an injected fault there is a replica failure, a ``latency:S`` policy
past the dispatch timeout is a hang. ``tools/chaos_check.py``'s serving
gate kills one replica mid-traffic this way and asserts zero lost
futures, survivor bit-identity, and half-open re-admission.

Telemetry: ``mxnet_serving_replica_healthy{replica}`` (1 closed /
0.5 half-open / 0 open), ``mxnet_serving_breaker_transitions_total``,
``mxnet_serving_shed_total{reason}``,
``mxnet_serving_failover_total{replica}``,
``mxnet_serving_route_retry_total{reason}``,
``mxnet_serving_router_queue_depth``,
``mxnet_serving_router_queue_wait_seconds``.
"""
from __future__ import annotations

import logging
import threading
import time
import weakref
from collections import deque
from concurrent.futures import Future
from typing import List, Optional, Sequence

import numpy as np

from .. import fault, telemetry, tracing
from ..base import MXNetError
from ..fault import _state as _fault_state
from ..telemetry import _state as _telemetry_state
from ..tracing import _state as _tracing_state
from .health import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    Heartbeat,
    _env_float,
)
from .kvcache import CacheFull
from .server import DEFAULT_MODEL, Server, TenantThrottled

__all__ = ["Router", "ServerOverloaded", "FailoverExhausted",
           "ReplicaFault", "live_routers"]

_log = logging.getLogger(__name__)

# every running router, for the test-suite leak guard (mirrors
# server._live_servers)
_live_routers = weakref.WeakSet()


def live_routers():
    """Routers whose dispatcher thread is currently running."""
    return [r for r in list(_live_routers) if r.is_running]


class ServerOverloaded(MXNetError):
    """Typed admission-control rejection: the router queue is full, the
    predicted queue wait exceeds the request's deadline, or the request's
    deadline expired while it was still queued. Synchronous at
    ``submit`` whenever the overload is knowable there — never a hung
    future."""


class FailoverExhausted(MXNetError):
    """A request failed on every replica it was routed to and its retry
    budget (``MXNET_SERVING_RETRY_BUDGET``) is spent. Chained to the
    last underlying replica error."""


class ReplicaFault(MXNetError):
    """An injected ``serving.replica`` fault: the replica 'crashed' on
    this dispatch. Deliberately NOT retry-transient — a killed replica
    must fail over at the router, not retry locally inside the corpse."""


_HEALTH_VALUE = {CLOSED: 1.0, HALF_OPEN: 0.5, OPEN: 0.0}


class _RouteReq:
    """One routed request: the router-facing future plus retry state.
    ``resolve_*`` are first-wins (a failover copy and a late replica
    result may race) and always leave the future resolved."""

    __slots__ = ("sample", "future", "t_enqueue", "deadline", "attempts",
                 "started", "_lock", "trace", "span", "own_trace",
                 "model", "priority")

    def __init__(self, sample, deadline_s: float, model=None,
                 priority=None):
        # tenant fields ride the request through requeues and
        # failovers: a retried dispatch must land in the SAME tenant's
        # queue on the next replica
        self.model = model
        self.priority = priority
        self.sample = sample
        self.future = Future()
        self.t_enqueue = time.perf_counter()
        self.deadline = self.t_enqueue + deadline_s
        self.attempts = 0          # dispatch attempts so far
        self.started = False       # set_running_or_notify_cancel done
        self._lock = threading.Lock()
        # tracing (MXNET_TRACING=1): the request's Trace, its currently
        # open router.queue span, and whether this router minted the
        # trace (an ingress that handed it in finishes it instead)
        self.trace = None
        self.span = None
        self.own_trace = False

    def begin(self) -> bool:
        """First dispatch: flip the future to RUNNING; False if the
        caller already cancelled it."""
        if self.started:
            return True
        if not self.future.set_running_or_notify_cancel():
            return False
        self.started = True
        return True

    def resolve_result(self, result) -> bool:
        with self._lock:
            if self.future.done():
                return False
            if not self.started:
                if not self.future.set_running_or_notify_cancel():
                    return False
                self.started = True
            self.future.set_result(result)
            return True

    def resolve_exc(self, exc: BaseException) -> bool:
        with self._lock:
            if self.future.done():
                return False
            if not self.started:
                if not self.future.set_running_or_notify_cancel():
                    return False
                self.started = True
            self.future.set_exception(exc)
            return True


class _Flight:
    """One request currently forwarded to one replica. Holds the
    :class:`_Replica` OBJECT, not a position in the replica list — the
    list is mutable now (``add_replica``/``remove_replica``) and a
    positional index would dangle the moment the fleet changes under an
    outstanding dispatch."""

    __slots__ = ("req", "rep", "t_sent", "rfut", "probe", "span")

    def __init__(self, req, rep, t_sent, probe):
        self.req = req
        self.rep = rep
        self.t_sent = t_sent
        self.rfut = None
        self.probe = probe
        self.span = None      # router.attempt span (tracing on)


class _Replica:
    """Router-side state for one managed Server replica. ``index`` is a
    stable id assigned at admission (monotonic, never reused), not a
    list position."""

    __slots__ = ("server", "index", "breaker", "inflight", "n_ok",
                 "n_failed", "last_state", "draining", "crashes_seen")

    def __init__(self, server: Server, index: int,
                 failure_threshold, cooldown_s):
        self.server = server
        self.index = index
        self.breaker = CircuitBreaker(
            server.name, failure_threshold=failure_threshold,
            cooldown_s=cooldown_s)
        self.inflight = 0          # router-forwarded, not yet resolved
        self.n_ok = 0
        self.n_failed = 0
        self.last_state = CLOSED   # for transition counting
        self.draining = False      # remove_replica in progress: no new
        #                            dispatches, in-flight ones finish
        # last RemoteReplica.crash_count this router turned into a
        # breaker trip — seeded from the server's CURRENT count, not 0:
        # a worker with prior crash history re-admitted via add_replica
        # (or fronted by a new Router) must not trip its fresh breaker
        # for crashes that predate this membership
        self.crashes_seen = getattr(server, "crash_count", 0)


class Router:
    """Front N ``Server`` replicas behind one ``submit() -> Future``.

    ::

        reps = [serving.Server(build_net(), name=f"r{i}", ...)
                for i in range(n)]
        router = serving.Router(reps, slo_ms=50).start()
        fut = router.submit(sample)          # same contract as Server
        out = fut.result()                   # result or typed error
        router.stop()

    Replicas must share one bucket grid (same batch and shape buckets):
    responses must be bit-identical whichever replica serves them, and
    that only holds at matched buckets. ``start()`` starts replicas
    that are not already running; ``stop()`` stops every replica
    (pass ``stop_replicas=False`` to leave them serving).

    A replica may be an in-process :class:`Server` or an out-of-process
    :class:`~.remote.RemoteReplica` (same dispatch contract) — breakers,
    hung-dispatch detection, failover and drain apply identically, and
    a remote replica's ``crash_count`` (connection drop / ``waitpid``)
    trips its breaker immediately: process death is unambiguous,
    unlike a slow dispatch.
    """

    def __init__(self, replicas: Sequence[Server],
                 slo_ms: Optional[float] = None,
                 max_queue: int = 4096,
                 retry_budget: Optional[int] = None,
                 dispatch_timeout_s: Optional[float] = None,
                 watchdog_timeout_s: Optional[float] = None,
                 name: Optional[str] = None):
        replicas = list(replicas)
        if not replicas:
            raise MXNetError("Router needs at least one Server replica")
        g0 = replicas[0].grid
        for s in replicas[1:]:
            if s.grid.batch_buckets != g0.batch_buckets or \
                    s.grid.shape_buckets != g0.shape_buckets:
                raise MXNetError(
                    f"replica {s.name} has a different bucket grid than "
                    f"{replicas[0].name} — replicas must share one grid "
                    "(matched-bucket bit-identity)")
        names = [s.name for s in replicas]
        if len(set(names)) != len(names):
            raise MXNetError(f"replica names must be unique, got {names}")
        self._next_index = len(replicas)   # stable replica ids, never reused
        if max_queue < 1:
            raise MXNetError(f"max_queue must be >= 1, got {max_queue}")
        if retry_budget is None:
            retry_budget = int(_env_float("MXNET_SERVING_RETRY_BUDGET", 2))
        if retry_budget < 0:
            raise MXNetError(
                f"retry_budget must be >= 0, got {retry_budget}")
        if dispatch_timeout_s is None:
            dispatch_timeout_s = _env_float(
                "MXNET_SERVING_DISPATCH_TIMEOUT", 30.0)
        if dispatch_timeout_s < 0.2:
            # an idle replica scheduler touches its heartbeat every
            # <=0.1 s wait tick; a timeout inside that granularity
            # would declare healthy replicas hung
            raise MXNetError(
                "dispatch timeout must be >= 0.2 s (scheduler "
                f"heartbeat granularity), got {dispatch_timeout_s}")
        if watchdog_timeout_s is None:
            watchdog_timeout_s = _env_float(
                "MXNET_SERVING_WATCHDOG_TIMEOUT", 5.0)
        if watchdog_timeout_s <= 0:
            raise MXNetError(
                f"watchdog timeout must be > 0, got {watchdog_timeout_s}")
        self.name = name or f"router_{id(self):x}"
        self.grid = g0
        self.slo_s = (slo_ms / 1e3 if slo_ms is not None
                      else replicas[0].slo_s)
        if self.slo_s <= 0:
            raise MXNetError(f"slo_ms must be > 0, got {slo_ms}")
        self.max_queue = int(max_queue)
        self.retry_budget = int(retry_budget)
        self.dispatch_timeout_s = float(dispatch_timeout_s)
        self.watchdog_timeout_s = float(watchdog_timeout_s)
        # copy-on-write: fleet changes REPLACE the list (atomic store
        # under the GIL), so dispatcher/monitor threads iterating a
        # captured snapshot never see a half-mutated fleet
        self._replicas: List[_Replica] = [
            _Replica(s, i, None, None) for i, s in enumerate(replicas)]
        # serializes fleet admin (add/remove/rolling upgrade) — the
        # dispatch path never takes it
        self._admin_lock = threading.Lock()
        # tenant registry: name -> registration spec, so every replica
        # (including ones admitted later) serves the same model set and
        # submit() can reject an unknown tenant synchronously instead
        # of refuse-spinning it against the fleet
        self._models: dict = {}

        self._cond = threading.Condition()
        self._queue: deque = deque()
        self._flights: dict = {}            # id(flight) -> _Flight
        self._n_inflight = 0
        self._done_ts: deque = deque(maxlen=64)   # completion timestamps
        self._accepting = False
        self._running = False
        self._wedged = False
        self._routing: Optional[_RouteReq] = None   # popped, in _route
        self._thread: Optional[threading.Thread] = None
        self._monitor: Optional[threading.Thread] = None
        self._monitor_stop = threading.Event()
        self.hb = Heartbeat()
        # always-on light counters (telemetry has the full story)
        self.n_requests = 0
        self.n_shed = 0
        self.n_failovers = 0
        self.n_ok = 0
        self.n_errors = 0

    @property
    def _shed_arm_pending(self) -> int:
        # predicted-wait shedding arms only past this backlog (queued +
        # in flight): below a couple of full fleet batches the observed
        # completion rate measures demand, not capacity, and a burst
        # into an idle fleet would shed against a spuriously low
        # estimate. Backlog counts IN-FLIGHT too — under overload the
        # requests pile up in the replica queues, not the router's.
        # A property because the fleet is elastic now: the threshold
        # tracks the CURRENT replica count.
        return max(32, 2 * self.grid.max_batch * len(self._replicas))

    # -- replica fault plumbing ----------------------------------------
    def _replica_fault_hook(self, r: _Replica):
        """The ``serving.replica`` injection point, run INSIDE the
        replica's scheduler thread per dispatched batch. An injected
        fault is wrapped :class:`ReplicaFault` (non-transient: the
        replica's own ``serving.dispatch`` retry must NOT resurrect a
        killed replica — failover at the router is the recovery path);
        a ``latency:S`` policy sleeps here, which is exactly a hung
        dispatch."""
        name, idx = r.server.name, r.index

        def hook(sig):
            if not _fault_state.enabled:
                return
            sub = f"serving.replica.{idx}"
            try:
                fault.check("serving.replica", f"{name} batch={sig}")
                if fault.has_policy(sub):   # no double-count under '*'
                    fault.check(sub, f"{name} batch={sig}")
            except fault.FaultInjected as e:
                raise ReplicaFault(
                    f"replica {name} (index {idx}) failed: {e}") from e
        return hook

    # -- lifecycle -----------------------------------------------------
    @property
    def is_running(self) -> bool:
        return self._running or (self._thread is not None
                                 and self._thread.is_alive())

    def start(self) -> "Router":
        if self.is_running:
            raise MXNetError(f"{self.name}: already running")
        to_start = []
        for r in self._replicas:
            # hooks live only while the router does: an orphaned hook on
            # a server kept serving standalone would raise ReplicaFault
            # (deliberately non-transient) with no failover layer left
            r.server._pre_dispatch = self._replica_fault_hook(r)
            if not r.server.is_running:
                to_start.append(r.server)
        if len(to_start) == 1:
            to_start[0].start()
        elif to_start:
            # warm replicas CONCURRENTLY: Server.start() AOT-compiles the
            # whole bucket grid, and N replicas of one architecture used
            # to pay that serially, N times over. Grid compiles now route
            # through the compilation service's in-process executable
            # table (single-flight per lowered program), so the first
            # replica to lower a bucket compiles it and the other N-1
            # warm threads block briefly and share the executable —
            # replica fleet warmup costs one compile set + (N-1) cheap
            # traces, wall-clocked across a thread pool
            from concurrent.futures import ThreadPoolExecutor

            try:
                with ThreadPoolExecutor(
                        max_workers=min(8, len(to_start)),
                        thread_name_prefix=f"{self.name}-warm") as pool:
                    # list() re-raises the first failed replica start
                    list(pool.map(lambda s: s.start(), to_start))
            except BaseException:
                # one replica failed mid-fleet-start: the pool already
                # launched the others — stop every server THIS call
                # started and drop the hooks, or they would keep serving
                # standalone with a ReplicaFault hook and no failover
                # layer above it
                for r in self._replicas:
                    r.server._pre_dispatch = None
                for s in to_start:
                    if s.is_running:
                        try:
                            s.stop(drain=False, timeout=5)
                        except Exception:   # noqa: BLE001 - best effort
                            pass
                raise
        self._accepting = True
        self._running = True
        self._wedged = False
        self.hb.touch()
        self._thread = threading.Thread(
            target=self._dispatch_loop, name=self.name, daemon=True)
        self._thread.start()
        self._monitor_stop.clear()
        self._monitor = threading.Thread(
            target=self._monitor_loop, name=f"{self.name}-monitor",
            daemon=True)
        self._monitor.start()
        _live_routers.add(self)
        return self

    def stop(self, drain: bool = True, timeout: Optional[float] = None,
             stop_replicas: bool = True) -> None:
        """Stop the router. ``drain=True`` (default) routes every queued
        request and waits (bounded by ``timeout``) for in-flight ones;
        ``drain=False`` fails queued futures with :class:`MXNetError`
        (in-flight ones still resolve through their replicas)."""
        deadline = (time.monotonic() + timeout) if timeout is not None \
            else None
        with self._cond:
            self._accepting = False
            if not drain:
                pending, self._queue = list(self._queue), deque()
            else:
                pending = []
            self._cond.notify_all()
        self._fail_queued(pending)
        if drain:
            with self._cond:
                while self._queue or self._n_inflight:
                    if deadline is not None and \
                            time.monotonic() >= deadline:
                        break
                    self._cond.wait(0.05)
        with self._cond:
            self._running = False
            leftovers, self._queue = list(self._queue), deque()
            self._cond.notify_all()
        self._fail_queued(leftovers)    # drain timed out, queue wedged
        self._monitor_stop.set()

        def _remaining():
            # ONE budget for the whole stop: joins and replica stops
            # spend the same deadline (floored so a spent budget still
            # makes each join/stop attempt briefly rather than hanging)
            if deadline is None:
                return None
            return max(deadline - time.monotonic(), 0.1)

        errors = []
        for t in (self._thread, self._monitor):
            if t is not None:
                t.join(_remaining())
                if t.is_alive():
                    errors.append(MXNetError(
                        f"{self.name}: thread {t.name} did not exit "
                        f"within {timeout}s"))
        self._thread = None
        self._monitor = None
        # belt for the stop-vs-failover race: anything that slipped
        # into the queue after the leftovers sweep (a callback that won
        # the requeue race an instant before _running flipped) has no
        # consumer now — resolve it typed rather than strand it
        with self._cond:
            tail, self._queue = list(self._queue), deque()
        self._fail_queued(tail)
        for r in self._replicas:      # hooks die with the router, even
            r.server._pre_dispatch = None   # when replicas keep serving
        if stop_replicas:
            for r in self._replicas:
                srv = r.server
                if not srv.is_running:
                    continue
                try:
                    srv.stop(drain=drain, timeout=_remaining())
                except MXNetError as e:   # a wedged replica must not
                    errors.append(e)      # leak the rest un-stopped
        _live_routers.discard(self)
        if errors:
            raise errors[0]

    def _fail_queued(self, reqs) -> None:
        """Resolve de-queued requests with the typed stopped error."""
        for req in reqs:
            if req.resolve_exc(MXNetError(
                    f"{self.name}: router stopped before this request "
                    "was dispatched")):
                if req.span is not None:
                    req.span.end(outcome="stopped")
                self._count_request("rejected")

    def __enter__(self) -> "Router":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop(drain=not any(exc))

    # -- fleet management (the control plane's seam) -------------------
    def _check_compatible(self, server: Server) -> None:
        g0 = self.grid
        if server.grid.batch_buckets != g0.batch_buckets or \
                server.grid.shape_buckets != g0.shape_buckets:
            raise MXNetError(
                f"replica {server.name} has a different bucket grid "
                "than the fleet — replicas must share one grid "
                "(matched-bucket bit-identity)")
        if any(r.server.name == server.name for r in self._replicas):
            raise MXNetError(
                f"replica name {server.name!r} already in the fleet")

    def register_model(self, name: str, factory, *,
                       slo_class: str = "standard", priority: int = 0,
                       weight: float = 1.0,
                       slo_ms: Optional[float] = None,
                       rate_limit: Optional[float] = None,
                       burst: Optional[float] = None,
                       factory_kwargs: Optional[dict] = None) -> None:
        """Register tenant ``name`` on EVERY replica in the fleet.

        ``factory`` builds the tenant's block: a zero-(or kw-)arg
        callable for in-process fleets (called once PER replica — each
        replica owns its parameters), or a ``"module:function"`` spec
        string, which is REQUIRED when any replica is out-of-process
        (a callable cannot cross the exec boundary; the refusal is
        typed, not a pickle crash). Replicas share one bucket grid, so
        the tenant's executables land in the compilation service's
        signature-keyed table once and every replica's warmup after the
        first is a table hit. Serialized with fleet admin; replicas
        admitted later via :meth:`add_replica` get the same model set
        replayed before they take traffic."""
        with self._admin_lock:
            if name in self._models:
                raise MXNetError(
                    f"{self.name}: model {name!r} is already registered")
            reps = list(self._replicas)
            remote = [r for r in reps
                      if not isinstance(r.server, Server)]
            if remote and callable(factory):
                raise MXNetError(
                    f"{self.name}: model {name!r} uses a callable "
                    "factory but the fleet includes out-of-process "
                    f"replica {remote[0].server.name!r} — a callable "
                    "cannot cross the process boundary; pass a "
                    "'module:function' spec string instead")
            kwargs = dict(factory_kwargs or {})
            done: List[str] = []
            try:
                for r in reps:
                    self._register_on(r.server, name, factory, kwargs,
                                      slo_class, priority, weight,
                                      slo_ms, rate_limit, burst)
                    done.append(r.server.name)
            except MXNetError as e:
                # partial registration is worse than none — a request
                # routed at an unregistered replica would refuse-spin.
                # There is no unregister seam, so surface exactly which
                # replicas took it and refuse the registry entry.
                raise MXNetError(
                    f"{self.name}: registering model {name!r} failed "
                    f"after replicas {done} accepted it: {e}") from e
            self._models[name] = {
                "factory": factory, "factory_kwargs": kwargs,
                "slo_class": slo_class, "priority": priority,
                "weight": weight, "slo_ms": slo_ms,
                "rate_limit": rate_limit, "burst": burst}

    @staticmethod
    def _register_on(server, name, factory, kwargs, slo_class,
                     priority, weight, slo_ms, rate_limit, burst):
        """Register one tenant on one replica, in-process or remote."""
        if isinstance(server, Server):
            if callable(factory):
                block = factory(**kwargs)
            else:
                from .worker import load_factory
                block = load_factory(factory)(**kwargs)
            server.register_model(
                name, block, slo_class=slo_class, priority=priority,
                weight=weight, slo_ms=slo_ms, rate_limit=rate_limit,
                burst=burst)
        else:
            server.register_model(
                name, factory, slo_class=slo_class, priority=priority,
                weight=weight, slo_ms=slo_ms, rate_limit=rate_limit,
                burst=burst, factory_kwargs=kwargs)

    def models(self) -> list:
        """Registered tenant names (router registry; the default
        tenant every replica carries is not listed)."""
        return sorted(self._models)

    def add_replica(self, server: Server) -> None:
        """Admit one more ``Server`` replica into the fleet, live.

        The server's grid must match the fleet's (bit-identity at
        matched buckets) and its name must be unique. On a running
        router the server is started first when it is not already —
        ``Server.start()`` AOT-warms the whole bucket grid through the
        compilation service, so a scale-up of an architecture any
        in-process replica already compiled is an executable-table hit,
        not a fresh XLA compile — and only then joins the dispatch set:
        no request is ever routed at a cold replica. Thread-safe
        (serialized with ``remove_replica``/rolling upgrades)."""
        with self._admin_lock:      # serializes fleet admin: the name /
            self._check_compatible(server)   # grid check cannot race
            # replay the tenant registry BEFORE the replica takes
            # traffic: a submit(model=X) routed at a replica without X
            # would refuse-spin against the fleet
            have = getattr(server, "models", None)
            have = set(have()) if have is not None else set()
            for mname, spec in self._models.items():
                if mname in have:
                    continue
                self._register_on(
                    server, mname, spec["factory"],
                    spec["factory_kwargs"], spec["slo_class"],
                    spec["priority"], spec["weight"], spec["slo_ms"],
                    spec["rate_limit"], spec["burst"])
            if self.is_running:
                server._pre_dispatch = self._replica_fault_hook_for(server)
                if not server.is_running:
                    try:
                        server.start()      # warm BEFORE taking traffic
                    except BaseException:
                        server._pre_dispatch = None
                        raise
            with self._cond:
                rep = _Replica(server, self._next_index, None, None)
                self._next_index += 1
                # the start-window hook had no stable index; swap in
                # the real one (sub-site ``serving.replica.<index>``)
                if self.is_running:
                    server._pre_dispatch = self._replica_fault_hook(rep)
                self._replicas = self._replicas + [rep]
                self._cond.notify_all()
        if _telemetry_state.enabled:
            telemetry.set_fleet_size(len(self._replicas),
                                     router=self.name)

    def _replica_fault_hook_for(self, server: Server):
        """Placeholder hook for the start window of an admitted-but-not-
        yet-committed replica: family site only (it has no stable index
        yet). Replaced by the indexed hook at commit."""
        name = server.name

        def hook(sig):
            if not _fault_state.enabled:
                return
            try:
                fault.check("serving.replica", f"{name} batch={sig}")
            except fault.FaultInjected as e:
                raise ReplicaFault(
                    f"replica {name} (joining) failed: {e}") from e
        return hook

    def remove_replica(self, name: str, drain: bool = True,
                       timeout: Optional[float] = None,
                       stop_server: bool = True) -> Server:
        """Retire the replica called ``name`` from the fleet.

        ``drain=True`` (default) first stops routing NEW requests at it
        (the picker skips draining replicas) and waits — bounded by
        ``timeout`` — for its router-forwarded in-flight requests to
        resolve; anything still outstanding at the deadline is failed
        over to the rest of the fleet (zero lost futures). The replica
        is then detached and, with ``stop_server=True``, stopped.
        Removing the LAST replica is refused — scale to zero is
        ``Router.stop()``, not a drain. Returns the detached
        ``Server``."""
        with self._admin_lock:
            # deadline starts AFTER the admin lock is ours: time spent
            # queued behind a rolling upgrade's bakes or a scale-up
            # warm must not consume the caller's drain budget
            deadline = (time.monotonic() + timeout) \
                if timeout is not None else None
            with self._cond:
                target = next((r for r in self._replicas
                               if r.server.name == name), None)
                if target is None:
                    raise MXNetError(
                        f"{self.name}: no replica named {name!r}")
                if len(self._replicas) <= 1:
                    raise MXNetError(
                        f"{self.name}: refusing to remove the last "
                        f"replica {name!r} — stop the router instead")
                target.draining = True
                self._cond.notify_all()
            if drain and self.is_running:
                with self._cond:
                    while target.inflight > 0:
                        if deadline is not None and \
                                time.monotonic() >= deadline:
                            break
                        self._cond.wait(0.02)
            # anything still in flight (drain=False, or the timeout
            # expired): evict and fail over — the fleet it drains into
            # is healthy, the replica is leaving either way
            evicted = self._take_flights_of(target)
            for f in evicted:
                self._retry_or_fail(
                    f.req,
                    MXNetError(f"replica {name} drained out of the "
                               "fleet with this request in flight"),
                    reason="drained", replica=target)
            with self._cond:
                self._replicas = [r for r in self._replicas
                                  if r is not target]
                self._cond.notify_all()
            target.server._pre_dispatch = None
        if _telemetry_state.enabled:
            telemetry.set_fleet_size(len(self._replicas),
                                     router=self.name)
        if stop_server and target.server.is_running:
            remaining = (max(deadline - time.monotonic(), 0.1)
                         if deadline is not None else None)
            try:
                target.server.stop(drain=drain, timeout=remaining)
            except MXNetError:
                # a scheduler wedged in dispatch can outlive the drain
                # deadline — the REMOVAL already succeeded (replica
                # detached, flights failed over), so don't fail it;
                # the daemon thread exits when the dispatch returns
                _log.warning(
                    "%s: removed replica %s did not stop within its "
                    "drain deadline (scheduler wedged in dispatch?); "
                    "its thread will exit when the dispatch returns",
                    self.name, name)
        return target.server

    def replicas(self) -> list:
        """Fleet snapshot for the control plane: one dict per replica
        (name, stable index, breaker state, inflight, draining)."""
        return [{"name": r.server.name, "index": r.index,
                 "state": r.breaker.state, "inflight": r.inflight,
                 "draining": r.draining, "server": r.server,
                 "breaker": r.breaker}
                for r in self._replicas]

    def fleet_size(self, include_draining: bool = False) -> int:
        reps = self._replicas
        if include_draining:
            return len(reps)
        return sum(1 for r in reps if not r.draining)

    def predicted_wait(self) -> float:
        """The admission controller's current completion-time estimate
        for a request submitted now (0.0 when there is no estimate) —
        the autoscaler's primary scale-up signal. Armed by the same
        backlog threshold as predicted-wait shedding: an idle fleet
        that JUST finished a burst still has a nonzero raw estimate
        (a fresh request would ride a full fleet batch), and reporting
        it would scale up a fleet with nothing queued."""
        with self._cond:
            pending = len(self._queue) + self._n_inflight
            if pending <= self._shed_arm_pending:
                return 0.0
            return self._predicted_wait_locked(pending)

    # -- admission -----------------------------------------------------
    # completions older than the window do not inform the service-rate
    # estimate, and gaps between completions are capped: idle time
    # between traffic bursts is not service time, and counting it would
    # make the router look slower than it is and shed spuriously
    _PRED_WINDOW_S = 2.0
    _PRED_GAP_CAP_S = 0.05

    def _predicted_wait_locked(self, pending: int) -> float:
        """Predicted time-to-completion for a request admitted now:
        (pending work + two full fleet batches — the request waits out
        the dispatch already RUNNING and then rides its OWN) over the
        measured service rate (last <=64 completions inside a recent
        window, busy time only). With fewer than 8 recent completions
        there is no estimate — admit (the bounded queue still caps the
        damage)."""
        now = time.perf_counter()
        ts = self._done_ts
        while ts and now - ts[0] > self._PRED_WINDOW_S:
            ts.popleft()
        if len(ts) < 8:
            return 0.0
        busy = 0.0
        prev = None
        for t in ts:
            if prev is not None:
                busy += min(t - prev, self._PRED_GAP_CAP_S)
            prev = t
        busy += min(now - prev, self._PRED_GAP_CAP_S)
        if busy <= 1e-6:
            return 0.0
        fleet_batch = self.grid.max_batch * len(self._replicas)
        return (pending + 2 * fleet_batch) * busy / len(ts)

    def _check_model(self, model: Optional[str]) -> None:
        """Reject an unknown tenant SYNCHRONOUSLY at admission: letting
        it through would refuse-spin the request against every replica
        until its deadline expired, reading as overload instead of a
        caller bug. Tenants registered directly on an in-process Server
        (bypassing the router registry) still pass."""
        if model is None or model == DEFAULT_MODEL \
                or model in self._models:
            return
        for r in self._replicas:
            ms = getattr(r.server, "models", None)
            if ms is not None:
                if model in ms():
                    return
                break
        self._count_request("rejected")
        raise MXNetError(
            f"{self.name}: unknown model {model!r} — register it with "
            "Router.register_model first")

    def submit(self, sample, deadline_ms: Optional[float] = None,
               model: Optional[str] = None,
               priority: Optional[int] = None) -> Future:
        """Enqueue one sample (no batch dimension) for the replica
        fleet; same contract as :meth:`Server.submit`. Raises
        synchronously — :class:`ServerOverloaded` on queue-full or a
        predicted deadline miss, :class:`MXNetError` when stopped, no
        shape bucket fits, or ``model`` names an unregistered tenant.
        Thread-safe. When the queue is empty the dispatch itself runs
        on this thread (never blocking on it — replica submits are
        enqueue-and-return); a backlog is drained in FIFO order by the
        dispatcher thread."""
        self._check_model(model)
        shape = getattr(sample, "shape", None)
        if shape is None:
            shape = np.asarray(sample).shape
        self.grid.bucket_shape(shape)       # raises if no bucket fits
        deadline_s = (deadline_ms / 1e3 if deadline_ms is not None
                      else self.slo_s)
        with self._cond:
            if not self._accepting:
                self._count_request("rejected")
                raise MXNetError(f"{self.name}: router is not running")
            pending = len(self._queue) + self._n_inflight
            if pending >= self.max_queue:
                self._shed_locked("queue_full", model=model)
                raise ServerOverloaded(
                    f"{self.name}: router queue full ({self.max_queue} "
                    "requests queued or in flight)")
            wait = (self._predicted_wait_locked(pending)
                    if pending > self._shed_arm_pending else 0.0)
            if wait > deadline_s:
                self._shed_locked("predicted_wait", model=model)
                raise ServerOverloaded(
                    f"{self.name}: predicted queue wait {wait * 1e3:.1f}"
                    f" ms exceeds the request deadline "
                    f"{deadline_s * 1e3:.1f} ms ({pending} pending)")
            req = _RouteReq(sample, deadline_s, model=model,
                            priority=priority)
            if _tracing_state.enabled:
                # the span must exist BEFORE the queue append: the
                # dispatcher thread may route this request before
                # submit returns
                amb = tracing.ambient()
                if amb is not None:
                    req.trace = amb[0]
                    req.span = req.trace.begin(
                        "router.queue", parent=amb[1], router=self.name)
                else:
                    req.trace = tracing.new_trace(
                        "request", router=self.name)
                    req.own_trace = True
                    req.span = req.trace.begin(
                        "router.queue", router=self.name)
                    req.future.add_done_callback(
                        req.trace.finish_from_future)
            # fast path: with nothing queued ahead (FIFO preserved),
            # route on the SUBMITTING thread — decode-to-dispatch is
            # one GIL hold with no queue hand-off and no dispatcher
            # wake-up. On a contended interpreter the hand-off is not
            # free: a wave of submits used to sit in the queue burning
            # deadline while the dispatcher thread waited for its next
            # slice (measured as head-of-line expiry through the socket
            # ingress). The dispatcher thread still owns the backlog:
            # anything the fast path cannot place immediately falls
            # back to the queue it drains. Under FAULT INJECTION the
            # fast path stands down entirely: chaos targets the
            # dispatcher's routing loop (``serving.route`` hits burn
            # budget there, latency faults wedge the dispatcher where
            # the watchdog contains them) — routing on a caller thread
            # would move the blast radius onto the client. With faults
            # off, every surface the fast path touches
            # (``_pick_replica``, a replica ``submit``) is
            # enqueue-and-return by construction, so ``submit`` stays
            # non-blocking.
            inline = not self._queue and not _fault_state.enabled
            if not inline:
                self._queue.append(req)
                depth = len(self._queue)
                self._cond.notify_all()
            else:
                depth = 0
        if inline:
            self._route(req, inline=True)
        if _telemetry_state.enabled:
            telemetry.set_router_queue_depth(depth, router=self.name)
        return req.future

    def submit_generate(self, prompt, max_new_tokens: int,
                        deadline_ms: Optional[float] = None,
                        on_token=None, model: Optional[str] = None,
                        priority: Optional[int] = None):
        """Route one autoregressive generate to a decode-capable
        replica (least-loaded CLOSED breaker). Returns the replica's
        :class:`~.server.GenerateHandle` directly — tokens stream
        straight from the serving replica; the router stays out of the
        per-token path.

        Unlike :meth:`submit`, a generate does NOT fail over
        mid-stream: by the time a replica dies the caller may have
        consumed half the completion, and replaying it elsewhere would
        duplicate streamed tokens. A crash resolves the handle's
        future with the typed replica error and counts as breaker
        evidence — the CALLER decides whether to resubmit.
        :class:`~.kvcache.CacheFull` (the request can never fit the
        replica's cache budget) sheds synchronously and typed
        (``mxnet_serving_shed_total{reason="kvcache_full"}``) —
        replicas share one cache geometry, so another replica would
        refuse it identically. So does :class:`TenantThrottled`
        (``reason="throttled"``) — retrying a tenant's rate-limit
        refusal on a sibling would multiply the tenant's configured
        rate by the fleet size."""
        self._check_model(model)
        with self._cond:
            if not self._accepting:
                self._count_request("rejected")
                raise MXNetError(f"{self.name}: router is not running")
        last_err: Optional[MXNetError] = None
        # half-open probes excluded: one multi-second generate is a
        # bad canary — recovery detection stays on short requests
        live = [r for r in self._replicas
                if r.server.is_running and not r.draining
                and r.breaker.state == CLOSED]
        for r in sorted(live, key=lambda r: r.inflight):
            if not r.breaker.admit():
                continue
            trace = span = None
            own = False
            if _tracing_state.enabled:
                amb = tracing.ambient()
                if amb is not None:
                    trace = amb[0]
                    span = trace.begin("router.generate", parent=amb[1],
                                       replica=r.server.name,
                                       model=model or DEFAULT_MODEL)
                else:
                    trace = tracing.new_trace("generate",
                                              router=self.name)
                    own = True
                    span = trace.begin("router.generate",
                                       replica=r.server.name,
                                       model=model or DEFAULT_MODEL)
            try:
                if span is not None:
                    with tracing.active(trace, span):
                        handle = r.server.submit_generate(
                            prompt, max_new_tokens,
                            deadline_ms=deadline_ms, on_token=on_token,
                            model=model, priority=priority)
                else:
                    handle = r.server.submit_generate(
                        prompt, max_new_tokens, deadline_ms=deadline_ms,
                        on_token=on_token, model=model,
                        priority=priority)
            except CacheFull:
                if span is not None:
                    span.end(outcome="shed")
                if own:
                    trace.finish("kvcache_full")
                with self._cond:
                    self._shed_locked("kvcache_full", model=model)
                raise
            except TenantThrottled:
                if span is not None:
                    span.end(outcome="shed")
                if own:
                    trace.finish("throttled")
                with self._cond:
                    self._shed_locked("throttled", model=model)
                raise
            except MXNetError as e:
                # this replica refuses (decode off / queue full): not
                # terminal for the request — try the next one
                if span is not None:
                    span.end(outcome="refused", error=type(e).__name__)
                if own:
                    trace.finish("refused")
                last_err = e
                continue
            with self._cond:
                r.inflight += 1
                self._n_inflight += 1
            t_enq = time.perf_counter()

            def _done(f, rep=r, sp=span, tr=trace, own_tr=own,
                      t0=t_enq):
                with self._cond:
                    rep.inflight -= 1
                    self._n_inflight -= 1
                    self._cond.notify_all()
                try:
                    exc = f.exception()
                except BaseException as e:  # noqa: BLE001 - cancelled
                    exc = e
                if exc is None:
                    rep.breaker.record_success()
                    rep.n_ok += 1
                elif not isinstance(exc, CacheFull):
                    # CacheFull is capacity, not health; anything else
                    # (crash, fault, wedge) is breaker evidence
                    rep.breaker.record_failure()
                    rep.n_failed += 1
                if sp is not None:
                    sp.end(outcome="ok" if exc is None else "error")
                self._count_request(
                    "ok" if exc is None else "error", t_enqueue=t0,
                    trace_id=tr.trace_id if tr is not None else None)
                if own_tr:
                    tr.finish("ok" if exc is None
                              else type(exc).__name__)

            handle.future.add_done_callback(_done)
            return handle
        if last_err is not None:
            raise last_err
        with self._cond:
            self._shed_locked("queue_full", model=model)
        raise ServerOverloaded(
            f"{self.name}: no decode-capable healthy replica admits "
            "generate requests right now")

    def _shed_locked(self, reason: str,
                     model: Optional[str] = None) -> None:
        self.n_shed += 1
        self.n_requests += 1
        if _telemetry_state.enabled:
            telemetry.record_serving_shed(reason, model=model)
        if _tracing_state.enabled:
            tracing.record_event("shed", reason=reason, router=self.name,
                                 model=model or DEFAULT_MODEL)

    def _count_request(self, outcome: str,
                       t_enqueue: Optional[float] = None,
                       trace_id: Optional[str] = None) -> None:
        self.n_requests += 1
        if outcome == "ok":
            self.n_ok += 1
        elif outcome == "error":
            self.n_errors += 1
        if _telemetry_state.enabled:
            lat = (time.perf_counter() - t_enqueue
                   if t_enqueue is not None else 0.0)
            telemetry.record_router_request(lat, outcome,
                                            trace_id=trace_id)

    # -- dispatcher ----------------------------------------------------
    def _dispatch_loop(self) -> None:
        try:
            while True:
                self.hb.touch()
                with self._cond:
                    while not self._queue and self._running:
                        self._cond.wait(0.05)
                        self.hb.touch()
                    if not self._queue:
                        return          # stopped, queue empty
                    req = self._queue.popleft()
                    # track the popped request IMMEDIATELY (same locked
                    # section): if this thread wedges or dies anywhere
                    # after the pop, the watchdog/containment must fail
                    # THIS future too, not just the still-queued ones
                    self._routing = req
                    if _telemetry_state.enabled:
                        telemetry.set_router_queue_depth(
                            len(self._queue), router=self.name)
                self._route(req)
                self._routing = None
        except BaseException:
            # loud containment, same contract as Server: a dead
            # dispatcher must not leave a queue nobody drains
            self._fail_all_queued("dispatcher thread crashed")
            raise

    def _fail_all_queued(self, why: str) -> None:
        with self._cond:
            self._accepting = False
            pending, self._queue = list(self._queue), deque()
            routing = self._routing
            self._cond.notify_all()
        if routing is not None:
            pending = [routing] + pending   # first-wins guards the race
        for req in pending:                 # with a later un-wedge
            if req.resolve_exc(MXNetError(f"{self.name}: {why}")):
                if req.span is not None:
                    req.span.end(outcome="error")
                self._count_request("error", t_enqueue=req.t_enqueue)
        if _tracing_state.enabled:
            tracing.record_event("router_wedged", router=self.name,
                                 why=why)
            tracing.maybe_dump("router_wedged")

    def _route(self, req: _RouteReq, inline: bool = False) -> None:
        """Forward one request to the best replica, retrying admission
        refusals briefly; requeues / resolves on terminal conditions.
        ``inline=True`` = running on the SUBMITTING thread (the fast
        path): transient can't-place-right-now conditions hand the
        request to the dispatcher's queue instead of backing off in
        place — a client/ingress thread must not sleep inside
        ``submit``."""
        if req.future.done():
            return      # already resolved (watchdog / late failover)
        if not req.begin():
            return                              # caller cancelled it
        now = time.perf_counter()
        if now >= req.deadline:
            # shed-in-queue safety net: dispatching it would burn a
            # replica slot on an already-dead request
            if req.resolve_exc(ServerOverloaded(
                    f"{self.name}: request deadline expired after "
                    f"{(now - req.t_enqueue) * 1e3:.1f} ms in the router "
                    f"queue ({req.attempts} dispatch attempt(s))")):
                if req.span is not None:
                    req.span.end(outcome="expired")
                with self._cond:
                    self._shed_locked("expired")
            return
        if _fault_state.enabled:
            try:
                fault.check("serving.route", f"{self.name}")
            except fault.FaultInjected as e:
                # a routing fault burns one unit of the request's
                # budget (else every:1 would requeue forever) but is
                # NOT replica health evidence
                req.attempts += 1
                if req.trace is not None:
                    req.trace.note(f"injected route fault: {e}")
                self._retry_or_fail(req, e, reason="route_fault")
                return
        target = self._pick_replica()
        if target is None:
            # nothing healthy admits right now: put it back and let the
            # dispatcher breathe (a breaker cooldown or an in-flight
            # completion will move things)
            self._hand_to_dispatcher(req, inline, wait_s=0.005)
            return
        r, probe = target
        flight = _Flight(req, r, time.perf_counter(), probe)
        remaining_ms = max((req.deadline - time.perf_counter()) * 1e3,
                           1.0)
        with self._cond:
            self._flights[id(flight)] = flight
            r.inflight += 1
            self._n_inflight += 1
        if req.trace is not None:
            # queue time ends the moment a replica is chosen; each
            # dispatch attempt gets its own span so a failover reads as
            # attempt-on-victim -> attempt-on-survivor under one trace
            if req.span is not None:
                req.span.end()
                req.span = None
            flight.span = req.trace.begin(
                "router.attempt", replica=r.server.name,
                attempt=req.attempts + 1)
        try:
            if flight.span is not None:
                # ambient context so the replica's submit (local Server
                # or RemoteReplica wire frame) joins this trace
                with tracing.active(req.trace, flight.span):
                    rfut = r.server.submit(req.sample,
                                           deadline_ms=remaining_ms,
                                           model=req.model,
                                           priority=req.priority)
            else:
                rfut = r.server.submit(req.sample,
                                       deadline_ms=remaining_ms,
                                       model=req.model,
                                       priority=req.priority)
        except Exception as e:  # noqa: BLE001 - sync admission refusal
            with self._cond:
                # guard like _on_replica_done: the hung-dispatch sweep
                # may have removed this flight (and decremented for it)
                # between registration and the submit raising — an
                # unconditional decrement would drive the counts
                # negative and double-queue the request
                live = self._flights.pop(id(flight), None) is not None
                if live:
                    r.inflight -= 1
                    self._n_inflight -= 1
                    self._cond.notify_all()
            if not live:
                return      # the sweep owns this request's fate now
            if isinstance(e, TenantThrottled):
                # per-tenant rate-limit refusal: typed and TERMINAL —
                # retrying on a sibling replica would multiply the
                # tenant's configured rate by the fleet size
                if flight.span is not None:
                    flight.span.end(outcome="shed",
                                    error=type(e).__name__)
                if probe:
                    r.breaker.release_probe()
                if req.resolve_exc(e):
                    with self._cond:
                        self._shed_locked("throttled", model=req.model)
                return
            if flight.span is not None:
                flight.span.end(outcome="refused",
                                error=type(e).__name__)
                # back to queued state: reopen a queue span so the
                # re-route attempt is attributed to scheduling time
                req.span = req.trace.begin("router.queue",
                                           router=self.name,
                                           requeue="refused")
            if probe:
                r.breaker.release_probe()
            if isinstance(e, MXNetError) and not r.server.is_running:
                # replica died between health check and submit
                r.breaker.record_failure()
                self._retry_or_fail(req, e, reason="replica_down",
                                    replica=r)
            else:
                # queue-full style refusal: not a health event; retry
                # the route (does not burn the retry budget — the
                # request was never dispatched)
                if _telemetry_state.enabled:
                    telemetry.record_serving_route_retry("refused")
                self._hand_to_dispatcher(req, inline, wait_s=0.002)
            return
        req.attempts += 1
        flight.rfut = rfut
        if _telemetry_state.enabled:
            telemetry.record_router_queue_wait(
                flight.t_sent - req.t_enqueue)
        rfut.add_done_callback(
            lambda f, fl=flight: self._on_replica_done(fl, f))

    def _hand_to_dispatcher(self, req: _RouteReq, inline: bool,
                            wait_s: float) -> None:
        """A route attempt could not place ``req`` right now (no
        admitting replica / transient refusal). Dispatcher thread:
        head-requeue and breathe — it owns the backoff loop. Inline
        fast path: tail-enqueue for the dispatcher and return (the
        submitting thread must not sleep here); if the router stopped
        while we were routing, resolve typed instead of stranding the
        request in a queue nobody will drain."""
        with self._cond:
            if not inline:
                self._queue.appendleft(req)
                self._cond.wait(wait_s)
                return
            if self._accepting:
                self._queue.append(req)
                self._cond.notify_all()
                return
        if req.resolve_exc(MXNetError(
                f"{self.name}: router stopped before this request "
                "was dispatched")):
            if req.span is not None:
                req.span.end(outcome="stopped")
            self._count_request("rejected")

    def _pick_replica(self):
        """(replica, is_probe) — HALF_OPEN probes first (recovery must
        be detected under any traffic), then least-loaded CLOSED.
        Draining replicas (a ``remove_replica`` in progress) take no new
        work — their in-flight dispatches finish through the normal
        resolution path."""
        live = [r for r in self._replicas
                if r.server.is_running and not r.draining]
        for r in live:
            if r.breaker.state == HALF_OPEN and r.breaker.admit():
                return r, True
        closed = [r for r in live if r.breaker.state == CLOSED]
        for r in sorted(closed, key=lambda r: r.inflight):
            if r.breaker.admit():
                return r, False
        return None

    def _on_replica_done(self, flight: _Flight, rfut) -> None:
        """Replica future resolved (runs on the replica's scheduler
        thread — keep it quick). ``late`` = the hung-dispatch sweep
        already removed this flight and failed it over; its breaker
        verdict stands, but a late SUCCESS is still a usable result
        (first resolution wins)."""
        with self._cond:
            late = self._flights.pop(id(flight), None) is None
            if not late:
                flight.rep.inflight -= 1
                self._n_inflight -= 1
                self._cond.notify_all()
        r = flight.rep
        try:
            exc = rfut.exception()
        except BaseException as e:  # noqa: BLE001 - cancelled etc.
            exc = e
        if exc is None:
            if not late:
                r.breaker.record_success()
                r.n_ok += 1
                with self._cond:
                    self._done_ts.append(time.perf_counter())
            if flight.span is not None:
                flight.span.end(outcome="ok")
            if flight.req.resolve_result(rfut.result()):
                self._count_request(
                    "ok", t_enqueue=flight.req.t_enqueue,
                    trace_id=(flight.req.trace.trace_id
                              if flight.req.trace is not None else None))
            return
        if flight.span is not None:
            flight.span.end(outcome="error", error=type(exc).__name__)
        if late:
            return                  # hung flight already failed over
        r.breaker.record_failure()
        r.n_failed += 1
        if r.breaker.state == OPEN:
            # the trip's collateral: every OTHER flight at this replica
            # is sitting in its batch queue and would ride the same
            # sick dispatch — or worse, wait out the deadline-close
            # window first and fail over with no deadline left. Evict
            # them through the failover path NOW, while their budgets
            # still buy a healthy replica (their late resolutions, if
            # the replica gets to them anyway, drop first-wins).
            for f in self._take_flights_of(r):
                if f.rfut is not None:
                    f.rfut.cancel()     # spare the sick replica's queue
                r.n_failed += 1
                self._retry_or_fail(
                    f.req,
                    MXNetError(
                        f"replica {r.server.name} circuit breaker "
                        "opened with this request in flight"),
                    reason="breaker_open", replica=r)
        self._retry_or_fail(flight.req, exc, reason="replica_error",
                            replica=r)

    def _retry_or_fail(self, req: _RouteReq, exc: BaseException,
                       reason: str, replica: Optional[_Replica] = None
                       ) -> None:
        """Failover: requeue at the FRONT (it has waited longest) under
        the retry budget, else resolve with a typed error. Never leaves
        the future unresolved."""
        if req.future.done():
            return
        if _telemetry_state.enabled:
            telemetry.record_serving_route_retry(reason)
        budget = 1 + self.retry_budget           # total dispatches
        requeued = False
        if req.attempts < budget:
            # re-check _running in the SAME critical section as the
            # requeue: a stop() racing between a stale check and the
            # appendleft would strand the request in a queue with no
            # consumer — a lost future
            with self._cond:
                if self._running:
                    self._queue.appendleft(req)
                    self._cond.notify_all()
                    requeued = True
        if requeued:
            self.n_failovers += 1
            if _telemetry_state.enabled and replica is not None:
                telemetry.record_serving_failover(replica.server.name)
            if req.trace is not None:
                victim = (replica.server.name if replica is not None
                          else "?")
                req.trace.note(
                    f"failover: {reason} on {victim} "
                    f"({type(exc).__name__}: {exc}); requeued "
                    f"(attempt {req.attempts} of {budget})")
                if req.span is None or req.span._done:
                    req.span = req.trace.begin(
                        "router.queue", router=self.name, requeue=reason)
                tracing.record_event(
                    "failover", router=self.name, reason=reason,
                    replica=victim, trace_id=req.trace.trace_id)
            return
        detail = (f" (last replica: {replica.server.name})"
                  if replica is not None else "")
        if req.resolve_exc(FailoverExhausted(
                f"{self.name}: request failed after {req.attempts} "
                f"dispatch attempt(s), retry budget "
                f"{self.retry_budget} spent{detail}: {exc}")):
            if req.span is not None:
                req.span.end(outcome="exhausted")
            if req.trace is not None:
                tracing.record_event(
                    "failover_exhausted", router=self.name,
                    reason=reason, trace_id=req.trace.trace_id)
            self._count_request(
                "error", t_enqueue=req.t_enqueue,
                trace_id=(req.trace.trace_id
                          if req.trace is not None else None))

    # -- monitor: hung dispatches, breaker gauges, watchdog ------------
    def _monitor_loop(self) -> None:
        interval = min(0.05, self.dispatch_timeout_s / 4)
        while not self._monitor_stop.wait(interval):
            self._sweep_hung()
            self._publish_health()
            self._check_dispatcher()

    def _take_flights_of(self, r: _Replica) -> list:
        """Remove and return every flight currently at replica ``r``
        (their late resolutions, if any, are dropped first-wins)."""
        with self._cond:
            mine = [f for f in self._flights.values()
                    if f.rep is r]
            for f in mine:
                self._flights.pop(id(f), None)
                r.inflight -= 1
                self._n_inflight -= 1
            if mine:
                self._cond.notify_all()
        return mine

    def _sweep_hung(self) -> None:
        """Hung-dispatch detection. Primary signal: a replica's
        scheduler heartbeat (touched once per loop iteration, so
        between touches at most ONE dispatch runs) stale past the
        dispatch timeout while it has router flights outstanding — a
        scheduler patiently filling a batch keeps touching, a wedged
        dispatch does not. Trip the breaker and fail over EVERY flight
        at that replica at once. Backstop: any single flight
        outstanding a full timeout past its own deadline (a live
        replica resolves by the deadline — its batch closes at
        deadline - margin) fails over too, so a silently dropped
        callback can never strand a future."""
        now = time.perf_counter()
        hung: List = []
        for r in self._replicas:
            srv = r.server
            if not srv.is_running:
                continue        # crash containment fails its futures
            with self._cond:
                busy = r.inflight > 0
            if busy and srv.hb.stale(self.dispatch_timeout_s):
                r.breaker.record_hang()
                taken = self._take_flights_of(r)
                r.n_failed += len(taken)
                age = srv.hb.age()
                for f in taken:
                    hung.append((f, r, MXNetError(
                        f"replica {srv.name} scheduler silent for "
                        f"{age:.2f}s > MXNET_SERVING_DISPATCH_TIMEOUT="
                        f"{self.dispatch_timeout_s:g}s with this "
                        "request in flight (hung dispatch)")))
        with self._cond:
            overdue = [f for f in self._flights.values()
                       if now > max(f.req.deadline, f.t_sent)
                       + self.dispatch_timeout_s]
            for f in overdue:
                self._flights.pop(id(f), None)
                f.rep.inflight -= 1
                self._n_inflight -= 1
            if overdue:
                self._cond.notify_all()
        for f in overdue:
            r = f.rep
            r.breaker.record_hang()
            r.n_failed += 1
            hung.append((f, r, MXNetError(
                f"dispatch at replica {r.server.name} still "
                f"outstanding {self.dispatch_timeout_s:g}s past the "
                "request deadline (unresponsive replica)")))
        for f, r, err in hung:
            if f.span is not None:
                f.span.end(outcome="hung")
            self._retry_or_fail(f.req, err, reason="hung", replica=r)

    def _publish_health(self) -> None:
        for r in self._replicas:
            # out-of-process replicas report crashes explicitly
            # (connection drop / waitpid — see serving/remote.py): an
            # UNAMBIGUOUS death trips the breaker immediately instead
            # of burning a failure threshold against a corpse (crash !=
            # slow); the respawned worker re-enters through the
            # half-open probe like any recovered replica
            cc = getattr(r.server, "crash_count", 0)
            if cc > r.crashes_seen:
                r.crashes_seen = cc
                r.breaker.record_hang()
                if _tracing_state.enabled:
                    tracing.record_event(
                        "worker_crash", replica=r.server.name,
                        crash_count=cc, router=self.name)
            state = r.breaker.state
            if state != r.last_state:
                if _telemetry_state.enabled:
                    telemetry.record_breaker_transition(
                        r.server.name, state)
                if _tracing_state.enabled:
                    tracing.record_event(
                        "breaker", replica=r.server.name,
                        from_state=r.last_state, to_state=state,
                        router=self.name)
                    if state == OPEN:
                        # a breaker trip is exactly the moment the
                        # flight recorder exists for: persist the ring
                        # so the trip can be explained post-mortem
                        tracing.maybe_dump("breaker_open")
                r.last_state = state
            if _telemetry_state.enabled:
                telemetry.set_replica_health(
                    r.server.name, _HEALTH_VALUE[state])
        if _telemetry_state.enabled:
            # the scrape-fed control plane's signal set: every gauge a
            # remote FleetController needs rides /metrics from here
            with self._cond:
                depth = len(self._queue)
                inflight = self._n_inflight
                by_model: dict = {}
                for q in self._queue:
                    m = q.model or DEFAULT_MODEL
                    by_model[m] = by_model.get(m, 0) + 1
            telemetry.set_router_queue_depth(depth, router=self.name)
            telemetry.set_router_inflight(inflight, router=self.name)
            telemetry.set_predicted_wait(self.predicted_wait(),
                                         router=self.name)
            telemetry.set_fleet_size(self.fleet_size(),
                                     router=self.name)
            # per-tenant depth: every registered tenant gets a sample
            # (zero included) so a drained queue reads as 0, not stale
            for m in ({DEFAULT_MODEL} | set(self._models)
                      | set(by_model)):
                telemetry.set_tenant_queue_depth(
                    by_model.get(m, 0), m, router=self.name)

    def _check_dispatcher(self) -> None:
        if self._wedged or not self._running:
            return
        t = self._thread
        dead = t is not None and not t.is_alive()
        stale = self.hb.stale(self.watchdog_timeout_s)
        if not (dead or stale):
            return
        # the dispatcher is gone or wedged: requests already forwarded
        # will still resolve through their replicas, but the queue has
        # no consumer — fail it loudly NOW (zero hung futures), and
        # stop admitting
        self._wedged = True
        why = ("dispatcher thread died" if dead else
               f"dispatcher silent for {self.hb.age():.1f}s > "
               f"MXNET_SERVING_WATCHDOG_TIMEOUT="
               f"{self.watchdog_timeout_s:g}s (wedged)")
        self._fail_all_queued(f"scheduler-liveness watchdog: {why}")

    # -- introspection -------------------------------------------------
    def stats(self) -> dict:
        with self._cond:
            depth = len(self._queue)
            inflight = self._n_inflight
        return {
            "requests": self.n_requests, "ok": self.n_ok,
            "errors": self.n_errors, "shed": self.n_shed,
            "failovers": self.n_failovers, "queue_depth": depth,
            "inflight": inflight, "running": self.is_running,
            "wedged": self._wedged,
            "fleet_size": self.fleet_size(),
            "models": sorted(self._models),
            "replicas": [
                {"name": r.server.name, "index": r.index,
                 "state": r.breaker.state, "inflight": r.inflight,
                 "ok": r.n_ok, "failed": r.n_failed,
                 "draining": r.draining,
                 "trips": r.breaker.n_trips}
                for r in self._replicas],
        }
