"""``mx.serving.controller`` — the traffic-driven control plane.

The robustness subsystems exist (elastic training, health-checked
multi-replica routing, warm-started compilation); this module composes
them into *operations* (ROADMAP item 5): the piece that turns "a
server" into "a deployable system".

* **Autoscaling.** :class:`FleetController` watches the Router's own
  admission signals — shed events, the predicted-wait estimate the
  admission controller already computes, fleet utilization — and grows
  or shrinks the replica fleet between ``min_replicas`` and
  ``max_replicas``. Scale-up spawns a replica through the user's
  ``replica_factory`` and admits it via :meth:`Router.add_replica`,
  which warms the full bucket grid BEFORE the replica takes traffic;
  because grid compiles route through the compilation service's
  executable table and disk cache, a scale-up of an architecture the
  process has seen is a cache hit, not an XLA compile — fast enough to
  matter under a traffic surge. Scale-down drains: the victim stops
  receiving new requests, in-flight ones resolve, then it is detached
  and stopped (zero lost futures by construction). Decisions live in
  :class:`ScalePolicy` — a pure function of
  :class:`FleetSignals` + time, unit-testable with a fake clock:
  scale-up on any shedding or a predicted wait beyond
  ``up_wait_factor``·SLO (one replica per ``up_cooldown_s``);
  scale-down only after utilization stays under
  ``down_utilization`` with an empty queue for ``down_hold_s``
  (hysteresis — a quiet second must not tear down capacity a burst
  needs back).

* **Rolling upgrades.** :func:`rolling_upgrade` walks the fleet one
  replica at a time: build the new model via ``model_factory``, warm it
  for every signature in live use (``Server.swap_model`` — the old
  graph serves throughout, zero downtime), swap, then **bake**: watch
  the replica's circuit breaker and dispatch-error delta for
  ``bake_s``. A breaker trip or any new dispatch error during the bake
  rolls the WHOLE rollout back — every already-upgraded replica gets
  its old model (and old version number) restored, newest first — and
  raises :class:`UpgradeRolledBack`. N-1 replicas serve the old
  version while one bakes, so a poisoned model build costs one
  replica's bake window, never the fleet.

* **Preemption tolerance** lives in the training half of the plane:
  ``parallel/elastic.py``'s graceful-leave protocol (checkpoint on the
  preemption signal, fast leave, supervisor respawn outside the restart
  budget — see ``ElasticRunner.install_preemption_handler`` and
  ``tools/launch.py --preempt-rc``).

Fault sites: ``controller.scale`` fires per scale action (an injected
fault is contained — counted, logged, retried on a later tick);
``serving.upgrade`` fires per replica upgrade (an injected fault
aborts the rollout and exercises the rollback path — that is how the
tests drive it).

Telemetry: ``mxnet_controller_fleet_size``,
``mxnet_controller_scale_total{direction,outcome}``,
``mxnet_controller_scale_seconds{direction}``,
``mxnet_serving_upgrade_total{outcome}``.
"""
from __future__ import annotations

import logging
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Callable, List, Optional

from .. import fault, telemetry
from ..base import MXNetError
from ..fault import _state as _fault_state
from ..telemetry import _state as _telemetry_state
from .health import CLOSED, _env_float
from .router import Router
from .server import DEFAULT_MODEL

__all__ = ["FleetController", "FleetSignals", "ScalePolicy",
           "ScrapeFleetSignals", "UpgradeRolledBack", "rolling_upgrade",
           "live_controllers"]

_log = logging.getLogger(__name__)

# running controllers, for the test-suite leak guard (same pattern as
# server._live_servers / router._live_routers)
_live_controllers = weakref.WeakSet()


def live_controllers():
    """Controllers whose tick thread is currently running."""
    return [c for c in list(_live_controllers) if c.is_running]


class UpgradeRolledBack(MXNetError):
    """A rolling upgrade failed its bake (breaker trip / dispatch
    errors / injected ``serving.upgrade`` fault) and every upgraded
    replica was restored to the previous model. The fleet serves the
    OLD version when this raises."""


@dataclass(frozen=True)
class FleetSignals:
    """One tick's worth of router observations — everything
    :class:`ScalePolicy` is allowed to look at. Pure data so policy
    decisions are replayable in tests without a router."""

    n_replicas: int          # non-draining replicas
    queue_depth: int         # router-queued (not yet dispatched)
    inflight: int            # forwarded, unresolved
    shed_delta: int          # sheds since the previous tick
    predicted_wait_s: float  # admission controller's estimate (0 = none)
    slo_s: float             # the fleet's latency objective
    max_batch: int           # one replica's largest batch bucket
    token_rate: float = 0.0  # decoded tokens/s over the last window
    #                          (0.0 when the fleet serves no generates)

    @property
    def utilization(self) -> float:
        """In-flight work over fleet capacity (1.0 = every replica has
        a full largest-bucket batch outstanding)."""
        cap = self.n_replicas * self.max_batch
        return self.inflight / cap if cap > 0 else 0.0


class ScrapeFleetSignals:
    """Build :class:`FleetSignals` from ``/metrics`` scrapes instead of
    in-process router state — the control plane's signal source when
    the fleet it scales is NOT in its address space (out-of-process
    replica workers, or a router host observed by a separate
    controller process).

    ::

        exporter = telemetry.start_exporter()          # router host
        src = ScrapeFleetSignals(exporter.url,
                                 slo_s=router.slo_s,
                                 max_batch=router.grid.max_batch)
        ctl = FleetController(router, factory, signals_source=src)

    Scrapes the router host's exporter for the gauges the Router's
    monitor publishes every tick (``mxnet_serving_router_queue_depth``,
    ``mxnet_serving_router_inflight``,
    ``mxnet_serving_predicted_wait_seconds``,
    ``mxnet_controller_fleet_size``) plus the
    ``mxnet_serving_shed_total`` counter, whose between-scrape delta is
    computed here (counters are cumulative on the wire), and the
    ``mxnet_serving_tokens_total`` counter, rated into decode
    tokens/s over the scrape window (``FleetSignals.token_rate``; 0.0
    on a fleet that serves no generates). ``slo_s`` and
    ``max_batch`` are deploy-time configuration, not scrapable state.

    A failed scrape returns ``None`` — the controller skips that tick
    (no signal is not the same as a quiet fleet; acting on a default
    would tear down capacity every time the exporter hiccups).

    ``router`` selects ONE router's gauge series by its ``{router=}``
    label when the scraped process hosts several Routers (a deployed
    host usually has one). Without it the gauges are
    summed across routers — exact for a single-router host, ambiguous
    otherwise. ``mxnet_serving_shed_total`` has no router dimension,
    so the shed delta is always process-wide: point this source at an
    exporter whose process serves one fleet when sheds matter.
    """

    def __init__(self, url: str, slo_s: float, max_batch: int,
                 timeout_s: float = 2.0,
                 router: Optional[str] = None):
        if slo_s <= 0 or max_batch < 1:
            raise MXNetError(
                f"slo_s must be > 0 and max_batch >= 1, got "
                f"{slo_s}/{max_batch}")
        self.url = url
        self.slo_s = float(slo_s)
        self.max_batch = int(max_batch)
        self.timeout_s = float(timeout_s)
        self.router_label = ({"router": router} if router is not None
                             else None)
        self._last_shed: Optional[float] = None
        # per-tenant router queue depths from the latest good scrape
        # ({model: depth}) — a side-channel for multi-tenant dashboards
        # and tests; FleetSignals itself stays tenant-agnostic (the
        # scale policy sizes the fleet, not any one tenant)
        self.last_tenant_depths: dict = {}
        # decode token-rate window: previous tokens_total reading and
        # when it was taken (same reset-clamp rule as the shed counter)
        self._last_tokens: Optional[float] = None
        self._last_tokens_t: float = 0.0
        self.n_scrapes = 0
        self.n_failures = 0

    def __call__(self) -> Optional[FleetSignals]:
        try:
            parsed = telemetry.scrape(self.url, timeout_s=self.timeout_s)
        except Exception as e:  # noqa: BLE001 - a missed scrape skips
            self.n_failures += 1            # the tick, typed+logged
            _log.warning("scrape of %s failed (%s); skipping this "
                         "tick", self.url, e)
            return None
        self.n_scrapes += 1
        shed = telemetry.prom_value(parsed, "mxnet_serving_shed_total")
        if self._last_shed is None:
            delta = 0.0     # first scrape: no window to delta over
        else:
            # counter reset (router restart) reads as delta<0: clamp —
            # stale pressure must not survive a restart
            delta = max(shed - self._last_shed, 0.0)
        self._last_shed = shed
        now = time.monotonic()
        tokens = telemetry.prom_value(
            parsed, "mxnet_serving_tokens_total", default=0.0)
        if self._last_tokens is None or now <= self._last_tokens_t:
            token_rate = 0.0    # first scrape: no window to rate over
        else:
            token_rate = (max(tokens - self._last_tokens, 0.0)
                          / (now - self._last_tokens_t))
        self._last_tokens = tokens
        self._last_tokens_t = now
        n_replicas = telemetry.prom_value(
            parsed, "mxnet_controller_fleet_size",
            labels=self.router_label, default=-1.0)
        # per-tenant queue depths (one gauge series per model); the
        # router= label filter keeps replica-level series (router="")
        # out when this source watches one named router
        depths: dict = {}
        fam = parsed.get("mxnet_serving_tenant_queue_depth")
        if fam is not None:
            want = self.router_label or {}
            for s in fam["samples"]:
                if s["name"] != "mxnet_serving_tenant_queue_depth":
                    continue
                if not all(s["labels"].get(k) == v
                           for k, v in want.items()):
                    continue
                m = s["labels"].get("model", "")
                if m:
                    depths[m] = depths.get(m, 0) + int(s["value"])
        self.last_tenant_depths = depths
        if n_replicas < 1:
            # the router host publishes its gauges from the monitor
            # tick — an exporter that answers before the first tick (or
            # with telemetry disabled) has no fleet view yet; no signal
            # beats a made-up one
            return None
        return FleetSignals(
            n_replicas=int(n_replicas),
            queue_depth=int(telemetry.prom_value(
                parsed, "mxnet_serving_router_queue_depth",
                labels=self.router_label)),
            inflight=int(telemetry.prom_value(
                parsed, "mxnet_serving_router_inflight",
                labels=self.router_label)),
            shed_delta=int(delta),
            predicted_wait_s=telemetry.prom_value(
                parsed, "mxnet_serving_predicted_wait_seconds",
                labels=self.router_label),
            slo_s=self.slo_s, max_batch=self.max_batch,
            token_rate=token_rate)


class ScalePolicy:
    """The autoscaling decision function (pure: signals + clock in,
    desired fleet size out). Injectable ``time_fn`` so tests replay
    traffic traces against a fake clock.

    Scale-up (urgent, acts on one signal): any shedding since the last
    tick, or a predicted queue wait past ``up_wait_factor``·SLO — one
    replica per ``up_cooldown_s``. Scale-down (conservative,
    hysteresis): utilization under ``down_utilization`` AND an empty
    queue AND no shedding, sustained for ``down_hold_s``, at most one
    replica per ``down_cooldown_s``; any pressure resets the hold
    clock. Bounds ``[min_replicas, max_replicas]`` always win.
    """

    def __init__(self, min_replicas: int = 1, max_replicas: int = 4,
                 up_wait_factor: Optional[float] = None,
                 up_cooldown_s: Optional[float] = None,
                 down_utilization: Optional[float] = None,
                 down_hold_s: Optional[float] = None,
                 down_cooldown_s: Optional[float] = None,
                 time_fn=time.monotonic):
        if min_replicas < 1:
            raise MXNetError(
                f"min_replicas must be >= 1, got {min_replicas}")
        if max_replicas < min_replicas:
            raise MXNetError(
                f"max_replicas ({max_replicas}) must be >= min_replicas "
                f"({min_replicas})")
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.up_wait_factor = _env_float(
            "MXNET_CONTROLLER_UP_WAIT_FACTOR", 0.5) \
            if up_wait_factor is None else float(up_wait_factor)
        self.up_cooldown_s = _env_float(
            "MXNET_CONTROLLER_UP_COOLDOWN", 2.0) \
            if up_cooldown_s is None else float(up_cooldown_s)
        self.down_utilization = _env_float(
            "MXNET_CONTROLLER_DOWN_UTILIZATION", 0.25) \
            if down_utilization is None else float(down_utilization)
        self.down_hold_s = _env_float(
            "MXNET_CONTROLLER_DOWN_HOLD", 10.0) \
            if down_hold_s is None else float(down_hold_s)
        self.down_cooldown_s = _env_float(
            "MXNET_CONTROLLER_DOWN_COOLDOWN", 5.0) \
            if down_cooldown_s is None else float(down_cooldown_s)
        if not 0 < self.up_wait_factor:
            raise MXNetError("up_wait_factor must be > 0")
        if self.up_cooldown_s < 0 or self.down_cooldown_s < 0 \
                or self.down_hold_s < 0:
            raise MXNetError("cooldowns/hold must be >= 0")
        self._time = time_fn
        self._last_up = float("-inf")
        self._last_down = float("-inf")
        self._low_since: Optional[float] = None
        self.last_reason = "steady"

    def desired(self, s: FleetSignals) -> int:
        """Desired fleet size for this tick (moves at most one step from
        ``s.n_replicas``). Sets ``last_reason`` for telemetry labels."""
        now = self._time()
        n = s.n_replicas
        pressured = s.shed_delta > 0 or (
            s.predicted_wait_s > self.up_wait_factor * s.slo_s)
        if pressured:
            self._low_since = None      # pressure resets the down hold
            self.last_reason = ("shed" if s.shed_delta > 0
                                else "predicted_wait")
            if n < self.max_replicas and \
                    now - self._last_up >= self.up_cooldown_s:
                self._last_up = now
                return n + 1
            return max(n, self.min_replicas)
        quiet = (s.queue_depth == 0
                 and s.utilization < self.down_utilization)
        if not quiet:
            self._low_since = None
            self.last_reason = "steady"
            return max(n, self.min_replicas)
        if self._low_since is None:
            self._low_since = now
        self.last_reason = "idle"
        if n > self.min_replicas \
                and now - self._low_since >= self.down_hold_s \
                and now - self._last_down >= self.down_cooldown_s:
            self._last_down = now
            # one step down per cooldown; the hold clock keeps running
            # so a long-idle fleet steps down once per cooldown, not
            # once per hold
            return n - 1
        return max(n, self.min_replicas)

    def action_failed(self, direction: str) -> None:
        """The controller reports a scale action that did NOT happen
        (replica factory raised, drain failed): un-stamp that
        direction's cooldown so the next tick can retry immediately —
        the cooldown paces *successful* fleet changes, and a failed
        spawn under sustained shedding must not buy the failure a
        whole cooldown of continued shedding."""
        if direction == "up":
            self._last_up = float("-inf")
        else:
            self._last_down = float("-inf")


class FleetController:
    """Scale a :class:`Router`'s replica fleet from its own traffic
    signals.

    ::

        def factory(i):                    # UNSTARTED replica, same grid
            return serving.Server(build_net(), name=f"rep{i}",
                                  batch_buckets=..., shape_buckets=...,
                                  slo_ms=...)

        ctl = serving.FleetController(router, factory,
                                      policy=ScalePolicy(1, 4))
        ctl.start()                        # ticks in the background
        ...
        ctl.stop()

    ``replica_factory(index)`` builds an **unstarted** Server whose grid
    matches the fleet's; the controller starts it (full grid warmup —
    executable-table/disk-cache hits when the architecture is known)
    and admits it. A factory/start failure is contained: counted
    (``outcome="failed"``), logged, retried on a later tick — the
    controller thread never dies of a bad spawn. Scale-down picks the
    non-draining replica with the fewest in-flight requests (ties: the
    newest) and drains it through :meth:`Router.remove_replica`.

    ``tick()`` is public and synchronous so tests (and hand-rolled
    loops) can drive the controller without the thread.
    """

    def __init__(self, router: Router,
                 replica_factory: Callable[[int], object],
                 policy: Optional[ScalePolicy] = None,
                 interval_s: Optional[float] = None,
                 drain_timeout_s: float = 30.0,
                 signals_source: Optional[Callable[
                     [], Optional[FleetSignals]]] = None,
                 name: Optional[str] = None):
        if interval_s is None:
            interval_s = _env_float("MXNET_CONTROLLER_INTERVAL", 0.5)
        if interval_s <= 0:
            raise MXNetError(
                f"controller interval must be > 0, got {interval_s}")
        self.router = router
        self.replica_factory = replica_factory
        self.policy = policy or ScalePolicy()
        self.signals_source = signals_source
        self.interval_s = float(interval_s)
        self.drain_timeout_s = float(drain_timeout_s)
        self.name = name or f"controller_{id(self):x}"
        self._spawned = 0           # factory indices, never reused
        self._last_shed = router.n_shed
        self._last_tokens = self._fleet_tokens()
        self._last_tokens_t = time.monotonic()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # light counters
        self.n_ticks = 0
        self.n_scale_up = 0
        self.n_scale_down = 0
        self.n_scale_failed = 0
        self.scale_events: List[dict] = []

    # -- lifecycle -----------------------------------------------------
    @property
    def is_running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> "FleetController":
        if self.is_running:
            raise MXNetError(f"{self.name}: already running")
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name=self.name, daemon=True)
        self._thread.start()
        _live_controllers.add(self)
        if _telemetry_state.enabled:
            telemetry.set_fleet_size(self.router.fleet_size(),
                                     router=self.router.name)
        return self

    def stop(self, timeout: Optional[float] = None) -> None:
        """Stop the tick thread (the router and its replicas keep
        serving — the controller is an overlay, not an owner)."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout if timeout is not None
                   else max(5.0, 4 * self.interval_s))
            if t.is_alive():
                raise MXNetError(
                    f"{self.name}: tick thread did not exit (a drain "
                    "in flight?)")
        self._thread = None
        _live_controllers.discard(self)

    def __enter__(self) -> "FleetController":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:   # noqa: BLE001 - the loop must survive
                _log.exception("%s: tick failed (contained)", self.name)

    # -- one control iteration -----------------------------------------
    def _fleet_tokens(self) -> int:
        """Fleet-wide decoded-token counter (in-process servers only —
        a RemoteReplica's tokens are scrape territory, see
        :class:`ScrapeFleetSignals`)."""
        return sum(getattr(rep.server, "n_tokens", 0)
                   for rep in self.router._replicas)

    def signals(self) -> FleetSignals:
        r = self.router
        shed = r.n_shed
        delta = shed - self._last_shed
        self._last_shed = shed
        now = time.monotonic()
        tokens = self._fleet_tokens()
        dt = now - self._last_tokens_t
        # a removed replica takes its counter with it: clamp, same as
        # the scrape source does on counter reset
        token_rate = (max(tokens - self._last_tokens, 0) / dt
                      if dt > 0 else 0.0)
        self._last_tokens, self._last_tokens_t = tokens, now
        with r._cond:
            depth = len(r._queue)
            inflight = r._n_inflight
        return FleetSignals(
            n_replicas=r.fleet_size(), queue_depth=depth,
            inflight=inflight, shed_delta=delta,
            predicted_wait_s=r.predicted_wait(), slo_s=r.slo_s,
            max_batch=r.grid.max_batch, token_rate=token_rate)

    def tick(self) -> Optional[str]:
        """Observe, decide, act (at most one scale action). Returns
        ``"up"`` / ``"down"`` / ``None`` for what happened. With a
        ``signals_source`` (e.g. :class:`ScrapeFleetSignals`) the
        observation comes from there — a source returning ``None``
        (failed scrape) skips the tick entirely: no decision on no
        data."""
        self.n_ticks += 1
        if not self.router.is_running:
            return None
        s = self.signals_source() if self.signals_source is not None \
            else self.signals()
        if s is None:
            return None
        want = self.policy.desired(s)
        if want > s.n_replicas:
            return "up" if self._scale_up() else None
        if want < s.n_replicas:
            return "down" if self._scale_down() else None
        return None

    def _scale_up(self) -> bool:
        reason = self.policy.last_reason
        t0 = time.perf_counter()
        try:
            if _fault_state.enabled:
                fault.check("controller.scale", f"{self.name} up")
            idx = self._spawned
            self._spawned += 1
            server = self.replica_factory(idx)
            self.router.add_replica(server)   # starts + warms first
        except Exception as e:  # noqa: BLE001 - contained, retried later
            self.n_scale_failed += 1
            self.policy.action_failed("up")    # no cooldown for a no-op
            if _telemetry_state.enabled:
                telemetry.record_fleet_scale("up", "failed")
            _log.warning("%s: scale-up failed (%s); will retry on a "
                         "later tick", self.name, e)
            return False
        dt = time.perf_counter() - t0
        self.n_scale_up += 1
        self.scale_events.append(
            {"dir": "up", "reason": reason, "replica": server.name,
             "seconds": dt})
        if _telemetry_state.enabled:
            telemetry.record_fleet_scale("up")
            telemetry.record_fleet_scale_seconds("up", dt)
        _log.info("%s: scaled up to %d (%s, %.2fs warm)", self.name,
                  self.router.fleet_size(), reason, dt)
        return True

    def _scale_down(self) -> bool:
        # victim: fewest in-flight among non-draining; ties -> newest
        # (highest stable index) so long-lived replicas stay put
        candidates = [r for r in self.router.replicas()
                      if not r["draining"]]
        if len(candidates) <= 1:
            return False
        victim = min(candidates,
                     key=lambda r: (r["inflight"], -r["index"]))
        t0 = time.perf_counter()
        try:
            if _fault_state.enabled:
                fault.check("controller.scale", f"{self.name} down")
            self.router.remove_replica(
                victim["name"], drain=True,
                timeout=self.drain_timeout_s)
        except Exception as e:  # noqa: BLE001 - contained, retried later
            self.n_scale_failed += 1
            self.policy.action_failed("down")
            if _telemetry_state.enabled:
                telemetry.record_fleet_scale("down", "failed")
            _log.warning("%s: scale-down of %s failed (%s)", self.name,
                         victim["name"], e)
            return False
        dt = time.perf_counter() - t0
        self.n_scale_down += 1
        self.scale_events.append(
            {"dir": "down", "reason": self.policy.last_reason,
             "replica": victim["name"], "seconds": dt})
        if _telemetry_state.enabled:
            telemetry.record_fleet_scale("down")
            telemetry.record_fleet_scale_seconds("down", dt)
        _log.info("%s: drained %s, fleet now %d", self.name,
                  victim["name"], self.router.fleet_size())
        return True

    def stats(self) -> dict:
        return {"ticks": self.n_ticks, "scale_up": self.n_scale_up,
                "scale_down": self.n_scale_down,
                "scale_failed": self.n_scale_failed,
                "fleet_size": self.router.fleet_size(),
                "events": list(self.scale_events),
                "running": self.is_running}


# ---------------------------------------------------------------------------
# rolling upgrade
# ---------------------------------------------------------------------------

def _bake(rep: dict, bake_s: float, poll_s: float = 0.05) -> Optional[str]:
    """Watch one freshly-upgraded replica for ``bake_s``: returns None
    when it baked healthy, else the failure description. Signals: the
    replica's breaker leaving CLOSED (the router's own failure/hang
    evidence) or ANY new dispatch error on the server (a batch the new
    model failed — visible even before the breaker's threshold).
    Deliberately conservative: the server dispatches one batch at a
    time, so at most one OLD-model batch can still be in flight when
    the swap lands — if that one errors into the bake window the
    rollout rolls back on ambiguous evidence rather than baking a
    possibly-bad build through it."""
    server, breaker = rep["server"], rep["breaker"]
    err0 = server.n_errors
    deadline = time.monotonic() + max(0.0, bake_s)
    while True:
        if breaker.state != CLOSED:
            return (f"breaker {breaker.state} during bake "
                    f"(trips={breaker.n_trips})")
        if server.n_errors > err0:
            return (f"{server.n_errors - err0} dispatch error(s) "
                    "during bake")
        if time.monotonic() >= deadline:
            return None
        time.sleep(min(poll_s, max(bake_s, 1e-3)))


def rolling_upgrade(router: Router, model_factory: Callable,
                    bake_s: Optional[float] = None,
                    version: Optional[int] = None,
                    model: Optional[str] = None) -> dict:
    """Upgrade every replica of ``router`` to a new model, one at a
    time, with automatic rollback.

    ``model_factory(server)`` builds the NEW block for one replica (load
    new weights, hybridize — the ``ReloadWatcher`` factory contract,
    handed the live ``Server`` instead of a bundle path). Per replica:
    fault-check ``serving.upgrade`` → build → ``swap_model`` (warms
    every live signature first; the old graph serves until the swap) →
    bake for ``bake_s`` (``MXNET_UPGRADE_BAKE``, default 1.0 s)
    watching the breaker and dispatch errors. Any failure rolls back
    every replica touched so far — old model AND old version number,
    newest first — and raises :class:`UpgradeRolledBack` chained to the
    cause. On success every replica reports the same new
    ``model_version`` (``version`` or max(old)+1).

    ``model`` selects WHICH tenant is upgraded on a multi-tenant fleet
    (default: the default tenant). The swap, the bake and a rollback
    touch that tenant's block and version only — upgrading (or rolling
    back) tenant A never rebuilds or rolls back tenant B, even though
    both share the replica's cache pool and executable table.

    Returns ``{"version", "model", "upgraded": [names...],
    "seconds"}``. Serialized against scale actions via the router's
    admin lock — the fleet cannot change shape mid-rollout.
    """
    if bake_s is None:
        bake_s = _env_float("MXNET_UPGRADE_BAKE", 1.0)
    t_start = time.perf_counter()
    with router._admin_lock:
        reps = [r for r in router.replicas() if not r["draining"]]
        if not reps:
            raise MXNetError("rolling_upgrade: no replicas to upgrade")
        # the bake reads each replica's breaker as evidence AGAINST the
        # new model — a breaker already non-CLOSED would fail its bake
        # instantly and blame pre-existing unhealth on the build, so a
        # degraded fleet refuses the rollout up front (typed, nothing
        # swapped) instead of rolling back half an upgrade
        sick = [r["name"] for r in reps if r["state"] != CLOSED]
        if sick:
            raise MXNetError(
                f"rolling_upgrade: fleet not healthy — breaker not "
                f"closed on {sick}; let the fleet recover (half-open "
                "probes re-admit) before upgrading")
        # in-place swap needs the in-process Server surface; an
        # out-of-process RemoteReplica has no swap_model — refuse the
        # whole rollout typed BEFORE anything is swapped (upgrading a
        # worker fleet is respawn-with-a-new-factory, not a live swap)
        remote = [r["name"] for r in reps
                  if not hasattr(r["server"], "swap_model")]
        if remote:
            raise MXNetError(
                f"rolling_upgrade: replicas {remote} are out-of-process"
                " workers without in-place swap_model; upgrade a worker"
                " fleet by respawning workers with the new factory "
                "(remove_replica/add_replica)")
        tenant = DEFAULT_MODEL if model is None else model
        # every replica must serve the tenant BEFORE anything swaps —
        # a mid-rollout unknown-model refusal would strand a partial
        # upgrade (same shape as the remote refusal above)
        missing = [r["name"] for r in reps
                   if tenant not in r["server"].model_versions()]
        if missing:
            raise MXNetError(
                f"rolling_upgrade: replicas {missing} do not serve "
                f"model {tenant!r}; register it on the whole fleet "
                "(Router.register_model) before upgrading it")
        new_version = (
            max(r["server"].model_versions()[tenant] for r in reps) + 1
            if version is None else int(version))
        done: List[tuple] = []      # (rep, old_block, old_version)

        def _rollback(cause: BaseException, failed_at: str):
            for rep, old_block, old_version in reversed(done):
                try:
                    rep["server"].swap_model(old_block,
                                             version=old_version,
                                             model=tenant)
                except Exception:   # noqa: BLE001 - keep restoring
                    _log.exception(
                        "rollback of replica %s failed — it keeps the "
                        "NEW model", rep["name"])
                if _telemetry_state.enabled:
                    telemetry.record_upgrade_replica("rolled_back")
            raise UpgradeRolledBack(
                f"upgrade of model {tenant!r} to version {new_version} "
                f"failed at replica {failed_at} ({cause}); {len(done)} "
                "replica(s) rolled back to the previous model"
                ) from cause

        for rep in reps:
            server = rep["server"]
            old_block = server.current_model(model=tenant)
            old_version = server.model_versions()[tenant]
            try:
                if _fault_state.enabled:
                    fault.check("serving.upgrade", server.name)
                new_block = model_factory(server)
                server.swap_model(new_block, version=new_version,
                                  model=tenant)
            except Exception as e:  # noqa: BLE001 - rollback path
                if _telemetry_state.enabled:
                    telemetry.record_upgrade_replica("aborted")
                _rollback(e, server.name)
            done.append((rep, old_block, old_version))
            failure = _bake(rep, bake_s)
            if failure is not None:
                _rollback(MXNetError(failure), server.name)
            if _telemetry_state.enabled:
                telemetry.record_upgrade_replica("ok")
            _log.info("rolling upgrade: %s model %s now at version %d",
                      server.name, tenant, new_version)
    return {"version": new_version, "model": tenant,
            "upgraded": [r["name"] for r in reps],
            "seconds": time.perf_counter() - t_start}
