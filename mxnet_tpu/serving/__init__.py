"""``mx.serving`` — the inference serving stack (ROADMAP item 1).

A model server over the ``_CachedGraph`` compiled path: concurrent
requests enter through ``Server.submit`` (thread-safe, Future out), a
scheduler drains them into dynamic batches padded onto a
``BucketGrid`` — the ``BucketingModule`` idea (PAPER.md §2.3) re-keyed
to compiled-graph cache entries — and dispatches each batch as one warm
XLA executable under a per-request latency SLO. ``Router`` fronts N
``Server`` replicas behind the same ``submit() -> Future`` contract
with least-loaded dispatch, per-replica circuit breakers, bounded
failover (no future is ever lost) and deadline-aware admission control
(synchronous typed ``ServerOverloaded`` shedding). The fleet is
elastic: ``Router.add_replica``/``remove_replica`` grow and drain it
live, ``FleetController`` drives them from the router's own traffic
signals, and ``rolling_upgrade`` walks a new model through the fleet
with breaker-gated automatic rollback (see :mod:`.controller`).

The stack also serves **autoregressive decode** with continuous
batching: ``Server.submit_generate() -> GenerateHandle`` streams
tokens as they are produced, per-request KV state lives in a paged
``PagePool`` (:mod:`.kvcache`), prefill lands on the ``BucketGrid``'s
length buckets, and every decode step for every in-flight request
rejoins one warm ``(batch, 1)`` executable — zero steady-state
retraces. Capacity exhaustion is a synchronous typed ``CacheFull``.
The same contract crosses the process boundary: ``RemoteReplica``,
``Router`` and ``IngressClient`` all expose ``submit_generate`` with
token streaming over the wire.

The fleet is also **crash-isolated**: a replica may be an
out-of-process worker (``RemoteReplica`` over
``python -m mxnet_tpu.serving.worker``, one supervised OS process per
replica speaking the :mod:`.wire` frame protocol) — a segfault or
SIGKILL there is an unambiguous, typed failure the router routes
around and the supervisor respawns with backoff. ``Ingress`` puts a
socket edge in front of the Router (bounded per-connection windows,
backpressure as typed error frames; ``IngressClient`` is the matching
client), and ``ScrapeFleetSignals`` feeds the autoscaler from
``/metrics`` scrapes so the control plane works across address
spaces. Hot reload, fault injection/retry and Prometheus telemetry
ride the PR-1/PR-3 infrastructure; see :mod:`.server`,
:mod:`.buckets`, :mod:`.reload`, :mod:`.router`, :mod:`.health`,
:mod:`.wire`, :mod:`.worker`, :mod:`.remote`, :mod:`.ingress`.

The stack is **multi-tenant**: ``Server.register_model`` /
``Router.register_model`` put several hybridized blocks behind one
replica fleet (each tenant carries an SLO class, a priority, a
weighted-fair share and an optional ``TokenBucket`` rate limit), the
scheduler interleaves tenants per decode step under weighted
admission, and when the shared KV-cache pool fills a higher-priority
arrival preempts the lowest-priority active stream BETWEEN decode
steps — the victim resolves typed (``Preempted``) with a sealed
clean-prefix token stream, never a torn token. ``model=`` /
``priority=`` ride every seam (wire frames, worker, ``RemoteReplica``,
``Ingress``); an absent field means the default tenant, so old peers
interoperate.
"""
from .buckets import DEFAULT_LEN_BUCKETS, BucketGrid, TokenBucket
from .controller import (
    FleetController,
    FleetSignals,
    ScalePolicy,
    ScrapeFleetSignals,
    UpgradeRolledBack,
    live_controllers,
    rolling_upgrade,
)
from .health import CircuitBreaker, Heartbeat
from .ingress import (
    Ingress,
    IngressClient,
    IngressDisconnected,
    live_ingresses,
)
from .kvcache import CacheFull, PagePool, Preempted, StateSlots
from .reload import ReloadWatcher
from .remote import RemoteReplica, WorkerCrashed, live_workers
from .router import (
    FailoverExhausted,
    ReplicaFault,
    Router,
    ServerOverloaded,
    live_routers,
)
from .server import (
    DEFAULT_MODEL,
    GenerateHandle,
    ImageMismatch,
    Server,
    TenantThrottled,
    live_servers,
)

__all__ = [
    "Server", "BucketGrid", "ReloadWatcher", "live_servers",
    "GenerateHandle", "PagePool", "StateSlots", "CacheFull", "DEFAULT_LEN_BUCKETS",
    "DEFAULT_MODEL", "TenantThrottled", "ImageMismatch", "Preempted", "TokenBucket",
    "Router", "ServerOverloaded", "FailoverExhausted", "ReplicaFault",
    "CircuitBreaker", "Heartbeat", "live_routers",
    "FleetController", "FleetSignals", "ScalePolicy",
    "ScrapeFleetSignals",
    "UpgradeRolledBack", "rolling_upgrade", "live_controllers",
    "RemoteReplica", "WorkerCrashed", "live_workers",
    "Ingress", "IngressClient", "IngressDisconnected", "live_ingresses",
]
