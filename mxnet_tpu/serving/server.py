"""``mx.serving.Server`` — continuous-batching model server.

The repo trains fast; this is the piece that *serves* (ROADMAP item 1).
One server wraps one hybridized (optionally int8-quantized) Gluon block
and turns concurrent single-sample requests into bucket-padded batches:

* :meth:`Server.submit` is the thread-safe ingress — any thread hands in
  one sample and gets a ``concurrent.futures.Future`` back;
* a scheduler thread drains the queue into dynamic batches under a
  per-request latency SLO: it keeps filling while the oldest queued
  request is comfortably inside its deadline and dispatches early the
  moment it is not (deadline-aware batch close);
* each batch is padded up to the nearest :class:`~.buckets.BucketGrid`
  entry, so every dispatch lands on one warm ``_CachedGraph`` executable
  (``HybridBlock.warmup`` pre-compiles the whole grid at load time);
* per-request outputs are sliced from the real rows and resolved into
  the futures; padded rows never reach a caller.

Resilience reuses the PR-3 runtime: every dispatch runs under
``fault.retry_call`` at site ``serving.dispatch`` (transient failures
retry with backoff; deterministic ones fail the batch's futures, not the
server), and hot reload (``serving.reload``) swaps a freshly-built,
freshly-WARMED model in behind a lock — the old graph serves every
request that arrives while the new one compiles (see
:mod:`mxnet_tpu.serving.reload`).

Telemetry (``MXNET_TELEMETRY=1`` / ``telemetry.enable()``):
``mxnet_serving_queue_depth``, ``mxnet_serving_batch_occupancy``,
``mxnet_serving_time_in_queue_seconds``, ``mxnet_serving_request_seconds``
(p50/p99 from the fine ``SERVING_BUCKETS``), ``mxnet_serving_requests_total``,
``mxnet_serving_batches_total{reason}``, ``mxnet_serving_reloads_total`` —
all exported via ``telemetry.prom_text()``.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
import weakref
from concurrent.futures import Future
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from .. import autograd, fault, telemetry, tracing
from ..base import MXNetError
from ..fault import _state as _fault_state
from ..telemetry import _state as _telemetry_state
from ..tracing import _state as _tracing_state
from .buckets import DEFAULT_LEN_BUCKETS, BucketGrid, TokenBucket
from .engine import PagedDecodeEngine
from .health import Heartbeat
from .kvcache import CacheFull, PagePool, Preempted

__all__ = ["Server", "GenerateHandle", "TenantThrottled", "live_servers"]

DEFAULT_MODEL = "default"


class TenantThrottled(MXNetError):
    """Typed per-tenant admission shed: this tenant's token bucket is
    empty. Synchronous at submit (never a queued request burning another
    tenant's deadline budget) and scoped to ONE tenant — the fleet is
    not overloaded, this tenant's configured rate is. Crosses
    :mod:`.wire` under the stable name ``throttled``."""


class ImageMismatch(MXNetError):
    """``submit_generate(images=)`` was refused: the model takes no
    images, an image is not a whole grid of patches, or the prompt's run
    of placeholder ids disagrees with the images' grids."""


class _Tenant:
    """One registered model sharing this server's replica.

    Tenants share the bucket grid, the scheduler thread, and (when
    decode is on) the ONE :class:`PagePool` — page accounting is the
    multi-tenant contention point priority preemption arbitrates. Each
    tenant owns its block, its decode engine (its own K/V arenas over
    the shared page numbering), its model version, its admission
    token-bucket, and its weighted-fair credit state (credits are only
    ever touched by the scheduler thread)."""

    __slots__ = ("name", "block", "slo_class", "priority", "weight",
                 "slo_s", "bucket", "engine", "engine_version",
                 "model_version", "credit", "dcredit", "warm_sigs",
                 "n_requests", "n_shed", "n_preempted", "n_tokens",
                 "long_wave")

    def __init__(self, name, block, slo_class, priority, weight, slo_s,
                 bucket):
        self.name = name
        self.block = block
        self.slo_class = slo_class
        self.priority = int(priority)
        self.weight = float(weight)
        self.slo_s = float(slo_s)
        self.bucket = bucket            # TokenBucket or None
        self.engine = None
        self.engine_version = -1
        self.model_version = 0
        self.credit = 0.0               # weighted-fair classify pick
        self.dcredit = 0.0              # weighted-fair decode slots
        self.warm_sigs = set()          # sigs THIS tenant has served
        self.long_wave = -1             # last arrival of the long prompts
        #                                 being admitted longest first
        self.n_requests = 0
        self.n_shed = 0
        self.n_preempted = 0            # streams evicted FROM this tenant
        self.n_tokens = 0

# every running server, for the test-suite leak guard: a test that leaves
# a scheduler (or watcher) thread running would tax every later test
_live_servers = weakref.WeakSet()


def live_servers():
    """Servers whose scheduler thread is currently running."""
    return [s for s in list(_live_servers) if s.is_running]


class _Request:
    __slots__ = ("sample", "shape_key", "future", "t_enqueue", "deadline",
                 "trace", "span", "own_trace", "tenant")

    def __init__(self, sample, shape_key, deadline_s, tenant=None):
        self.sample = sample
        self.shape_key = shape_key
        self.tenant = tenant
        self.future = Future()
        self.t_enqueue = time.perf_counter()
        self.deadline = self.t_enqueue + deadline_s
        # tracing (MXNET_TRACING=1): the request's Trace, its live
        # batch.wait span, and whether THIS server minted the trace
        # (a router/worker that handed it in finishes it instead)
        self.trace = None
        self.span = None
        self.own_trace = False


class GenerateHandle:
    """Streaming handle for one autoregressive generate request.

    ``future`` resolves to the full int32 token array when the
    completion finishes (or raises the typed failure — ``CacheFull``,
    ``WorkerCrashed``, ``MXNetError`` — exactly like ``submit``'s
    future: a generate NEVER wedges). Tokens stream as they are
    decoded: ``on_token(index, token)`` fires per token (from the
    scheduler/reader thread — keep it cheap), ``tokens()`` snapshots
    what has arrived, and ``next_token(i)`` blocks until token ``i``
    exists or the stream ends (returns None when it ended first).
    """

    def __init__(self, on_token=None):
        self.future = Future()
        self._on_token = on_token
        self._cond = threading.Condition()
        self._tokens: list = []
        # a stream that generates by diffusion over blocks: the
        # denoising step of its block at which each token was unmasked,
        # one byte a token (None for every other stream)
        self._steps: Optional[bytearray] = None

    def _push(self, token: int, step: Optional[int] = None) -> None:
        with self._cond:
            self._tokens.append(int(token))
            if step is not None:
                if self._steps is None:
                    self._steps = bytearray()
                self._steps.append(step)
            i = len(self._tokens) - 1
            self._cond.notify_all()
        cb = self._on_token
        if cb is not None:
            try:
                cb(i, int(token))
            except Exception:   # noqa: BLE001 - user callback stays user's
                pass

    def _seal(self) -> None:
        """Wake every next_token() waiter once the future resolved."""
        with self._cond:
            self._cond.notify_all()

    def tokens(self) -> list:
        with self._cond:
            return list(self._tokens)

    def unmask_steps(self) -> Optional[list]:
        """For a model that generates by diffusion over blocks: the
        denoising step (0-based, within its block) at which each token
        that has arrived was unmasked; None for every other model."""
        with self._cond:
            return None if self._steps is None else list(self._steps)

    def next_token(self, i: int, timeout: Optional[float] = None):
        """Block until token ``i`` streams in; None when the request
        finished (or failed — check ``future``) before producing it."""
        deadline = (time.perf_counter() + timeout
                    if timeout is not None else None)
        with self._cond:
            while len(self._tokens) <= i:
                if self.future.done():
                    return None
                wait = 0.05 if deadline is None \
                    else min(0.05, deadline - time.perf_counter())
                if wait <= 0:
                    return None
                self._cond.wait(wait)
            return self._tokens[i]

    def result(self, timeout: Optional[float] = None):
        return self.future.result(timeout)


class _GenRequest:
    __slots__ = ("prompt", "max_new", "handle", "pages", "length",
                 "generated", "t_submit", "t_last", "deadline", "trace",
                 "span", "own_trace", "len_bucket", "model_version",
                 "tenant", "priority", "seq", "prefilled", "slot",
                 "images", "encoded", "embeds", "embed_at", "reserve",
                 "block", "block_steps", "step", "pushed")

    def __init__(self, prompt, max_new, handle, deadline_s, tenant=None,
                 priority=0, seq=0, images=None, embed_at=None, tail=None,
                 reserve=None):
        self.prompt = prompt                 # 1-D int32 token array
        self.max_new = int(max_new)
        self.handle = handle
        self.tenant = tenant
        self.priority = int(priority)        # preemption rank
        self.seq = int(seq)                  # stream id (preempt events)
        self.pages = None                    # page list once admitted
        self.slot = None                     # state slot, where the
        #                                      engine keeps such state
        self.length = len(prompt)            # tokens written OR known
        # tokens its pages have to hold
        self.reserve = (len(prompt) + self.max_new if reserve is None
                        else int(reserve))
        # a stream of an engine that steps BLOCKS (`block_length` > 1):
        # `prompt` is the prompt's whole blocks (what a prefill writes),
        # `tail` the tokens left over, which open the first block
        # already unmasked; `block` the current block's state (a list of
        # ids, the mask id where still masked), `block_steps` the
        # denoising step at which each of its positions was unmasked
        # (plain lists: a round touches them a stream at a time), `step`
        # the next denoising step of the block and `pushed` the positions of it
        # handed to the caller or known from the prompt. `length` counts
        # the blocks committed to the cache.
        self.block = None
        self.block_steps = None
        self.step = 0
        self.pushed = 0
        if tail is not None:
            engine = tenant.engine
            bk = engine.block_length
            self.block = tail.tolist() + [engine.mask_id] * (bk - tail.size)
            self.block_steps = [0] * bk
            self.pushed = int(tail.size)
        self.generated: list = []
        self.t_submit = time.perf_counter()
        self.t_last = self.t_submit          # last token emit (per-token lat)
        self.deadline = (self.t_submit + deadline_s
                         if deadline_s is not None else None)
        self.trace = None
        self.span = None                     # live gen.queue / phase span
        self.own_trace = False
        self.len_bucket = 0
        self.prefilled = 0                   # prompt tokens in the cache
        self.model_version = -1
        # a request with images: [(patches, (rows, cols))], how many of
        # them the tower has encoded, the device buffer of their rows
        # (held from the first encode to the prompt's last chunk) and the
        # prompt positions that take those rows, in order
        self.images = images
        self.encoded = 0
        self.embeds = None
        self.embed_at = embed_at


class Server:
    """Serve a Gluon block under a latency SLO with bucketed batching.

    ::

        net.hybridize()
        srv = mx.serving.Server(net, batch_buckets=(1, 4, 16, 32),
                                shape_buckets=[(3, 224, 224)], slo_ms=50)
        srv.start()                       # warms every grid bucket
        fut = srv.submit(image)           # any thread; one sample, no
        probs = fut.result()              # batch dim; numpy out
        srv.stop()                        # drains in-flight requests

    ``block``: the model. A ``HybridBlock`` is hybridized (if it is not
    already) and every grid bucket is AOT-warmed at :meth:`start`; a
    plain ``Block`` serves eagerly (no warmup — useful for tests).

    ``slo_ms`` is the per-request latency objective: a request's batch
    closes no later than ``slo_ms - close_margin_ms`` after its submit,
    however empty the batch is; under load batches close early on
    ``full``. ``deadline_ms=`` at submit overrides per request.

    ``batch_timeout_ms`` caps how long the OLDEST queued request waits
    for co-batching before its batch closes anyway (the TF-Serving
    ``batch_timeout`` knob). ``None`` (default) keeps the legacy
    deadline-keyed patience: the scheduler fills toward the biggest
    bucket until ``deadline - close_margin``. That patience is optimal
    when arrivals come in tight waves (an in-process closed loop
    refills atomically), but an arrival stream SPREAD by a pipeline —
    results trickling back over a socket, clients refilling one by one
    — never quite fills the bucket, so every batch closes at the SLO
    edge and p50 ~= SLO however light the load (measured: 100% of
    worker batches ``deadline``-closed through the ingress). A few ms
    here trades a few points of occupancy for an SLO-independent
    latency floor; out-of-process workers default it on
    (``serving.RemoteReplica(batch_timeout_ms=5)``).

    ``max_prefill_tokens`` bounds ONE prefill dispatch of
    ``submit_generate``: admission closes a (tenant, length bucket)
    group before ``batch bucket x length bucket`` would pass it and
    leaves the rest pending for the next tick, in order. A server whose
    decode width (hundreds of streams) is far above a safe prefill
    width needs it; ``None`` (default) prefills a tick's whole length
    group as one batch.

    A prompt LONGER than the largest length bucket is served by an
    engine that declares ``chunked_prefill``
    (:class:`~.engine.PagedDecodeEngine`): its pages (prompt + budget)
    are allocated at admission as for any request, and it is prefilled
    one chunk of the largest length bucket a tick (its tail in the
    smallest bucket that holds it), each chunk attending to the
    chunks before it through the cache; the last chunk emits the first
    token. Within ``max_prefill_tokens`` a tick prefills chunks of the
    prompts in flight first, in the order they were admitted, then new
    admissions, and the decode round of the active streams follows
    every tick, so a long prompt delays a live stream by one chunk a
    token, never by the whole prompt. Long prompts that WAIT TOGETHER
    are admitted longest first (:meth:`_next_pending`): a stream that
    already decodes waits through every chunk of every prompt admitted
    after its own, so the costliest prompts go while the fewest streams
    decode behind them. The streams' total stall is then the least any
    order gives and does not depend on which caller happened to arrive
    first; the last first token of the wave comes no later.

    An engine that declares ``state_slots`` (per-stream state that is not
    pages: a scan's state, a window's ring) gets one slot a stream,
    taken with the pages at admission (both or neither), handed over
    with every dispatch and freed with the pages on every way out of a
    stream; the pool carries ``largest batch bucket + 1`` of them (slot 0
    is the padding rows' scratch), so at most a decode round's width of
    such streams hold state at once and the rest wait as for pages.

    An engine that declares a vision encoder (``engine.vision``) serves
    requests that carry IMAGES (``submit_generate(images=)``), on a
    server built with ``patch_buckets`` (the patch counts an image is
    padded to) and ``max_image_tokens`` (the rows of image embeddings a
    request may hold). Such a request is admitted like any other (pages
    for prompt + budget, preemption, deadlines) and then waits in line
    for the ENCODE stage: each tick runs at most ONE image through the
    tower, before the tick's prefill chunks and its decode round, and
    keeps the image's rows in the request's embedding buffer on the
    device; once all its images are rows, the request's prompt goes a
    dispatch of its own a chunk (the chunk's positions that hold the
    placeholder id take their rows through the engine's embeddings seam)
    and the buffer goes when the last chunk is in the cache, or with the
    pages on any other way out of the stream. A request without images
    takes the path it took before the stage existed.

    ``dtype``: samples are cast to it on submit. Futures resolve with
    numpy arrays (or the model's output structure with numpy leaves).
    """

    def __init__(self, block, batch_buckets=(1, 2, 4, 8, 16, 32),
                 shape_buckets=None, slo_ms: float = 100.0,
                 close_margin_ms: float = 5.0, max_queue: int = 4096,
                 dtype: str = "float32", ctx=None, warmup: bool = True,
                 name: Optional[str] = None,
                 batch_timeout_ms: Optional[float] = None,
                 decode_pages: Optional[int] = None, page_size: int = 16,
                 len_buckets=None,
                 max_generate_tokens: Optional[int] = None,
                 slo_class: str = "standard", priority: int = 0,
                 weight: float = 1.0, rate_limit: Optional[float] = None,
                 burst: Optional[float] = None,
                 defrag_threshold: Optional[float] = 0.25,
                 max_prefill_tokens: Optional[int] = None,
                 patch_buckets=None, max_image_tokens: Optional[int] = None):
        if slo_ms <= 0:
            raise MXNetError(f"slo_ms must be > 0, got {slo_ms}")
        if close_margin_ms < 0 or close_margin_ms >= slo_ms:
            raise MXNetError(
                f"close_margin_ms must be in [0, slo_ms), got "
                f"{close_margin_ms} (slo_ms={slo_ms})")
        if batch_timeout_ms is not None and batch_timeout_ms <= 0:
            raise MXNetError(
                f"batch_timeout_ms must be > 0 (or None for the "
                f"deadline-keyed close), got {batch_timeout_ms}")
        if max_queue < 1:
            raise MXNetError(f"max_queue must be >= 1, got {max_queue}")
        # autoregressive decode: a page pool + a model-provided decode
        # engine turn on submit_generate (see _decode_tick)
        self._decode_pages = decode_pages
        if decode_pages is not None and len_buckets is None:
            len_buckets = DEFAULT_LEN_BUCKETS
        self.grid = BucketGrid(batch_buckets, shape_buckets,
                               len_buckets=len_buckets)
        self._page_size = int(page_size)
        if decode_pages is not None:
            cap = (int(decode_pages) - 1) * self._page_size
            self._max_gen_tokens = int(
                max_generate_tokens if max_generate_tokens is not None
                else min(cap, self.grid.len_buckets[-1] + 256))
            if self._max_gen_tokens > cap:
                raise MXNetError(
                    f"max_generate_tokens={self._max_gen_tokens} exceeds "
                    f"the pool's {cap}-token capacity "
                    f"({decode_pages} pages x {page_size}, scratch "
                    "page excluded)")
        # bound on the padded tokens (batch bucket x len bucket) of ONE
        # prefill dispatch; None: a tick's whole length group is one batch
        if max_prefill_tokens is not None and max_prefill_tokens < 1:
            raise MXNetError(
                f"max_prefill_tokens must be >= 1, got {max_prefill_tokens}")
        self._max_prefill_tokens = (int(max_prefill_tokens)
                                    if max_prefill_tokens is not None
                                    else None)
        # the vision stage of an engine that declares one: the
        # patch-count buckets an image is padded to and the rows of a
        # request's embedding buffer
        self._patch_buckets = tuple(patch_buckets or ())
        self._max_image_tokens = max_image_tokens
        self._pool: Optional[PagePool] = None
        self._gen_table_w = 0
        # streams that hold pages; one whose prompt is not yet whole in
        # the cache (``prefilled < prompt.size``) takes prefill chunks,
        # the others decode
        self._gen_active: list = []
        self.n_tokens = 0
        self.slo_s = slo_ms / 1e3
        self.margin_s = close_margin_ms / 1e3
        self.batch_timeout_s = (batch_timeout_ms / 1e3
                                if batch_timeout_ms is not None else None)
        self.max_queue = int(max_queue)
        self.dtype = dtype
        self.ctx = ctx
        self.name = name or f"server_{id(self):x}"
        self._warmup = bool(warmup)
        self._model_lock = threading.Lock()
        self._cond = threading.Condition()
        # multi-tenant registry: the constructor block IS tenant
        # "default" (single-tenant callers never see the registry);
        # register_model() adds tenants sharing this replica. Per-tenant
        # queues so one tenant's burst cannot push another's requests
        # back in a shared FIFO.
        self._tenants: Dict[str, _Tenant] = {}
        self._queues: Dict[str, list] = {}
        self._gen_pending: Dict[str, list] = {}
        self._seq = itertools.count()       # stream ids (preempt events)
        if weight <= 0:
            raise MXNetError(f"weight must be > 0, got {weight}")
        bucket = (TokenBucket(rate_limit, burst)
                  if rate_limit is not None else None)
        t0 = _Tenant(DEFAULT_MODEL, block, str(slo_class), priority,
                     weight, self.slo_s, bucket)
        self._tenants[DEFAULT_MODEL] = t0
        self._queues[DEFAULT_MODEL] = []
        self._gen_pending[DEFAULT_MODEL] = []
        # automatic defrag trigger: pack the pool when free holes below
        # its high-water mark exceed this many pages (None disables)
        self._defrag_min_pages: Optional[int] = None
        if defrag_threshold is not None and decode_pages is not None:
            if not 0 < float(defrag_threshold) <= 1:
                raise MXNetError(
                    f"defrag_threshold must be in (0, 1] or None, got "
                    f"{defrag_threshold}")
            self._defrag_min_pages = max(
                2, int(float(defrag_threshold) * (int(decode_pages) - 1)))
        self._drain = True
        self._running = False
        self._thread: Optional[threading.Thread] = None
        self._watcher = None        # reload.ReloadWatcher, when enabled
        # pre-dispatch hook, set by serving.Router on managed replicas:
        # runs INSIDE run() (the retried dispatch body) so an injected
        # replica fault / latency lands exactly where a real replica
        # failure would — in this scheduler thread, per batch
        self._pre_dispatch = None
        # scheduler-loop liveness beacon: touched once per loop
        # iteration (so between two touches at most ONE dispatch runs).
        # A Router reads it to tell a *hung* dispatch from a scheduler
        # patiently filling a batch toward its deadline close.
        self.hb = Heartbeat()
        self.loaded_step: Optional[int] = None
        # signatures actually compiled/used — the reload warmup manifest
        # (union across tenants; each tenant also tracks its own)
        self._warm_sigs = set()
        # always-on light counters (telemetry covers the full story)
        self.n_requests = 0
        self.n_batches = 0
        self.n_errors = 0
        self.n_reloads = 0
        self.n_preemptions = 0
        self.n_defrags = 0
        # decode rounds dispatched, and the phase clock of the round in
        # hand (scheduler thread only; None unless tracing or telemetry
        # was on at its tick's entry: nothing then reads a clock for it)
        self._n_rounds = 0
        self._round_clock: Optional[_RoundClock] = None
        self._round_end_ns: Optional[int] = None

    # -- single-tenant compat: the default tenant's block/version are
    # the server's (tests, controller and chaos gates read these) ------
    @property
    def _model(self):
        return self._tenants[DEFAULT_MODEL].block

    @_model.setter
    def _model(self, block) -> None:
        self._tenants[DEFAULT_MODEL].block = block

    @property
    def model_version(self) -> int:
        """The DEFAULT tenant's monotonic model-version counter: bumps
        on every swap_model / reload; a rolling-upgrade rollback
        restores the OLD number so fleet version agreement is
        observable (Router/controller read it, never write it).
        Per-tenant versions: :meth:`model_versions`."""
        return self._tenants[DEFAULT_MODEL].model_version

    @model_version.setter
    def model_version(self, v: int) -> None:
        self._tenants[DEFAULT_MODEL].model_version = int(v)

    def model_versions(self) -> Dict[str, int]:
        """Per-tenant model versions (upgrading tenant A never touches
        tenant B's number — the per-model rolling-upgrade contract)."""
        with self._model_lock:
            return {n: t.model_version for n, t in self._tenants.items()}

    def models(self):
        """Registered tenant names (``"default"`` always present)."""
        return sorted(self._tenants)

    def _tenant(self, model) -> _Tenant:
        name = DEFAULT_MODEL if model is None else str(model)
        t = self._tenants.get(name)
        if t is None:
            raise MXNetError(
                f"{self.name}: unknown model {name!r} (registered: "
                f"{sorted(self._tenants)})")
        return t

    def register_model(self, name: str, block, slo_class: str = "standard",
                       priority: int = 0, weight: float = 1.0,
                       slo_ms: Optional[float] = None,
                       rate_limit: Optional[float] = None,
                       burst: Optional[float] = None) -> "_Tenant":
        """Register a second (third, ...) model to serve from THIS
        replica. Tenants share the scheduler, the bucket grid and — when
        decode is on — the one page pool; through the compilation
        service's signature-keyed executable table an identical-config
        tenant costs a warmup, not a second fleet.

        ``slo_class`` is a label carried into telemetry/trace spans;
        ``priority`` orders preemption (higher preempts lower when the
        page pool is full); ``weight`` sets this tenant's weighted-fair
        share of batch-close picks and decode slots; ``rate_limit``
        (requests/second, with ``burst``) arms a per-tenant admission
        token bucket — an empty bucket sheds synchronously with
        :class:`TenantThrottled`. ``slo_ms`` overrides the server SLO
        for this tenant's default deadline."""
        name = str(name)
        if not name:
            raise MXNetError("tenant name must be non-empty")
        if weight <= 0:
            raise MXNetError(f"weight must be > 0, got {weight}")
        if name in self._tenants:
            raise MXNetError(
                f"{self.name}: model {name!r} is already registered")
        bucket = (TokenBucket(rate_limit, burst)
                  if rate_limit is not None else None)
        t = _Tenant(name, block, str(slo_class), priority, weight,
                    slo_ms / 1e3 if slo_ms is not None else self.slo_s,
                    bucket)
        if self.is_running:
            # warm + build the decode engine BEFORE the tenant is
            # visible to submitters: its first request must not retrace
            self._warm_block(block, prime=True)
            if self._decode_pages is not None:
                t.engine = self._make_engine(block)
                t.engine_version = t.model_version
        with self._cond:
            if name in self._tenants:
                raise MXNetError(
                    f"{self.name}: model {name!r} is already registered")
            self._tenants[name] = t
            self._queues[name] = []
            self._gen_pending[name] = []
            self._cond.notify_all()
        return t

    # -- lifecycle -----------------------------------------------------
    @property
    def is_running(self) -> bool:
        return self._running or (self._thread is not None
                                 and self._thread.is_alive())

    def _make_engine(self, block):
        """Build ``block``'s decode engine over the SHARED page pool.
        The engine's KV/compute dtype and device are the model's own,
        not the request I/O dtype (token servers run dtype="int32")."""
        make = getattr(block, "decode_engine", None)
        if make is None:
            raise MXNetError(
                f"{self.name}: decode_pages set but the model has no "
                "decode_engine() seam (paged-KV generate needs a "
                "decode-capable model)")
        engine = make(self._pool)
        if not isinstance(engine, PagedDecodeEngine):
            raise MXNetError(
                f"{self.name}: the model's decode_engine() returned "
                f"{type(engine).__name__}, which is not a "
                "serving.engine.PagedDecodeEngine")
        if engine.vision is not None and self._patch_buckets:
            rows = (self._max_image_tokens
                    if self._max_image_tokens is not None
                    else self._max_gen_tokens)
            # the last image's padding rows land behind its live ones
            engine.vision.configure(
                self._patch_buckets, rows + self._patch_buckets[-1] // 4)
        return engine

    def start(self) -> "Server":
        """Warm the bucket grid and start the scheduler thread."""
        if self.is_running:
            raise MXNetError(f"{self.name}: already running")
        for t in self._tenants.values():
            self._warm_block(t.block, prime=True)
        if self._decode_pages is not None:
            # a slot a stream of the widest decode round + the scratch
            # slot; only an engine with ``state_slots`` sizes arrays by it
            self._pool = PagePool(self._decode_pages, self._page_size,
                                  n_state_slots=self.grid.max_batch + 1)
            for t in self._tenants.values():
                t.engine = self._make_engine(t.block)
                t.engine_version = t.model_version
            self._gen_table_w = self._pool.pages_for(self._max_gen_tokens)
        self._running = True
        self._thread = threading.Thread(
            target=self._scheduler_loop, name=self.name, daemon=True)
        self._thread.start()
        _live_servers.add(self)
        return self

    def stop(self, drain: bool = True, timeout: Optional[float] = None
             ) -> None:
        """Stop the server. ``drain=True`` (default) serves every queued
        request first (dispatching immediately, SLO waits skipped);
        ``drain=False`` fails pending futures with :class:`MXNetError`."""
        with self._cond:
            self._running = False
            self._drain = bool(drain)
            if not drain:
                pending = [r for q in self._queues.values() for r in q]
                for q in self._queues.values():
                    del q[:]
                for r in pending:
                    if not r.future.set_running_or_notify_cancel():
                        continue        # caller already cancelled it
                    r.future.set_exception(
                        MXNetError(f"{self.name}: server stopped before "
                                   "this request was dispatched"))
                    self._count_request(outcome="rejected",
                                        tenant=r.tenant)
                    self._end_trace_rejected(r)
            self._cond.notify_all()
        if self._watcher is not None:
            self._watcher.stop(timeout)
            self._watcher = None
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise MXNetError(
                    f"{self.name}: scheduler thread did not exit within "
                    f"{timeout}s")
            self._thread = None
        _live_servers.discard(self)

    def __enter__(self) -> "Server":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop(drain=not any(exc))

    # -- ingress -------------------------------------------------------
    def _throttle(self, t: _Tenant) -> None:
        """Per-tenant token-bucket admission: raises
        :class:`TenantThrottled` (synchronous, typed, scoped to ONE
        tenant) when ``t``'s bucket is empty."""
        if t.bucket is None or t.bucket.take():
            return
        t.n_shed += 1
        self._count_request(outcome="rejected", tenant=t)
        if _telemetry_state.enabled:
            telemetry.record_serving_shed("throttled", model=t.name)
        raise TenantThrottled(
            f"{self.name}: tenant {t.name!r} over its admission rate "
            f"({t.bucket.rate:g}/s, burst {t.bucket.burst:g})")

    def submit(self, sample, deadline_ms: Optional[float] = None,
               model: Optional[str] = None,
               priority: Optional[int] = None) -> Future:
        """Enqueue one sample (NO batch dimension); returns a Future that
        resolves to the model output for that sample (numpy leaves).
        Thread-safe. Raises :class:`MXNetError` immediately when the
        server is not running, the queue is full, or no shape bucket
        fits the sample — rejection is synchronous, never a hung future.

        ``model=`` selects the tenant (default: the constructor block);
        its SLO class sets the default deadline and its token bucket
        (if armed) may shed with :class:`TenantThrottled`. ``priority``
        is accepted for wire symmetry (classify requests are never
        preempted — only generate streams hold pages).
        """
        t = self._tenant(model)
        self._throttle(t)
        arr = sample.asnumpy() if hasattr(sample, "asnumpy") \
            else np.asarray(sample)
        arr = np.ascontiguousarray(arr, dtype=self.dtype)
        bucket = self.grid.bucket_shape(arr.shape)   # raises if none fits
        arr = self.grid.pad_sample(arr, bucket)
        deadline_s = (deadline_ms / 1e3 if deadline_ms is not None
                      else t.slo_s)
        req = _Request(arr, bucket, deadline_s, tenant=t)
        if _tracing_state.enabled:
            # the span must exist BEFORE the queue append: the scheduler
            # may batch-close this request before submit returns
            amb = tracing.ambient()
            if amb is not None:
                req.trace = amb[0]
                req.span = req.trace.begin(
                    "batch.wait", parent=amb[1], replica=self.name,
                    model=t.name, slo_class=t.slo_class)
            else:
                req.trace = tracing.new_trace(
                    "request", replica=self.name, model=t.name,
                    slo_class=t.slo_class)
                req.own_trace = True
                req.span = req.trace.begin(
                    "batch.wait", replica=self.name, model=t.name,
                    slo_class=t.slo_class)
        with self._cond:
            if not self._running:
                self._count_request(outcome="rejected", tenant=t)
                self._end_trace_rejected(req)
                raise MXNetError(f"{self.name}: server is not running")
            q = self._queues[t.name]
            if len(q) >= self.max_queue:
                self._count_request(outcome="rejected", tenant=t)
                self._end_trace_rejected(req)
                raise MXNetError(
                    f"{self.name}: submission queue full for model "
                    f"{t.name!r} ({self.max_queue} requests)")
            q.append(req)
            depth = sum(len(x) for x in self._queues.values())
            tenant_depth = len(q)
            self._cond.notify_all()
        if _telemetry_state.enabled:
            telemetry.set_serving_queue_depth(depth)
            telemetry.set_tenant_queue_depth(tenant_depth, t.name)
        return req.future

    def submit_generate(self, prompt, max_new_tokens: int,
                        deadline_ms: Optional[float] = None,
                        on_token=None, model: Optional[str] = None,
                        priority: Optional[int] = None,
                        images=None) -> GenerateHandle:
        """Enqueue one autoregressive generate request: ``prompt`` is a
        1-D int32 token array, ``max_new_tokens`` the completion budget
        (greedy decode). Returns a :class:`GenerateHandle` streaming
        tokens as the continuous batcher produces them.

        Rejection is synchronous and typed, like :meth:`submit`:
        :class:`~.kvcache.CacheFull` when the request cannot EVER fit
        the cache budget, :class:`MXNetError` when the server is not
        running or no len bucket fits the prompt AND the model's engine
        cannot prefill in chunks (one that can takes any prompt the
        cache budget holds, a chunk of the largest len bucket a tick:
        see the class docstring). A request admitted but
        later starved (deadline blown waiting for pages) fails its
        future typed — a generate never wedges on an exhausted arena.

        ``deadline_ms`` bounds the WHOLE completion (default: none —
        generates outlive the per-request SLO by design).

        ``model=`` selects the tenant; ``priority`` overrides the
        tenant's preemption rank for this stream (higher-priority
        arrivals may reclaim a lower-priority stream's pages — the
        victim resolves typed :class:`~.kvcache.Preempted` with a
        sealed clean-prefix stream).

        ``images``: a sequence of images for a model whose engine
        declares a vision encoder (``engine.vision``), each
        ``(patches (N, patch_dim), (rows, cols))`` in the tower's patch
        order or an ``H x W x 3`` array, which is cut here
        (:func:`~mxnet_tpu.gluon.model_zoo.vision.navit.patchify`). The
        prompt holds, for the images in order, ``rows * cols / 4``
        placeholder ids each (the engine's ``image_token_id``); a count
        that disagrees with the grids, an image larger than the largest
        patch-count bucket or a model without a tower is refused typed
        (:class:`ImageMismatch`). The scheduler encodes the images ONE a
        tick before the request's first prefill chunk, keeps their rows
        on the device and hands each chunk its rows through the engine's
        embeddings seam; everything else (admission, chunks, deadlines,
        preemption, cancellation) is a request's like any other.
        """
        if self._decode_pages is None:
            raise MXNetError(f"{self.name}: decode is not enabled "
                             "(construct the server with decode_pages=)")
        t = self._tenant(model)
        self._throttle(t)
        arr = prompt.asnumpy() if hasattr(prompt, "asnumpy") \
            else np.asarray(prompt)
        arr = np.ascontiguousarray(arr, dtype=np.int32).reshape(-1)
        if arr.size < 1:
            raise MXNetError(f"{self.name}: empty prompt")
        if int(max_new_tokens) < 1:
            raise MXNetError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        embed_at = None
        if images is not None:
            images, embed_at = self._check_images(t, arr, images)
        tail = None
        bk = getattr(t.engine, "block_length", 1)
        if bk > 1:
            # generation by diffusion over blocks: the prompt's whole
            # blocks are prefilled, what is left over opens the first
            # generated block, and the pages reach that block's end
            whole = bk * (arr.size // bk)
            arr, tail = arr[:whole], arr[whole:]
        largest = (self.grid.len_buckets or (0,))[-1]
        if arr.size > largest > 0 and getattr(t.engine, "chunked_prefill",
                                              False):
            len_bucket = largest            # the chunk; the tail's own
        else:
            try:
                # a prompt shorter than a block is never prefilled
                len_bucket = self.grid.prefill_bucket(arr.size) \
                    if arr.size else 0
            except MXNetError as e:         # no fit, and no chunking
                if not arr.size > largest > 0:
                    raise
                raise MXNetError(
                    f"{e}; a longer prompt needs an engine that prefills "
                    "in chunks (chunked_prefill), which this model's "
                    "does not") from None
        total = arr.size + int(max_new_tokens)
        if tail is not None:                # to the last block's end
            total = arr.size + bk * -(-(tail.size + int(max_new_tokens))
                                      // bk)
        if total > self._max_gen_tokens:
            t.n_shed += 1
            if _telemetry_state.enabled:
                telemetry.record_serving_shed("kvcache_full",
                                              model=t.name)
            raise CacheFull(
                f"{self.name}: prompt {arr.size} + max_new_tokens "
                f"{max_new_tokens} exceeds the {self._max_gen_tokens}-"
                "token per-request cache budget")
        handle = GenerateHandle(on_token)
        req = _GenRequest(arr, max_new_tokens, handle,
                          deadline_ms / 1e3 if deadline_ms is not None
                          else None, tenant=t,
                          priority=(t.priority if priority is None
                                    else priority),
                          seq=next(self._seq), images=images,
                          embed_at=embed_at, tail=tail, reserve=total)
        req.len_bucket = len_bucket
        if _tracing_state.enabled:
            amb = tracing.ambient()
            if amb is not None:
                req.trace = amb[0]
                req.span = req.trace.begin("gen.queue", parent=amb[1],
                                           replica=self.name,
                                           model=t.name,
                                           slo_class=t.slo_class)
            else:
                req.trace = tracing.new_trace(
                    "generate", replica=self.name,
                    prompt_len=int(arr.size if tail is None
                                   else arr.size + tail.size),
                    max_new=int(max_new_tokens), model=t.name,
                    slo_class=t.slo_class)
                req.own_trace = True
                req.span = req.trace.begin("gen.queue", replica=self.name,
                                           model=t.name,
                                           slo_class=t.slo_class)
        with self._cond:
            if not self._running:
                self._count_request(outcome="rejected", tenant=t)
                self._end_gen_rejected(req)
                raise MXNetError(f"{self.name}: server is not running")
            q = self._gen_pending[t.name]
            if len(q) >= self.max_queue:
                self._count_request(outcome="rejected", tenant=t)
                self._end_gen_rejected(req)
                raise MXNetError(
                    f"{self.name}: generate queue full for model "
                    f"{t.name!r} ({self.max_queue} requests)")
            q.append(req)
            self._cond.notify_all()
        return handle

    def _check_images(self, t: _Tenant, prompt, images) -> tuple:
        """``images`` as ``[(patches, (rows, cols))]`` in the tower's
        dtype and the prompt positions that take their rows; typed
        refusal of what the tower cannot take."""
        vision = t.engine.vision if t.engine is not None else None
        if vision is None or not vision.buckets:
            raise ImageMismatch(
                f"{self.name}: model {t.name!r} takes no images (its "
                "engine declares no vision encoder, or the server was "
                "built without patch_buckets=)")
        from ..gluon.model_zoo.vision.navit import patchify

        out, rows_total = [], 0
        for im in images:
            patches, grid = im if isinstance(im, tuple) else patchify(im)
            patches = np.asarray(patches, dtype=vision.dtype)
            rows, cols = int(grid[0]), int(grid[1])
            if (patches.ndim != 2 or rows % 2 or cols % 2
                    or patches.shape[0] != rows * cols
                    or patches.shape[1] != vision.cfg["patch_dim"]):
                raise ImageMismatch(
                    f"{self.name}: an image is (patches (rows * cols, "
                    f"{vision.cfg['patch_dim']}), an even x even grid); got "
                    f"{patches.shape} for {rows} x {cols}")
            if patches.shape[0] > vision.buckets[-1]:
                raise ImageMismatch(
                    f"{self.name}: an image of {patches.shape[0]} patches "
                    "is larger than the largest patch-count bucket "
                    f"({vision.buckets[-1]})")
            out.append((patches, (rows, cols)))
            rows_total += rows * cols // 4
        at = np.flatnonzero(prompt == t.engine.image_token_id)
        if at.size != rows_total or not out:
            raise ImageMismatch(
                f"{self.name}: the prompt holds {at.size} placeholder ids "
                f"({t.engine.image_token_id}) where its {len(out)} images' "
                f"grids make {rows_total} rows")
        if rows_total > vision.max_rows - vision.buckets[-1] // 4:
            raise ImageMismatch(
                f"{self.name}: {rows_total} rows of image embeddings pass "
                "the server's max_image_tokens")
        return out, at

    @staticmethod
    def _end_gen_rejected(req: "_GenRequest",
                          status: str = "rejected") -> None:
        if req.trace is None:
            return
        if req.span is not None:
            req.span.end(outcome=status)
            req.span = None
        if req.own_trace:
            req.trace.finish(status)

    # -- decode phase (continuous batching) ----------------------------
    @staticmethod
    def _wrr_pick(tenants, field: str = "credit") -> _Tenant:
        """Smooth weighted round-robin over ``tenants``: every pick adds
        each tenant's weight to its credit, takes the max, and charges
        the winner the total — long-run pick shares converge to the
        configured weights (scheduler thread only)."""
        total = 0.0
        for t in tenants:
            total += t.weight
            setattr(t, field, getattr(t, field) + t.weight)
        best = max(tenants, key=lambda t: getattr(t, field))
        setattr(best, field, getattr(best, field) - total)
        return best

    def _preempt(self, victim: "_GenRequest",
                 beneficiary: "_GenRequest") -> None:
        """Evict ``victim`` for a higher-priority arrival — AT a decode
        step boundary, so every token it streamed is a clean, sealed
        prefix (never a torn token). The handle resolves typed
        :class:`~.kvcache.Preempted`; the flight recorder names victim
        and beneficiary."""
        victim.tenant.n_preempted += 1
        self.n_preemptions += 1
        if _telemetry_state.enabled:
            telemetry.record_preemption(victim.tenant.name,
                                        beneficiary.tenant.name)
        if _tracing_state.enabled:
            tracing.record_event(
                "preempted", replica=self.name,
                victim=victim.seq, beneficiary=beneficiary.seq,
                victim_model=victim.tenant.name,
                beneficiary_model=beneficiary.tenant.name,
                victim_priority=victim.priority,
                beneficiary_priority=beneficiary.priority,
                victim_tokens=len(victim.generated))
        self._finalize_gen(victim, error=Preempted(
            f"{self.name}: stream preempted at token "
            f"{len(victim.generated)}/{victim.max_new}: pages reclaimed "
            f"for higher-priority {beneficiary.tenant.name!r} arrival "
            f"(priority {beneficiary.priority} > {victim.priority})"))

    def _admit_pages(self, g: "_GenRequest", active: list):
        """All-or-nothing page allocation for ``g`` (and, for an engine
        with ``state_slots``, its state slot: both or neither),
        preempting lower-priority active streams (lowest priority first,
        then the one with the least progress to waste) until it fits.
        Victims are removed from ``active`` in place. Raises
        :class:`~.kvcache.CacheFull` when ``g`` cannot fit even with
        every lower-priority stream evicted."""
        while True:
            try:
                pages = self._pool.alloc(g, g.reserve)
                if g.tenant.engine.state_slots:
                    try:
                        g.slot = self._pool.state_slots.alloc(g)
                    except CacheFull:
                        self._pool.free(g)
                        raise
                return pages
            except CacheFull:
                lower = [v for v in active if v.priority < g.priority]
                if not lower:
                    raise
                # evict nobody unless eviction actually admits g: a
                # too-big arrival must not waste victims' work
                need = self._pool.pages_for(g.reserve)
                avail = (self._pool.stats()["free"]
                         + sum(len(self._pool.owned(v)) for v in lower))
                if need > avail:
                    raise
                victim = min(lower,
                             key=lambda v: (v.priority, len(v.generated)))
                self._preempt(victim, beneficiary=g)
                active.remove(victim)

    def _decode_tick(self) -> bool:
        """One continuous-batching turn: admit pending generates
        (prefill), then run ONE decode step round for active requests.
        Requests join and leave the decode batch at any step boundary.
        Multi-tenant: admission interleaves per-tenant pending queues
        weighted-fair, a full pool preempts the lowest-priority active
        stream for a higher-priority arrival, and decode slots are
        assigned weighted-fair per round. Returns False when nothing
        could move (scheduler backs off)."""
        progressed = False
        if _tracing_state.enabled or _telemetry_state.enabled:
            t_tick = time.time_ns()     # round.wait | round.sched
            self._round_clock = _RoundClock(self._round_end_ns or t_tick,
                                            t_tick, self.n_batches)
        else:
            self._round_clock = self._round_end_ns = None
        now = time.perf_counter()
        with self._cond:
            active = list(self._gen_active)
            pending = {n: list(q) for n, q in self._gen_pending.items()
                       if q}
        # deferred per-tenant weight swap: a completion runs entirely on
        # ONE model version, so a hot reload reaches a tenant's decode
        # engine only while that tenant has no active completions —
        # never mid-request (and never another tenant's swap)
        for t in self._tenants.values():
            if (t.engine is not None
                    and t.engine_version != t.model_version
                    and not any(g.tenant is t for g in active)):
                t.engine.refresh_params(t.block)
                t.engine_version = t.model_version
        # -- chunks of the long prompts in flight come first, as they
        #    were admitted, within the tick's prefill bound (one always
        #    runs)
        bound = self._max_prefill_tokens
        spent = 0
        encoded = False         # the tick's ONE image has been encoded
        full = False            # the tick's prefill bound has been reached
        for g in [g for g in active if g.prefilled < g.prompt.size]:
            if g.deadline is not None and now > g.deadline:
                self._finalize_gen(g, error=MXNetError(
                    f"{self.name}: generate deadline expired at prompt "
                    f"token {g.prefilled}/{g.prompt.size}"))
                progressed = True
                continue
            if g.images is not None and g.encoded < len(g.images):
                # the ENCODE stage: one image a tick, the first stream
                # in line; its chunks follow once its images are rows
                if not encoded:
                    encoded = progressed = True
                    self._encode_image(g)
                continue
            cost = self._chunk_of(g)[1]
            if full or (bound is not None and spent
                        and spent + cost > bound):
                full = True     # later chunks wait; a later encode need not
                continue
            self._prefill_batch([g], cost)
            spent += cost
            progressed = True
        # what still holds pages: a higher-priority arrival may reclaim
        # a half-prefilled stream's as an active one's (freed whole)
        active = [g for g in active if g.pages is not None]
        # -- admission: weighted-fair across tenants, all-or-nothing
        #    page allocation per request, preemption on a full pool
        admitted: list = []
        group_n: dict = {}      # (tenant, len bucket) -> requests admitted
        while pending and len(admitted) < self.grid.max_batch:
            t = self._wrr_pick([self._tenants[n] for n in pending])
            queue = pending[t.name]
            g = queue.pop(self._next_pending(t, queue))
            if not queue:
                del pending[t.name]
            if g.deadline is not None and now > g.deadline:
                self._remove_pending(g)
                self._finalize_gen(g, error=MXNetError(
                    f"{self.name}: generate deadline expired before "
                    "prefill (cache/backlog starvation)"))
                progressed = True
                continue
            if bound is not None and g.images is None:
                # close the (tenant, len bucket) group before its padded
                # prefill would pass what the tick's chunks left of the
                # bound; the rest of this tenant's queue waits for the
                # next tick, in arrival order
                key = (t.name, g.len_bucket)
                n = group_n.get(key, 0) + 1
                if (n > 1 or spent) and (
                        self.grid.batch_bucket(n) * g.len_bucket
                        > bound - spent):
                    pending.pop(t.name, None)
                    continue
                group_n[key] = n
            try:
                g.pages = self._admit_pages(g, active)
            except CacheFull as e:
                if not active and not admitted:
                    # nothing holds pages and it STILL does not fit:
                    # waiting cannot help — shed typed, never wedge
                    t.n_shed += 1
                    if _telemetry_state.enabled:
                        telemetry.record_serving_shed("kvcache_full",
                                                      model=t.name)
                    self._remove_pending(g)
                    self._finalize_gen(g, error=e)
                    progressed = True
                    continue
                # this tenant's head is blocked until actives free
                # pages; other tenants keep admitting this tick
                pending.pop(t.name, None)
                continue
            self._remove_pending(g)
            if g.images is not None:
                # holds its pages and waits in line for the encode stage;
                # its chunks go one dispatch each, as a long prompt's
                with self._cond:
                    self._gen_active.append(g)
                if not encoded:
                    encoded = True
                    self._encode_image(g)
                progressed = True
                continue
            if not g.prompt.size:
                # a prompt shorter than a block (an engine that steps
                # blocks): nothing to prefill, the stream starts inside
                # its first block
                g.model_version = t.engine_version
                if g.span is not None:      # gen.queue ends here
                    g.span.end(outcome="ok")
                    g.span = None
                with self._cond:
                    self._gen_active.append(g)
                progressed = True
                continue
            admitted.append(g)
            if g.prompt.size > g.len_bucket and bound is not None:
                # a long prompt's first chunk is a dispatch of its own
                group_n.pop((t.name, g.len_bucket), None)
                spent += g.len_bucket
        if admitted:
            groups: dict = {}
            for g in admitted:
                if g.prompt.size > g.len_bucket:
                    # longer than the largest bucket: its first chunk
                    # now, the rest a chunk a tick
                    self._prefill_batch([g], self._chunk_of(g)[1])
                    continue
                groups.setdefault((g.tenant.name, g.len_bucket),
                                  []).append(g)
            for key in sorted(groups):
                self._prefill_batch(groups[key], key[1])
            progressed = True
        # -- decode step round (chunked to the grid, never mixing
        #    tenants in one dispatch)
        with self._cond:
            active = [g for g in self._gen_active
                      if g.prefilled >= g.prompt.size]
        expired = [g for g in active
                   if g.deadline is not None and now > g.deadline]
        for g in expired:
            self._finalize_gen(g, error=MXNetError(
                f"{self.name}: generate deadline expired at token "
                f"{len(g.generated)}/{g.max_new}"))
        active = [g for g in active if g not in expired]
        if active:
            self._decode_round(active)
        if self._pool is not None:
            self._maybe_defrag()
        return progressed or bool(active) or bool(expired)

    def _decode_round(self, active: list) -> None:
        """One decode step for active streams. Single-tenant: every
        stream steps, chunked to the grid (the legacy path). Multiple
        tenants resident: ``grid.max_batch`` decode slots per round are
        assigned weighted-fair across tenants with live streams, each
        tenant's picks step as its OWN batch (a dispatch runs one
        tenant's executable), and stepped streams rotate to the back of
        the active list so no stream starves within its tenant."""
        by_tenant: dict = {}
        for g in active:
            by_tenant.setdefault(g.tenant.name, []).append(g)
        if len(by_tenant) == 1:
            cap = self.grid.max_batch
            for i in range(0, len(active), cap):
                self._decode_batch(active[i:i + cap])
            return
        tenants = [self._tenants[n] for n in by_tenant]
        remaining = {t.name: len(by_tenant[t.name]) for t in tenants}
        share = {t.name: 0 for t in tenants}
        slots = min(self.grid.max_batch, len(active))
        for _ in range(slots):
            elig = [t for t in tenants if remaining[t.name] > 0]
            if not elig:
                break
            t = self._wrr_pick(elig, field="dcredit")
            share[t.name] += 1
            remaining[t.name] -= 1
        for t in tenants:
            n = share[t.name]
            if n == 0:
                continue
            streams = by_tenant[t.name]
            self._decode_batch(streams[:n])
            if n < len(streams):
                # rotate the stepped streams behind the unstepped ones
                with self._cond:
                    for g in streams[:n]:
                        try:
                            self._gen_active.remove(g)
                        except ValueError:
                            continue    # finalized during the step
                        self._gen_active.append(g)

    def _maybe_defrag(self) -> None:
        """Automatic defrag, checked between decode steps: when the
        free holes below the pool's high-water mark exceed the
        configured threshold, pack live pages down, replay the
        permutation onto EVERY tenant's arenas, and refresh every
        active stream's page snapshot (``defrag`` renumbers the pool in
        place — a ``g.pages`` list taken at admission is stale the
        moment the pool packs)."""
        if self._defrag_min_pages is None:
            return
        n_live, span = self._pool.frag_info()
        if n_live == 0 or span - n_live < self._defrag_min_pages:
            return
        engines = [t.engine for t in self._tenants.values()
                   if t.engine is not None]
        if not engines:
            return
        moves = self._pool.defrag()
        if not moves:
            return
        for e in engines:
            e.apply_defrag(moves)
        with self._cond:
            for g in self._gen_active:
                g.pages = self._pool.owned(g)
        self.n_defrags += 1
        if _telemetry_state.enabled:
            telemetry.record_kvcache_defrag(len(moves))
        if _tracing_state.enabled:
            tracing.record_event("kvcache.defrag", replica=self.name,
                                 moves=len(moves), live_pages=n_live)

    @staticmethod
    def _next_pending(t, queue) -> int:
        """Which request of tenant ``t``'s pending ``queue`` (arrival
        order) is admitted next: its head; where the head is a prompt
        longer than its length bucket (prefilled in chunks), the LONGEST
        such prompt of the head's wave, equal ones in arrival order. A
        wave is the long prompts that were waiting together when the
        wave before it had all been admitted (``t.long_wave``: its last
        arrival), so a later arrival passes over none of them: nothing
        starves. Shorter prompts keep their place behind the head."""
        if queue[0].prompt.size <= queue[0].len_bucket:
            return 0
        long_ = [i for i, g in enumerate(queue)
                 if g.prompt.size > g.len_bucket]
        wave = [i for i in long_ if queue[i].seq <= t.long_wave]
        if not wave:
            t.long_wave = max(queue[i].seq for i in long_)
            wave = long_
        return max(wave, key=lambda i: (queue[i].prompt.size, -i))

    def _remove_pending(self, g) -> None:
        with self._cond:
            q = self._gen_pending.get(g.tenant.name)
            if q is not None:
                try:
                    q.remove(g)
                except ValueError:
                    pass

    def _dispatch_gen(self, phase: str, sig, call, streams, spans=()):
        """One generate dispatch (``phase`` ``prefill`` or ``decode``) of
        signature ``sig``: the pre-dispatch hook, the fault check and
        ``call()`` under the ``serving.dispatch`` retry policy. Returns
        the next token ids, or None after an error has been fanned out:
        the open ``spans`` ended, every stream of ``streams`` finalized
        with it."""
        def run():
            hook = self._pre_dispatch
            if hook is not None:
                hook(sig)
            if _fault_state.enabled:
                fault.check("serving.dispatch",
                            f"{self.name} {phase}={sig}")
            return call()

        try:
            return fault.retry_call("serving.dispatch", run,
                                    detail=self.name)
        except Exception as e:  # noqa: BLE001 - forwarded to handles
            self.n_errors += 1
            for sp in spans:
                if sp is not None:
                    sp.end(outcome="error", error=type(e).__name__)
            for g in streams:
                self._finalize_gen(g, error=e)
            return None

    def _prefill_batch(self, group, len_bucket: int) -> None:
        """Prefill one len-bucket group: write the prompts' K/V into
        their pages and emit each request's FIRST token (the
        time-to-first-token dispatch). A prompt longer than the largest
        len bucket contributes its NEXT CHUNK, at the offset of what is
        already in the cache (the rows attend to the chunks before them
        through it); only its last chunk emits the token and moves the
        stream into the decode round."""
        tenant = group[0].tenant
        engine = tenant.engine
        cap = self.grid.batch_bucket(len(group))
        w = self._gen_table_w
        tokens = np.zeros((cap, len_bucket), dtype=np.int32)
        lengths = np.zeros((cap,), dtype=np.int32)
        offsets = np.zeros((cap,), dtype=np.int32)
        table = np.zeros((cap, w), dtype=np.int32)
        for i, g in enumerate(group):
            off, n = g.prefilled, self._chunk_of(g)[0]
            tokens[i, :n] = g.prompt[off:off + n]
            lengths[i], offsets[i] = off + n, off
            table[i, :len(g.pages)] = g.pages
            g.model_version = tenant.engine_version
            if g.span is not None:          # gen.queue ends here
                g.span.end(outcome="ok")
            tags = ({"chunk": off // g.len_bucket, "offset": off,
                     "chunks": -(-g.prompt.size // g.len_bucket)}
                    if g.prompt.size > g.len_bucket else {})
            if g.slot is not None:
                tags["slot"] = g.slot
            g.span = (g.trace.begin("prefill", replica=self.name,
                                    len_bucket=len_bucket,
                                    model=tenant.name,
                                    slo_class=tenant.slo_class, **tags)
                      if g.trace is not None else None)
        # a prompt's first (or only) chunk is the plain prefill call
        args = (tokens, lengths, table) + ((offsets,) if offsets.any()
                                           else ())
        seam = {}
        if group[0].images is not None:     # such a stream goes alone
            g = group[0]
            off, n = offsets[0], lengths[0] - offsets[0]
            rows = np.full((cap, len_bucket), -1, dtype=np.int32)
            first = np.searchsorted(g.embed_at, off)
            at = g.embed_at[first:np.searchsorted(g.embed_at, off + n)]
            rows[0, at - off] = first + np.arange(at.size)
            seam = {"embeds": g.embeds, "embed_rows": rows}
        if engine.state_slots:      # the rows whose chunk ends their prompt
            final = np.zeros((cap,), dtype=bool)
            final[:len(group)] = [n == g.prompt.size
                                  for g, n in zip(group, lengths)]
            seam = {"slots": self._slots_of(group, cap), "final": final}
        ids = self._dispatch_gen("prefill", (cap, len_bucket),
                                 lambda: engine.prefill(*args, **seam),
                                 group)
        if ids is None:
            return
        self.n_batches += 1
        if _telemetry_state.enabled:
            telemetry.record_serving_batch(len(group), cap, "prefill")
        with self._cond:                    # a stream's first dispatch
            self._gen_active.extend(g for g in group
                                    if not g.prefilled and g.images is None)
        t_now = time.perf_counter()
        for g, token, length in zip(group, ids.tolist(), lengths.tolist()):
            if g.span is not None:
                g.span.end(outcome="ok")
                g.span = None
            if (g.prompt.size > g.len_bucket
                    and _telemetry_state.enabled):
                telemetry.record_prefill_chunk(model=tenant.name)
            g.prefilled = length
            if length == g.prompt.size:     # else more chunks, a tick each
                g.embeds = None             # its image rows are in the cache
                if g.block is None:         # else: its first block's steps
                    self._emit_token(g, token, t_now)

    def _encode_image(self, g) -> None:
        """The ENCODE stage: run ``g``'s next image through its engine's
        vision tower (one dispatch, 42 layers of it for the model this
        was built for: as long as tens of decode rounds) and keep its
        rows in the request's embedding buffer on the device. A
        ``vision.encode`` span in the request's trace carries the
        image's patches and bucket. An error is the stream's, typed."""
        vision = g.tenant.engine.vision
        patches, grid = g.images[g.encoded]
        row0 = sum(r * c // 4 for _, (r, c) in g.images[:g.encoded])
        if g.span is not None and g.encoded == 0:   # gen.queue ends here
            g.span.end(outcome="ok")
            g.span = None
        span = (g.trace.begin("vision.encode", replica=self.name,
                              patches=int(patches.shape[0]),
                              bucket=vision.bucket_of(patches.shape[0]),
                              request=g.seq, image=g.encoded)
                if g.trace is not None else None)

        def call():
            buf = g.embeds if g.embeds is not None else vision.new_buffer()
            g.embeds = None                 # donated to the encode
            g.embeds, _ = vision.encode(patches, grid, buf, row0)
            return True

        if self._dispatch_gen("encode", (patches.shape[0],), call, [g],
                              (span,)) is None:
            return
        if span is not None:
            span.end(outcome="ok")
        g.encoded += 1
        if g.encoded == len(g.images):
            g.images = ()                   # the host copies go

    @staticmethod
    def _slots_of(streams, cap: int):
        """The rows' state slots, for an engine with ``state_slots``; a
        padding row keeps 0, the scratch slot."""
        slots = np.zeros((cap,), dtype=np.int32)
        slots[:len(streams)] = [g.slot for g in streams]
        return slots

    def _chunk_of(self, g) -> tuple:
        """What ``g``'s next prefill dispatch takes of its prompt:
        (tokens, their len bucket): the whole prompt, or the next chunk
        of one longer than the largest bucket."""
        n = min(g.len_bucket, g.prompt.size - g.prefilled)
        return n, self.grid.prefill_bucket(n)

    def _decode_batch(self, chunk) -> None:
        """ONE decode step for up to max_batch active requests of ONE
        tenant — the (batch, 1) executable, whatever depth each request
        is at. For an engine that steps BLOCKS (``block_length`` > 1) the
        round is the (batch, block_length) executable: each stream's
        current block, at whatever denoising step it is, or its commit;
        it hands a stream 0 to ``block_length`` new tokens."""
        clock = self._round_clock
        if clock is not None:
            clock.begin_round(chunk)    # round.sched | round.build
        self._n_rounds += 1
        tenant = chunk[0].tenant
        engine = tenant.engine
        bk = engine.block_length
        cap = self.grid.batch_bucket(len(chunk))
        w = self._gen_table_w
        tokens = np.zeros((cap,) if bk == 1 else (cap, bk), dtype=np.int32)
        lengths = np.zeros((cap,), dtype=np.int32)
        table = np.zeros((cap, w), dtype=np.int32)
        quota = masked = None
        if bk > 1:
            # every stream's block at once: which positions are masked,
            # which rows commit, what each step has to unmask at least
            n = len(chunk)
            tokens[:n] = [g.block for g in chunk]
            masked = tokens[:n] == engine.mask_id
            commits = (~masked.any(axis=1)).tolist()
            quota = np.zeros((cap,), dtype=np.int32)
            quota[:n] = [0 if c else engine.transfer[g.step]
                         for g, c in zip(chunk, commits)]
            masked = masked.tolist()
        spans = []
        for i, g in enumerate(chunk):
            tags = {}
            if bk == 1:
                tokens[i] = g.generated[-1]
                lengths[i] = g.length
            else:
                lengths[i] = g.length + bk      # the block counts
                tags = {"step": g.step, "commit": commits[i]}
            table[i, :len(g.pages)] = g.pages
            spans.append(g.trace.begin("decode.step", replica=self.name,
                                       token=len(g.generated),
                                       model=tenant.name,
                                       round=self._n_rounds, **tags)
                         if g.trace is not None else None)
        seam = ({"slots": self._slots_of(chunk, cap)}
                if engine.state_slots else {})
        if clock is not None:
            clock.launch()              # round.build | round.launch
        ids = self._dispatch_gen(
            "decode", (cap, bk),
            (lambda: engine.decode_step(tokens, lengths, table, **seam))
            if bk == 1 else
            (lambda: engine.decode_block(tokens, lengths, table, quota)),
            chunk, spans)
        if clock is not None:
            # round.fetch | round.emit; launch | fetch is the engine's
            clock.returned(engine.run_done_ns)
        if ids is None:
            if clock is not None:
                self._end_round(clock, chunk, cap, "error")
            return
        if _telemetry_state.enabled:
            telemetry.record_decode_step(len(chunk), model=tenant.name)
        t_now = time.perf_counter()
        if bk == 1:
            for g, sp, token in zip(chunk, spans, ids.tolist()):
                if sp is not None:
                    sp.end(outcome="ok")
                self._emit_token(g, token, t_now)
        else:
            n_commits = unmasked = 0
            for g, sp, state, was in zip(chunk, spans, ids.tolist(), masked):
                n = self._advance_block(g, state, was, t_now)
                n_commits += n < 0
                unmasked += max(n, 0)
                if sp is not None:
                    sp.end(outcome="ok", unmasked=max(n, 0))
            if _telemetry_state.enabled:
                telemetry.record_block_round(len(chunk) - n_commits,
                                             n_commits, unmasked)
        if clock is not None:
            self._end_round(clock, chunk, cap, "ok")

    def _advance_block(self, g, state: list, was_masked: list,
                       t_now: float) -> int:
        """What one round made of ``g``'s block: ``state`` is the block
        after it, ``was_masked`` which of its positions were masked
        before. A commit (nothing was masked; its keys and values are the
        cache's now) moves the stream on to its next block, all masked,
        and returns -1. A denoising step records the positions it
        unmasked, pushes every token that it and all positions before it
        now have, in position order, and returns how many it unmasked. A
        request ends with the last token of its budget, whatever is left
        of its last block."""
        mask_id = g.tenant.engine.mask_id
        bk = len(state)
        if True not in was_masked:
            g.length += bk
            g.block = [mask_id] * bk
            g.step = g.pushed = 0
            return -1
        unmasked = 0
        for i in range(bk):
            if was_masked[i] and state[i] != mask_id:
                g.block_steps[i] = g.step
                unmasked += 1
        g.block = state
        g.step += 1
        while g.pushed < bk and state[g.pushed] != mask_id:
            i, g.pushed = g.pushed, g.pushed + 1
            self._push_token(g, state[i], t_now, g.block_steps[i])
            if g.pages is None:         # its budget's last token: sealed
                break
        return unmasked

    def _end_round(self, clock: "_RoundClock", chunk, cap: int,
                   outcome: str) -> None:
        """Seal the record of the decode round ``chunk`` just made: six
        ``mxnet_serving_round_phase_seconds_total{phase}`` increments
        (telemetry) and, in the trace of the round's first traced
        stream, a ``decode.round`` span with its six ``round.*``
        children, which tile it. A dispatch that raised leaves the
        phases it did not reach empty."""
        marks = clock.end_round()
        self._round_end_ns = marks[-1]
        if _telemetry_state.enabled:
            telemetry.record_round_phases(
                [(phase, (b - a) / 1e9) for phase, a, b
                 in zip(_ROUND_PHASES, marks, marks[1:])])
        prefills, clock.batches = (self.n_batches - clock.batches,
                                   self.n_batches)
        trace, clock.trace = clock.trace, None
        if trace is None:
            return
        us = [t // 1000 for t in marks]     # spans are in epoch us
        parent = trace.add_raw(
            "decode.round", us[0], us[-1] - us[0], parent=trace.root,
            round=self._n_rounds, streams=len(chunk), cap=cap,
            model=chunk[0].tenant.name, replica=self.name, outcome=outcome)
        tags = {"sched": {"prefills": prefills},
                "emit": {"callback_us": clock.callback_ns // 1000}}
        for phase, a, b in zip(_ROUND_PHASES, us, us[1:]):
            trace.add_raw("round." + phase, a, b - a, parent=parent,
                          **tags.get(phase, {}))
        if clock.sealing is not None:       # its own trace, held open
            trace.finish(clock.sealing)
            clock.sealing = None

    def _emit_token(self, g, token: int, t_now: float) -> None:
        g.length += 1
        self._push_token(g, token, t_now)

    def _push_token(self, g, token: int, t_now: float,
                    step: Optional[int] = None) -> None:
        """Hand ``token`` to ``g``'s caller and end the request with the
        last token of its budget. ``step``: the denoising step that
        unmasked it (a stream that steps blocks)."""
        g.generated.append(token)
        self.n_tokens += 1
        g.tenant.n_tokens += 1
        if _telemetry_state.enabled:
            telemetry.record_token(t_now - g.t_last, model=g.tenant.name)
        g.t_last = t_now
        clock = self._round_clock
        if clock is None:
            g.handle._push(token, step)
        else:                   # the caller's code, on this thread
            t0 = time.time_ns()
            g.handle._push(token, step)
            clock.callback_ns += time.time_ns() - t0
        if len(g.generated) >= g.max_new:
            self._finalize_gen(g)

    def _finalize_gen(self, g, error: Optional[Exception] = None) -> None:
        """Resolve one generate request: free its pages and its state
        slot, leave the batch, settle the future (exactly once) and seal
        the stream."""
        if g.pages is not None:
            self._pool.free(g)
            g.pages = None
        if g.slot is not None:
            self._pool.state_slots.free(g)
            g.slot = None
        g.embeds = None             # its image rows go with its pages
        with self._cond:
            try:
                self._gen_active.remove(g)
            except ValueError:
                pass
        fut = g.handle.future
        clock = self._round_clock
        t0 = time.time_ns() if clock is not None else 0
        try:
            if error is None:
                fut.set_result(np.asarray(g.generated, dtype=np.int32))
            else:
                fut.set_exception(error)
        except Exception:   # noqa: BLE001 - already settled (racing stop)
            pass
        g.handle._seal()
        if clock is not None:   # the future's callbacks are the caller's
            clock.callback_ns += time.time_ns() - t0
        if error is not None:
            self.n_errors += 1
        self._count_request(
            outcome="ok" if error is None else "error",
            t_enqueue=g.t_submit,
            trace_id=g.trace.trace_id if g.trace is not None else None,
            tenant=g.tenant)
        if g.span is not None:
            g.span.end(outcome="ok" if error is None else "error")
            g.span = None
        if g.own_trace and g.trace is not None:
            status = "ok" if error is None else type(error).__name__
            if clock is not None and clock.trace is g.trace:
                # the round in hand records into this trace: it is
                # sealed once that record is in (`_end_round`)
                clock.sealing = status
            else:
                g.trace.finish(status)

    def _fail_generates(self, exc: Exception) -> None:
        with self._cond:
            doomed = [g for q in self._gen_pending.values() for g in q]
            doomed += self._gen_active
            for q in self._gen_pending.values():
                del q[:]
        for g in doomed:
            self._finalize_gen(g, error=exc)

    # -- scheduler -----------------------------------------------------
    def _scheduler_loop(self) -> None:
        try:
            while True:
                self.hb.touch()
                batch, reason = self._next_batch()
                if batch is None:
                    # non-drain shutdown may leave generates behind
                    self._fail_generates(MXNetError(
                        f"{self.name}: server stopped before this "
                        "generate completed"))
                    return
                if batch:
                    self._dispatch(batch, reason)
                if self._gen_pending or self._gen_active:
                    if not self._decode_tick():
                        # nothing admissible this instant (pool full,
                        # actives still hold pages): breathe, retry
                        with self._cond:
                            self._cond.wait(0.005)
        except BaseException:
            # a scheduler death must be LOUD, not a server that accepts
            # requests into a queue nobody drains: stop accepting and
            # fail everything queued
            self._round_clock = None    # no round's record is open now
            with self._cond:
                self._running = False
                pending = [r for q in self._queues.values() for r in q]
                for q in self._queues.values():
                    del q[:]
            for r in pending:
                if r.future.set_running_or_notify_cancel():
                    r.future.set_exception(MXNetError(
                        f"{self.name}: scheduler thread crashed"))
                    self._end_trace_rejected(r, "error")
            self._fail_generates(MXNetError(
                f"{self.name}: scheduler thread crashed"))
            raise

    def _next_batch(self):
        """Block until a batch should close; returns (requests, reason),
        ``([], "decode")`` when decode work should run NOW (continuous
        batching never parks the scheduler while generates are live),
        or (None, None) on shutdown with nothing left to serve.

        Multi-tenant: every non-empty tenant queue is evaluated with
        the single-tenant close rules (full / drain / timeout /
        deadline) against ITS OWN requests, so one tenant's burst never
        advances or delays another tenant's close time; when several
        tenants are closeable at once the pick is smooth weighted
        round-robin, and a closed batch never mixes tenants."""
        with self._cond:
            while True:
                self.hb.touch()
                gen_work = (any(self._gen_pending.values())
                            or bool(self._gen_active))
                nonempty = [n for n in self._queues if self._queues[n]]
                if not nonempty:
                    if not self._running:
                        if gen_work and self._drain:
                            return [], "decode"
                        return None, None
                    if gen_work:
                        return [], "decode"
                    self._cond.wait(0.1)
                    continue
                cap = self.grid.max_batch
                now = time.perf_counter()
                full, closeable = [], []
                min_close_at = None
                for name in nonempty:
                    q = self._queues[name]
                    head = q[0]
                    key = head.shape_key
                    matching = sum(1 for r in q if r.shape_key == key)
                    if matching >= cap:
                        full.append(name)
                        continue
                    # close on the TIGHTEST deadline in this tenant's
                    # queue, not just the head's: a short-deadline
                    # request behind a lazy head (same key: it rides
                    # this batch; different key: it is served right
                    # after) must not wait out the head's SLO
                    deadline_at = min(r.deadline for r in q) \
                        - self.margin_s
                    # batch timeout: the head is the oldest enqueue
                    # (submit order is FIFO within a tenant) — cap its
                    # co-batching wait independently of the SLO
                    timeout_at = (head.t_enqueue + self.batch_timeout_s
                                  if self.batch_timeout_s is not None
                                  else None)
                    close_at = deadline_at if timeout_at is None \
                        else min(deadline_at, timeout_at)
                    if now >= close_at:
                        reason = ("timeout" if timeout_at is not None
                                  and timeout_at <= close_at + 1e-9
                                  and now < deadline_at else "deadline")
                        closeable.append((name, reason))
                    elif min_close_at is None or close_at < min_close_at:
                        min_close_at = close_at
                if full:
                    picked = self._wrr_pick(
                        [self._tenants[n] for n in full]).name
                    reason = "full"
                elif not self._running:
                    # drain: oldest head across tenants goes first
                    picked = min(
                        nonempty,
                        key=lambda n: self._queues[n][0].t_enqueue)
                    reason = "drain"
                elif closeable:
                    if len(closeable) == 1:
                        picked, reason = closeable[0]
                    else:
                        picked = self._wrr_pick(
                            [self._tenants[n] for n, _ in closeable]).name
                        reason = dict(closeable)[picked]
                else:
                    if gen_work:
                        # decode steps interleave with the batch fill:
                        # the classic batch keeps its SLO patience, the
                        # scheduler just doesn't SLEEP through it
                        return [], "decode"
                    # fill otherwise: sleep until the earliest close
                    # time or the next submit, whichever is first
                    self._cond.wait(min(min_close_at - now, 0.1))
                    continue
                q = self._queues[picked]
                key = q[0].shape_key
                taken, rest = [], []
                for r in q:
                    if len(taken) < cap and r.shape_key == key:
                        taken.append(r)
                    else:
                        rest.append(r)
                self._queues[picked] = rest
                if _telemetry_state.enabled:
                    telemetry.set_serving_queue_depth(
                        sum(len(x) for x in self._queues.values()))
                    telemetry.set_tenant_queue_depth(len(rest), picked)
                return taken, reason

    def _dispatch(self, batch, reason: str) -> None:
        """Pad, run, slice, resolve — one bucketed inference dispatch."""
        from ..ndarray import array as nd_array

        t_start = time.perf_counter()
        # a caller may have cancelled a still-queued future; drop those
        # rows now — set_result on a cancelled future would raise and
        # kill the scheduler thread
        batch = [r for r in batch
                 if r.future.set_running_or_notify_cancel()]
        if not batch:
            return
        n = len(batch)
        key = batch[0].shape_key
        tenant = batch[0].tenant
        cap = self.grid.batch_bucket(n)
        payload = np.zeros((cap,) + key, dtype=self.dtype)
        for i, r in enumerate(batch):
            payload[i] = r.sample
        model = tenant.block         # reload swaps the attribute, not us
        sig = (cap,) + key

        bsp = None
        if _tracing_state.enabled:
            traced = [(r.trace, r.span) for r in batch
                      if r.trace is not None]
            if traced:
                # the N co-batched wait spans end here (flow-linked to
                # the ONE dispatch span that serves them all)
                bsp = tracing.begin_batch(
                    traced, wait_tags={"close_reason": reason},
                    replica=self.name, sig=str(sig), reason=reason,
                    model=tenant.name)

        def run():
            hook = self._pre_dispatch
            if hook is not None:
                hook(sig)
            if _fault_state.enabled:
                fault.check("serving.dispatch", f"{self.name} batch={sig}")
            x = nd_array(payload, ctx=self.ctx)
            with autograd.pause():
                out = model(x)
            return self._materialize(out)

        # injected faults / retries inside the dispatch annotate the
        # batch span (fault.py calls tracing.note against the ambient)
        amb = (tracing.active(batch[0].trace, bsp) if bsp is not None
               else contextlib.nullcontext())
        try:
            with amb:
                leaves, tree = fault.retry_call(
                    "serving.dispatch", run, detail=self.name)
        except Exception as e:  # noqa: BLE001 - forwarded to the futures
            self.n_errors += 1
            tracing.end_batch(bsp, outcome="error",
                              error=type(e).__name__)
            for r in batch:
                r.future.set_exception(e)
                self._count_request(
                    outcome="error", t_enqueue=r.t_enqueue,
                    trace_id=r.trace.trace_id if r.trace is not None
                    else None, tenant=tenant)
                if r.own_trace:
                    r.trace.finish(type(e).__name__)
            return
        tracing.end_batch(bsp, outcome="ok")
        self.n_batches += 1
        if self.n_batches == 1:
            from .. import compiler

            # replica cold-start milestone: start() -> first served batch
            compiler.mark_event("first_response")
        if _telemetry_state.enabled:
            telemetry.record_serving_batch(n, cap, reason)
            for r in batch:
                telemetry.record_serving_queue_time(t_start - r.t_enqueue)
        with self._model_lock:      # the reload warmup copies this set
            self._warm_sigs.add(sig)
            tenant.warm_sigs.add(sig)
        from ..gluon.block import nested_unflatten_nd

        try:
            for i, r in enumerate(batch):
                # copy: a row VIEW would pin the whole padded batch
                # array for as long as the caller holds the result
                r.future.set_result(nested_unflatten_nd(
                    tree, [leaf[i].copy() for leaf in leaves]))
                self._count_request(
                    outcome="ok", t_enqueue=r.t_enqueue,
                    trace_id=r.trace.trace_id if r.trace is not None
                    else None, tenant=tenant)
                if r.own_trace:
                    r.trace.finish("ok")
        except Exception as e:  # noqa: BLE001 - e.g. non-batch-major leaf
            self.n_errors += 1
            for r in batch:
                if not r.future.done():
                    r.future.set_exception(e)
                    self._count_request(outcome="error",
                                        t_enqueue=r.t_enqueue,
                                        tenant=tenant)
                if r.own_trace:
                    r.trace.finish(type(e).__name__)

    @staticmethod
    def _materialize(out):
        """Flatten the model output and pull each leaf to host numpy once
        per batch (futures hand out row slices of these)."""
        from ..gluon.block import nested_flatten_nd

        flat, tree = nested_flatten_nd(out)
        return [leaf.asnumpy() for leaf in flat], tree

    def _count_request(self, outcome: str, t_enqueue: Optional[float] = None,
                       trace_id: Optional[str] = None,
                       tenant: Optional[_Tenant] = None) -> None:
        self.n_requests += 1
        if tenant is not None:
            tenant.n_requests += 1
        if _telemetry_state.enabled:
            lat = (time.perf_counter() - t_enqueue
                   if t_enqueue is not None else 0.0)
            telemetry.record_serving_request(
                lat, outcome, trace_id=trace_id,
                model=tenant.name if tenant is not None else None)

    @staticmethod
    def _end_trace_rejected(req: _Request, status: str = "rejected") -> None:
        """Seal a traced request that never reached a batch."""
        if req.trace is None:
            return
        if req.span is not None:
            req.span.end(outcome=status)
        if req.own_trace:
            req.trace.finish(status)

    # -- model management ----------------------------------------------
    def _warm_block(self, block, prime: bool = False) -> int:
        """AOT-compile ``block`` for every known signature: the full
        grid when it is enumerable (``prime=True`` + shape buckets), and
        always every signature this server has actually served — so a
        hot-reloaded model is warm for live traffic before the swap.

        Warm compiles route through the compilation service: a replica
        (or a reloaded model) whose program another in-process replica
        already compiled is an executable-table hit, not a second XLA
        compile — N replicas of one architecture warm for the price of
        one. When a signature manifest is being recorded, its journal is
        replayed against the block first, so signatures served by a
        PREVIOUS process warm too (the manifest may know more than the
        enumerable grid)."""
        if not self._warmup or not hasattr(block, "warmup"):
            return 0
        from .. import compiler

        man = compiler.recorder()
        if man is not None:
            try:
                compiler.warm_start(man, blocks=[block])
            except Exception:   # noqa: BLE001 - warm is best-effort
                pass
        with self._model_lock:      # the scheduler adds sigs concurrently
            sigs = set(self._warm_sigs)
        if prime and self.grid.shape_buckets is not None:
            sigs.update(self.grid.input_signatures())
        if not sigs:
            return 0
        if getattr(block, "_active", None) is False:
            block.hybridize()
        return block.warmup(sorted(sigs), dtype=self.dtype, ctx=self.ctx)

    def current_model(self, model: Optional[str] = None):
        """The block currently being served for ``model`` (default
        tenant when None; the rolling-upgrade machinery keeps it for
        rollback)."""
        return self._tenant(model).block

    def swap_model(self, block, version: Optional[int] = None,
                   model: Optional[str] = None) -> None:
        """Atomically replace ONE tenant's served model with ``block``,
        warming it for every signature in live use first — requests
        dispatched during the warmup keep hitting the old graph, and
        other tenants' blocks/versions are untouched (the per-model
        upgrade contract). ``version`` overrides the monotonic bump (a
        rollback restores the old number)."""
        t = self._tenant(model)
        self._warm_block(block, prime=True)
        with self._model_lock:
            t.block = block
            t.model_version = (t.model_version + 1
                               if version is None else int(version))
        self.n_reloads += 1

    def reload(self, manager, model_factory, step: Optional[int] = None
               ) -> int:
        """Zero-downtime reload from a :class:`CheckpointManager` bundle:
        build a fresh block via ``model_factory(bundle_path)``, warm it,
        swap it in. The old graph serves until the swap. Fault site
        ``serving.reload``; transient failures retry, persistent ones
        raise (the old model keeps serving). Returns the loaded step."""
        t0 = time.perf_counter()
        if step is None:
            step = manager.latest_step()
            if step is None:
                raise MXNetError(
                    f"{self.name}: no checksum-valid checkpoint under "
                    f"{manager.directory!r} to reload from")
        path = manager.path(step)

        def build():
            if _fault_state.enabled:
                fault.check("serving.reload", path)
            return model_factory(path)

        try:
            block = fault.retry_call("serving.reload", build, detail=path)
            self.swap_model(block)
        except Exception:
            if _telemetry_state.enabled:
                telemetry.record_serving_reload(0.0, outcome="error")
            raise
        self.loaded_step = step
        if _telemetry_state.enabled:
            telemetry.record_serving_reload(time.perf_counter() - t0)
        return step

    def enable_hot_reload(self, manager, model_factory,
                          interval_s: float = 0.5,
                          tag: Optional[str] = None):
        """Start a watcher thread that polls ``manager`` (via
        :meth:`CheckpointManager.poll_newest`) and hot-reloads on every
        new valid bundle. See :class:`~.reload.ReloadWatcher`."""
        from .reload import ReloadWatcher

        if self._watcher is not None:
            raise MXNetError(f"{self.name}: hot reload already enabled")
        self._watcher = ReloadWatcher(
            self, manager, model_factory, interval_s=interval_s,
            tag=tag or self.name)
        self._watcher.start()
        return self._watcher

    def stats(self) -> dict:
        """Light always-on counters (telemetry has the full story). With
        a page pool: ``kvcache`` (pages by state) and ``state_slots``
        (per-stream state slots free / used; only an engine that
        declares ``state_slots`` ever takes one)."""
        with self._cond:
            depth = sum(len(q) for q in self._queues.values())
            gen_pending = sum(len(q)
                              for q in self._gen_pending.values())
            gen_active = len(self._gen_active)
            models = {
                n: {"slo_class": t.slo_class, "priority": t.priority,
                    "weight": t.weight, "version": t.model_version,
                    "requests": t.n_requests, "shed": t.n_shed,
                    "preempted": t.n_preempted, "tokens": t.n_tokens,
                    "queue_depth": len(self._queues[n]),
                    "generates_pending": len(self._gen_pending[n])}
                for n, t in self._tenants.items()}
        out = {"requests": self.n_requests, "batches": self.n_batches,
               "errors": self.n_errors, "reloads": self.n_reloads,
               "queue_depth": depth, "loaded_step": self.loaded_step,
               "model_version": self.model_version,
               "running": self.is_running, "models": models,
               "preemptions": self.n_preemptions}
        if self._decode_pages is not None:
            out.update(tokens=self.n_tokens, generates_pending=gen_pending,
                       generates_active=gen_active,
                       defrags=self.n_defrags,
                       kvcache=self._pool.stats() if self._pool else None,
                       state_slots=self._pool.state_slots.stats()
                       if self._pool else None)
        return out


_ROUND_PHASES = ("wait", "sched", "build", "launch", "fetch", "emit")


class _RoundClock:
    """``time.time_ns()`` at the boundaries of the phases of a decode
    round, read once per boundary on the scheduler thread, so that the
    phases tile the thread's time: ``wait`` (from the end of the last
    round's emit to the entry of ``_decode_tick``: the heartbeat,
    ``_next_batch`` and its waits, a non-generate dispatch, ticks that
    ran no round), ``sched`` (to the entry of ``_decode_batch``:
    admission, prefills, deadlines, the tenant split), ``build`` (to the
    call of ``engine.decode_step``: the host arrays and the per-stream
    spans), ``launch`` (until ``PagedDecodeEngine.forward`` has its
    programs dispatched), ``fetch`` (until the ids are on the host and
    the dispatch has returned) and ``emit`` (to the end of the
    ``_emit_token`` loop). One per tick; a tick's second round (another
    tenant's, or the rest of more streams than the grid holds) starts
    where the first ended, with an empty ``wait``."""

    __slots__ = ("marks", "batches", "callback_ns", "trace", "sealing")

    def __init__(self, t_wait: int, t_tick: int, batches: int):
        self.marks = [t_wait, t_tick]
        self.batches = batches      # Server.n_batches where sched began
        self.callback_ns = 0        # inside handle._push / the future
        self.trace = None           # of the round's first traced stream
        self.sealing = None         # that trace's status, if it ended

    def begin_round(self, chunk) -> None:
        self.marks.append(time.time_ns())
        self.trace = next((g.trace for g in chunk if g.trace is not None),
                          None)

    def launch(self) -> None:
        self.marks.append(time.time_ns())

    def returned(self, t_run_done: Optional[int]) -> None:
        """The dispatch is back (fetch | emit); ``t_run_done`` is the
        engine's reading of launch | fetch, which stands only if it was
        taken inside this dispatch (one that raised may not have reached
        it: its launch then runs to here). What the callers' callbacks
        took before this point (a prefill's first tokens) is not emit's."""
        now = time.time_ns()
        if t_run_done is None or not self.marks[-1] <= t_run_done <= now:
            t_run_done = now
        self.marks += [t_run_done, now]
        self.callback_ns = 0

    def end_round(self) -> list:
        """The round's seven boundaries; the clock is left where a
        second round of the same tick starts."""
        now = time.time_ns()
        marks, self.marks = self.marks + [now], [now, now]
        return marks
