"""KVStore implementations (see package docstring for the design map)."""
from __future__ import annotations

import os
import pickle
import threading
import time
import warnings
import weakref
from typing import Dict, List, Optional, Tuple

import numpy as _np

from .. import fault
from .. import optimizer as opt
from .. import telemetry
from ..base import MXNetError
from ..fault import _state as _fault_state
from ..ndarray import NDArray
from ..ndarray import array as nd_array
from ..telemetry import _state as _telemetry_state
from .bucketing import bucket_cap_bytes, pack, plan_buckets, unpacker

_FUSED_SUM = None


def _fused_sum(arrs):
    """One jitted stack-and-sum over N same-shape arrays (one XLA
    dispatch; jit caches per (N, shape, dtype) signature)."""
    global _FUSED_SUM
    if _FUSED_SUM is None:
        import jax
        import jax.numpy as jnp

        _FUSED_SUM = jax.jit(lambda *xs: jnp.sum(jnp.stack(xs), axis=0))
    return _FUSED_SUM(*arrs)


def _nd_bytes(v) -> int:
    """Payload size of one NDArray (shape x dtype itemsize)."""
    try:
        d = v.dtype
        itemsize = getattr(d, "itemsize", None) or _np.dtype(d).itemsize
        return int(v.size) * int(itemsize)
    except Exception:
        return 0


def _payload_bytes(vals) -> int:
    return sum(_nd_bytes(v) for v in vals)

__all__ = ["BarrierTimeoutError", "KVStore", "KVStoreDistAsyncEmu",
           "KVStoreLocal", "KVStoreTPUSync", "create",
           "reset_barrier_epoch"]


# ---------------------------------------------------------------------------
# Bounded barriers — a dead worker must surface as a typed error naming
# the site and the missing ranks, never as an unbounded hang.
# ---------------------------------------------------------------------------

# SPMD-consistent store-creation ordinal (every process creates its
# stores in the same program order) — namespaces each store's
# cross-process barrier keys so two stores can never alias rendezvous.
_STORE_ORDINAL = 0

# Elastic membership epoch the barrier keyspace is based on. Per-site
# barrier sequence numbers live in process memory, so a restarted rank
# would re-count from zero while survivors kept counting — the ranks
# would announce under different key prefixes and every post-restart
# barrier would time out. The elastic runtime calls
# :func:`reset_barrier_epoch` at every membership transition (and at a
# rejoiner's start), which re-bases EVERY rank's counters to zero under
# an epoch-tagged namespace: survivors and the restarted rank meet at
# seq 1 of the new epoch.
_BARRIER_EPOCH = 0


def reset_barrier_epoch(epoch: int) -> None:
    """Re-base cross-process barrier sequence numbering to an elastic
    membership ``epoch``. Called by ``parallel.elastic`` at each epoch
    transition on every surviving rank (a restarted rank's counters are
    fresh anyway), so all ranks' barriers rendezvous under the same
    ``e{epoch}`` key namespace starting from sequence 1."""
    global _BARRIER_EPOCH
    _BARRIER_EPOCH = int(epoch)


class BarrierTimeoutError(MXNetError):
    """A kvstore barrier (local drain or cross-process rendezvous) did
    not complete within ``MXNET_KV_BARRIER_TIMEOUT`` — the typed signal
    the elastic runtime and exit paths branch on instead of wedging."""


def _barrier_timeout_s() -> float:
    """Default barrier bound (seconds). <= 0 disables the bound (the
    pre-supervision behavior, for jobs that want to block forever)."""
    try:
        return float(os.environ.get("MXNET_KV_BARRIER_TIMEOUT", "300"))
    except ValueError as e:
        raise MXNetError(
            "MXNET_KV_BARRIER_TIMEOUT="
            f"{os.environ['MXNET_KV_BARRIER_TIMEOUT']!r} is not a "
            "number") from e


def _bootstrap_timeout_s() -> int:
    """The ``jax.distributed.initialize`` rendezvous bound (seconds):
    ``MXNET_KV_BOOTSTRAP_TIMEOUT`` falling back to the barrier knob.
    jax wants a positive integer and has no unbounded mode, so <= 0
    (the documented bound opt-out) maps to ~24 days, and fractions
    round UP so 0.5 never truncates to instant failure. Shared by
    ``_maybe_init_distributed`` and the elastic re-bootstrap so the
    opt-out means the same thing at both sites."""
    try:
        t = float(os.environ.get(
            "MXNET_KV_BOOTSTRAP_TIMEOUT", "") or _barrier_timeout_s())
    except ValueError as e:
        raise MXNetError(
            "MXNET_KV_BOOTSTRAP_TIMEOUT="
            f"{os.environ['MXNET_KV_BOOTSTRAP_TIMEOUT']!r} is not a "
            "number") from e
    import math

    return 2**31 // 1000 if t <= 0 else max(1, math.ceil(t))


def _bounded_waitall(site: str, timeout: float) -> None:
    """Drain local async device work, bounded: ``waitall`` runs on a
    daemon thread joined with ``timeout``. On expiry the caller gets
    :class:`BarrierTimeoutError` naming the site — the wedged device
    work stays wedged (nothing can cancel it), but the *process* regains
    control to checkpoint, report, or exit."""
    from .. import ndarray as _nd

    if timeout <= 0:
        _nd.waitall()
        return
    done = threading.Event()
    err: List[BaseException] = []

    def _drain():
        try:
            _nd.waitall()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            err.append(e)
        finally:
            done.set()

    threading.Thread(target=_drain, name="mxnet-kv-barrier-wait",
                     daemon=True).start()
    if not done.wait(timeout):
        raise BarrierTimeoutError(
            f"kvstore.barrier[{site}]: local device drain did not "
            f"complete within {timeout:g}s (MXNET_KV_BARRIER_TIMEOUT) — "
            "outstanding async work is wedged (dead collective peer?)")
    if err:
        raise err[0]


def _coord_client():
    """The jax coordination-service KV client, or None when this process
    was not bootstrapped through ``jax.distributed``."""
    try:
        from jax._src import distributed

        return distributed.global_state.client
    except Exception:
        return None


def dist_initialized() -> bool:
    """Is ``jax.distributed`` bootstrapped in this process?
    ``jax.distributed.is_initialized`` only exists in newer jax; older
    containers (this one included) fall back to the coordination-service
    client handle, which is set by ``initialize`` and cleared by
    ``shutdown`` on every version in support."""
    import jax

    fn = getattr(jax.distributed, "is_initialized", None)
    if fn is not None:
        return bool(fn())
    return _coord_client() is not None


def _kv_set_once(client, key: str, value: str) -> None:
    """``key_value_set`` tolerating re-announcement (a retried barrier
    attempt re-sets its own key; ALREADY_EXISTS is success)."""
    try:
        client.key_value_set(key, value)
    except Exception as e:  # noqa: BLE001 - status string filtered
        # only ALREADY_EXISTS is success; "does not exist" / NOT_FOUND
        # style failures must surface (a swallowed announcement would
        # make every PEER's timeout blame this healthy rank)
        msg = str(e).lower()
        if not ("already" in msg and "exist" in msg):
            raise


def _cross_process_barrier(client, site: str, seq: int, rank: int,
                           num_workers: int, timeout: float,
                           poll_interval: float = 0.05,
                           key_ns: str = "",
                           time_fn=time.monotonic,
                           sleep_fn=time.sleep) -> List[int]:
    """Rendezvous ``num_workers`` ranks through the coordination-service
    KV store: announce ``.../{site}/{seq}/{rank}``, poll the directory
    until every rank announced or the deadline passes. On expiry raises
    :class:`BarrierTimeoutError` naming the site AND the missing ranks —
    the diagnostic a hung ``psum`` can never give. Announcements are
    idempotent, so the surrounding ``fault.retry_call`` is safe."""
    prefix = f"mxnet_tpu/barrier/{key_ns}{site}/{int(seq)}"
    _kv_set_once(client, f"{prefix}/{int(rank)}", str(int(rank)))
    deadline = time_fn() + timeout
    while True:
        if _fault_state.enabled:
            fault.check("kvstore.barrier", f"{site} seq {seq}")
        present = set()
        for item in client.key_value_dir_get(prefix):
            key = item[0] if isinstance(item, (tuple, list)) else item
            tail = str(key).rsplit("/", 1)[-1]
            if tail.isdigit():
                present.add(int(tail))
        if len(present) >= num_workers:
            return sorted(present)
        if timeout > 0 and time_fn() >= deadline:
            missing = sorted(set(range(num_workers)) - present)
            raise BarrierTimeoutError(
                f"kvstore.barrier[{site}] (seq {seq}) timed out after "
                f"{timeout:g}s: missing ranks {missing} of "
                f"{num_workers} (arrived: {sorted(present)}) — restart "
                "the dead worker (tools/launch.py --max-restarts) or "
                "tear the job down; MXNET_KV_BARRIER_TIMEOUT bounds "
                "this wait")
        sleep_fn(poll_interval)


def _register_exit_barrier(store: "KVStore") -> None:
    """Run the store's bounded exit barrier at interpreter exit so a
    multi-process job's ranks leave together when they can — and leave
    ANYWAY (with a warning) when a peer is already gone."""
    import atexit

    ref = weakref.ref(store)

    def _hook():
        s = ref()
        if s is not None:
            s._barrier_before_exit()

    atexit.register(_hook)


def create(name="local") -> "KVStore":
    """reference: mx.kv.create / KVStore::Create."""
    name = str(name).lower()
    if name in ("local", "local_update_cpu", "local_allreduce_cpu",
                "local_allreduce_device", "device"):
        return KVStoreLocal(name)
    if name in ("tpu_sync", "nccl", "dist_device_sync", "dist_sync"):
        return KVStoreTPUSync(name)
    if name in ("dist_async",):
        import os

        if os.environ.get("MXNET_KVSTORE_DIST_ASYNC_EMU") == "1":
            return KVStoreDistAsyncEmu(name)
        raise MXNetError(
            "kvstore 'dist_async' (parameter-server async mode) has no "
            "TPU-native equivalent; use 'tpu_sync' (synchronous in-graph "
            "allreduce over the mesh), or opt into the bounded-staleness "
            "emulation with MXNET_KVSTORE_DIST_ASYNC_EMU=1 "
            "(MXNET_KVSTORE_ASYNC_STALENESS bounds the drift) — "
            "SURVEY.md §5.8, ADR-002")
    if name in ("horovod", "byteps"):
        raise MXNetError(
            f"kvstore '{name}' plugin is replaced by 'tpu_sync' on TPU")
    raise MXNetError(f"unknown kvstore type {name!r}")


class KVStore:
    """Base interface (reference: include/mxnet/kvstore.h)."""

    def __init__(self, type_name):
        self._type = type_name
        self._updater = None
        self._optimizer = None
        self._compression = None

    @property
    def type(self):
        return self._type

    @property
    def rank(self) -> int:
        return 0

    @property
    def num_workers(self) -> int:
        return 1

    def init(self, key, value):
        raise NotImplementedError

    def push(self, key, value, priority=0):
        raise NotImplementedError

    def pull(self, key, out, priority=0, ignore_sparse=True):
        raise NotImplementedError

    def pushpull(self, key, value, out=None, priority=0):
        """Fused push+pull (reference: kvstore.py::pushpull).

        The batched form — ``pushpull(keys, values, outs, priorities)``
        with parallel lists — is the REAL fused entry: stores that
        support it coalesce the keys into flat dtype-segregated buckets
        of ``MXNET_KV_BUCKET_MB`` (default 25) MB and run ONE collective
        per bucket instead of one per key. The scalar form is a thin
        wrapper over a one-key batch.

        Priority contract (previously accepted and ignored, now
        honored): keys are exchanged in DESCENDING priority order,
        stable for ties. The Gluon trainer passes ``priority=-i``, so
        parameter 0's bucket is dispatched first and its reduced
        gradient reaches the optimizer soonest; bucket *i+1*'s
        collective is dispatched before bucket *i*'s scatter, so via
        JAX async dispatch the collective overlaps the previous
        bucket's scatter + optimizer update.
        """
        if isinstance(key, (list, tuple)):
            keys = list(key)
            values = list(value)
            if len(values) != len(keys):
                raise MXNetError(
                    f"batched pushpull: {len(keys)} keys but "
                    f"{len(values)} values")
            if out is None:
                outs = values
            else:
                outs = list(out) if isinstance(out, (list, tuple)) \
                    else [out]
                if len(outs) != len(keys):
                    raise MXNetError(
                        f"batched pushpull: {len(keys)} keys but "
                        f"{len(outs)} outs")
            if isinstance(priority, (list, tuple)):
                if len(priority) != len(keys):
                    raise MXNetError(
                        f"batched pushpull: {len(keys)} keys but "
                        f"{len(priority)} priorities")
                priorities = [int(p) for p in priority]
            else:
                priorities = [int(priority)] * len(keys)
            return self._pushpull_batched(keys, values, outs, priorities)
        return self._pushpull_batched(
            [key], [value], [out if out is not None else value],
            [int(priority)])

    def _pushpull_batched(self, keys, values, outs, priorities):
        """Per-key decomposition — the fallback for stores without a
        fused bucketed path and for the server-side-optimizer mode
        (the updater applies per key). Still honors the priority order
        (descending, stable)."""
        for i in sorted(range(len(keys)), key=lambda j: -priorities[j]):
            self.push(keys[i], values[i], priorities[i])
            self.pull(keys[i], outs[i], priorities[i])

    def row_sparse_pull(self, key, out, priority=0, row_ids=None):
        """Pull ONLY the requested rows (reference: kvstore.py::
        row_sparse_pull for RowSparseNDArray weights).

        ``row_ids``: int NDArray of row indices (duplicates fine). The
        pulled rows are gathered server-side — the traffic and the
        ``out`` payload are O(len(row_ids) x dim), never the full table.
        ``out`` RowSparseNDArrays get a factored (indices, values)
        payload; dense NDArrays get rows written in place.
        """
        if row_ids is None:
            return self.pull(key, out, priority)
        if isinstance(key, (list, tuple)):
            rids = row_ids if isinstance(row_ids, (list, tuple)) \
                else [row_ids] * len(key)
            for k, o, r in zip(key, out, rids):
                self.row_sparse_pull(k, o, priority, r)
            return
        import jax.numpy as jnp

        from ..ndarray.sparse import RowSparseNDArray

        key = self._canon(key)
        self._check_init(key)
        src = self._store[key]
        outs = out if isinstance(out, (list, tuple)) else [out]
        rows = row_ids.data.astype(jnp.int32) \
            if isinstance(row_ids, NDArray) else jnp.asarray(
                row_ids, dtype=jnp.int32)
        # pad/dedupe slots park on an OUT-OF-RANGE sentinel so they can
        # never alias a real table row; scatter drops them, factored
        # getters compress them out
        rows = jnp.unique(rows, size=rows.size, fill_value=src.shape[0])
        vals = src.data[rows]            # sentinel reads clamp (ignored)
        for o in outs:
            if isinstance(o, RowSparseNDArray):
                o.set_rows(rows, vals, src.shape)
            else:
                o._set_data(o.data.at[rows].set(vals, mode="drop"))

    def set_optimizer(self, optimizer):
        self._optimizer = optimizer
        self._updater = opt.get_updater(optimizer)

    def set_gradient_compression(self, compression_params):
        """2-bit threshold quantization with error feedback on every
        pushed gradient (reference: kvstore.py::set_gradient_compression
        → gradient_compression.cc)."""
        from .gradient_compression import create_compression

        self._compression = create_compression(compression_params)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise MXNetError("no optimizer set on this kvstore")
        from ..checkpoint import atomic_write

        atomic_write(fname, self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("no optimizer set on this kvstore")
        from ..checkpoint import apply_state_bytes, read_state_bytes

        states = read_state_bytes(fname, "load_optimizer_states")
        apply_state_bytes(states, self._updater.set_states, fname,
                          "load_optimizer_states")

    def barrier(self, site: str = "user", timeout: Optional[float] = None):
        """Synchronization barrier, BOUNDED (reference: kvstore.py::
        barrier — an unbounded ``waitall``). Drains local async device
        work within ``timeout`` seconds (default
        ``MXNET_KV_BARRIER_TIMEOUT``, 300; <= 0 restores the unbounded
        wait); distributed stores additionally rendezvous every process.
        On expiry raises :class:`BarrierTimeoutError` naming ``site``
        (and, cross-process, the missing ranks) instead of wedging the
        job on a dead worker. Fault site ``kvstore.barrier``."""
        timeout = _barrier_timeout_s() if timeout is None \
            else float(timeout)
        if _fault_state.enabled:
            fault.check("kvstore.barrier", site)
        _bounded_waitall(site, timeout)

    def _barrier_before_exit(self) -> bool:
        """Bounded exit drain (was a no-op): let a multi-process job's
        ranks leave together, but NEVER wedge teardown — a barrier
        timeout (dead peer) is reported as a warning carrying the typed
        error and exit proceeds. Returns True when the barrier
        completed. ``MXNET_KV_EXIT_BARRIER_TIMEOUT`` (default 10 s,
        capped by the main barrier knob) bounds the wait."""
        try:
            cap = _barrier_timeout_s()
            timeout = float(os.environ.get(
                "MXNET_KV_EXIT_BARRIER_TIMEOUT", "10"))
            if cap > 0:
                timeout = min(timeout, cap)
        except Exception:  # noqa: BLE001 - incl. MXNetError from a
            # malformed knob: this runs from atexit, never raise
            timeout = 10.0
        try:
            self.barrier(site="exit", timeout=timeout)
            return True
        except Exception as e:  # noqa: BLE001 - exit path: warn, never
            # raise (incl. a coordination client already torn down by
            # interpreter shutdown — this runs from atexit)
            warnings.warn(
                f"kvstore exit barrier abandoned (exit continues): {e}",
                RuntimeWarning, stacklevel=2)
            return False


class KVStoreLocal(KVStore):
    """Single-process aggregation across device copies
    (reference: src/kvstore/kvstore_local.h + comm.h::CommCPU/CommDevice).

    'local' reduces via a host-side sum, 'device' sums on the first device —
    with XLA both are a single fused add chain; the distinction is kept for
    API parity."""

    def __init__(self, type_name="local"):
        super().__init__(type_name)
        self._store: Dict = {}
        # fused-pushpull bucket cap (bytes); 0 disables bucketing.
        # Mutable attribute so benches/dryruns can force the per-key
        # path on one store without touching the environment.
        self._bucket_bytes = bucket_cap_bytes()
        # key sets already warned about falling off the fused path (one
        # warning per distinct set, not one per step)
        self._warned_fallback: set = set()

    def init(self, key, value):
        key = self._canon(key)
        if isinstance(value, (list, tuple)):
            value = value[0]
        self._store[key] = value.copy()

    def _canon(self, key):
        return key if isinstance(key, (int, str)) else int(key)

    def _check_init(self, key):
        if key not in self._store:
            raise MXNetError(f"kvstore key {key!r} was not initialized")

    def push(self, key, value, priority=0):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.push(k, v, priority)
            return
        _tel = _telemetry_state.enabled
        t0 = time.perf_counter() if _tel else 0.0
        key = self._canon(key)
        self._check_init(key)
        vals = list(value) if isinstance(value, (list, tuple)) else [value]
        if self._compression is not None:
            # quantize each worker-slot's gradient before the reduce —
            # the same point the reference compresses before the wire.
            # NOT inside the retry: compression carries error-feedback
            # state, so re-compressing on retry would double-apply it.
            vals = [self._compression.compress(key, i, v)
                    for i, v in enumerate(vals)]

        def _reduce():
            if _fault_state.enabled:
                fault.check("kvstore.push", f"key {key!r}")
            return self._aggregate(vals)

        # bounded exponential-backoff retry around the device work only
        # (the reduce); the updater/store application below runs once —
        # retrying a half-applied optimizer update is not idempotent
        agg = fault.retry_call("kvstore.push", _reduce,
                               detail=f"key {key!r}")
        if self._updater is not None:
            # server-side optimizer path (update_on_kvstore=True). The key
            # itself indexes updater state: ints and strings are both
            # stable across processes/restarts (hash() is neither).
            self._updater(key, agg, self._store[key])
        else:
            self._store_reduced(key, agg)
        if _tel:
            telemetry.record_kv("push", _payload_bytes(vals),
                                time.perf_counter() - t0)
            telemetry.record_kv_collective("per_key")

    def _aggregate(self, vals: List[NDArray]) -> NDArray:
        """Reduce per-device copies to one value (subclass hook).

        ONE fused stack-and-sum dispatch instead of N-1 sequential
        in-place adds (each of which was its own XLA dispatch); copies
        living on other devices are staged onto the first copy's device
        first. The reduction order over the N copies is fixed by the
        stack, so results are deterministic across calls."""
        if len(vals) == 1:
            return vals[0]
        import jax

        dev = next(iter(vals[0].data.devices()))
        arrs = [v.data if next(iter(v.data.devices())) == dev
                else jax.device_put(v.data, dev) for v in vals]
        return NDArray(data=_fused_sum(arrs), ctx=vals[0].context)

    def _store_reduced(self, key, agg: NDArray):
        # snapshot the (immutable) payload — never alias the caller's
        # NDArray, which it may keep mutating in place
        dst = self._store[key]
        dst._set_data(agg.as_in_context(dst.context).data
                      if dst.context != agg.context else agg.data)

    def pull(self, key, out, priority=0, ignore_sparse=True):
        if isinstance(key, (list, tuple)):
            for k, o in zip(key, out):
                self.pull(k, o, priority)
            return
        _tel = _telemetry_state.enabled
        t0 = time.perf_counter() if _tel else 0.0
        key = self._canon(key)
        self._check_init(key)
        outs = out if isinstance(out, (list, tuple)) else [out]
        src = self._store[key]

        def _copy_out():
            if _fault_state.enabled:
                fault.check("kvstore.pull", f"key {key!r}")
            for o in outs:
                o._set_data(src.as_in_context(o.context).data
                            if o.context != src.context else src.data)

        # idempotent (plain overwrite of the outs) — safe to retry whole
        fault.retry_call("kvstore.pull", _copy_out, detail=f"key {key!r}")
        if _tel:
            telemetry.record_kv("pull", _nd_bytes(src) * len(outs),
                                time.perf_counter() - t0)

    # -- bucketed fused pushpull ---------------------------------------
    def _pushpull_batched(self, keys, values, outs, priorities):
        """The fused entry: keys are coalesced into dtype-segregated flat
        buckets (``MXNET_KV_BUCKET_MB``) and each bucket is reduced by
        ONE dispatch (`_bucket_reduce` — a fused stack-and-sum here, one
        compiled psum in ``tpu_sync``), then scattered back into the
        per-param store entries and out views.

        Pipelining: buckets are processed in descending-priority order
        and bucket *i+1*'s reduce is dispatched BEFORE bucket *i*'s
        scatter, so the collective runs while the host enqueues the
        previous bucket's unpack (JAX async dispatch — nothing here
        blocks on device work).

        Falls back to the per-key decomposition when the fused path
        cannot apply: server-side optimizer installed (the updater
        applies per key), bucketing disabled (``MXNET_KV_BUCKET_MB=0``
        or ``store._bucket_bytes = 0``), or — per key — a payload that
        is not a dense NDArray (row-sparse gradients keep their
        specialized path).
        """
        if self._updater is not None or self._bucket_bytes <= 0:
            return KVStore._pushpull_batched(
                self, keys, values, outs, priorities)
        _tel = _telemetry_state.enabled
        t0 = time.perf_counter() if _tel else 0.0
        order = sorted(range(len(keys)), key=lambda j: -priorities[j])
        entries = []          # planner input, in dispatch order
        fallback = set()      # positions exchanged per-key
        vals_by_pos: Dict = {}
        outs_by_pos: Dict = {}
        total_bytes = 0
        for pos in order:
            key = self._canon(keys[pos])
            self._check_init(key)
            vals = list(values[pos]) if isinstance(
                values[pos], (list, tuple)) else [values[pos]]
            outs_i = list(outs[pos]) if isinstance(
                outs[pos], (list, tuple)) else [outs[pos]]
            vals_by_pos[pos] = (key, vals)
            outs_by_pos[pos] = outs_i
            entry = self._bucket_entry(pos, vals, outs_i)
            if entry is None:
                fallback.add(pos)
                continue
            entries.append(entry)
            # pushed copies in + pulled outs back, matching what the
            # per-key path records under push+pull — the two paths'
            # byte counters must stay comparable
            total_bytes += entry[4] * (len(vals) + len(outs_i))
        if fallback:
            # the coverage gap is OBSERVABLE (ISSUE 19 satellite): count
            # every per-key fallback and warn once per distinct key set —
            # a model quietly paying O(keys) dispatches (or training
            # un-sharded under ZeRO) should not be a mystery
            if _tel:
                telemetry.record_kv_bucket_fallback("row_sparse",
                                                    len(fallback))
            keyset = frozenset(vals_by_pos[pos][0] for pos in fallback)
            if keyset not in self._warned_fallback:
                self._warned_fallback.add(keyset)
                shown = sorted(map(str, keyset))
                more = "" if len(shown) <= 8 else f" (+{len(shown) - 8})"
                warnings.warn(
                    f"{len(keyset)} key(s) fell back to per-key pushpull "
                    f"(non-default storage, e.g. row_sparse): "
                    f"{shown[:8]}{more} — these keys are outside the "
                    "fused-bucket (and ZeRO) path",
                    stacklevel=3)
        buckets = plan_buckets(entries, self._bucket_bytes)
        # one dispatch plan in global priority order: a bucket is issued
        # at its FIRST member's slot, per-key fallbacks (sparse payloads)
        # at their own slot — not banished behind every bucket
        bucket_at = {b.indices[0]: b for b in buckets}
        pending = None
        for pos in order:
            b = bucket_at.get(pos)
            if b is not None:
                reduced = self._bucket_exchange_reduce(b, vals_by_pos)
                if _tel:
                    telemetry.record_kv_bucket(b.nbytes, len(b))
                    telemetry.record_kv_collective(
                        self._bucket_path_label(b))
                if pending is not None:
                    self._bucket_scatter(pending[0], pending[1],
                                         vals_by_pos, outs_by_pos)
                pending = (b, reduced)
            elif pos in fallback:
                if pending is not None:
                    self._bucket_scatter(pending[0], pending[1],
                                         vals_by_pos, outs_by_pos)
                    pending = None
                key, vals = vals_by_pos[pos]
                self.push(key, vals, priorities[pos])
                self.pull(key, outs_by_pos[pos], priorities[pos])
        if pending is not None:
            self._bucket_scatter(pending[0], pending[1],
                                 vals_by_pos, outs_by_pos)
        if _tel:
            telemetry.record_kv("pushpull", total_bytes,
                                time.perf_counter() - t0)

    def _bucket_path_label(self, bucket) -> str:
        """Telemetry ``path`` label for one fused-bucket dispatch —
        ``bucketed`` here; ``tpu_sync`` reports ``hierarchical`` when a
        host topology factors its mesh (the label then counts INTER-HOST
        collectives: exactly one per bucket)."""
        return "bucketed"

    @staticmethod
    def _bucket_entry(pos, vals, outs_i):
        """Planner entry for one key's payload, or None for the per-key
        fallback (any non-dense val OR out). The single eligibility/
        grouping rule shared by ``_pushpull_batched`` and
        ``plan_pushpull`` — the dry-run must never predict a bucket the
        batched path would not form. Group: members of one bucket must
        share dtype, copy count and per-slot device placement so each
        slot packs into one same-device flat buffer."""
        if not all(getattr(a, "stype", "default") == "default"
                   for a in vals + outs_i):
            return None
        v0 = vals[0]
        devsig = tuple(str(next(iter(v.data.devices()))) for v in vals)
        return (pos, tuple(v0.shape), v0.dtype,
                (str(v0.dtype), len(vals), devsig), _nd_bytes(v0))

    def plan_pushpull(self, keys, values, priorities=None, outs=None):
        """Dry-run of ``_pushpull_batched``'s bucket plan: the key GROUPS
        a batched call with these arguments would coalesce, as lists of
        positions into ``keys``, in dispatch (descending-priority) order.

        The overlapped-comms Trainer uses this to dispatch each group as
        its own ``pushpull`` the moment its members' gradients finalize
        during backward: a group re-planned alone reproduces exactly the
        batched call's bucket (same members, same flat-buffer layout,
        same reduce arity), so the overlapped exchange stays bit-identical
        to the one-shot batched path. Per-key fallbacks (sparse payloads,
        bucketing disabled, server-side optimizer) come back as singleton
        groups. ``outs`` defaults to ``values`` (the Trainer's in-place
        exchange); pass the real outs when they differ — eligibility
        depends on both.
        """
        n = len(keys)
        priorities = [0] * n if priorities is None else \
            [int(p) for p in priorities]
        order = sorted(range(n), key=lambda j: -priorities[j])
        if self._updater is not None or self._bucket_bytes <= 0:
            return [[pos] for pos in order]
        if outs is None:
            outs = values
        entries = []
        fallback = set()
        for pos in order:
            vals = list(values[pos]) if isinstance(
                values[pos], (list, tuple)) else [values[pos]]
            outs_i = list(outs[pos]) if isinstance(
                outs[pos], (list, tuple)) else [outs[pos]]
            entry = self._bucket_entry(pos, vals, outs_i)
            if entry is None:
                fallback.add(pos)
                continue
            entries.append(entry)
        buckets = plan_buckets(entries, self._bucket_bytes)
        bucket_at = {b.indices[0]: b for b in buckets}
        groups = []
        for pos in order:
            b = bucket_at.get(pos)
            if b is not None:
                groups.append(list(b.indices))
            elif pos in fallback:
                groups.append([pos])
        return groups

    def _bucket_exchange_reduce(self, bucket, vals_by_pos):
        """Pack each device slot's member gradients into one flat buffer
        (one jitted dispatch per slot), compress per bucket when a
        compressor is set, and reduce the slots. Returns the reduced
        flat jax array."""
        nslots = bucket.group[1]
        flats = []
        for s in range(nslots):
            flat = pack([vals_by_pos[pos][1][s].data
                         for pos in bucket.indices])
            if self._compression is not None:
                # per-BUCKET quantize: one jitted kernel over the flat
                # buffer, residual keyed by the bucket's member keys —
                # compression cost stops scaling with parameter count.
                # NOT inside the retry below: error-feedback state, so a
                # retry must not re-apply it (same rule as push()).
                bkey = tuple(vals_by_pos[pos][0]
                             for pos in bucket.indices)
                flat = self._compression.compress_flat(bkey, s, flat)
            flats.append(flat)

        def _reduce():
            if _fault_state.enabled:
                fault.check("kvstore.push",
                            f"bucket[{len(bucket)} keys]")
            return self._bucket_reduce(flats)

        return fault.retry_call("kvstore.push", _reduce,
                                detail=f"bucket[{len(bucket)} keys]")

    def _bucket_reduce(self, flats):
        """Reduce per-slot flat buffers to one (subclass hook): fused
        stack-and-sum on the first slot's device — the flat-buffer twin
        of `_aggregate`, elementwise-identical to reducing each member
        in its own per-key call."""
        if len(flats) == 1:
            return flats[0]
        import jax

        dev = next(iter(flats[0].devices()))
        arrs = [f if next(iter(f.devices())) == dev
                else jax.device_put(f, dev) for f in flats]
        return _fused_sum(arrs)

    def _bucket_scatter(self, bucket, reduced, vals_by_pos, outs_by_pos):
        """Unpack the reduced flat buffer back into the store entries and
        every out view — ONE jitted unpack dispatch per target device
        (replicated tpu_sync results scatter from each device's local
        shard; other devices get one whole-flat transfer, not one per
        key)."""
        import jax

        unpack = unpacker(bucket.shapes)
        shard_by_dev = {s.device: s.data
                        for s in getattr(reduced, "addressable_shards", [])} \
            if hasattr(reduced, "sharding") \
            and len(reduced.sharding.device_set) > 1 else {}
        pieces_by_dev: Dict = {}

        def pieces_for(dev):
            p = pieces_by_dev.get(dev)
            if p is None:
                f = shard_by_dev.get(dev)
                if f is None:
                    if shard_by_dev:
                        f = jax.device_put(
                            next(iter(shard_by_dev.values())), dev)
                    else:
                        f = reduced \
                            if next(iter(reduced.devices())) == dev \
                            else jax.device_put(reduced, dev)
                p = unpack(f)
                pieces_by_dev[dev] = p
            return p

        def _copy_out():
            if _fault_state.enabled:
                fault.check("kvstore.pull",
                            f"bucket[{len(bucket)} keys]")
            for j, pos in enumerate(bucket.indices):
                key = vals_by_pos[pos][0]
                dst = self._store[key]
                dst._set_data(pieces_for(dst.context.jax_device())[j])
                for o in outs_by_pos[pos]:
                    o._set_data(pieces_for(o.context.jax_device())[j])

        # idempotent overwrite — safe to retry whole, like pull()
        fault.retry_call("kvstore.pull", _copy_out,
                         detail=f"bucket[{len(bucket)} keys]")


class KVStoreTPUSync(KVStoreLocal):
    """Collective data-parallel sync over the device mesh.

    Reference roles replaced: ``kvstore_nccl.h::KVStoreNCCL`` (intra-node
    collectives) and ``kvstore_dist.h`` sync mode (multi-host). A push of
    per-device gradient copies lowers to ONE compiled XLA all-reduce
    (``shard_map`` + ``lax.psum`` over a device mesh). Single-process: the
    mesh is the devices holding the copies (psum rides ICI). Multi-process
    (``dist_sync`` after the ``jax.distributed`` bootstrap): the mesh is
    ALL processes' devices — each process contributes its local copies and
    the psum crosses hosts over DCN. The reduced value is a replicated
    ``jax.Array``, so ``pull`` into any participating device's context is
    a local view, not a transfer.
    """

    def __init__(self, type_name="tpu_sync"):
        super().__init__(type_name)
        if type_name in ("dist_sync", "dist_device_sync"):
            _maybe_init_distributed()
            # dist modes are SUPERVISED: ranks leave through a bounded
            # exit barrier (never wedging on a dead peer)
            _register_exit_barrier(self)
        self._mesh = None
        self._reducers: Dict = {}
        # topology-aware hierarchical collectives: number of (virtual)
        # hosts the mesh slots factor into, or None to resolve from
        # MXNET_KV_HOSTS ("auto" = one host per process). When a
        # topology is active the reduce mesh is 2-D ("dcn" x "ici") and
        # every bucket reduction is ONE collective over the factored
        # mesh — XLA's lowering runs the intra-host phase on ICI and
        # crosses DCN once per host pair, and the combined-axes psum is
        # bit-identical to the flat 1-D psum (tests/test_zero.py).
        self._hier_hosts: Optional[int] = None
        # cross-process barrier namespace: (store creation ordinal, per-
        # site sequence). The ordinal is SPMD-consistent (every process
        # creates its stores in the same program order), and keeps two
        # stores' barriers from aliasing each other's rendezvous keys.
        global _STORE_ORDINAL
        _STORE_ORDINAL += 1
        self._barrier_ns = _STORE_ORDINAL
        self._barrier_seq: Dict[str, int] = {}
        self._barrier_epoch = _BARRIER_EPOCH

    def _next_barrier_seq(self, site: str) -> Tuple[int, str]:
        """Allocate this barrier's (sequence, key namespace). Sequences
        count per site IN process memory, so they are re-based whenever
        the elastic membership epoch advanced (``reset_barrier_epoch``):
        every survivor clears its counters at the transition and a
        restarted rank's counters are fresh anyway, so all ranks meet at
        seq 1 under the epoch-tagged namespace instead of the survivors
        announcing seq k+1 against a rejoiner's seq 1 forever."""
        if self._barrier_epoch != _BARRIER_EPOCH:
            self._barrier_epoch = _BARRIER_EPOCH
            self._barrier_seq.clear()
        seq = self._barrier_seq.get(site, 0) + 1
        self._barrier_seq[site] = seq
        return seq, f"e{self._barrier_epoch}/s{self._barrier_ns}/"

    def barrier(self, site: str = "user", timeout: Optional[float] = None):
        """Local drain + cross-process rendezvous, both bounded. The
        rendezvous rides the coordination-service KV store (one
        announce + a poll loop — per-site sequence numbers keep repeated
        barriers distinct under the SPMD contract that every process
        calls them in the same order, re-based at each elastic epoch so
        restarted ranks re-converge), so a timeout can name exactly
        which ranks never arrived — the diagnostic a hung psum cannot
        give. Wrapped in ``fault.retry_call`` at ``kvstore.barrier``
        (announcements are idempotent)."""
        timeout = _barrier_timeout_s() if timeout is None \
            else float(timeout)
        t0 = time.monotonic()
        super().barrier(site, timeout)
        import jax

        if jax.process_count() <= 1:
            return
        client = _coord_client()
        if client is None:       # bootstrapped out-of-band (TPU pod rt)
            return
        # ONE budget for the whole barrier: the rendezvous gets what the
        # local drain left (floored so an instant drain cannot zero it),
        # not a fresh timeout — callers rely on the documented bound
        remaining = timeout if timeout <= 0 else \
            max(0.05, timeout - (time.monotonic() - t0))
        seq, key_ns = self._next_barrier_seq(site)
        fault.retry_call(
            "kvstore.barrier",
            lambda: _cross_process_barrier(
                client, site, seq, self.rank, self.num_workers,
                remaining, key_ns=key_ns),
            detail=f"site {site!r} seq {seq}")

    def attach_mesh(self, mesh):
        """Pin the reduction mesh (default: pushed copies' own devices in
        single-process mode, all global devices in multi-process mode)."""
        self._mesh = mesh

    def set_topology(self, hosts) -> None:
        """Declare the host topology for hierarchical collectives.

        ``hosts``: how many (virtual) hosts the mesh slots split into —
        the mesh becomes ``(hosts, slots_per_host)`` with axes
        ``("dcn", "ici")`` and every bucket reduce is one psum over the
        factored mesh. ``"auto"`` derives one host per process;
        ``None``/``0``/``1`` restores the flat 1-D mesh. Slots group
        contiguously in device-id order, matching how
        ``--xla_force_host_platform_device_count`` virtualizes hosts and
        how real pods enumerate chips per host."""
        if hosts in (None, 0, 1):
            self._hier_hosts = 0          # explicit flat (skip the env)
        elif hosts == "auto":
            import jax

            self._hier_hosts = max(jax.process_count(), 1)
        else:
            h = int(hosts)
            if h < 1:
                raise MXNetError(f"set_topology: hosts must be >= 1 or "
                                 f"'auto', got {hosts!r}")
            self._hier_hosts = h
        self._reducers.clear()

    def _topology_hosts(self, nslots: int) -> int:
        """Resolved host count for an ``nslots``-slot mesh; 0 = flat.
        A topology that does not divide the slot count is rejected
        loudly — a silently-flat mesh would fake the DCN savings."""
        h = self._hier_hosts
        if h is None:
            raw = os.environ.get("MXNET_KV_HOSTS", "").strip()
            if not raw:
                return 0
            if raw == "auto":
                import jax

                h = max(jax.process_count(), 1)
            else:
                h = int(raw)
        if h in (0, 1) or nslots <= 1:
            return 0
        if nslots % h != 0:
            raise MXNetError(
                f"hierarchical topology: {h} hosts do not evenly divide "
                f"{nslots} mesh slots — fix MXNET_KV_HOSTS/set_topology "
                "or the per-key copy count")
        return h

    def _bucket_path_label(self, bucket) -> str:
        """``hierarchical`` when this bucket's reduce ran over a factored
        ("dcn" x "ici") mesh, else ``bucketed`` — mirrors the
        ``_needs_collective`` gate + ``_reduce_mesh`` factoring the
        exchange itself just used (the label is recorded after the
        reduce, so an invalid topology has already raised)."""
        import jax

        nslots = bucket.group[1]
        devsig = bucket.group[2]
        needs = (jax.process_count() > 1 or self._mesh is not None
                 or (nslots > 1 and len(set(devsig)) == nslots))
        if not needs:
            return "bucketed"
        if self._mesh is not None:
            total = int(self._mesh.devices.size)
        elif jax.process_count() > 1:
            total = nslots * jax.process_count()
        else:
            total = nslots
        return "hierarchical" if self._topology_hosts(total) \
            else "bucketed"

    @property
    def num_workers(self):
        import jax

        return jax.process_count()

    @property
    def rank(self):
        import jax

        return jax.process_index()

    # -- the collective ------------------------------------------------
    def _reduce_mesh(self, vals):
        """The mesh a push's psum runs over, and the devices expected to
        contribute one copy each from THIS process."""
        import jax
        import numpy as np
        from jax.sharding import Mesh

        if self._mesh is not None:
            mesh = self._mesh
            local = [d for d in mesh.devices.flat
                     if d.process_index == jax.process_index()]
            return mesh, local
        if jax.process_count() > 1:
            # one mesh slot per PUSHED COPY per process, not per device:
            # a single-context worker (one model replica per process, the
            # common deployment) pushes one copy even when the process
            # exposes several devices. The mesh depends ONLY on the copy
            # COUNT (slot i -> every process's i-th local device in id
            # order), never on which local devices this rank's copies
            # happen to sit on — per-rank placement must not produce
            # per-rank meshes (a disagreeing device set deadlocks the
            # collective). SPMD contract: every process pushes the same
            # number of copies per key; _collective_sum's device check
            # surfaces placement mismatches loudly.
            k = len(vals)
            by_proc = {}
            for d in jax.devices():       # same order on every process
                by_proc.setdefault(d.process_index, []).append(d)
            chosen = []
            for p in sorted(by_proc):
                proc_devs = sorted(by_proc[p], key=lambda d: d.id)
                chosen.extend(proc_devs[:k])
            local = [d for d in chosen
                     if d.process_index == jax.process_index()]
            return self._mesh_over(chosen), local
        devs = [next(iter(v.data.devices())) for v in vals]
        return self._mesh_over(devs), devs

    def _mesh_over(self, devs):
        """Mesh over an ordered flat device list: 1-D ``("kv",)`` by
        default; with a host topology, 2-D ``("dcn", "ici")`` — device
        order is preserved (row-major flattening of the 2-D mesh is the
        flat list), so the factored psum reduces the same operands."""
        import numpy as np
        from jax.sharding import Mesh

        hosts = self._topology_hosts(len(devs))
        if hosts:
            arr = np.array(devs).reshape(hosts, len(devs) // hosts)
            return Mesh(arr, ("dcn", "ici"))
        return Mesh(np.array(devs), ("kv",))

    def _reducer(self, mesh, ndev, shape, dtype):
        """jit(shard_map(psum)) per (mesh, ndev, shape, dtype) — compiled
        once, reused for every push of this signature (the reference
        pre-creates one NCCL reduction per key; here the executable is the
        bucket)."""
        # Mesh hashes by devices+axes, so equal meshes share the entry
        sig = (mesh, ndev, tuple(shape), str(dtype))
        fn = self._reducers.get(sig)
        if fn is None:
            import jax
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            # all mesh axes at once: on the 2-D hierarchical mesh this is
            # ONE collective whose lowering factors into intra-host (ici)
            # + inter-host (dcn) phases, and a combined-axes psum is
            # bit-identical to the flat 1-D psum (sequential two-stage
            # psums are NOT — measured ULP drift — which is why the
            # policy factors the mesh instead of chaining collectives)
            axes = tuple(mesh.axis_names)

            def allreduce(stacked):
                # each shard is one device's (1, *shape) copy; psum over
                # the mesh and drop the stack dim
                red = shard_map(
                    lambda x: jax.lax.psum(x[0], axes), mesh=mesh,
                    in_specs=P(axes), out_specs=P())
                return red(stacked)

            fn = jax.jit(allreduce)
            self._reducers[sig] = fn
        return fn

    def _collective_sum(self, vals: List[NDArray]):
        """All-reduce per-device copies: one XLA psum over the mesh.

        The collective is wrapped in the bounded retry
        (``fault.retry_call``, site ``kvstore.allreduce``): a psum is
        stateless, so re-dispatching after a transient collective
        failure is safe. Exhaustion raises MXNetError naming the site
        and attempt count."""

        def _reduce():
            if _fault_state.enabled:
                fault.check(
                    "kvstore.allreduce",
                    f"{tuple(vals[0].shape)} x {len(vals)} copies")
            return self._collective_sum_impl(vals)

        if not _telemetry_state.enabled:
            return fault.retry_call("kvstore.allreduce", _reduce)
        t0 = time.perf_counter()
        reduced = fault.retry_call("kvstore.allreduce", _reduce)
        # payload entering the psum: one copy per mesh slot — the reduced
        # array is replicated over the mesh (out_specs=P()), so its device
        # set IS the mesh; a failed collective records nothing
        telemetry.record_kv(
            "allreduce", _nd_bytes(vals[0]) * len(reduced.sharding.device_set),
            time.perf_counter() - t0)
        return reduced

    def _collective_sum_impl(self, vals: List[NDArray]):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, local_devs = self._reduce_mesh(vals)
        ndev = mesh.devices.size
        spec = P(tuple(mesh.axis_names))   # leading dim over ALL axes
        shape = tuple(vals[0].shape)
        by_dev = {next(iter(v.data.devices())): v for v in vals}
        if set(by_dev) != set(local_devs):
            if jax.process_count() > 1 and len(by_dev) == len(local_devs):
                # multi-process slot mesh (see _reduce_mesh): the mesh
                # slots are position-derived, so a copy pinned to a
                # different local device is relocated onto its slot
                # (deterministic: copies ordered by source device id)
                ordered = [by_dev[d] for d in
                           sorted(by_dev, key=lambda d: d.id)]
                by_dev = {ld: jax.device_put(v.data, ld)
                          for ld, v in zip(local_devs, ordered)}
                shards = [by_dev[d].reshape((1,) + shape)
                          for d in local_devs]
                stacked = jax.make_array_from_single_device_arrays(
                    (ndev,) + shape, NamedSharding(mesh, spec), shards)
                return self._reducer(mesh, ndev, shape,
                                     vals[0].dtype)(stacked)
            raise MXNetError(
                f"tpu_sync push expects one gradient copy per local mesh "
                f"device ({len(local_devs)}); got copies on "
                f"{sorted(str(d) for d in by_dev)}")
        # stack the copies as a global array sharded over 'kv' — each
        # device contributes its local shard in place (across processes,
        # make_array assembles the global view from addressable shards)
        shards = [by_dev[d].data.reshape((1,) + shape) for d in local_devs]
        stacked = jax.make_array_from_single_device_arrays(
            (ndev,) + shape, NamedSharding(mesh, spec), shards)
        return self._reducer(mesh, ndev, shape, vals[0].dtype)(stacked)

    def _needs_collective(self, arrs) -> bool:
        """Whether these per-copy jax arrays must reduce via the mesh
        collective. ONE gate shared by the per-key (`_aggregate`) and
        bucketed (`_bucket_reduce`) paths — if they disagreed, the two
        paths could pick different reduction mechanisms in the same
        configuration and the bucketed-equals-per-key bit-identity
        guarantee would silently break."""
        import jax

        return (jax.process_count() > 1 or self._mesh is not None
                or (len(arrs) > 1
                    and len({next(iter(a.devices())) for a in arrs})
                    == len(arrs)))

    def _aggregate(self, vals: List[NDArray]) -> NDArray:
        if self._needs_collective([v.data for v in vals]):
            return NDArray(data=self._collective_sum(vals),
                           ctx=vals[0].context)
        return super()._aggregate(vals)

    def _bucket_reduce(self, flats):
        """ONE compiled psum over the mesh per bucket. The reducer cache
        keys by the flat shape, so every same-layout step replays one
        executable per bucket — O(params·bytes / bucket_cap) collectives
        per step instead of O(params)."""
        if not self._needs_collective(flats):
            return super()._bucket_reduce(flats)
        wrapped = [NDArray(data=f) for f in flats]
        return self._collective_sum(wrapped)

    def _store_reduced(self, key, agg: NDArray):
        data = agg.data
        if hasattr(data, "sharding") and len(data.sharding.device_set) > 1:
            # keep the replicated multi-device array: pulls become local
            # per-device views
            self._store[key]._set_data(data)
        else:
            super()._store_reduced(key, agg)

    def pull(self, key, out, priority=0, ignore_sparse=True):
        import jax

        if isinstance(key, (list, tuple)):
            for k, o in zip(key, out):
                self.pull(k, o, priority)
            return
        _tel = _telemetry_state.enabled
        t0 = time.perf_counter() if _tel else 0.0
        key = self._canon(key)
        self._check_init(key)
        outs = out if isinstance(out, (list, tuple)) else [out]
        src = self._store[key]
        data = src.data
        # replicated jax.Array: per-device shards are local views of the
        # reduced value (works even when the array spans other processes'
        # devices, where a whole-array device_put would be illegal)
        shard_by_dev = {s.device: s.data
                        for s in getattr(data, "addressable_shards", [])} \
            if hasattr(data, "sharding") \
            and len(data.sharding.device_set) > 1 else {}

        def _copy_out():
            if _fault_state.enabled:
                fault.check("kvstore.pull", f"key {key!r}")
            for o in outs:
                dev = o.context.jax_device()
                if dev in shard_by_dev:
                    o._set_data(shard_by_dev[dev])
                else:
                    o._set_data(src.as_in_context(o.context).data
                                if o.context != src.context else data)

        fault.retry_call("kvstore.pull", _copy_out, detail=f"key {key!r}")
        if _tel:
            telemetry.record_kv("pull", _nd_bytes(src) * len(outs),
                                time.perf_counter() - t0)


class KVStoreDistAsyncEmu(KVStoreTPUSync):
    """Bounded-staleness emulation of the reference's ``dist_async`` mode
    (reference: kvstore_dist.h server mode over ps-lite — workers push
    gradients, servers apply the optimizer immediately, no cross-worker
    barrier, unbounded staleness).

    TPU pods have no parameter server, and XLA collectives are
    synchronous by construction — true unbounded-async cannot exist
    in this execution model. The emulation keeps the convergence-relevant
    property (each worker trains on locally-stale weights, applying its
    own updates without waiting for peers) with a BOUND instead: the
    server-side optimizer runs on the process-local replica at every
    push, and every ``MXNET_KVSTORE_ASYNC_STALENESS`` pushes per key
    (default 4) the replicas are averaged with one psum across processes.
    ``staleness=1`` degenerates to per-step synchronous weight averaging.

    Opt-in via ``MXNET_KVSTORE_DIST_ASYNC_EMU=1`` because the semantics
    are an approximation of the reference's, not a match — ADR-002
    records the decision (SURVEY.md §5.8 "deprecated with emulation
    shim").

    **Lockstep push-count contract.** The replica sync triggers every
    ``staleness`` pushes per key, counted process-locally, and runs a
    collective — so every process must push every key the SAME number
    of times (the natural shape: identical training loops over equal
    step counts). Uneven per-key push counts would leave the fast
    processes inside a psum the slow ones never join; the sync
    therefore runs a bounded rendezvous first
    (``MXNET_KV_BARRIER_TIMEOUT``, default 300 s) and raises
    :class:`BarrierTimeoutError` naming the key and the missing ranks
    instead of deadlocking. ADR-002 records the contract.
    """

    def __init__(self, type_name="dist_async"):
        import os

        super().__init__(type_name)
        _maybe_init_distributed()
        self._staleness = max(1, int(os.environ.get(
            "MXNET_KVSTORE_ASYNC_STALENESS", "4")))
        self._push_count: Dict = {}

    @property
    def staleness(self) -> int:
        return self._staleness

    def push(self, key, value, priority=0):
        if isinstance(key, (list, tuple)):
            for k, v in zip(key, value):
                self.push(k, v, priority)
            return
        _tel = _telemetry_state.enabled
        t0 = time.perf_counter() if _tel else 0.0
        key = self._canon(key)
        self._check_init(key)
        if self._updater is None:
            raise MXNetError(
                "dist_async requires the server-side optimizer "
                "(set_optimizer / Trainer with update_on_kvstore=True), "
                "matching the reference's async server mode")
        vals = list(value) if isinstance(value, (list, tuple)) else [value]
        if self._compression is not None:
            vals = [self._compression.compress(key, i, v)
                    for i, v in enumerate(vals)]
        # LOCAL aggregation only — the async property: no cross-process
        # barrier on the push path

        def _reduce():
            if _fault_state.enabled:
                fault.check("kvstore.push", f"key {key!r}")
            return KVStoreLocal._aggregate(self, vals)

        agg = fault.retry_call("kvstore.push", _reduce,
                               detail=f"key {key!r}")
        self._updater(key, agg, self._store[key])
        n = self._push_count[key] = self._push_count.get(key, 0) + 1
        if n % self._staleness == 0:
            self._sync_replicas(key)
        if _tel:
            telemetry.record_kv("push", _payload_bytes(vals),
                                time.perf_counter() - t0)
            telemetry.record_kv_collective("per_key")

    def _pushpull_batched(self, keys, values, outs, priorities):
        # Server-side optimizer semantics: the updater (and the
        # bounded-staleness replica sync) applies per KEY, so the
        # batched form decomposes here; the per-push local slot
        # aggregation is already one fused stack-and-sum dispatch.
        return KVStore._pushpull_batched(self, keys, values, outs,
                                         priorities)

    def _sync_replicas(self, key):
        """Average the process-local replicas: one psum over all
        processes' devices (each local device contributes replica /
        n_local, so every process has unit weight regardless of its
        device count), then divide by the process count.

        LOCKSTEP CONTRACT (see the class docstring and ADR-002): the
        sync fires every ``staleness`` pushes *per key*, counted
        process-locally — so every process must push each key the same
        number of times. Uneven per-key push counts leave some
        processes inside this collective and others never arriving,
        which would wedge the psum forever; a bounded rendezvous runs
        first (``MXNET_KV_BARRIER_TIMEOUT``) and raises
        :class:`BarrierTimeoutError` NAMING the key and the missing
        ranks instead."""
        import jax

        if jax.process_count() == 1:
            return
        client = _coord_client()
        if client is not None:
            # pre-collective rendezvous, bounded: the psum itself can
            # give no diagnostic when a peer never joins
            timeout = _barrier_timeout_s()
            # ONE site string for both the sequence counter and the
            # rendezvous keys: allocating under one name but announcing
            # under another would let an identically-named user barrier
            # (independent counter) alias this rendezvous's KV prefix
            # and release ranks that never actually met
            site = f"async_sync/{key}"
            seq, key_ns = self._next_barrier_seq(site)
            try:
                # tight poll: this runs per key every `staleness` pushes
                # on a throughput path — the default 50 ms tick would
                # quantize every sync by up to a tick per rank
                _cross_process_barrier(
                    client, site, seq, self.rank,
                    self.num_workers, timeout, poll_interval=0.003,
                    key_ns=key_ns)
            except BarrierTimeoutError as e:
                raise BarrierTimeoutError(
                    f"dist_async replica sync for key {key!r} (sync "
                    f"#{seq}) timed out: not every process reached "
                    f"push-count multiple {self._staleness} for this "
                    "key — dist_async requires LOCKSTEP per-key push "
                    "counts across processes (see ADR-002); underlying: "
                    f"{e}") from e
        src = self._store[key]
        local = jax.local_devices()
        scaled = src.data / float(len(local))
        copies = [NDArray(data=jax.device_put(scaled, d), ctx=src.context)
                  for d in local]
        total = self._collective_sum(copies)
        # materialize the mean as a process-LOCAL array on the replica's
        # own device: async pulls are local by contract, and the next
        # push's updater keeps applying to a single-device replica
        mean = total.addressable_data(0) / float(jax.process_count())
        dev = next(iter(src.data.devices()))
        src._set_data(jax.device_put(mean, dev))


def _maybe_init_distributed():
    """Bootstrap ``jax.distributed`` for multi-host dist_sync.

    Env contract (SURVEY.md §5.6.4): the reference launcher exports
    ``DMLC_PS_ROOT_URI``/``DMLC_PS_ROOT_PORT``/``DMLC_NUM_WORKER``/
    ``DMLC_WORKER_ID``; the TPU-native launcher (tools/launch.py) exports
    the same names, mapped here onto the JAX coordination service. When
    DMLC_* vars are set they win (they are passed explicitly, overriding
    JAX's own env); a job already initialized by the user or a TPU-pod
    runtime is left untouched.
    """
    import os

    uri = os.environ.get("DMLC_PS_ROOT_URI")
    n = int(os.environ.get("DMLC_NUM_WORKER", "1"))
    if not uri or n <= 1:
        return
    if dist_initialized():
        return  # coordination service already up (launcher or user)
    port = os.environ.get("DMLC_PS_ROOT_PORT", "9091")
    rank = int(os.environ.get("DMLC_WORKER_ID", "0"))
    # the rendezvous is BOUNDED: a worker that never comes up must
    # surface as a typed error naming the site, not an eternal hang
    # (MXNET_KV_BOOTSTRAP_TIMEOUT, falling back to the barrier knob)
    timeout_s = _bootstrap_timeout_s()
    import jax

    try:
        jax.distributed.initialize(
            coordinator_address=f"{uri}:{port}",
            num_processes=n, process_id=rank,
            initialization_timeout=timeout_s)
    except Exception as e:
        raise MXNetError(
            f"kvstore.bootstrap: jax.distributed rendezvous at "
            f"{uri}:{port} failed for rank {rank}/{n} within "
            f"{timeout_s}s: {e} — check that all {n} workers launched "
            "(tools/launch.py supervises and restarts them) and that "
            "the coordinator address/port is reachable") from e
