"""ZeRO-sharded optimizer state over the bucketed collective seam.

The replicated data-parallel step keeps a full copy of every optimizer
state tensor on every rank, so the largest trainable model is capped by
one chip's HBM (ROADMAP item 2; Rajbhandari et al., "ZeRO: Memory
Optimizations Toward Training Trillion Parameter Models", SC'20). This
module shards that state across data-parallel ranks on the EXISTING
seams — the kvstore bucket planner (``plan_buckets(partition=...)``)
and the multi-tensor fused sweep's elementwise formulas — instead of
introducing a new trainer:

* **zero1** — optimizer state is sharded; the fused allreduce becomes
  ``lax.psum_scatter`` (each rank reduces only its contiguous shard of
  the flat bucket), the sweep updates the local shard, and
  ``lax.all_gather`` broadcasts the updated weights back. The fully
  reduced gradient is also gathered and written back into ``p.grad()``
  so post-step gradient inspection matches the replicated path.
* **zero2** — same, but the gathered gradient write-back is skipped:
  each rank keeps only its reduced shard (gradients outside the local
  shard are never materialized reduced).

Bit-identity contract: XLA's ``psum_scatter`` + ``all_gather`` produce
the same bits as the fused ``psum`` (same reduction tree — asserted
empirically by ``tests/test_zero.py``), the shard carve is pure indexing, and the shard update runs
the *same* elementwise formulas (``_sgd_elem`` / ``_adam_elem`` /
``_adamw_elem``) the replicated fused sweep runs — elementwise math on
a contiguous slice is bit-equal to the same slice of the full-buffer
sweep. So zero1/zero2 training trajectories are bit-identical to the
replicated baseline.

Hierarchical composition: the collective axes come from the kvstore's
``_mesh_over`` factorization — under ``set_topology(hosts)`` /
``MXNET_KV_HOSTS`` the same ``psum_scatter``/``all_gather`` run as
multi-axis collectives over the ("dcn", "ici") mesh, and multi-axis
reduce keeps the combined-psum bit pattern (shard order follows the
linearized mesh index).

Two execution modes:

* **mesh mode** — more than one in-process gradient copy (multi-context
  trainer on a collective ``tpu_sync`` store): world = number of
  copies, real reduce-scatter over the device mesh.
* **virtual mode** — single context with an explicit (rank, world)
  identity (``reconfigure``, ``MXNET_ZERO_RANK``/``MXNET_ZERO_WORLD``):
  the update itself is local full-buffer (elementwise ⇒ bit-equal to
  shard-wise), but *serialization* is sharded — ``export_state`` emits
  only the owned shard, so checkpoint bundles carry per-rank shard
  files and rejoin must gather + re-shard. This is the mode
  ``ElasticRunner`` exercises, and ``import_state`` re-shards a payload
  saved at world N into a trainer running at world M (member-level
  remap through the flat-bucket layout).
"""
from __future__ import annotations

import os
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as _np

from .. import telemetry
from ..base import MXNetError
from ..kvstore.bucketing import (PARTITION_MODES, ShardPlan,
                                 bucket_cap_bytes, plan_buckets)
from . import multi_tensor as mt

__all__ = ["PartitionMismatchError", "ZeroEngine", "supported_family",
           "FALLBACK_FAMILY", "FALLBACK_MULTI_PRECISION", "FALLBACK_SPARSE"]

STATE_VERSION = 1

# fallback-counter reasons (mxnet_kvstore_bucket_fallback_total{reason})
FALLBACK_FAMILY = "zero_family"
FALLBACK_MULTI_PRECISION = "zero_multi_precision"
FALLBACK_SPARSE = "zero_sparse"

_ELEM_FNS = {"sgd": mt._sgd_elem, "adam": mt._adam_elem,
             "adamw": mt._adamw_elem}


class PartitionMismatchError(MXNetError):
    """Sharded optimizer state loaded at an incompatible partition plan
    (wrong mode/world/bucket layout, or sharded↔replicated mismatch).
    The message names both plans; use ``Trainer.load_states_resharded``
    / elastic rejoin to re-shard across world sizes on purpose."""


def supported_family(optimizer) -> Optional[str]:
    """The fused-sweep family name if this optimizer's update can run
    sharded, else None. LAMB is excluded: its trust-ratio norms are
    cross-member reductions over the whole bucket, which a shard-local
    sweep cannot reproduce bit-identically."""
    family = mt.family_of(optimizer)
    if family in ("sgd", "adam", "adamw"):
        return family
    return None


def _plan_digest(plan_table, mode, world) -> str:
    nparams = sum(len(b["members"]) for b in plan_table)
    return f"{mode}@world={world}:{len(plan_table)}buckets/{nparams}params"


def _sizes_offsets(shapes):
    sizes = []
    for s in shapes:
        n = 1
        for d in s:
            n *= int(d)
        sizes.append(n)
    offsets = [0]
    for n in sizes:
        offsets.append(offsets[-1] + n)
    return sizes, offsets


class _BucketState:
    """One planned ZeRO bucket: layout + persistent sharded state."""

    __slots__ = ("indices", "shapes", "sizes", "offsets", "wdtype",
                 "gdtype", "plan", "nbytes", "states", "fn", "unstitch")

    def __init__(self, indices, shapes, wdtype, gdtype, plan, nbytes):
        self.indices: List[int] = list(indices)
        self.shapes: List[Tuple[int, ...]] = [tuple(s) for s in shapes]
        self.sizes, self.offsets = _sizes_offsets(self.shapes)
        self.wdtype = wdtype
        self.gdtype = gdtype
        self.plan: ShardPlan = plan
        self.nbytes = int(nbytes)
        self.states: Dict[str, object] = {}      # role -> jax array
        self.fn = None                           # jitted sweep
        self.unstitch = None                     # jitted flat->members

    @property
    def total(self):
        return self.offsets[-1]


class ZeroEngine:
    """Shard-partitioned optimizer sweep bound to one Trainer.

    Owns the partitioned buckets' persistent state arrays, the jitted
    reduce-scatter/update/allgather dispatch, and the sharded
    serialization (:meth:`export_state` / :meth:`import_state`).
    """

    def __init__(self, trainer, mode: str, rank: Optional[int] = None,
                 world: Optional[int] = None):
        if mode not in PARTITION_MODES:
            raise MXNetError(
                f"unknown partition mode {mode!r}; expected one of "
                f"{PARTITION_MODES}")
        self._trainer = trainer
        self._mode = mode
        self._family = supported_family(trainer._optimizer)
        if self._family is None:
            raise MXNetError(
                f"partition={mode!r} requires a fused-sweep optimizer "
                f"family (sgd/adam/adamw); got "
                f"{type(trainer._optimizer).__name__}")
        self._explicit_rank = rank
        self._explicit_world = world
        self._ready = False
        self._mesh_mode = False
        self._rank = 0
        self._world = 1
        self._mesh = None
        self._devs: Tuple = ()
        self._buckets: List[_BucketState] = []
        self._fallback: Dict[int, str] = {}      # param idx -> reason
        self._virtual_fns: Dict[Tuple, object] = {}

    # -- identity ----------------------------------------------------------

    @property
    def mode(self) -> str:
        return self._mode

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def world(self) -> int:
        return self._world

    @property
    def fallback_reasons(self) -> Dict[int, str]:
        """param index -> reason for params outside the sharded sweep."""
        self.ensure_ready()
        return dict(self._fallback)

    def eligible_indices(self) -> List[int]:
        self.ensure_ready()
        out: List[int] = []
        for b in self._buckets:
            out.extend(b.indices)
        return sorted(out)

    # -- planning ----------------------------------------------------------

    def _resolve_identity(self):
        """Pick mesh vs virtual mode and the (rank, world) identity."""
        import jax

        trainer = self._trainer
        ncopies = len(trainer._contexts)
        if jax.process_count() > 1:
            raise MXNetError(
                "multi-process ZeRO partitioning is not supported yet; "
                "run one context per process and re-shard through the "
                "elastic virtual mode")
        if ncopies > 1:
            store = trainer._kvstore
            if store is None or not hasattr(store, "_mesh_over"):
                raise MXNetError(
                    f"partition={self._mode!r} with {ncopies} contexts "
                    "requires a collective kvstore (tpu_sync); got "
                    f"{type(store).__name__ if store else None}")
            if self._explicit_world not in (None, ncopies):
                raise MXNetError(
                    f"explicit partition world {self._explicit_world} "
                    f"conflicts with {ncopies} gradient copies (mesh "
                    "mode shards across the copies)")
            self._mesh_mode = True
            self._world = ncopies
            self._rank = 0           # all shards are process-local
            return
        # virtual: explicit args > env > single-rank default
        world = self._explicit_world
        rank = self._explicit_rank
        if world is None:
            world = int(os.environ.get("MXNET_ZERO_WORLD", "1") or 1)
        if rank is None:
            rank = int(os.environ.get("MXNET_ZERO_RANK", "0") or 0)
        world = int(world)
        rank = int(rank)
        if world < 1 or not (0 <= rank < world):
            raise MXNetError(
                f"invalid partition identity rank={rank} world={world}")
        self._mesh_mode = False
        self._world = world
        self._rank = rank

    def _classify(self):
        """Split trainer params into sharded-sweep members and fallback
        (reason-tagged) leftovers. Mirrors the fused-sweep eligibility
        gates in ``multi_tensor.plan_eager``."""
        trainer = self._trainer
        opt = trainer._optimizer
        eligible: List[int] = []
        fallback: Dict[int, str] = {}
        for i, p in enumerate(trainer._params):
            if p.grad_req == "null":
                continue
            stype = getattr(p, "_stype", "default")
            gstype = getattr(p, "grad_stype", "default")
            if stype != "default" or gstype != "default":
                fallback[i] = FALLBACK_SPARSE
                continue
            if getattr(opt, "multi_precision", False) and \
                    str(p.dtype) in ("float16", "bfloat16"):
                fallback[i] = FALLBACK_MULTI_PRECISION
                continue
            eligible.append(i)
        return eligible, fallback

    def ensure_ready(self) -> None:
        """Plan buckets, allocate sharded state, build dispatch fns.
        Idempotent; called lazily once params are initialized."""
        if self._ready:
            return
        import jax

        self._resolve_identity()
        trainer = self._trainer
        eligible, self._fallback = self._classify()
        if self._fallback:
            by_reason: Dict[str, int] = {}
            for reason in self._fallback.values():
                by_reason[reason] = by_reason.get(reason, 0) + 1
            for reason, n in sorted(by_reason.items()):
                telemetry.record_kv_bucket_fallback(reason, n)
            warnings.warn(
                f"{len(self._fallback)} parameter(s) fell outside the "
                f"ZeRO sharded sweep "
                f"({', '.join(f'{r}:{n}' for r, n in sorted(by_reason.items()))}) "
                "— they update replicated through the per-param path",
                stacklevel=3)

        params = trainer._params
        ctxs = trainer._contexts
        if eligible:
            dev_src = params[eligible[0]].list_data()
            self._devs = tuple(next(iter(a.data.devices()))
                               for a in dev_src)
        if self._mesh_mode:
            store = trainer._kvstore
            self._mesh = store._mesh_over(list(self._devs))

        store = trainer._kvstore
        cap = getattr(store, "_bucket_bytes", None) if store else None
        if not cap:
            cap = bucket_cap_bytes()
        entries = []
        for i in eligible:
            p = params[i]
            shape = tuple(int(d) for d in p.shape)
            wdt = _np.dtype(p.dtype)
            gdt = _np.dtype(p.list_grad()[0].dtype)
            n = 1
            for d in shape:
                n *= d
            entries.append((i, shape, str(wdt),
                            (str(wdt), str(gdt)), n * gdt.itemsize))
        raw = plan_buckets(entries, cap, partition=self._mode,
                           world=self._world)
        self._buckets = []
        for b in raw:
            wdt = _np.dtype(b.group[0])
            gdt = _np.dtype(b.group[1])
            self._buckets.append(_BucketState(
                b.indices, b.shapes, wdt, gdt, b.shard_plan, b.nbytes))
        for bs in self._buckets:
            self._init_states(bs)
        self._record_state_bytes()
        self._ready = True

    def _record_state_bytes(self) -> None:
        roles = self._roles()
        per_rank = 0
        replicated = 0
        for bs in self._buckets:
            isz = bs.wdtype.itemsize
            per_rank += len(roles) * bs.plan.shard_len * isz
            replicated += len(roles) * bs.total * isz
        telemetry.record_optimizer_state_bytes(self._mode, per_rank)
        telemetry.record_optimizer_state_bytes("replicated", replicated)
        self._state_bytes = (per_rank, replicated)

    def _roles(self) -> Tuple[str, ...]:
        static = dict(mt.family_static(self._trainer._optimizer,
                                       self._family))
        return mt.state_roles(self._family, static)

    def _static_items(self) -> tuple:
        return mt.family_static(self._trainer._optimizer, self._family)

    def _init_states(self, bs: _BucketState) -> None:
        import jax

        roles = self._roles()
        if not roles:
            return
        if self._mesh_mode:
            from jax.sharding import NamedSharding, PartitionSpec as P

            axes = tuple(self._mesh.axis_names)
            sharding = NamedSharding(self._mesh, P(axes))
            zero = _np.zeros(bs.plan.shard_len, bs.wdtype)
            for role in roles:
                shards = [jax.device_put(zero, d)
                          for d in self._mesh.devices.flat]
                bs.states[role] = \
                    jax.make_array_from_single_device_arrays(
                        (bs.plan.padded,), sharding, shards)
        else:
            dev = self._devs[0] if self._devs else None
            zero = _np.zeros(bs.plan.padded, bs.wdtype)
            for role in roles:
                bs.states[role] = jax.device_put(zero, dev) \
                    if dev is not None else jax.numpy.asarray(zero)

    # -- jitted dispatch ---------------------------------------------------

    def _unstitch_fn(self, bs: _BucketState):
        """Jitted padded-flat -> per-member arrays (pad dropped)."""
        if bs.unstitch is None:
            import jax

            segs = list(zip(bs.shapes, bs.offsets[:-1], bs.offsets[1:]))

            def unstitch(flat):
                return tuple(
                    flat[o:o2].reshape(shape if shape else ())
                    for shape, o, o2 in segs)

            bs.unstitch = jax.jit(unstitch)
        return bs.unstitch

    def _build_mesh_fn(self, bs: _BucketState, vec_names):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh
        axes = tuple(mesh.axis_names)
        ax_sizes = [mesh.shape[a] for a in axes]
        family = self._family
        static = dict(self._static_items())
        roles = self._roles()
        elem = _ELEM_FNS[family]
        shard_len = bs.plan.shard_len
        padded = bs.plan.padded
        total = bs.total
        wdt = bs.wdtype
        sizes = _np.asarray(bs.sizes, _np.int64)
        segs = list(zip(bs.offsets[:-1], bs.offsets[1:]))
        gather_grads = (self._mode == "zero1")
        nr, nv = len(roles), len(vec_names)

        def body(gstk, wstk, *ops):
            states = ops[:nr]
            vecs = ops[nr:nr + nv]
            rescale = jnp.asarray(ops[-1], jnp.float32)
            # reduce-scatter: each rank sums only its shard (tiled
            # multi-axis psum_scatter keeps the combined-psum bits —
            # the load-bearing bit-identity fact, see module docstring)
            g_shard = jax.lax.psum_scatter(
                gstk[0], axes, scatter_dimension=0, tiled=True)
            idx = 0
            for a, s in zip(axes, ax_sizes):
                idx = idx * s + jax.lax.axis_index(a)
            off = idx * shard_len
            w_shard = jax.lax.dynamic_slice(wstk[0], (off,), (shard_len,))
            env = {"w": w_shard, "g": g_shard, "rescale": rescale}
            for role, s in zip(roles, states):
                env[role] = s
            for name, v in zip(vec_names, vecs):
                env[name] = v
            g_full = None
            if family == "adamw" or gather_grads:
                # all_gather of the scattered shards == the fused psum
                # bits (verified), so the gathered grad is exactly the
                # replicated reduced gradient
                g_full = jax.lax.all_gather(
                    g_shard, axes, axis=0, tiled=True)
            if family == "adamw":
                # per-member AMP overflow scan needs the FULL reduced
                # grad (isfinite is a cross-shard member reduction)
                g32 = g_full.astype(jnp.float32) * rescale
                clip = static["clip_gradient"]
                if clip is not None and clip >= 0:
                    g32 = jnp.clip(g32, -clip, clip)
                oks = [jnp.isfinite(g32[o:o2]).all() for o, o2 in segs]
                ok_el = jnp.repeat(jnp.stack(oks).astype(jnp.float32),
                                   sizes, total_repeat_length=total)
                if padded > total:
                    ok_el = jnp.concatenate(
                        [ok_el, jnp.zeros(padded - total, jnp.float32)])
                env["ok"] = jax.lax.dynamic_slice(
                    ok_el, (off,), (shard_len,))
            new = elem(env, static)
            new_w = new["w"].astype(wdt)
            w_full = jax.lax.all_gather(new_w, axes, axis=0, tiled=True)
            outs = [w_full] + [new[r].astype(wdt) for r in roles]
            if gather_grads:
                outs.append(g_full)
            return tuple(outs)

        in_specs = (P(axes), P(axes)) + (P(axes),) * (nr + nv) + (P(),)
        out_specs = (P(),) + (P(axes),) * nr
        if gather_grads:
            out_specs = out_specs + (P(),)
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))

    def _build_virtual_fn(self, bs: _BucketState, vec_names):
        import jax
        import jax.numpy as jnp

        family = self._family
        static = dict(self._static_items())
        roles = self._roles()
        elem = _ELEM_FNS[family]
        padded = bs.plan.padded
        total = bs.total
        wdt = bs.wdtype
        sizes = _np.asarray(bs.sizes, _np.int64)
        segs = list(zip(bs.offsets[:-1], bs.offsets[1:]))
        nr, nv = len(roles), len(vec_names)

        def body(g, w, *ops):
            states = ops[:nr]
            vecs = ops[nr:nr + nv]
            rescale = jnp.asarray(ops[-1], jnp.float32)
            env = {"w": w, "g": g, "rescale": rescale}
            for role, s in zip(roles, states):
                env[role] = s
            for name, v in zip(vec_names, vecs):
                env[name] = v
            if family == "adamw":
                g32 = g.astype(jnp.float32) * rescale
                clip = static["clip_gradient"]
                if clip is not None and clip >= 0:
                    g32 = jnp.clip(g32, -clip, clip)
                oks = [jnp.isfinite(g32[o:o2]).all() for o, o2 in segs]
                ok_el = jnp.repeat(jnp.stack(oks).astype(jnp.float32),
                                   sizes, total_repeat_length=total)
                if padded > total:
                    ok_el = jnp.concatenate(
                        [ok_el, jnp.zeros(padded - total, jnp.float32)])
                env["ok"] = ok_el
            new = elem(env, static)
            outs = [new["w"].astype(wdt)] + \
                [new[r].astype(wdt) for r in roles]
            return tuple(outs)

        return jax.jit(body)

    def _pad_fn(self, total, padded, dtype):
        import jax
        import jax.numpy as jnp

        key = ("pad", total, padded, str(dtype))
        fn = self._virtual_fns.get(key)
        if fn is None:
            if padded > total:
                fn = jax.jit(lambda x: jnp.concatenate(
                    [x, jnp.zeros(padded - total, x.dtype)]))
            else:
                fn = jax.jit(lambda x: x)
            self._virtual_fns[key] = fn
        return fn

    # -- the step ----------------------------------------------------------

    def step(self) -> None:
        """Run the sharded sweep over every partitioned bucket. Advances
        the optimizer's per-index update clock exactly once per step
        (the engine replaces BOTH the allreduce and the per-context
        update loop for its members)."""
        self.ensure_ready()
        opt = self._trainer._optimizer
        params = self._trainer._params
        # clock first, then scalar collection — mirrors apply_eager_plan.
        # Tick EVERY device stream (leftover per-param members tick
        # theirs in the trainer loop): streams stay pairwise equal, so
        # a later state dump reads the same clock from any of them.
        nstreams = max(1, len(self._trainer._updaters or ()))
        for ci in range(nstreams):
            opt._set_current_context(ci)
            for bs in self._buckets:
                for i in bs.indices:
                    opt._update_count(i)
        opt._set_current_context(0)
        for bs in self._buckets:
            vecs = mt.collect_scalars(opt, self._family, bs.indices)
            vec_names = sorted(vecs)
            if self._mesh_mode:
                self._step_mesh(bs, vecs, vec_names, params)
            else:
                self._step_virtual(bs, vecs, vec_names, params)
            telemetry.record_optimizer_dispatch("zero_sweep", 1)
            telemetry.record_optimizer_bucket(bs.nbytes, len(bs.indices))

    def _vec_el(self, bs: _BucketState, vecs, vec_names):
        out = []
        for name in vec_names:
            v = _np.repeat(_np.asarray(vecs[name], _np.float32),
                           bs.sizes)
            if bs.plan.padded > bs.total:
                v = _np.concatenate(
                    [v, _np.zeros(bs.plan.padded - bs.total,
                                  _np.float32)])
            out.append(v)
        return out

    def _step_mesh(self, bs, vecs, vec_names, params) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..kvstore.bucketing import pack

        mesh = self._mesh
        axes = tuple(mesh.axis_names)
        devs = list(mesh.devices.flat)
        pad = self._pad_fn(bs.total, bs.plan.padded, bs.gdtype)
        padw = self._pad_fn(bs.total, bs.plan.padded, bs.wdtype)
        gslots = []
        wslots = []
        for ci in range(len(devs)):
            garrs = [params[i].list_grad()[ci].data for i in bs.indices]
            warrs = [params[i].list_data()[ci].data for i in bs.indices]
            gslots.append(pad(pack(garrs)).reshape(1, bs.plan.padded))
            wslots.append(padw(pack(warrs)).reshape(1, bs.plan.padded))
        sharding = NamedSharding(mesh, P(axes))
        gstk = jax.make_array_from_single_device_arrays(
            (len(devs), bs.plan.padded), sharding, gslots)
        wstk = jax.make_array_from_single_device_arrays(
            (len(devs), bs.plan.padded), sharding, wslots)
        if bs.fn is None:
            bs.fn = self._build_mesh_fn(bs, vec_names)
        roles = self._roles()
        args = [gstk, wstk] + [bs.states[r] for r in roles] + \
            self._vec_el(bs, vecs, vec_names) + \
            [_np.float32(self._trainer._optimizer.rescale_grad)]
        outs = bs.fn(*args)
        w_full = outs[0]
        for k, role in enumerate(roles):
            bs.states[role] = outs[1 + k]
        telemetry.record_kv_collective("zero")
        unstitch = self._unstitch_fn(bs)
        self._scatter(bs, w_full, devs,
                      lambda i, ci: params[i].list_data()[ci], unstitch)
        if self._mode == "zero1":
            g_full = outs[-1]
            self._scatter(bs, g_full, devs,
                          lambda i, ci: params[i].list_grad()[ci],
                          unstitch)

    def _scatter(self, bs, arr, devs, nd_of, unstitch) -> None:
        """Write a replicated (padded,) result back into the per-context
        NDArrays — per-device shard data in, so outputs stay committed
        to the right device."""
        by_dev = {s.device: s.data for s in arr.addressable_shards}
        for ci, d in enumerate(devs):
            pieces = unstitch(by_dev[d])
            for i, piece in zip(bs.indices, pieces):
                nd_of(i, ci)._set_data(piece)

    def _step_virtual(self, bs, vecs, vec_names, params) -> None:
        from ..kvstore.bucketing import pack

        pad = self._pad_fn(bs.total, bs.plan.padded, bs.gdtype)
        padw = self._pad_fn(bs.total, bs.plan.padded, bs.wdtype)
        g = pad(pack([params[i].list_grad()[0].data
                      for i in bs.indices]))
        w = padw(pack([params[i].list_data()[0].data
                       for i in bs.indices]))
        if bs.fn is None:
            bs.fn = self._build_virtual_fn(bs, vec_names)
        roles = self._roles()
        args = [g, w] + [bs.states[r] for r in roles] + \
            self._vec_el(bs, vecs, vec_names) + \
            [_np.float32(self._trainer._optimizer.rescale_grad)]
        outs = bs.fn(*args)
        for k, role in enumerate(roles):
            bs.states[role] = outs[1 + k]
        pieces = self._unstitch_fn(bs)(outs[0])
        for i, piece in zip(bs.indices, pieces):
            params[i].list_data()[0]._set_data(piece)

    # -- elastic re-identity ----------------------------------------------

    def reconfigure(self, rank: int, world: int) -> None:
        """Adopt a new (rank, world) identity — virtual mode only (the
        state is full locally; only the serialization carve changes).
        Used by elastic rejoin when membership changes."""
        self.ensure_ready()
        rank, world = int(rank), int(world)
        if self._mesh_mode:
            if world != self._world:
                raise MXNetError(
                    f"cannot reconfigure a mesh-mode partition (world "
                    f"{self._world}) to world {world}")
            return
        if world < 1 or not (0 <= rank < world):
            raise MXNetError(
                f"invalid partition identity rank={rank} world={world}")
        if world == self._world and rank == self._rank:
            return
        self._rank, self._world = rank, world
        from ..kvstore.bucketing import shard_layout

        for bs in self._buckets:
            old = bs.plan
            bs.plan = shard_layout(self._mode, bs.total, world)
            if bs.plan.padded != old.padded:
                # padded length changed: re-pad the full state buffers
                # (tail is zeros — inert) and drop layout-bound jits
                import jax
                import numpy as np

                for role in list(bs.states):
                    full = np.asarray(bs.states[role])[:bs.total]
                    buf = np.zeros(bs.plan.padded, bs.wdtype)
                    buf[:bs.total] = full
                    dev = self._devs[0] if self._devs else None
                    bs.states[role] = jax.device_put(buf, dev) \
                        if dev is not None else jax.numpy.asarray(buf)
                bs.fn = None
        self._record_state_bytes()

    # -- serialization -----------------------------------------------------

    def describe(self) -> str:
        self.ensure_ready()
        return _plan_digest(self._plan_table(), self._mode, self._world)

    def _plan_table(self):
        table = []
        for bs in self._buckets:
            table.append({
                "members": list(bs.indices),
                "shapes": [list(s) for s in bs.shapes],
                "wdtype": str(bs.wdtype),
                "total": bs.total,
                "padded": bs.plan.padded,
                "shard_len": bs.plan.shard_len,
            })
        return table

    def partition_manifest(self) -> dict:
        """Plan metadata (no tensors) for checkpoint manifests."""
        self.ensure_ready()
        return {
            "version": STATE_VERSION,
            "mode": self._mode,
            "world": self._world,
            "rank": self._rank,
            "family": self._family,
            "digest": self.describe(),
            "plan": self._plan_table(),
        }

    def _owned_ranks(self) -> List[int]:
        if self._mesh_mode or self._world == 1:
            return list(range(self._world))
        return [self._rank]

    def export_state(self, all_ranks: bool = False) -> dict:
        """Sharded state payload. Mesh mode owns every rank's shard
        (they are all process-local); virtual mode emits only the owned
        rank's shard unless ``all_ranks`` (possible because the virtual
        state buffer is full) — elastic bundles stay 1/world sized."""
        self.ensure_ready()
        roles = self._roles()
        owned = list(range(self._world)) if all_ranks \
            else self._owned_ranks()
        shards: Dict[int, Dict[int, Dict[str, object]]] = {}
        for bid, bs in enumerate(self._buckets):
            per_rank: Dict[int, Dict[str, object]] = {r: {}
                                                      for r in owned}
            for role in roles:
                arr = bs.states[role]
                if self._mesh_mode:
                    by_dev = {s.device: _np.asarray(s.data)
                              for s in arr.addressable_shards}
                    flat_devs = list(self._mesh.devices.flat)
                    for r in owned:
                        per_rank[r][role] = by_dev[flat_devs[r]]
                else:
                    full = _np.asarray(arr)
                    for r in owned:
                        lo, hi = bs.plan.shard_range(r)
                        per_rank[r][role] = full[lo:hi].copy()
            shards[bid] = per_rank
        opt = self._trainer._optimizer
        clock = {
            "num_update": int(opt.num_update),
            "index_update_count": {
                int(i): int(opt._index_update_count[i])
                for bs in self._buckets for i in bs.indices
                if i in opt._index_update_count},
        }
        return {
            "version": STATE_VERSION,
            "mode": self._mode,
            "world": self._world,
            "family": self._family,
            "roles": list(roles),
            "plan": self._plan_table(),
            "owned": owned,
            "clock": clock,
            "shards": shards,
        }

    def check_compatible(self, payload: dict) -> None:
        """Raise :class:`PartitionMismatchError` unless ``payload`` was
        exported at exactly this engine's partition plan (strict
        ``Trainer.load_states`` contract — re-sharding is the explicit
        ``import_state``/elastic path, never an accident)."""
        self.ensure_ready()
        src = _plan_digest(payload.get("plan", []),
                           payload.get("mode"), payload.get("world"))
        cur = self.describe()
        if payload.get("mode") != self._mode or \
                int(payload.get("world", -1)) != self._world or \
                payload.get("plan") != self._plan_table():
            raise PartitionMismatchError(
                f"sharded optimizer state was saved under partition "
                f"plan [{src}] but this trainer runs plan [{cur}]; "
                "use Trainer.load_states_resharded / elastic rejoin to "
                "re-shard across plans")

    def import_state(self, payloads: Sequence[dict]) -> None:
        """Merge per-rank payloads (possibly saved at a DIFFERENT world
        size or bucket layout) and re-shard into the current plan.

        Requires full coverage of the source world: every rank
        0..src_world-1 must appear in some payload, else a typed error
        names the missing ranks. The remap runs at *member* level
        (param index -> flat vector) so any world/bucket-layout change
        re-shards losslessly; trailing pad is rebuilt as zeros.
        """
        self.ensure_ready()
        if not payloads:
            raise MXNetError("import_state: no payloads given")
        head = payloads[0]
        roles = self._roles()
        if head.get("family") != self._family:
            raise PartitionMismatchError(
                f"sharded state family {head.get('family')!r} does not "
                f"match this trainer's optimizer family "
                f"{self._family!r}")
        src_plan = head.get("plan")
        src_world = int(head.get("world", 0))
        for p in payloads[1:]:
            if p.get("plan") != src_plan or \
                    int(p.get("world", 0)) != src_world:
                raise PartitionMismatchError(
                    "import_state payloads disagree on the source "
                    "partition plan — they must all come from the same "
                    "checkpoint step")
        # source member map must cover exactly the current members
        src_members: Dict[int, Tuple[Tuple[int, ...], str]] = {}
        for b in src_plan:
            for i, s in zip(b["members"], b["shapes"]):
                src_members[int(i)] = (tuple(int(d) for d in s),
                                       b["wdtype"])
        cur_members = {int(i): (tuple(s), str(bs.wdtype))
                       for bs in self._buckets
                       for i, s in zip(bs.indices, bs.shapes)}
        if src_members != cur_members:
            raise PartitionMismatchError(
                f"sharded state members do not match this trainer: "
                f"saved {len(src_members)} member(s), trainer has "
                f"{len(cur_members)} — shapes/dtypes/indices must agree "
                "(same model) to re-shard")
        # merge shard fragments across payloads
        merged: Dict[int, Dict[int, Dict[str, object]]] = {}
        for p in payloads:
            for bid, per_rank in p.get("shards", {}).items():
                dst = merged.setdefault(int(bid), {})
                for r, role_map in per_rank.items():
                    dst.setdefault(int(r), role_map)
        # stitch each source bucket back to full member vectors
        member_state: Dict[int, Dict[str, object]] = {}
        for bid, b in enumerate(src_plan):
            per_rank = merged.get(bid, {})
            missing = [r for r in range(src_world) if r not in per_rank]
            if missing:
                raise PartitionMismatchError(
                    f"cannot re-shard optimizer state: source world "
                    f"{src_world} but shard(s) for rank(s) {missing} "
                    f"of bucket {bid} are missing — gather every "
                    "rank's bundle before rejoin")
            sizes, offsets = _sizes_offsets(
                [tuple(s) for s in b["shapes"]])
            for role in roles:
                full = _np.concatenate(
                    [_np.asarray(per_rank[r][role])
                     for r in range(src_world)])[:b["total"]]
                for i, o, o2 in zip(b["members"], offsets[:-1],
                                    offsets[1:]):
                    member_state.setdefault(int(i), {})[role] = \
                        full[o:o2]
        # repack into the current plan
        import jax

        for bs in self._buckets:
            for role in roles:
                full = _np.zeros(bs.plan.padded, bs.wdtype)
                off = 0
                for i, n in zip(bs.indices, bs.sizes):
                    full[off:off + n] = \
                        member_state[i][role].astype(bs.wdtype)
                    off += n
                if self._mesh_mode:
                    from jax.sharding import (NamedSharding,
                                              PartitionSpec as P)

                    axes = tuple(self._mesh.axis_names)
                    sl = bs.plan.shard_len
                    shards = [jax.device_put(full[r * sl:(r + 1) * sl],
                                             d)
                              for r, d in enumerate(
                                  self._mesh.devices.flat)]
                    bs.states[role] = \
                        jax.make_array_from_single_device_arrays(
                            (bs.plan.padded,),
                            NamedSharding(self._mesh, P(axes)), shards)
                else:
                    dev = self._devs[0] if self._devs else None
                    bs.states[role] = jax.device_put(full, dev) \
                        if dev is not None else jax.numpy.asarray(full)
        clock = head.get("clock") or {}
        opt = self._trainer._optimizer
        if clock:
            opt.num_update = max(int(opt.num_update),
                                 int(clock.get("num_update", 0)))
            for i, c in (clock.get("index_update_count") or {}).items():
                # mirror into the baseline so device streams created
                # after this restore resume the same clock
                opt._index_update_count[int(i)] = int(c)
                opt._count_baseline[int(i)] = int(c)
