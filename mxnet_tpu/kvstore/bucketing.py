"""Gradient bucketing for the kvstore's fused ``pushpull``.

The reference KVStore (``dist_device_sync`` / ``nccl``) reduces every
gradient key as its own collective; a ResNet-50 step pays ~160 separate
dispatches and a transformer one per weight tensor. The proven fix
(PyTorch DDP's 25 MB gradient buckets, Li et al. VLDB'20; Horovod tensor
fusion) is to coalesce gradients into large flat buffers and run ONE
collective per bucket. This module holds the mechanics shared by every
store type:

* :func:`plan_buckets` — greedy, order-preserving partition of keys into
  dtype-segregated buckets capped at ``MXNET_KV_BUCKET_MB`` (default 25)
  payload bytes. Keys arrive already sorted by priority (descending), so
  bucket *dispatch order* is the priority order. A single tensor larger
  than the cap gets a bucket of its own — it is never split (the
  collective is one dispatch either way) and never silently dropped.
* :func:`pack` / :func:`unpacker` — jitted flatten-and-concatenate of a
  bucket's member gradients into one flat buffer and the inverse
  scatter. One XLA dispatch each; the unpacker executable is cached per
  bucket signature (member shapes), and ``jax.jit``'s own
  signature-keyed cache makes repeated steps replay compiled code.

Bit-identity contract: packing is pure reshape/concatenate and the
reduction over a flat bucket applies the same elementwise sum (same
operand order, same reduction arity) each member would see in its own
per-key collective — so the bucketed *uncompressed* exchange is
bit-identical to the per-key path, which
``tests/test_kvstore_bucketed.py`` asserts.

ZeRO partitioning (``partition="zero1"|"zero2"``) is a *layout* the
planner can attach to every bucket: the flat buffer, zero-padded to a
multiple of ``world``, is carved into ``world`` equal contiguous
per-rank shards (:class:`ShardPlan`). Rank ``r`` reduces only elements
``[r*shard_len, (r+1)*shard_len)`` (reduce-scatter), updates its shard,
and the updated weights are allgathered back. The carve is pure
indexing — it never crosses the reduction, so the sharded exchange
stays bit-identical to the fused allreduce (asserted by
``tests/test_zero.py``).
"""
from __future__ import annotations

import os
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

__all__ = ["Bucket", "PARTITION_MODES", "ShardPlan", "bucket_cap_bytes",
           "pack", "plan_buckets", "shard_layout", "unpacker"]

DEFAULT_BUCKET_MB = 25.0  # PyTorch DDP's default gradient-bucket size

# the ZeRO stages the planner knows how to lay out: "zero1" shards
# optimizer state only (full gradients still materialize on every
# rank), "zero2" also leaves gradients reduce-scattered (each rank
# keeps only its reduced shard)
PARTITION_MODES = ("zero1", "zero2")


class ShardPlan(NamedTuple):
    """Per-rank carve of one flat bucket under ZeRO partitioning.

    ``total``: unpadded flat element count; ``padded``: total rounded up
    to a multiple of ``world`` (the tail is zero-filled — zeros are
    inert through sum-reduction and are dropped before scatter);
    ``shard_len``: ``padded // world`` elements owned per rank.
    """

    mode: str
    world: int
    total: int
    padded: int
    shard_len: int

    def shard_range(self, rank: int) -> Tuple[int, int]:
        """[start, stop) of ``rank``'s shard in the padded flat buffer."""
        if not (0 <= rank < self.world):
            raise ValueError(
                f"rank {rank} outside partition world {self.world}")
        return rank * self.shard_len, (rank + 1) * self.shard_len


def shard_layout(mode: str, total: int, world: int) -> ShardPlan:
    """The :class:`ShardPlan` for a flat buffer of ``total`` elements
    partitioned across ``world`` ranks."""
    if mode not in PARTITION_MODES:
        raise ValueError(
            f"unknown partition mode {mode!r}; expected one of "
            f"{PARTITION_MODES}")
    world = int(world)
    if world < 1:
        raise ValueError(f"partition world must be >= 1, got {world}")
    shard_len = -(-int(total) // world)          # ceil div
    return ShardPlan(mode, world, int(total), shard_len * world,
                     shard_len)


def bucket_cap_bytes() -> int:
    """Resolve ``MXNET_KV_BUCKET_MB`` (float MB; 0 disables bucketing)."""
    mb = float(os.environ.get("MXNET_KV_BUCKET_MB", str(DEFAULT_BUCKET_MB)))
    return int(mb * (1 << 20))


class Bucket:
    """One planned bucket: member positions (indices into the caller's
    key list), their shapes, and the flat-buffer layout."""

    __slots__ = ("indices", "shapes", "dtype", "nbytes", "group",
                 "shard_plan")

    def __init__(self, dtype, group):
        self.indices: List[int] = []
        self.shapes: List[Tuple[int, ...]] = []
        self.dtype = dtype
        self.group = group          # (dtype_str, nslots, slot device sig)
        self.nbytes = 0
        self.shard_plan: Optional[ShardPlan] = None   # set by partition=

    def elements(self) -> int:
        n = 0
        for s in self.shapes:
            m = 1
            for d in s:
                m *= int(d)
            n += m
        return n

    def add(self, index: int, shape: Tuple[int, ...],
            nbytes: int) -> None:
        self.indices.append(index)
        self.shapes.append(tuple(shape))
        self.nbytes += int(nbytes)

    def __len__(self):
        return len(self.indices)

    def __repr__(self):
        return (f"Bucket(keys={len(self.indices)}, dtype={self.dtype}, "
                f"bytes={self.nbytes})")


def plan_buckets(entries: Sequence[Tuple[int, Tuple[int, ...], object,
                                         object, int]],
                 cap_bytes: int,
                 partition: Optional[str] = None,
                 world: int = 1) -> List[Bucket]:
    """Partition ``entries`` into buckets, preserving the given order.

    ``entries``: ``(index, shape, dtype, group, nbytes)`` tuples in
    dispatch (priority) order. ``group`` segregates members that cannot
    share a flat buffer — different dtypes, different device-copy counts
    or placements. Greedy: an entry joins the open bucket of its group
    unless that would exceed ``cap_bytes``; an entry alone larger than
    the cap still gets (and fills) its own bucket.

    ``partition``: when ``"zero1"`` / ``"zero2"``, every planned bucket
    additionally gets a :class:`ShardPlan` carving its flat buffer into
    ``world`` per-rank shards (the reduce-scatter / shard-update /
    allgather layout the ZeRO engine dispatches on).
    """
    if partition is not None and partition not in PARTITION_MODES:
        raise ValueError(
            f"unknown partition mode {partition!r}; expected one of "
            f"{PARTITION_MODES}")
    buckets: List[Bucket] = []
    open_by_group: Dict[object, Bucket] = {}
    for index, shape, dtype, group, nbytes in entries:
        b = open_by_group.get(group)
        if b is None or (len(b) > 0 and b.nbytes + nbytes > cap_bytes):
            b = Bucket(dtype, group)
            buckets.append(b)
            open_by_group[group] = b
        b.add(index, shape, nbytes)
    if partition is not None:
        for b in buckets:
            b.shard_plan = shard_layout(partition, b.elements(), world)
    return buckets


# --------------------------------------------------------------------------
# jitted pack / unpack
# --------------------------------------------------------------------------

_PACK = None                       # lazily-built jitted variadic packer
_UNPACKERS: Dict[Tuple, object] = {}


def pack(arrs):
    """Flatten + concatenate a bucket's member arrays (one dispatch).

    ``jax.jit`` caches per (arity, shapes, dtype) signature, so every
    step after the first replays a compiled executable. All members must
    be committed to the same device (the planner's ``group`` guarantees
    it); the flat buffer lands on that device.
    """
    global _PACK
    if _PACK is None:
        import jax
        import jax.numpy as jnp

        _PACK = jax.jit(
            lambda *xs: jnp.concatenate([x.reshape(-1) for x in xs]))
    return _PACK(*arrs)


def unpacker(shapes: Sequence[Tuple[int, ...]]):
    """Jitted inverse of :func:`pack` for a bucket signature: flat buffer
    -> tuple of member arrays (one dispatch). Cached per shapes tuple."""
    sig = tuple(tuple(s) for s in shapes)
    fn = _UNPACKERS.get(sig)
    if fn is None:
        import jax

        offsets = []
        off = 0
        for s in sig:
            n = 1
            for d in s:
                n *= int(d)
            offsets.append((off, n, s))
            off += n

        def unpack(flat):
            return tuple(flat[o:o + n].reshape(s) for o, n, s in offsets)

        fn = jax.jit(unpack)
        _UNPACKERS[sig] = fn
    return fn
