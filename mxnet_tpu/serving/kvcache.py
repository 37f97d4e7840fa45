"""Paged KV-cache pool for autoregressive decode (ROADMAP item 1).

The reference's ``BucketingModule`` amortized *compilation* across
sequence lengths but re-ran full-sequence compute every step; the
modern answer is a KV cache, and the serving-grade shape of that cache
is **paged** (vLLM's insight): keys/values live in fixed-size pages
inside one preallocated per-replica arena, and each request owns a
*list* of pages rather than a contiguous max-length slab. Continuous
batching then composes freely — requests of wildly different lengths
join and leave the decode batch at every step without copying or
re-packing anybody's cache.

This module is the **accounting** half: :class:`PagePool` hands out
page ids from a free list, tracks per-owner page lists, and raises the
typed :class:`CacheFull` when the arena cannot fit a request —
admission control, wired into the Router's shed machinery exactly like
``ServerOverloaded`` (shed reason ``kvcache_full``). The **storage**
half is one arena array per attention sublayer and kind of row
(:func:`make_latent_arena`) indexed by page: token ``i`` of a request
whose page table is ``pt`` lives at ``[pt[i // page_size], i % page_size]``.

Page 0 is **reserved as scratch**: batch-padding rows and padded tail
positions scatter their (meaningless) K/V there, so a padded dispatch
can write unconditionally without ever corrupting a live request's
pages — the same bit-transparent-padding contract the batcher already
guarantees (see :mod:`.buckets`).

Fixed-size pages cannot fragment in the classical sense (any free page
serves any request), but a long-lived fleet still wants
:meth:`PagePool.defrag`: it computes the permutation that packs live
pages down to the lowest indices (arena locality, and the precondition
for shrinking an arena), and :func:`apply_defrag` replays that
permutation onto the arena arrays.

A stream of a model with recurrent or windowed layers also holds state
that is NOT pages of tokens: a fixed-size **state slot**
(:class:`StateSlots`, beside the pool: convolution tails, a scan's state,
a window's ring), taken with the pages at admission and freed with them.

Telemetry (``MXNET_TELEMETRY=1``): every alloc/free publishes
``mxnet_serving_kvcache_pages{state=free|used|reserved}``; the slots
publish ``mxnet_state_slots_in_use`` and count
``mxnet_state_slot_allocs_total``.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..base import MXNetError
from ..telemetry import _state as _telemetry_state

__all__ = ["CacheFull", "Preempted", "PagePool", "StateSlots",
           "make_latent_arena", "apply_defrag"]


class CacheFull(MXNetError):
    """Typed admission error: the KV arena cannot hold this request.

    Raised synchronously at admission (never as a wedged future) and
    shipped over :mod:`.wire` under the stable name ``kvcache_full`` so
    a remote caller gets this exact type back. The Router counts it as
    a shed (``mxnet_serving_shed_total{reason="kvcache_full"}``).
    """


class Preempted(MXNetError):
    """This stream's pages were reclaimed for a higher-priority arrival.

    Resolved onto the victim's ``GenerateHandle.future`` at a decode-step
    boundary: every token streamed before the preemption is a clean,
    sealed prefix (the chaos-gate-9 crash contract — never a torn
    token), and the handle never wedges. Crosses :mod:`.wire` under the
    stable name ``preempted``. Counted per tenant as
    ``mxnet_serving_preempted_total{victim,beneficiary}``.
    """


class PagePool:
    """Free-list allocator over ``n_pages`` fixed-size cache pages.

    ``page_size`` is in tokens. Page 0 is reserved as the padding
    scratch page and is never handed out. Thread-safe: the serving
    scheduler allocates while ``stats()``/telemetry readers observe.
    ``n_state_slots`` adds a :class:`StateSlots` allocator as
    ``state_slots``, for engines that declare ``state_slots``.
    """

    def __init__(self, n_pages: int, page_size: int = 16,
                 n_state_slots: Optional[int] = None):
        if n_pages < 2:
            raise MXNetError(
                f"PagePool needs >= 2 pages (page 0 is the reserved "
                f"scratch page), got {n_pages}")
        if page_size < 1:
            raise MXNetError(f"page_size must be >= 1, got {page_size}")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self._lock = threading.Lock()
        self._free: deque = deque(range(1, self.n_pages))
        self._owned: Dict[object, List[int]] = {}
        # what a stream of a recurrent or windowed model holds beside
        # its pages (None: no engine over this pool keeps such state)
        self.state_slots: Optional[StateSlots] = (
            StateSlots(n_state_slots) if n_state_slots is not None else None)
        self._publish()

    # -- capacity ------------------------------------------------------
    @property
    def slots(self) -> int:
        """Total arena slots (tokens), scratch page included."""
        return self.n_pages * self.page_size

    @property
    def capacity_tokens(self) -> int:
        """Tokens the pool can hold for real requests (scratch excluded)."""
        return (self.n_pages - 1) * self.page_size

    def pages_for(self, n_tokens: int) -> int:
        return -(-max(int(n_tokens), 1) // self.page_size)

    # -- allocation ----------------------------------------------------
    def alloc(self, owner, n_tokens: int) -> List[int]:
        """Allocate pages covering ``n_tokens`` for ``owner``. Raises
        :class:`CacheFull` (allocating nothing) when the free list is
        short — admission is all-or-nothing, so a request can never
        wedge half-allocated."""
        need = self.pages_for(n_tokens)
        with self._lock:
            if owner in self._owned:
                raise MXNetError(f"PagePool: owner {owner!r} already holds "
                                 f"{len(self._owned[owner])} page(s)")
            if need > len(self._free):
                raise CacheFull(
                    f"kv cache full: need {need} page(s) for {n_tokens} "
                    f"token(s), {len(self._free)} of "
                    f"{self.n_pages - 1} free")
            pages = [self._free.popleft() for _ in range(need)]
            self._owned[owner] = pages
        self._publish()
        return list(pages)

    def extend(self, owner, n_tokens: int) -> List[int]:
        """Grow ``owner``'s allocation to cover ``n_tokens`` total.
        Raises :class:`CacheFull` without changing the allocation when
        the free list cannot cover the growth."""
        need = self.pages_for(n_tokens)
        with self._lock:
            held = self._owned.get(owner)
            if held is None:
                raise MXNetError(f"PagePool: unknown owner {owner!r}")
            grow = need - len(held)
            if grow <= 0:
                return list(held)
            if grow > len(self._free):
                raise CacheFull(
                    f"kv cache full: owner {owner!r} needs {grow} more "
                    f"page(s), {len(self._free)} free")
            held.extend(self._free.popleft() for _ in range(grow))
            pages = list(held)
        self._publish()
        return pages

    def free(self, owner) -> int:
        """Return ``owner``'s pages to the free list (idempotent);
        returns the number of pages released."""
        with self._lock:
            pages = self._owned.pop(owner, None)
            if pages:
                self._free.extend(pages)
        self._publish()
        return len(pages) if pages else 0

    def page_table(self, owner, width: Optional[int] = None) -> np.ndarray:
        """``owner``'s page list as an int32 vector padded with the
        scratch page (0) up to ``width`` — the dense per-row page table
        a batched dispatch gathers through."""
        with self._lock:
            pages = list(self._owned.get(owner, ()))
        if width is None:
            width = len(pages)
        if len(pages) > width:
            raise MXNetError(
                f"PagePool: owner {owner!r} holds {len(pages)} page(s), "
                f"page_table width {width} too small")
        out = np.zeros((width,), dtype=np.int32)
        out[:len(pages)] = pages
        return out

    def owned(self, owner) -> List[int]:
        """``owner``'s current page list (a copy). Needed after
        :meth:`defrag`, which renumbers pages in place — any snapshot a
        caller took at :meth:`alloc` time is stale the moment a defrag
        runs."""
        with self._lock:
            return list(self._owned.get(owner, ()))

    # -- observability -------------------------------------------------
    def frag_info(self) -> Tuple[int, int]:
        """``(n_live, span)``: live page count and the highest live page
        index (0 when empty). ``span - n_live`` is the number of free
        holes below the high-water mark — the fragmentation measure the
        serving scheduler's automatic :meth:`defrag` trigger thresholds
        on (a packed pool has ``span == n_live``)."""
        with self._lock:
            live = [p for pages in self._owned.values() for p in pages]
            return len(live), (max(live) if live else 0)

    def stats(self) -> dict:
        """Pages by state. Pages only: a server whose engine keeps
        per-stream state slots reports those beside this dict
        (``Server.stats()["state_slots"]``, :meth:`StateSlots.stats`)."""
        with self._lock:
            used = sum(len(p) for p in self._owned.values())
            return {"free": len(self._free), "used": used, "reserved": 1,
                    "owners": len(self._owned),
                    "page_size": self.page_size,
                    "n_pages": self.n_pages}

    def _publish(self) -> None:
        if not _telemetry_state.enabled:
            return
        from .. import telemetry

        s = self.stats()
        telemetry.set_kvcache_pages(s["free"], s["used"], s["reserved"])

    # -- defrag --------------------------------------------------------
    def defrag(self) -> List[Tuple[int, int]]:
        """Pack live pages down to the lowest page indices. Returns the
        ``(src, dst)`` page moves performed (empty when already packed);
        the caller replays them onto the arena with
        :func:`apply_defrag` *before* the next dispatch reads it.
        Accounting (page lists, free list) is updated here atomically.
        """
        with self._lock:
            live = sorted(p for pages in self._owned.values()
                          for p in pages)
            # target: live pages occupy 1..len(live) in order
            target = {src: dst for dst, src in
                      enumerate(live, start=1) if src != dst}
            if not target:
                return []
            moves = sorted(target.items(), key=lambda m: m[1])
            for pages in self._owned.values():
                for i, p in enumerate(pages):
                    pages[i] = target.get(p, p)
            n_live = len(live)
            self._free = deque(range(n_live + 1, self.n_pages))
            return moves


class StateSlots:
    """Allocator of ``n_slots`` fixed-size per-stream state slots, beside
    a :class:`PagePool`.

    A slot is one index of the leading axis of an engine's slot arrays
    (what a stream carries that does not grow with its tokens: the tail
    of a causal convolution, a scan's state - a vector a channel or a
    matrix a head -, a window's ring). A stream holds exactly one to its
    end. Slot 0 is **reserved as scratch**, as page 0 is: the padding
    rows of a batch bucket read and write it, so a padded dispatch never
    advances a live stream's state. A slot is handed out dirty: the
    engine starts a stream at offset 0 from zeros, whatever the slot
    holds. :meth:`PagePool.defrag` renumbers pages, never slots.
    Thread-safe, as the pool is."""

    def __init__(self, n_slots: int):
        if n_slots < 2:
            raise MXNetError(
                f"StateSlots needs >= 2 slots (slot 0 is the reserved "
                f"scratch slot), got {n_slots}")
        self.n_slots = int(n_slots)
        self._lock = threading.Lock()
        self._free: deque = deque(range(1, self.n_slots))
        self._owned: Dict[object, int] = {}

    def alloc(self, owner) -> int:
        """A free slot for ``owner``; :class:`CacheFull` (nothing taken)
        when none is left."""
        with self._lock:
            if owner in self._owned:
                raise MXNetError(f"StateSlots: owner {owner!r} already "
                                 f"holds slot {self._owned[owner]}")
            if not self._free:
                raise CacheFull(
                    f"state slots full: all {self.n_slots - 1} in use")
            slot = self._owned[owner] = self._free.popleft()
        self._publish(alloc=True)
        return slot

    def free(self, owner) -> Optional[int]:
        """Return ``owner``'s slot (idempotent); the slot, or None."""
        with self._lock:
            slot = self._owned.pop(owner, None)
            if slot is not None:
                self._free.append(slot)
        self._publish()
        return slot

    def stats(self) -> dict:
        with self._lock:
            return {"free": len(self._free), "used": len(self._owned),
                    "reserved": 1, "n_slots": self.n_slots}

    def _publish(self, alloc: bool = False) -> None:
        if not _telemetry_state.enabled:
            return
        from .. import telemetry

        telemetry.set_state_slots(self.stats()["used"], alloc)


def make_latent_arena(n_sublayers: int, pool: PagePool, width: int,
                      dtype="float32", device=None) -> tuple:
    """Preallocate a cache on ``device`` (default: the process's first
    device): a tuple of ``n_sublayers`` separate ``(pool.n_pages,
    pool.page_size, width)`` zero arrays, one per attention sublayer and
    kind of row. Latent attention caches one vector per token that every
    query head shares (the compressed key/value latent with the shared
    rotary key behind it), so there is no head axis; grouped-query
    attention keeps a key array and a value array a layer, a token's
    heads side by side in one row (``width = kv_heads * head_dim``,
    which the paged kernel reads as it lies: a ``(slots, kv_heads,
    head_dim)`` array is tiled a token at a time and relaid whole on
    every step). One array per sublayer, not one ``(layers, ...)``
    block: a decode step then reads and scatters each sublayer's arena in
    place, where indexing a stacked block copies the layer out first.
    Pages are the leading axis, so a stream's cache is gathered a page (one
    contiguous block) at a time. ``width`` is rounded up to the TPU's 128
    lanes: the tiled layout pads a row to that anyway, and an array whose
    minor dimension is not a lane multiple is laid out column-major by
    default, which a program that gathers rows undoes with a copy of the
    whole arena on every step. Token ``i`` of a request whose page table
    is ``pt`` lives at ``[pt[i // page_size], i % page_size]``; page 0 is
    scratch. The arrays are committed to the device (``device_put``) so
    their sharding matches what jit outputs carry: an uncommitted zeros
    array keys the first executable differently and forces a silent
    one-time recompile on the second forward. :func:`apply_defrag` moves
    their pages."""
    import jax
    import jax.numpy as jnp

    shape = (pool.n_pages, pool.page_size, -(-int(width) // 128) * 128)
    dev = device if device is not None else jax.local_devices()[0]
    return tuple(jax.device_put(jnp.zeros(shape, dtype=dtype, device=dev),
                                dev) for _ in range(int(n_sublayers)))


def apply_defrag(arena, moves):
    """Replay :meth:`PagePool.defrag` page moves onto one arena array
    (one of :func:`make_latent_arena`'s ``(pages, page_size, width)``: a
    page is one index of axis 0). Moves are applied from one snapshot,
    so overlapping src/dst chains are safe.

    An engine whose layers keep TWO sorts of per-token state on one page
    table (a latent row and an index key, say: two ``make_latent_arena``
    calls of different ``width`` over the same pool) lists both in its
    ``arenas``: a page is the same index of axis 0 in each, so the one
    permutation is replayed onto every array whatever its width
    (:meth:`~mxnet_tpu.serving.engine.PagedDecodeEngine.apply_defrag`).

    State slots (:class:`StateSlots`) do not move: a defrag renumbers
    pages, a stream keeps its slot, and an engine's slot arrays are not
    in its ``arenas``.
    """
    if not moves:
        return arena
    import jax.numpy as jnp

    src, dst = (jnp.asarray(np.asarray(side, dtype=np.int32))
                for side in zip(*moves))
    return arena.at[dst].set(jnp.take(arena, src, axis=0))
