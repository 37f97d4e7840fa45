"""Paged single-query (decode) attention kernel for grouped-query heads.

The decode step of an autoregressive request attends one query row
against every cached K/V token of that request, where the cache lives
in fixed-size pages of a shared arena (:mod:`mxnet_tpu.serving.kvcache`)
addressed through a per-request page table. A cached token is one
lane-dense row of ``n_kv_heads * head_dim`` values in the K arena and
one in the V arena.

The kernel reads a stream's LIVE pages from the arenas in place. Grid
``(batch,)``; a grid step is one stream, and walks its live tokens a
block of up to ``_BLOCK_TOKENS`` at a time: the pages of a block are
fetched by page-table-driven DMA into one of two VMEM buffers while the
block before is computed (the first block of the NEXT live stream while
this stream's last one is), each row once, and folded into an online
softmax (float32 scores, statistics and accumulator; probabilities cast
to the arena's dtype for PV). Pages past a stream's length are neither
fetched nor computed, and a row of length 0 (the padding rows of a batch
bucket) emits zeros and costs its grid step and nothing else. The walk
is that of :mod:`.mla_paged_attention` and :mod:`.diff_paged_attention`.

Grouped-query attention runs as two plain 2-D MXU contractions over a
block: the query is expanded to ``(H, KV*D)`` with every head's row zero
outside its own kv group's lanes, so ``q_exp @ k.T`` is exactly the
per-group score, and ``p @ v`` accumulates an ``(H, KV*D)`` tile whose
own-group lanes are summed at emit time. Any group size divides in (4
for 32 heads over 8, 5 for 20 over 4); the head rows are padded to a
whole sublane tile in the wrapper.

The custom call's first two operands are the int32 page table ``(B, P)``
and the int32 lengths ``(B,)``, in that order, with the walk's third
scalar operand behind them: the benchmark's trace readers find the
kernel by that signature (``benchmarks/kernels/paged_attention.py``).

Eligibility: ``paged_supported`` gates on TPU execution
(``base.current_execution_platform``) and a trace the SPMD partitioner
does not have to split, plus Mosaic-friendly shapes — one query row, a
head_dim of whole 128-lane tiles, pages of whole sublane tiles of the
arena's dtype (8 rows of float32, 16 of bfloat16), query and arenas of
one dtype. Everything else runs the eager gather in ``ops/attention.py``
(``_paged_reference``), which is also the oracle: CPU tests run this
kernel in ``interpret=True`` mode against it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from .flash_attention import _NEG_INF32, _prec_for, _x32_mode

__all__ = ["paged_attention_kernel", "paged_supported",
           "paged_shape_supported"]

# most tokens of one compute block (a block costs its chain wait - scores
# - max - exp - PV - rescale once, whatever its size), and most bytes of
# one of the four VMEM buffers that hold a block's keys or values
_BLOCK_TOKENS = 512
_BUFFER_BYTES = 1 << 20


def _sublanes(dtype) -> int:
    """Rows of one (sublane, lane) tile of ``dtype``; 0 for an item size
    the kernel does not take."""
    itemsize = jnp.dtype(dtype).itemsize
    return 8 * (4 // itemsize) if itemsize in (2, 4) else 0


def paged_shape_supported(q, k_arena, page_size: int) -> bool:
    """Platform-independent shape eligibility: one query row per batch
    element, heads of whole lane tiles in a whole number of kv groups,
    pages of whole sublane tiles, query and arena of one dtype."""
    if q.ndim != 4 or q.shape[2] != 1:
        return False            # decode kernel: exactly one query row
    d = q.shape[-1]
    h = q.shape[1]
    kv = k_arena.shape[-2]
    if d % 128 or d != k_arena.shape[-1]:
        return False
    sublanes = _sublanes(k_arena.dtype)
    if not sublanes or q.dtype != k_arena.dtype:
        return False
    if page_size % sublanes or k_arena.shape[0] % page_size:
        return False
    return h % kv == 0


def paged_supported(q, k_arena, page_size: int) -> bool:
    """TPU execution + shape eligibility (same contract as
    ``flash_supported``: platform comes from the framework's jit entry
    points, so a CPU-context op never takes the kernel path)."""
    from ..base import current_execution_platform
    from ..parallel.mesh import auto_partitioned

    if current_execution_platform(q) != "tpu" or auto_partitioned():
        return False
    return paged_shape_supported(q, k_arena, page_size)


def _decode_kernel(pt_ref, len_ref, live_ref, q_ref, k_ref, v_ref, o_ref,
                   kbuf, vbuf, sems, slot_ref, acc_ref, m_ref, l_ref, *,
                   scale, page_size, ppb, rep, batch):
    """One stream: walk its live blocks, emit its output rows.
    ``live_ref[r]`` is the first row at or after ``r`` with a length
    above 0 (``batch``: none); ``slot_ref[0]`` carries the buffer that
    holds the next block across grid steps."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    h, d = q_ref.shape[1:]
    width = kbuf.shape[-1]
    kv = width // d
    bk = page_size * ppb
    cap = pt_ref.shape[1] * page_size

    def tokens_of(row):
        return jnp.minimum(len_ref[row], cap)

    def block_pages(row, blk, slot, wait):
        """Start (or wait for) the copies of block ``blk`` of ``row``
        into buffer ``slot``: its live pages only, a key page and a value
        page each."""
        pages = jnp.clip(pl.cdiv(tokens_of(row), page_size) - blk * ppb,
                         0, ppb)

        def page(i, carry):
            src = 0 if wait else pt_ref[row, blk * ppb + i]
            for arena, buf, sem in ((k_ref, kbuf, 0), (v_ref, vbuf, 1)):
                copy = pltpu.make_async_copy(
                    arena.at[src], buf.at[slot, i], sems.at[sem, slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()
            return carry

        jax.lax.fori_loop(0, pages, page, 0)

    @pl.when(b == 0)
    def _first_step():
        # rows no copy has filled are masked out of the scores but still
        # meet a zero probability in PV: they must hold numbers
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        block_pages(jnp.minimum(live_ref[0], batch - 1), 0, 0, wait=False)

    n = tokens_of(b)
    o_ref[0] = jnp.zeros_like(o_ref[0])         # what a padding row emits

    @pl.when(n > 0)
    def _row():
        slot0 = slot_ref[0]
        n_blocks = pl.cdiv(n, bk)
        next_row = live_ref[b + 1]
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF32)
        l_ref[...] = jnp.zeros_like(l_ref)
        # own[r, c]: lane c of the (KV*D)-wide row belongs to head r's
        # group (no lane at all for a row that pads the heads)
        own = (jax.lax.broadcasted_iota(jnp.int32, (h, width), 1) // d
               == jax.lax.broadcasted_iota(jnp.int32, (h, width), 0) // rep)
        q = q_ref[0]                                        # (H, D)
        q_exp = jnp.where(own, jnp.concatenate([q] * kv, axis=1),
                          jnp.zeros((), q.dtype))           # (H, KV*D)
        prec = _prec_for(q.dtype)

        def block(i, carry):
            slot = jax.lax.rem(slot0 + i, 2)
            more = i + 1 < n_blocks

            @pl.when(jnp.logical_or(more, next_row < batch))
            def _next():
                block_pages(jnp.where(more, b, next_row),
                            jnp.where(more, i + 1, 0), 1 - slot, wait=False)

            block_pages(b, i, slot, wait=True)
            keys = kbuf[slot].reshape(bk, width)
            vals = vbuf[slot].reshape(bk, width)
            s = jax.lax.dot_general(
                q_exp, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec) * _np.float32(scale)        # (H, bk)
            pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (h, bk), 1)
            s = jnp.where(pos < n, s, _NEG_INF32)
            m_prev = m_ref[:, 0:1]                          # (H, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec)                             # (H, KV*D)
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        slot_ref[0] = jax.lax.rem(slot0 + n_blocks, 2)
        acc = jnp.where(own, acc_ref[...], _np.float32(0.0))
        out = acc[:, 0:d]
        for g in range(1, kv):
            out = out + acc[:, g * d:(g + 1) * d]
        o_ref[0] = (out / l_ref[:, 0:1]).astype(o_ref.dtype)


# a jit of its own: the sites of a forward's programs then trace and
# lower the kernel once each
@functools.partial(jax.jit,
                   static_argnames=("page_size", "scale", "interpret"))
def paged_attention_kernel(q, k_arena, v_arena, page_table, lengths, *,
                           page_size: int, scale: float,
                           interpret: bool = False):
    """Decode attention over paged K/V.

    ``q``: (B, H, 1, D); ``k_arena``/``v_arena``: (slots, KV, D) — ONE
    layer's arena; ``page_table``: (B, P) int32 page ids (scratch page 0
    pads the tail); ``lengths``: (B,) int32 valid tokens per row.
    Returns (B, H, 1, D) in q's dtype; zeros for a row of length 0.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, _, d = q.shape
    slots, kv, _ = k_arena.shape
    width = kv * d
    table_w = page_table.shape[1]
    page_bytes = page_size * width * k_arena.dtype.itemsize
    ppb = max(1, min(_BLOCK_TOKENS // page_size,
                     _BUFFER_BYTES // page_bytes, table_w))
    sublanes = _sublanes(q.dtype)
    hp = -(-h // sublanes) * sublanes
    q = jnp.pad(q.reshape(b, h, d), ((0, 0), (0, hp - h), (0, 0)))
    lengths = lengths.astype(jnp.int32)
    rows = jnp.arange(b, dtype=jnp.int32)
    live = jax.lax.cummin(jnp.where(lengths > 0, rows, jnp.int32(b)),
                          reverse=True)
    live = jnp.concatenate([live, jnp.full((1,), b, jnp.int32)])
    kernel = functools.partial(_decode_kernel, scale=scale,
                               page_size=page_size, ppb=ppb, rep=h // kv,
                               batch=b)
    row_spec = pl.BlockSpec((1, hp, d), lambda bi, *_: (bi, 0, 0))
    arena_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row_spec, arena_spec, arena_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, width), k_arena.dtype),
            pltpu.VMEM((2, ppb, page_size, width), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hp, width), jnp.float32),
            pltpu.VMEM((hp, 128), jnp.float32),
            pltpu.VMEM((hp, 128), jnp.float32),
        ],
    )
    pages = (slots // page_size, page_size, width)
    with _x32_mode():
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, hp, d), q.dtype),
            # the buffer slot and the copies in flight carry over from
            # one row to the next: rows run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(page_table.astype(jnp.int32), lengths, live, q,
          k_arena.reshape(pages), v_arena.reshape(pages))
    return out[:, :h].reshape(b, h, 1, d)
