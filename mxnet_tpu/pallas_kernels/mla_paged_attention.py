"""Paged one-token latent (MLA) attention kernel.

In the absorbed form of multi-head latent attention every query head of
a stream attends over ONE shared row per cached token, ``[c' | rotated
k_rope | lane padding]``, which is key (all lanes, against the padded
query) and value (its leading lanes) at once: one multi-query group of H
heads. The cache lives in pages of a ``(pages, page, width)`` arena
(:func:`mxnet_tpu.serving.kvcache.make_latent_arena`) addressed through a
per-stream page table.

The kernel reads a stream's LIVE pages from the arena in place. Grid
``(batch,)``; a grid step is one stream, and walks its live tokens a
block of ``_BLOCK_TOKENS`` at a time: the pages of a block are fetched
by page-table-driven DMA into one of two VMEM buffers while the block
before is computed (the first block of the NEXT live stream while this
stream's last one is), each row once, and folded into an online
softmax (float32 scores, statistics and accumulator; probabilities cast
to the cache's dtype for PV, as the XLA path casts them). Pages past a
stream's length are neither fetched nor computed, and a row of length 0
(the padding rows of a batch bucket) costs its grid step and nothing
else. This is the shape of ``jax.experimental.pallas.ops.tpu
.paged_attention``, cut to one kv head whose keys are its values, with
the operands left in bf16 and the accumulator in float32.

Separate from :mod:`.paged_attention` (the GQA kernel) on purpose: other
state (K and V arenas by slot against one latent row that is both),
other head grouping, one page per grid step there against live blocks
here.

``mla_paged_supported`` gates on TPU execution plus Mosaic-friendly
shapes; the gather in ``ops/attention.py::_mla_paged_reference`` is
the reference, and CPU tests run this kernel with ``interpret=True``
against it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from .flash_attention import _NEG_INF32, _prec_for, _x32_mode

__all__ = ["mla_paged_decode_kernel", "mla_paged_shape_supported",
           "mla_paged_supported"]

# Tokens of one compute block. A block costs ~0.5 us whatever its size
# (the chain wait - scores - max - exp - PV - rescale runs once) and
# ~0.28 us per 128 tokens, live or masked. On one v5e, 256 streams of
# ~370 live tokens read 0.60 / 0.53 / 0.60 ms at 256 / 512 / 1024 and 256
# full tables of 1,152 tokens 1.54 / 1.33 / 1.33 ms (the gather path:
# 2.36 and 2.57 ms).
_BLOCK_TOKENS = 512


def mla_paged_shape_supported(q, arena) -> bool:
    """Platform-independent shape eligibility: ``q`` (B, H, width) padded
    to the arena's row width, rows a whole number of 128-lane tiles,
    pages and heads a whole number of sublane tiles of their dtype (8
    rows of 4 bytes, 16 of 2)."""
    if q.ndim != 3 or arena.ndim != 3 or q.dtype != arena.dtype:
        return False
    itemsize = jnp.dtype(arena.dtype).itemsize
    if itemsize not in (2, 4):
        return False
    sublanes = 8 * (4 // itemsize)
    _, page_size, width = arena.shape
    return (width % 128 == 0 and q.shape[-1] == width
            and page_size % sublanes == 0 and q.shape[1] % sublanes == 0)


def mla_paged_supported(q, arena) -> bool:
    """TPU execution, a trace the SPMD partitioner does not have to
    split, and the shape gate (``paged_supported``'s twin)."""
    from ..base import current_execution_platform
    from ..parallel.mesh import auto_partitioned

    if current_execution_platform(q) != "tpu" or auto_partitioned():
        return False
    return mla_paged_shape_supported(q, arena)


def _decode_kernel(len_ref, pt_ref, live_ref, q_ref, arena_ref, o_ref,
                   buf, sems, slot_ref, acc_ref, m_ref, l_ref, *,
                   scale, page_size, ppb, table_w, batch):
    """One stream: walk its live blocks, emit its output row.

    ``live_ref[r]`` is the first row at or after ``r`` with a length
    above 0 (``batch`` where there is none): the DMA of a block is
    started one block ahead, across rows, so the schedule of live blocks
    has to be known ahead. ``slot_ref[0]`` carries the buffer that holds
    the next block across grid steps."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    h = q_ref.shape[1]
    out_w = o_ref.shape[-1]
    bk = page_size * ppb
    cap = table_w * page_size

    def tokens_of(row):
        return jnp.minimum(len_ref[row], cap)

    def block_pages(row, blk, slot, wait):
        """Start (or wait for) the copies of block ``blk`` of ``row``
        into ``buf[slot]``: its live pages only. A loop and not ``ppb``
        copies written out: tracing them costs a serving process seconds
        of set-up per decode program."""
        pages = jnp.clip(pl.cdiv(tokens_of(row), page_size) - blk * ppb,
                         0, ppb)
        base = row * table_w + blk * ppb

        def page(i, carry):
            copy = pltpu.make_async_copy(
                arena_ref.at[0 if wait else pt_ref[base + i]],
                buf.at[slot, i], sems.at[slot])
            if wait:
                copy.wait()
            else:
                copy.start()
            return carry

        jax.lax.fori_loop(0, pages, page, 0)

    @pl.when(b == 0)
    def _first_step():
        # rows of a buffer that no copy has filled are masked out of the
        # scores but still meet a zero probability in PV: they must hold
        # numbers, so never what VMEM held before the call
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        # the first row with tokens (none: the last row, no page)
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
        q = q_ref[0]                                        # (H, width)
        prec = _prec_for(q.dtype)

        def block(i, carry):
            slot = jax.lax.rem(slot0 + i, 2)

            # the block after this one: the row's next, or the first of
            # the next row that has tokens
            more = i + 1 < n_blocks

            @pl.when(jnp.logical_or(more, next_row < batch))
            def _next():
                block_pages(jnp.where(more, b, next_row),
                            jnp.where(more, i + 1, 0), 1 - slot, wait=False)

            block_pages(b, i, slot, wait=True)
            rows = buf[slot].reshape(bk, buf.shape[-1])     # (bk, width)
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec) * _np.float32(scale)        # (H, bk)
            pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (h, bk), 1)
            s = jnp.where(pos < n, s, _NEG_INF32)
            m_prev = m_ref[:, 0:1]                          # (H, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a visited block holds a live token, so m_new is a real
            # score and a masked one gives exp(-1e30 - m_new) = 0
            p = jnp.exp(s - m_new)
            l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(rows.dtype), rows[:, :out_w],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec)                             # (H, out_w)
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        slot_ref[0] = jax.lax.rem(slot0 + n_blocks, 2)
        o_ref[0] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)


# a jit of its own: the sites of one program (a LongCat double layer has
# two) then trace and lower the kernel once, which is most of what the
# kernel adds to a serving process's set-up
@functools.partial(jax.jit,
                   static_argnames=("scale", "out_width", "interpret"))
def mla_paged_decode_kernel(q, arena, page_table, lengths, *, scale: float,
                            out_width: int, interpret: bool = False):
    """Absorbed one-token latent attention over paged rows.

    ``q`` (B, H, width): the query in the latent space, zero-padded to
    the arena's row width, rotated and ready but for ``scale``;
    ``arena`` (pages, page, width): ONE sublayer's latent arena;
    ``page_table`` (B, P) int32 page ids (scratch page 0 pads the tail);
    ``lengths`` (B,) int32 valid tokens per row. Returns (B, H,
    ``out_width``): the probabilities times the leading ``out_width``
    lanes of the rows (a multiple of 128), in q's dtype; all zeros for a
    row of length 0.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, width = q.shape
    _, page_size, _ = arena.shape
    table_w = page_table.shape[1]
    ppb = max(1, _BLOCK_TOKENS // page_size)        # pages per block
    lengths = lengths.astype(jnp.int32)
    # live[r]: the first row at or after r that has tokens (b: none)
    rows = jnp.arange(b, dtype=jnp.int32)
    live = jax.lax.cummin(jnp.where(lengths > 0, rows, jnp.int32(b)),
                          reverse=True)
    live = jnp.concatenate([live, jnp.full((1,), b, jnp.int32)])
    kernel = functools.partial(_decode_kernel, scale=scale,
                               page_size=page_size, ppb=ppb,
                               table_w=table_w, batch=b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, width), lambda bi, *_: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, out_width), lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, width), arena.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, out_width), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ],
    )
    with _x32_mode():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, out_width), q.dtype),
            # the buffer slot and the copies in flight carry over from
            # one row to the next: rows run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="mla_paged_decode",
        )(lengths, page_table.astype(jnp.int32).reshape(-1), live, q, arena)
