"""Paged one-token attention over a lane-dense K arena and a V arena.

For decoders whose heads are narrower than a lane tile (64 wide, in
differential pairs: ``ops/diff_attention.py``). A cached token is ONE
row of ``n_kv_heads * head_dim`` lanes in a ``(pages, page, width)`` key
arena and one in a value arena of the same shape, addressed through a
per-stream page table. The query arrives spread over the key row
(``q_wide`` (B, H, width): head ``h``'s ``head_dim`` values sit in the
lanes of the key head it reads, zeros elsewhere), so the scores of all
heads are one product over the whole row, and every head's
probabilities multiply the whole value row: the caller keeps the lanes
of the value heads each query head reads. Narrow heads cost the MXU
lanes it would otherwise leave empty, and the rows, which bound a
decode step, are read once.

The walk is that of :mod:`.mla_paged_attention` (grid ``(batch,)``; a
stream's LIVE pages fetched by page-table-driven DMA a block of
``_BLOCK_TOKENS`` ahead into one of two VMEM buffers, folded into an
online softmax in float32; pages past a stream's length neither fetched
nor computed; a row of length 0 costs its grid step and nothing else),
with two arenas where that one has a row that is key and value at once.
A sliding window's ring of the last ``W`` tokens is read by the same
kernel: a ring is ``W / page`` consecutive pages of a ``(slots * W /
page, page, width)`` view, and attention does not care in what order the
live rows come.

``diff_paged_supported`` gates on TPU execution plus Mosaic-friendly
shapes; ``ops/diff_attention.py::_diff_paged_reference`` is the
reference, and CPU tests run this kernel with ``interpret=True``
against it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from .flash_attention import _NEG_INF32, _prec_for, _x32_mode

__all__ = ["diff_paged_decode_kernel", "diff_paged_shape_supported",
           "diff_paged_supported"]

# tokens of one compute block: 512 rows of 1,280 lanes are 1.3 MB of keys
# and as much of values, two buffers each
_BLOCK_TOKENS = 512


def diff_paged_shape_supported(q_wide, k_arena, v_arena) -> bool:
    """Platform-independent shape eligibility: ``q_wide`` (B, H, width)
    as wide as an arena row, rows a whole number of 128-lane tiles, pages
    and heads a whole number of sublane tiles of their dtype."""
    if (q_wide.ndim != 3 or k_arena.ndim != 3
            or k_arena.shape != v_arena.shape
            or not q_wide.dtype == k_arena.dtype == v_arena.dtype):
        return False
    itemsize = jnp.dtype(k_arena.dtype).itemsize
    if itemsize not in (2, 4):
        return False
    sublanes = 8 * (4 // itemsize)
    _, page_size, width = k_arena.shape
    return (width % 128 == 0 and q_wide.shape[-1] == width
            and page_size % sublanes == 0
            and q_wide.shape[1] % sublanes == 0)


def diff_paged_supported(q_wide, k_arena, v_arena) -> bool:
    """TPU execution, a trace the SPMD partitioner does not have to
    split, and the shape gate."""
    from ..base import current_execution_platform
    from ..parallel.mesh import auto_partitioned

    if current_execution_platform(q_wide) != "tpu" or auto_partitioned():
        return False
    return diff_paged_shape_supported(q_wide, k_arena, v_arena)


def _decode_kernel(len_ref, pt_ref, live_ref, q_ref, k_ref, v_ref, o_ref,
                   kbuf, vbuf, sems, slot_ref, acc_ref, m_ref, l_ref, *,
                   scale, page_size, ppb, table_w, batch):
    """One stream: walk its live blocks, emit its output rows.
    ``live_ref[r]`` is the first row at or after ``r`` with a length
    above 0 (``batch``: none); ``slot_ref[0]`` carries the buffer that
    holds the next block across grid steps."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    h = q_ref.shape[1]
    bk = page_size * ppb
    cap = table_w * page_size

    def tokens_of(row):
        return jnp.minimum(len_ref[row], cap)

    def block_pages(row, blk, slot, wait):
        """Start (or wait for) the copies of block ``blk`` of ``row``
        into buffer ``slot``: its live pages only, a key page and a value
        page each."""
        pages = jnp.clip(pl.cdiv(tokens_of(row), page_size) - blk * ppb,
                         0, ppb)
        base = row * table_w + blk * ppb

        def page(i, carry):
            src = 0 if wait else pt_ref[base + i]
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
        q = q_ref[0]                                        # (H, width)
        prec = _prec_for(q.dtype)

        def block(i, carry):
            slot = jax.lax.rem(slot0 + i, 2)
            more = i + 1 < n_blocks

            @pl.when(jnp.logical_or(more, next_row < batch))
            def _next():
                block_pages(jnp.where(more, b, next_row),
                            jnp.where(more, i + 1, 0), 1 - slot, wait=False)

            block_pages(b, i, slot, wait=True)
            keys = kbuf[slot].reshape(bk, kbuf.shape[-1])   # (bk, width)
            vals = vbuf[slot].reshape(bk, vbuf.shape[-1])
            s = jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())),
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
                precision=prec)                             # (H, width)
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        slot_ref[0] = jax.lax.rem(slot0 + n_blocks, 2)
        o_ref[0] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)


# a jit of its own: the sites of a forward's programs then trace and
# lower the kernel once each
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def diff_paged_decode_kernel(q_wide, k_arena, v_arena, page_table, lengths,
                             *, scale: float, interpret: bool = False):
    """One-token attention of every query head over a stream's live rows.

    ``q_wide`` (B, H, width): the queries spread over the key row (see
    the module text), ready but for ``scale``; ``k_arena``, ``v_arena``
    (pages, page, width); ``page_table`` (B, P) int32 page ids (scratch
    page 0 pads the tail); ``lengths`` (B,) int32 live tokens per row.
    Returns (B, H, width) float32: each head's probabilities times the
    whole value row; all zeros for a row of length 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, width = q_wide.shape
    _, page_size, _ = k_arena.shape
    table_w = page_table.shape[1]
    ppb = max(1, min(_BLOCK_TOKENS // page_size, table_w))
    lengths = lengths.astype(jnp.int32)
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
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, width), lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, width), k_arena.dtype),
            pltpu.VMEM((2, ppb, page_size, width), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, width), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ],
    )
    with _x32_mode():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, width), jnp.float32),
            # the buffer slot and the copies in flight carry over from
            # one row to the next: rows run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="diff_paged_decode",
        )(lengths, page_table.astype(jnp.int32).reshape(-1), live, q_wide,
          k_arena, v_arena)
