"""The paged one-token (decode) attention kernels: one walk, three callers.

A decode step attends one query row of every stream against that
stream's cached tokens, which live in fixed-size pages of shared
``(pages, page, width)`` arenas (:mod:`mxnet_tpu.serving.kvcache`)
addressed through a per-stream page table. A cached token is one
lane-dense row of an arena.

**The walk** (``_walk`` the kernel body, ``_paged_decode`` the call).
Grid ``(batch,)``; a grid step is one stream, and walks its LIVE tokens a
block of up to ``_BLOCK_TOKENS`` at a time: the pages of a block are
fetched by page-table-driven DMA into one of two VMEM buffers while the
block before is computed (the first block of the NEXT live stream while
this stream's last one is, so the list of live rows is a scalar operand
and the buffer slot is carried across grid steps), each row once, and
folded into an online softmax (float32 scores, statistics and
accumulator; probabilities cast to the arena's dtype for PV, as the XLA
paths cast them). Pages past a stream's length are neither fetched nor
computed, and a row of length 0 (the padding rows of a batch bucket)
emits zeros and costs its grid step and nothing else. This is the shape
of ``jax.experimental.pallas.ops.tpu.paged_attention`` with the operands
left in bf16 and the accumulator in float32.

Measured on one v5e with the latent kernel: a block costs ~0.5 us
whatever its size (the chain wait - scores - max - exp - PV - rescale
runs once) and ~0.28 us per 128 tokens, live or masked; 256 streams of
~370 live tokens read 0.60 / 0.53 / 0.60 ms at blocks of 256 / 512 /
1024 tokens and 256 full tables of 1,152 tokens 1.54 / 1.33 / 1.33 ms
(the gather path: 2.36 and 2.57 ms). Hence 512. A block's copies are a
loop and not pages-per-block copies written out: tracing them costs a
serving process seconds of set-up per decode program.

**What each kernel adds to the walk.**

- :func:`paged_attention_kernel` (grouped-query heads, 128 lanes a head;
  Mistral, Falcon-H1): a key and a value arena, a row ``n_kv_heads *
  head_dim`` wide. Grouped-query attention runs as two plain 2-D MXU
  contractions over a block: the query is expanded to ``(H, KV*D)`` with
  every head's row zero outside its own kv group's lanes, so ``q_exp @
  k.T`` is exactly the per-group score, and ``p @ v`` accumulates an
  ``(H, KV*D)`` tile whose own-group lanes are summed at emit time. Any
  group size divides in (4 for 32 heads over 8, 5 for 20 over 4); the
  head rows are padded to a whole sublane tile in the wrapper. Its
  custom call is unnamed and its first two operands are the int32 page
  table ``(B, P)`` and the int32 lengths ``(B,)``, in that order: the
  benchmark's trace readers find the kernel by that signature
  (``benchmarks/kernels/paged_attention.py``). The other two pass the
  lengths first and the table flattened, and carry a name.
- :func:`mla_paged_decode_kernel` (absorbed multi-head latent attention;
  LongCat-Flash, dots.vlm1): ONE arena
  (:func:`mxnet_tpu.serving.kvcache.make_latent_arena`) whose row ``[c' |
  rotated k_rope | lane padding]`` is key (all lanes, against the padded
  query) and value (its leading ``out_width`` lanes) at once: one
  multi-query group of H heads.
- :func:`diff_paged_decode_kernel` (heads narrower than a lane tile, 64
  wide in differential pairs, ``ops/diff_attention.py``;
  Phi-4-mini-flash): a key and a value arena. The query arrives spread
  over the key row (``q_wide`` (B, H, width): head ``h``'s ``head_dim``
  values sit in the lanes of the key head it reads, zeros elsewhere), so
  the scores of all heads are one product over the whole row, and every
  head's probabilities multiply the whole value row: the caller keeps
  the lanes of the value heads each query head reads. Narrow heads cost
  the MXU lanes it would otherwise leave empty, and the rows, which
  bound a decode step, are read once. 512 rows of 1,280 lanes are 1.3 MB
  of keys and as much of values, two buffers each. A sliding window's
  ring of the last ``W`` tokens is read by the same kernel: a ring is
  ``W / page`` consecutive pages of a ``(slots * W / page, page, width)``
  view, and attention does not care in what order the live rows come.

Eligibility: each ``*_supported`` gates on TPU execution
(``base.current_execution_platform``), a trace the SPMD partitioner does
not have to split, and Mosaic-friendly shapes (rows of whole 128-lane
tiles; pages, and heads where the wrapper does not pad them, of whole
sublane tiles of the arena's dtype: 8 rows of float32, 16 of bfloat16;
query and arenas of one dtype). Everything else runs the gathers of
``ops/attention.py`` (``_paged_reference``, ``_mla_paged_reference``)
and ``ops/diff_attention.py`` (``_diff_paged_reference``), which are
also the oracles: CPU tests run these kernels in ``interpret=True`` mode
against them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from .flash_attention import _NEG_INF32, _prec_for, _x32_mode

__all__ = ["paged_attention_kernel", "paged_supported",
           "paged_shape_supported", "mla_paged_decode_kernel",
           "mla_paged_supported", "mla_paged_shape_supported",
           "diff_paged_decode_kernel", "diff_paged_supported",
           "diff_paged_shape_supported", "pages_per_block"]

# most tokens of one compute block, and most bytes of one of the VMEM
# buffers that hold a block's rows (two an arena): every serving cell's
# block is 512 tokens (its widest rows, Phi's, make 1.3 MB of them); a
# row of 32 kv heads x 128 lanes is cut to 256
_BLOCK_TOKENS = 512
_BUFFER_BYTES = 2 << 20


def pages_per_block(page_size: int, width: int, itemsize: int,
                    table_w: int) -> int:
    """Pages of one block of the walk: ``_BLOCK_TOKENS`` of them, cut to
    ``_BUFFER_BYTES`` of rows and to the table's width."""
    return max(1, min(_BLOCK_TOKENS // page_size,
                      _BUFFER_BYTES // (page_size * width * itemsize),
                      table_w))


def _sublanes(dtype) -> int:
    """Rows of one (sublane, lane) tile of ``dtype``; 0 for an item size
    the kernels do not take."""
    itemsize = jnp.dtype(dtype).itemsize
    return 8 * (4 // itemsize) if itemsize in (2, 4) else 0


def _whole_tiles(arrays, lanes: int, rows) -> bool:
    """``arrays`` of ONE dtype the kernels take, ``lanes`` a whole number
    of 128-lane tiles and each of ``rows`` of that dtype's sublane
    tiles."""
    dtype = arrays[0].dtype
    sublanes = _sublanes(dtype)
    return bool(sublanes and all(a.dtype == dtype for a in arrays)
                and lanes % 128 == 0
                and all(n % sublanes == 0 for n in rows))


def _runs_on_chip(q) -> bool:
    """TPU execution (the platform comes from the framework's jit entry
    points, so a CPU-context op never takes a kernel) and a trace the
    SPMD partitioner does not have to split."""
    from ..base import current_execution_platform
    from ..parallel.mesh import auto_partitioned

    return current_execution_platform(q) == "tpu" and not auto_partitioned()


def paged_shape_supported(q, k_arena, page_size: int) -> bool:
    """Platform-independent shape eligibility: one query row per batch
    element, heads of whole lane tiles in a whole number of kv groups,
    pages of whole sublane tiles, query and arena of one dtype."""
    if q.ndim != 4 or q.shape[2] != 1:
        return False            # decode kernel: exactly one query row
    d = q.shape[-1]
    return (d == k_arena.shape[-1]
            and _whole_tiles((q, k_arena), d, (page_size,))
            and k_arena.shape[0] % page_size == 0
            and q.shape[1] % k_arena.shape[-2] == 0)


def paged_supported(q, k_arena, page_size: int) -> bool:
    """TPU execution + shape eligibility (same contract as
    ``flash_supported``)."""
    return _runs_on_chip(q) and paged_shape_supported(q, k_arena, page_size)


def mla_paged_shape_supported(q, arena) -> bool:
    """Platform-independent shape eligibility: ``q`` (B, H, width) padded
    to the arena's row width, rows a whole number of 128-lane tiles,
    pages and heads a whole number of sublane tiles of their dtype (8
    rows of 4 bytes, 16 of 2)."""
    if q.ndim != 3 or arena.ndim != 3:
        return False
    _, page_size, width = arena.shape
    return (q.shape[-1] == width
            and _whole_tiles((q, arena), width, (page_size, q.shape[1])))


def mla_paged_supported(q, arena) -> bool:
    """TPU execution, a trace the SPMD partitioner does not have to
    split, and the shape gate."""
    return _runs_on_chip(q) and mla_paged_shape_supported(q, arena)


def diff_paged_shape_supported(q_wide, k_arena, v_arena) -> bool:
    """Platform-independent shape eligibility: ``q_wide`` (B, H, width)
    as wide as an arena row, rows a whole number of 128-lane tiles, pages
    and heads a whole number of sublane tiles of their dtype."""
    if (q_wide.ndim != 3 or k_arena.ndim != 3
            or k_arena.shape != v_arena.shape):
        return False
    _, page_size, width = k_arena.shape
    return (q_wide.shape[-1] == width
            and _whole_tiles((q_wide, k_arena, v_arena), width,
                             (page_size, q_wide.shape[1])))


def diff_paged_supported(q_wide, k_arena, v_arena) -> bool:
    """TPU execution, a trace the SPMD partitioner does not have to
    split, and the shape gate."""
    return (_runs_on_chip(q_wide)
            and diff_paged_shape_supported(q_wide, k_arena, v_arena))


def _plain_heads(q_ref):
    """The query rows as they come; the accumulator as it is."""
    return q_ref[0], lambda acc: acc


def _grouped_heads(q_ref, *, kv, rep):
    """``kv`` groups of ``rep`` query heads over a (KV*D)-wide row: the
    query (H, D) expanded over its group's lanes, and the accumulator's
    own-group lanes summed into (H, D)."""
    h, d = q_ref.shape[1:]
    width = kv * d
    # own[r, c]: lane c of the row belongs to head r's group (no lane at
    # all for a row that pads the heads)
    own = (jax.lax.broadcasted_iota(jnp.int32, (h, width), 1) // d
           == jax.lax.broadcasted_iota(jnp.int32, (h, width), 0) // rep)
    q = q_ref[0]                                            # (H, D)
    q_exp = jnp.where(own, jnp.concatenate([q] * kv, axis=1),
                      jnp.zeros((), q.dtype))               # (H, KV*D)

    def emit(acc):
        acc = jnp.where(own, acc, _np.float32(0.0))
        out = acc[:, 0:d]
        for g in range(1, kv):
            out = out + acc[:, g * d:(g + 1) * d]
        return out

    return q_exp, emit


def _walk(*refs, n_arenas, table_leads, heads, scale, page_size, ppb,
          table_w, batch):
    """One stream: walk its live blocks, emit its output rows.

    ``refs``: the three scalar operands (the page table and the lengths,
    the table first and 2-D where ``table_leads``, else second and
    flattened; then ``live_ref``), the query block, ``n_arenas`` arenas
    (one: its rows are keys and, in their leading lanes, values; two:
    keys and values), the output block, a two-slot buffer an arena, the
    DMA semaphores, ``slot_ref`` and the softmax's ``acc`` / ``m`` /
    ``l``. ``live_ref[r]`` is the first row at or after ``r`` with a
    length above 0 (``batch``: none): the DMA of a block is started one
    block ahead, across rows, so the schedule of live blocks has to be
    known ahead. ``slot_ref[0]`` carries the buffer that holds the next
    block across grid steps. ``heads(q_ref)`` gives the query of the
    score product and what turns the accumulator into the output rows.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pt_ref, len_ref = refs[:2] if table_leads else (refs[1], refs[0])
    live_ref, q_ref = refs[2:4]
    arenas, o_ref = refs[4:4 + n_arenas], refs[4 + n_arenas]
    bufs = refs[5 + n_arenas:5 + 2 * n_arenas]
    sems, slot_ref, acc_ref, m_ref, l_ref = refs[5 + 2 * n_arenas:]
    b = pl.program_id(0)
    h = q_ref.shape[1]
    bk = page_size * ppb
    cap = table_w * page_size

    def tokens_of(row):
        return jnp.minimum(len_ref[row], cap)

    def block_pages(row, blk, slot, wait):
        """Start (or wait for) the copies of block ``blk`` of ``row``
        into buffer ``slot``: its live pages only, a page an arena."""
        pages = jnp.clip(pl.cdiv(tokens_of(row), page_size) - blk * ppb,
                         0, ppb)
        if not table_leads:
            base = row * table_w + blk * ppb

        def page(i, carry):
            if wait:
                src = 0
            elif table_leads:
                src = pt_ref[row, blk * ppb + i]
            else:
                src = pt_ref[base + i]
            # a semaphore a buffer: a row of two an arena (one arena: the
            # row alone)
            for k, (arena, buf) in enumerate(zip(arenas, bufs)):
                copy = pltpu.make_async_copy(
                    arena.at[src], buf.at[slot, i],
                    sems.at[k, slot] if n_arenas > 1 else sems.at[slot])
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
        for buf in bufs:
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
        q, emit = heads(q_ref)
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
            keys, *vals = (buf[slot].reshape(bk, buf.shape[-1])
                           for buf in bufs)                 # (bk, width)
            s = jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())),
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
                p.astype(bufs[-1].dtype),
                vals[0] if vals else keys[:, :acc_ref.shape[-1]],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec)                             # (H, values)
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)
        slot_ref[0] = jax.lax.rem(slot0 + n_blocks, 2)
        o_ref[0] = (emit(acc_ref[...]) / l_ref[:, 0:1]).astype(o_ref.dtype)


def _paged_decode(q, arenas, page_table, lengths, *, page_size, width,
                  out_width, out_dtype, table_leads, heads, scale, name,
                  interpret):
    """The walk as one ``pallas_call``: ``q`` (B, H, lanes), one block a
    stream; ``arenas`` one (its rows key and value) or a key and a value
    arena, each seen as (pages, ``page_size``, ``width``); ``page_table``
    (B, P); ``lengths`` (B,). Returns (B, H, ``out_width``) of
    ``out_dtype``. The accumulator is as wide as the values: the rows of
    a value arena, or the leading ``out_width`` lanes of the one arena."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, _ = q.shape
    table_w = page_table.shape[1]
    ppb = pages_per_block(page_size, width, arenas[0].dtype.itemsize, table_w)
    lengths = lengths.astype(jnp.int32)
    # live[r]: the first row at or after r that has tokens (b: none)
    rows = jnp.arange(b, dtype=jnp.int32)
    live = jax.lax.cummin(jnp.where(lengths > 0, rows, jnp.int32(b)),
                          reverse=True)
    live = jnp.concatenate([live, jnp.full((1,), b, jnp.int32)])
    n = len(arenas)
    kernel = functools.partial(_walk, n_arenas=n, table_leads=table_leads,
                               heads=heads, scale=scale, page_size=page_size,
                               ppb=ppb, table_w=table_w, batch=b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1,) + q.shape[1:], lambda bi, *_: (bi, 0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n,
        out_specs=pl.BlockSpec((1, h, out_width), lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppb, page_size, width), a.dtype)
                        for a in arenas] + [
            pltpu.SemaphoreType.DMA((n, 2) if n > 1 else (2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, width if n > 1 else out_width), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ],
    )
    table = page_table.astype(jnp.int32)
    with _x32_mode():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, out_width), out_dtype),
            # the buffer slot and the copies in flight carry over from
            # one row to the next: rows run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=name,
        )(*((table, lengths) if table_leads
            else (lengths, table.reshape(-1))), live, q,
          *(a.reshape(-1, page_size, width) for a in arenas))


# each a jit of its own: the sites of a forward's programs (a LongCat
# double layer has two) then trace and lower the kernel once, which is
# most of what a kernel adds to a serving process's set-up
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
    b, h, _, d = q.shape
    kv = k_arena.shape[1]
    sublanes = _sublanes(q.dtype)
    hp = -(-h // sublanes) * sublanes
    q = jnp.pad(q.reshape(b, h, d), ((0, 0), (0, hp - h), (0, 0)))
    out = _paged_decode(
        q, (k_arena, v_arena), page_table, lengths, page_size=page_size,
        width=kv * d, out_width=d, out_dtype=q.dtype, table_leads=True,
        heads=functools.partial(_grouped_heads, kv=kv, rep=h // kv),
        scale=scale, name=None, interpret=interpret)
    return out[:, :h].reshape(b, h, 1, d)


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
    _, page_size, width = arena.shape
    return _paged_decode(
        q, (arena,), page_table, lengths, page_size=page_size, width=width,
        out_width=out_width, out_dtype=q.dtype, table_leads=False,
        heads=_plain_heads, scale=scale, name="mla_paged_decode",
        interpret=interpret)


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
    _, page_size, width = k_arena.shape
    return _paged_decode(
        q_wide, (k_arena, v_arena), page_table, lengths, page_size=page_size,
        width=width, out_width=width, out_dtype=jnp.float32,
        table_leads=False, heads=_plain_heads, scale=scale,
        name="diff_paged_decode", interpret=interpret)
