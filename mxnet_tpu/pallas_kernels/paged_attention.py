"""Paged single-query (decode) attention kernel.

The decode step of an autoregressive request attends one query row
against every cached K/V token of that request, where the cache lives
in fixed-size pages of a shared arena (:mod:`mxnet_tpu.serving.kvcache`)
addressed through a per-request page table. The kernel is the
vLLM-style shape of that read: grid ``(batch, n_pages)``, the page
table scalar-prefetched so the BlockSpec index map steers each grid
step's DMA straight at the right arena page — no gather materializes,
no (batch, max_len) K/V copy exists, and VMEM holds one page of K and V
per step. Online softmax accumulates across the page axis exactly like
the flash kernels (f32 statistics, rescale-by-alpha per block).

Eligibility mirrors flash_attention: ``paged_supported`` gates on TPU
execution (``base.current_execution_platform``) plus Mosaic-friendly
shapes — head_dim a multiple of 128 and page_size a multiple of 8 (the
(sublane, lane) tile of an f32 page block). The eager gather in
``ops/attention.py`` (``_contrib_paged_attention``'s reference path) is
the bit-oracle; CPU tests run this kernel in ``interpret=True`` mode
against it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from .flash_attention import _NEG_INF32, _prec_for, _x32_mode

__all__ = ["paged_attention_kernel", "paged_supported",
           "paged_shape_supported"]


def paged_shape_supported(q, k_arena, page_size: int) -> bool:
    """Platform-independent shape eligibility: one query row per batch
    element, f32-tileable page blocks, and a head grouping the MXU can
    contract without relayout."""
    if q.ndim != 4 or q.shape[2] != 1:
        return False            # decode kernel: exactly one query row
    d = q.shape[-1]
    h = q.shape[1]
    kv = k_arena.shape[-2]
    if d % 128 or d != k_arena.shape[-1]:
        return False
    if page_size % 8 or k_arena.shape[0] % page_size:
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


def _decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, page_size, n_pages_req,
                   h, kv, d):
    """One (batch row, page) grid step: score the query heads against
    this page's keys, fold into the online-softmax accumulator, emit on
    the last page.

    The page arrives as a lane-dense ``(page, KV*D)`` tile. GQA runs as
    two plain 2-D MXU contractions over that tile: the query is expanded
    to ``(H, KV*D)`` with every head's row zero outside its own kv
    group's lanes, so ``q_exp @ k.T`` is exactly the per-group score,
    and ``p @ v`` accumulates an ``(H, KV*D)`` tile whose own-group
    lanes are selected at emit time. (Mosaic legalises neither a
    ``dot_general`` with batch dimensions in different positions nor
    1-D vectors; statistics live as lane-broadcast ``(H, 128)`` tiles.)
    """
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    j = pl.program_id(1)
    rep = h // kv
    n_valid = len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF32)
        l_ref[...] = jnp.zeros_like(l_ref)

    # own[h, c]: lane c of the (KV*D)-wide tile belongs to head h's group
    own = (jax.lax.broadcasted_iota(jnp.int32, (h, kv * d), 1) // d
           == jax.lax.broadcasted_iota(jnp.int32, (h, kv * d), 0) // rep)

    # a page wholly past the row's length (the scratch-padded tail of a
    # short request, or a padding row) contributes nothing
    @pl.when(j * page_size < n_valid)
    def _page():
        q = q_ref[0]                                        # (H, D)
        q_exp = jnp.where(own, jnp.concatenate([q] * kv, axis=1),
                          jnp.zeros((), q.dtype))           # (H, KV*D)
        prec = _prec_for(q.dtype)
        s = jax.lax.dot_general(
            q_exp, k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec) * _np.float32(scale)            # (H, ps)
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (h, page_size), 1)
        valid = pos < n_valid
        s = jnp.where(valid, s, _NEG_INF32)
        m_prev = m_ref[:, 0:1]                              # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), _np.float32(0.0))
        l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)                                 # (H, KV*D)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_pages_req - 1)
    def _emit():
        acc = jnp.where(own, acc_ref[...], _np.float32(0.0))
        out = acc[:, 0:d]
        for g in range(1, kv):
            out = out + acc[:, g * d:(g + 1) * d]
        l = l_ref[:, 0:1]
        l = jnp.where(l == 0.0, _np.float32(1.0), l)  # padding row: no key
        o_ref[0] = (out / l).astype(o_ref.dtype)


def paged_attention_kernel(q, k_arena, v_arena, page_table, lengths, *,
                           page_size: int, scale: float,
                           interpret: bool = False):
    """Decode attention over paged K/V.

    ``q``: (B, H, 1, D); ``k_arena``/``v_arena``: (slots, KV, D) — ONE
    layer's arena; ``page_table``: (B, P) int32 page ids (scratch page 0
    pads the tail); ``lengths``: (B,) int32 valid tokens per row.
    Returns (B, H, 1, D) in q's dtype.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, _, d = q.shape
    slots, kv, _ = k_arena.shape
    n_pages_req = page_table.shape[1]
    kernel = functools.partial(
        _decode_kernel, scale=float(scale), page_size=int(page_size),
        n_pages_req=int(n_pages_req), h=h, kv=kv, d=d)
    row_spec = pl.BlockSpec((1, h, d), lambda bi, j, pt, ln: (bi, 0, 0))
    # the scalar-prefetched page table steers each step's DMA: block
    # index IS the page id (block size = one page)
    page_spec = pl.BlockSpec((page_size, kv * d),
                             lambda bi, j, pt, ln: (pt[bi, j], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages_req),
        in_specs=[row_spec, page_spec, page_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((h, kv * d), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ],
    )
    with _x32_mode():
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
            interpret=interpret,
        )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
          q.reshape(b, h, d), k_arena.reshape(slots, kv * d),
          v_arena.reshape(slots, kv * d))
    return out.reshape(b, h, 1, d)
