"""Differential attention over narrow heads in pairs, as hybrid decoders
with one shared full-attention cache use it (SambaY, arXiv:2507.06607):
through a sliding window's ring, through the pages of the ONE paged K/V
cache several layers read, and the combination of a pair's two softmax
outputs. Pure JAX but for the paged read, which on the TPU is the kernel
``diff_paged_decode_kernel`` of ``pallas_kernels/paged_attention.py``. The equations are written
out in ``benchmarks/references/phi4flash.py``.

**Heads.** ``Hq`` query heads and ``Hkv`` key / value heads of ``d``
values, ``g = Hq / Hkv``. Heads pair as ``(i, j)``, ``j`` in {0, 1}:
query head ``2i + j``, key / value head ``2p + j``; query pair ``i``
reads key / value pair ``p = i // g``; a pair's value is its two value
heads side by side, ``V_p`` (2d wide). ``O_(i,j) = softmax(q_(i,j)
K_(p,j)^T * scale + mask) V_p``. The attention ops here return the
**paired outputs** ``(..., Hkv / 2, g, 2, 2d)`` (indexed ``[p, i - g p,
j]``), float32; :func:`diff_attention_combine` turns them into a layer's
``(..., Hq / 2 * 2d)`` attention output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

_MASKED = -1e30


def _paired(q, k, v):
    """``q`` (B, L, Hq, d), ``k`` / ``v`` (B, T, Hkv, d) -> q (B, L, P,
    g, 2, d), k (B, T, P, 2, d), v (B, T, P, 2d)."""
    b, l, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    p, g = hkv // 2, hq // hkv
    return (q.reshape(b, l, p, g, 2, d), k.reshape(b, t, p, 2, d),
            v.reshape(b, t, p, 2 * d))


def _attend_parts(q, parts, scale):
    """Softmax attention of paired queries ``q`` (B, L, P, g, 2, d) over
    the concatenation of ``parts``: (k (B, T, P, 2, d), v (B, T, P, 2d),
    mask (B, L, T)) each, without concatenating keys or values. Returns
    the paired outputs (B, L, P, g, 2, 2d), float32."""
    f32 = jnp.float32
    scores = []
    for k, _, mask in parts:
        s = jnp.einsum("blpijd,btpjd->bpijlt", q, k,
                       preferred_element_type=f32) * f32(scale)
        scores.append(jnp.where(mask[:, None, None, None], s, f32(_MASKED)))
    s = jnp.concatenate(scores, axis=-1) if len(scores) > 1 else scores[0]
    prob = jax.nn.softmax(s, axis=-1)
    out, at = 0.0, 0
    for _, v, _ in parts:
        t = v.shape[1]
        out = out + jnp.einsum("bpijlt,btpe->blpije",
                               prob[..., at:at + t].astype(v.dtype), v,
                               preferred_element_type=f32)
        at += t
    return out


@register("_contrib_diff_attention_combine",
          aliases=["diff_attention_combine"])
def diff_attention_combine(paired, lambda_q1, lambda_k1, lambda_q2,
                           lambda_k2, subln_weight, *, lambda_init,
                           eps=1e-5):
    """A differential pair's output from its two softmax outputs:

        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
        o_i = (1 - lambda_init) * rmsnorm(O_(i,0) - lam * O_(i,1)) * gain

    ``paired`` (..., P, g, 2, 2d) as the attention ops here return it;
    the four lambda vectors (d,); ``subln_weight`` (2d,) the sub-norm's
    gain. Returns (..., P * g * 2d) in the gain's dtype: the pairs'
    outputs side by side in pair order, ready for the out-projection.
    Float32 inside."""
    f32 = jnp.float32
    lam = (jnp.exp(jnp.sum(lambda_q1.astype(f32) * lambda_k1.astype(f32)))
           - jnp.exp(jnp.sum(lambda_q2.astype(f32) * lambda_k2.astype(f32)))
           + f32(lambda_init))
    paired = paired.astype(f32)
    diff = paired[..., 0, :] - lam * paired[..., 1, :]
    norm = diff * jax.lax.rsqrt(
        jnp.mean(diff * diff, axis=-1, keepdims=True) + f32(eps))
    out = norm * subln_weight.astype(f32) * f32(1.0 - lambda_init)
    return out.reshape(out.shape[:-3] + (-1,)).astype(subln_weight.dtype)


def ring_positions(first, window):
    """The position each ring index holds before a dispatch whose first
    position is ``first`` (B,): the largest ``p < first`` with ``p %
    window == index`` (negative: the index was never written)."""
    idx = jnp.arange(window, dtype=jnp.int32)
    last = first[:, None] - 1
    return last - jnp.mod(last - idx[None], window)


@register("_contrib_ring_window_attention",
          aliases=["ring_window_attention"])
def ring_window_attention(query, key, value, ring_k, ring_v, positions,
                          lengths, *, window, scale):
    """Sliding-window attention of a dispatch's rows over a ring of the
    last ``window`` cached tokens plus the dispatch's own tokens.

    ``query`` (B, L, Hq, d), ``key`` / ``value`` (B, L, Hkv, d): the
    dispatch's rows at ``positions`` (B, L), consecutive from
    ``positions[:, 0]``; ``ring_k`` / ``ring_v`` (B, window, Hkv * d):
    each stream's ring as it stood BEFORE the dispatch, token ``p`` at
    index ``p % window``; ``lengths`` (B,): a position at or beyond it is
    padding. Query ``t`` sees keys ``t - window < s <= t``. Returns the
    paired outputs (B, L, P, g, 2, 2d), float32 (padding rows: numbers
    that mean nothing).

    ``L <= window`` or ``L`` not a multiple of it: one block, every query
    against ring + dispatch. Else blocks of ``window`` queries, each
    against the ``2 * window`` keys that can reach it (the block before
    it, which for the first block is the ring, and its own): what makes
    a chunk's cost linear in its length."""
    b, l, hq, d = query.shape
    hkv = key.shape[2]
    w = int(window)
    first = positions[:, 0]
    q, k, v = _paired(query, key, value)
    rk = ring_k.reshape(b, w, hkv // 2, 2, d)
    rv = ring_v.reshape(b, w, hkv // 2, 2 * d)
    ring_pos = ring_positions(first, w)                     # (B, W)
    real = (positions >= 0) & (positions < lengths[:, None])

    def visible(q_pos, k_pos, k_real):
        return ((k_pos[:, None, :] <= q_pos[:, :, None])
                & (k_pos[:, None, :] > q_pos[:, :, None] - w)
                & k_real[:, None, :])

    if l <= w or l % w:
        return _attend_parts(q, [
            (rk, rv, visible(positions, ring_pos, ring_pos >= 0)),
            (k, v, visible(positions, positions, real))], scale)
    outs = []
    for j in range(l // w):
        rows = slice(j * w, (j + 1) * w)
        before = ((rk, rv, ring_pos, ring_pos >= 0) if j == 0 else
                  (k[:, (j - 1) * w:j * w], v[:, (j - 1) * w:j * w],
                   positions[:, (j - 1) * w:j * w],
                   real[:, (j - 1) * w:j * w]))
        outs.append(_attend_parts(q[:, rows], [
            (before[0], before[1],
             visible(positions[:, rows], before[2], before[3])),
            (k[:, rows], v[:, rows],
             visible(positions[:, rows], positions[:, rows],
                     real[:, rows]))], scale))
    return jnp.concatenate(outs, axis=1)


@register("_contrib_diff_attention", aliases=["diff_attention"])
def diff_attention(query, key, value, *, window=0, scale):
    """Causal paired attention over whole sequences (no cache): ``query``
    (B, L, Hq, d), ``key`` / ``value`` (B, L, Hkv, d); ``window`` > 0:
    query ``t`` sees ``t - window < s <= t``. Returns the paired outputs
    (B, L, P, g, 2, 2d), float32."""
    b, l = query.shape[:2]
    pos = jnp.arange(l, dtype=jnp.int32)
    seen = pos[None, :] <= pos[:, None]
    if window:
        seen &= pos[None, :] > pos[:, None] - int(window)
    q, k, v = _paired(query, key, value)
    return _attend_parts(q, [(k, v, jnp.broadcast_to(seen, (b, l, l)))],
                         scale)


def spread_queries(query, n_kv_heads, heads_to=None):
    """``query`` (B, Hq, d) spread over a key row: (B, H, Hkv * d) with
    head ``2i + j``'s values in the lanes of key head ``2 (i // g) + j``
    and zeros elsewhere; ``heads_to`` pads H with zero rows."""
    b, hq, d = query.shape
    g = hq // n_kv_heads
    r = jnp.arange(hq)
    kv_head = 2 * (r // (2 * g)) + r % 2
    wide = (query[:, :, None, :]
            * jax.nn.one_hot(kv_head, n_kv_heads,
                             dtype=query.dtype)[None, :, :, None])
    wide = wide.reshape(b, hq, n_kv_heads * d)
    if heads_to is not None and heads_to > hq:
        wide = jnp.pad(wide, ((0, 0), (0, heads_to - hq), (0, 0)))
    return wide


def paired_from_wide(out_wide, n_q_heads, n_kv_heads):
    """The paired outputs (B, P, g, 2, 2d) from (B, H, Hkv * d) rows of
    "probabilities times the whole value row": head ``2i + j`` keeps the
    lanes of value pair ``i // g``."""
    b, _, width = out_wide.shape
    p, g = n_kv_heads // 2, n_q_heads // n_kv_heads
    o = out_wide[:, :n_q_heads].reshape(b, p, g, 2, p, width // p)
    return jnp.einsum("bpijqe,pq->bpije", o,
                      jnp.eye(p, dtype=out_wide.dtype))


def _diff_paged_reference(q_wide, k_arena, v_arena, page_table, lengths,
                          scale):
    """The kernel's oracle and the path off the TPU: gather every page
    the tables reach, (B, H, width) float32 out."""
    b, _, width = q_wide.shape
    keys = jnp.take(k_arena, page_table, axis=0, mode="clip").reshape(
        b, -1, width)
    vals = jnp.take(v_arena, page_table, axis=0, mode="clip").reshape(
        b, -1, width)
    s = jnp.einsum("bhc,btc->bht", q_wide, keys,
                   preferred_element_type=jnp.float32) * scale
    key_pos = jnp.arange(keys.shape[1], dtype=jnp.int32)
    s = jnp.where(key_pos[None, None, :] < lengths[:, None, None], s,
                  jnp.float32(_MASKED))
    prob = jax.nn.softmax(s, axis=-1).astype(vals.dtype)
    out = jnp.einsum("bht,btc->bhc", prob, vals,
                     preferred_element_type=jnp.float32)
    return jnp.where(lengths[:, None, None] > 0, out, 0.0)


@register("_contrib_diff_paged_attention", aliases=["diff_paged_attention"])
def diff_paged_attention(query, k_arena, v_arena, page_table, lengths, *,
                         n_kv_heads, scale):
    """One-token paired attention through a page table: the shared-cache
    read. ``query`` (B, Hq, d), one row a stream; ``k_arena`` /
    ``v_arena`` (pages, page, >= Hkv * d): a token's ``n_kv_heads`` key
    heads side by side in one row (lane padding behind them), its value
    heads in another; ``page_table`` (B, P);
    ``lengths`` (B,) live tokens a row, the query's own included.
    Returns the paired outputs (B, P, g, 2, 2d), float32; zeros for a row
    of length 0.

    On the TPU, at eligible shapes, the Pallas kernel of
    pallas_kernels/paged_attention.py (``diff_paged_decode_kernel``), which reads a stream's LIVE
    pages in place; otherwise :func:`_diff_paged_reference`, which
    gathers the table's whole width. Routed by platform and shapes alone.
    A sliding window's ring goes through the same op as ``window /
    page`` pages a stream (attention does not care about the rows'
    order)."""
    from ..pallas_kernels.paged_attention import (
        diff_paged_decode_kernel, diff_paged_supported)

    hq, d = query.shape[1], query.shape[2]
    hkv = int(n_kv_heads)
    itemsize = jnp.dtype(query.dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    q_wide = spread_queries(query, hkv, -(-hq // sublanes) * sublanes)
    q_wide = jnp.pad(q_wide, ((0, 0), (0, 0),
                              (0, k_arena.shape[-1] - hkv * d)))
    if diff_paged_supported(q_wide, k_arena, v_arena):
        from .. import telemetry

        telemetry.record_pallas_dispatch("diff_paged_decode")
        out = diff_paged_decode_kernel(q_wide, k_arena, v_arena, page_table,
                                       lengths, scale=float(scale))
    else:
        out = _diff_paged_reference(q_wide, k_arena, v_arena, page_table,
                                    lengths, scale)
    return paired_from_wide(out[..., :hkv * d], hq, hkv)
