"""Fused attention ops.

Reference: ``src/operator/contrib/transformer.cc`` — MXNet's fused attention
is a pair of batched-matmul kernels (`_contrib_interleaved_matmul_selfatt_qk`
/ `..._valatt`) used by GluonNLP's Transformer/BERT. The TPU-native design
exposes ONE fused scaled-dot-product attention op instead: softmax statistics
in f32, bf16 matmuls on the MXU, and a single seam where the Pallas
flash-attention kernel (mxnet_tpu.pallas_kernels) replaces the reference
path on TPU for long sequences.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .registry import register


def _sdpa_reference(q, k, v, mask, scale, causal, layout="bhld",
                    dropout=0.0, seed=None):
    """f32-softmax attention. layout "bhld": (B, H, L, D); "blhd":
    (B, L, H, D) — head transposes fold into the einsum contractions.

    ``dropout``: attention-probability dropout using the SAME stateless
    position-hash mask as the Pallas flash kernels (bitwise identical
    given the same seed) — this path is the kernels' dense oracle."""
    dtype = q.dtype
    if layout == "blhd":
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    else:
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    scores = scores.astype(jnp.float32) * scale
    if causal:
        lq, lk = scores.shape[-2], scores.shape[-1]
        causal_mask = jnp.tril(jnp.ones((lq, lk), dtype=bool), k=lk - lq)
        scores = jnp.where(causal_mask, scores, jnp.float32(-1e9))
    if mask is not None:
        # mask: 1 = attend, 0 = ignore; broadcastable to (B, H, Lq, Lk)
        m = jnp.broadcast_to(mask.astype(bool), scores.shape)
        scores = jnp.where(m, scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout > 0.0:
        from ..pallas_kernels.flash_attention import (_drop_mask,
                                                      dropout_thresh)

        b, h, lq, lk = probs.shape
        shp = probs.shape
        head = (jax.lax.broadcasted_iota(jnp.int32, shp, 0) * h
                + jax.lax.broadcasted_iota(jnp.int32, shp, 1))
        qp = jax.lax.broadcasted_iota(jnp.int32, shp, 2)
        kp = jax.lax.broadcasted_iota(jnp.int32, shp, 3)
        keep = _drop_mask(head, qp, kp, lq, lk,
                          jnp.asarray(seed, jnp.uint32).reshape(-1)[0],
                          dropout_thresh(float(dropout)))
        probs = jnp.where(keep,
                          probs * jnp.float32(1.0 / (1.0 - dropout)), 0.0)
    probs = probs.astype(dtype)
    if layout == "blhd":
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@register("_contrib_sdp_attention", aliases=["sdp_attention"],
          needs_rng=True, pass_training_flag=True,
          rng_gate=lambda attrs: bool(attrs.get("dropout"))
          and bool(attrs.get("_training")))
def sdp_attention(rng, query, key, value, mask=None, *, scale=None,
                  causal=False, flash=True, layout="bhld", ring_axis=None,
                  dropout=0.0, _training=False):
    """Scaled dot-product attention.

    ``layout``: "bhld" (batch, heads, seq, head_dim) or "blhd" (batch, seq,
    heads, head_dim). blhd runs the XLA einsum path (head transposes fold
    into the contractions); the Pallas kernel currently takes bhld only —
    Mosaic cannot tile a per-head (seq, head_dim) block of a blhd array
    (squeezed H lands in sublane position), see flash_shape_supported.

    ``flash=True`` routes to the Pallas flash kernel on TPU when the shape
    qualifies (seq multiple of block size); otherwise the XLA reference path
    runs (which XLA fuses well on its own for short sequences).

    ``dropout``: attention-probability dropout (reference capability:
    GluonNLP MultiHeadAttentionCell applies dropout to the attention
    weights). Training-mode only. Generated INSIDE the flash kernels from
    a stateless position hash (pallas_kernels.flash_attention._drop_mask)
    seeded from this op's PRNG key; the reference/scan paths use the
    bitwise-identical mask, so every dispatch route drops the same
    elements for a given key.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(query.shape[-1])
    p_drop = float(dropout) if _training else 0.0
    seed = None
    if p_drop > 0.0:
        from ..pallas_kernels.flash_attention import fold_key_seed

        seed = fold_key_seed(rng)
    from ..parallel.ring_attention import ring_active

    if ring_axis is not None and mask is None and ring_active(ring_axis):
        # sequence-parallel exact attention over the mesh ring; when no
        # mesh/axis is active we fall through to the normal flash/
        # reference dispatch below instead of pinning the dense path
        from ..parallel.ring_attention import ring_attention

        if p_drop > 0.0:
            raise ValueError(
                "sdp_attention: attention dropout is not supported with "
                "ring (sequence-parallel) attention — the per-pair mask "
                "would need globally-consistent positions across shards")
        if layout == "blhd":
            out = ring_attention(query.transpose(0, 2, 1, 3),
                                 key.transpose(0, 2, 1, 3),
                                 value.transpose(0, 2, 1, 3),
                                 axis=ring_axis, causal=causal, scale=scale)
            return out.transpose(0, 2, 1, 3)
        return ring_attention(query, key, value, axis=ring_axis,
                              causal=causal, scale=scale)
    if flash and mask is None:
        from ..pallas_kernels import (flash_attention, flash_attention_scan,
                                      flash_supported)

        if flash_supported(query, key, value, causal=causal, layout=layout):
            from .. import telemetry

            telemetry.record_pallas_dispatch("flash_attention")
            return flash_attention(query, key, value, scale=scale,
                                   causal=causal, layout=layout,
                                   dropout=p_drop, seed=seed)
        seq_ax = 1 if layout == "blhd" else -2
        if key.shape[seq_ax] >= 2048:
            # long sequence off-TPU: O(L) memory blockwise path
            if layout == "blhd":
                out = flash_attention_scan(
                    query.transpose(0, 2, 1, 3), key.transpose(0, 2, 1, 3),
                    value.transpose(0, 2, 1, 3), scale=scale, causal=causal,
                    dropout=p_drop, seed=seed)
                return out.transpose(0, 2, 1, 3)
            return flash_attention_scan(query, key, value, scale=scale,
                                        causal=causal, dropout=p_drop,
                                        seed=seed)
    return _sdpa_reference(query, key, value, mask, scale, causal,
                           layout=layout, dropout=p_drop, seed=seed)


@register("_contrib_rms_norm", aliases=["rms_norm"])
def rms_norm(data, weight, *, eps=1e-6):
    """RMSNorm (no reference counterpart — Llama-era op, SURVEY.md §5.7).
    Statistics in f32, output in compute dtype. Under
    ``MXNET_PALLAS_FUSED=1`` + shape/platform gates the Pallas one-pass
    kernel takes it (pallas_kernels/fused_layers.py, RMS mode): the
    Llama blocks adopt the fused-layer path through this seam without
    any model change."""
    from ..pallas_kernels.fused_layers import (fused_layers_enabled,
                                               fused_ln_supported)

    if fused_layers_enabled() and fused_ln_supported(data):
        from .. import telemetry
        from ..pallas_kernels.fused_layers import fused_rms_norm

        telemetry.record_pallas_dispatch("fused_rms_norm")
        return fused_rms_norm(data, weight, eps=eps)
    x32 = data.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(data.dtype) * weight


def _paged_reference(q, k_arena, v_arena, page_table, lengths,
                     q_positions, page_size, scale):
    """Eager paged attention: gather K/V rows through the page table,
    then masked f32-softmax attention. The CPU oracle for the Pallas
    paged kernel, and the decode path everywhere off-TPU."""
    b, h, lq, d = q.shape
    kv = k_arena.shape[-2]
    ps = int(page_size)
    # flat slot indices for every token position the tables can reach:
    # token i of row b lives at page_table[b, i//ps]*ps + i%ps
    slots = (page_table[:, :, None] * ps
             + jnp.arange(ps, dtype=page_table.dtype)[None, None, :])
    slots = slots.reshape(b, -1)                        # (B, T)
    k = jnp.take(k_arena, slots, axis=0)                # (B, T, KV, D)
    v = jnp.take(v_arena, slots, axis=0)
    if kv != h:
        rep = h // kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    k = k.transpose(0, 2, 1, 3)                         # (B, H, T, D)
    v = v.transpose(0, 2, 1, 3)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    scores = scores.astype(jnp.float32) * scale
    key_pos = jnp.arange(slots.shape[1], dtype=jnp.int32)
    # causal over the request's own timeline: key position <= query
    # position (which is <= length-1 for every real row). A padding row
    # (length 0, position 0) sees only scratch key 0 — garbage, sliced
    # away by the batcher before any caller looks.
    mask = key_pos[None, None, None, :] <= \
        q_positions[:, None, :, None]
    mask = mask & (key_pos[None, None, None, :]
                   < lengths[:, None, None, None])
    scores = jnp.where(mask, scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@register("_contrib_paged_attention", aliases=["paged_attention"])
def paged_attention(query, k_arena, v_arena, page_table, lengths,
                    q_positions=None, *, page_size, scale=None):
    """Attention over a paged KV cache (serving decode path).

    ``query``: (B, H, Lq, D); ``k_arena``/``v_arena``: (slots, KV, D) —
    ONE layer's arena from :func:`mxnet_tpu.serving.kvcache.make_kv_arena`;
    ``page_table``: (B, P) int32 page ids (scratch page 0 pads the
    tail); ``lengths``: (B,) int32 tokens valid per row INCLUDING the
    current query tokens; ``q_positions``: (B, Lq) absolute positions of
    the query rows (default: the trailing positions, i.e.
    ``lengths - Lq + arange(Lq)`` — the decode/prefill common case).

    Under ``MXNET_PALLAS_FUSED=1`` the single-query decode shape routes
    to the Pallas paged kernel on TPU when eligible
    (pallas_kernels/paged_attention.py); everything else runs the eager
    gather, which doubles as the kernel's bit-oracle.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(query.shape[-1])
    lq = query.shape[2]
    if q_positions is None:
        q_positions = (lengths[:, None] - lq
                       + jnp.arange(lq, dtype=lengths.dtype)[None, :])
    from ..pallas_kernels.fused_layers import fused_layers_enabled
    from ..pallas_kernels.paged_attention import (paged_attention_kernel,
                                                  paged_supported)

    if lq == 1 and fused_layers_enabled() \
            and paged_supported(query, k_arena, page_size):
        from .. import telemetry

        telemetry.record_pallas_dispatch("paged_attention")
        return paged_attention_kernel(query, k_arena, v_arena,
                                      page_table, lengths,
                                      page_size=page_size, scale=scale)
    return _paged_reference(query, k_arena, v_arena, page_table, lengths,
                            q_positions, page_size, scale)


def _rotate_pairs(data, cos, sin, interleaved):
    """Rotate the (x1, x2) pairs of ``data`` (B, L, H, D) by the angle
    tables ``cos``/``sin`` (broadcastable to (B, L, 1, D/2)), in f32.

    The pairs are taken as a reshape and joined by stack + reshape. The
    textbook form — slice the two halves, concatenate the results —
    aborts the TPU compiler when the op is a jit of its own (libtpu
    0.0.34: "Check failed: IsFusibleUnalignedDUS"), which is how the
    eager path runs it; the arithmetic is the same either way."""
    b, l, h, d = data.shape
    x = data.astype(jnp.float32)
    if interleaved:
        pairs = x.reshape(b, l, h, d // 2, 2)
        x1, x2, axis = pairs[..., 0], pairs[..., 1], -1
    else:
        pairs = x.reshape(b, l, h, 2, d // 2)
        x1, x2, axis = pairs[..., 0, :], pairs[..., 1, :], -2
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    return jnp.stack([r1, r2], axis=axis).reshape(b, l, h, d) \
        .astype(data.dtype)


def rope_at(data, positions, *, theta=10000.0, interleaved=False):
    """:func:`rope` with explicit per-row absolute positions —
    ``positions`` (B, L) int — the decode-step form, where every row of
    the batch sits at a different depth of its own sequence. Bitwise
    identical to :func:`rope` when
    ``positions == offset + arange(L)`` broadcast over the batch (the
    cos/sin tables are built from positions the same way)."""
    d = data.shape[-1]
    pos = positions.astype(jnp.float32)                  # (B, L)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = pos[:, :, None] * inv_freq[None, None, :]   # (B, L, D/2)
    return _rotate_pairs(data, jnp.cos(angles)[:, :, None, :],
                         jnp.sin(angles)[:, :, None, :], interleaved)


@register("_contrib_rope", aliases=["rope"])
def rope(data, *, theta=10000.0, position_offset=0, interleaved=False):
    """Rotary position embedding over (B, L, H, D).

    Default is the true rotate-half convention (Llama / HF checkpoints):
    the head dim is split into first/second halves and rotated as
    ``concat(x1*cos - x2*sin, x2*cos + x1*sin)``, so weights ported from
    Llama-family checkpoints produce identical activations.
    ``interleaved=True`` selects the GPT-J/NeoX even-odd pair convention.
    Computed in-graph from positions — no host-side tables."""
    l, d = data.shape[1], data.shape[-1]
    pos = jnp.arange(position_offset, position_offset + l,
                     dtype=jnp.float32)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = pos[:, None] * inv_freq[None, :]            # (L, D/2)
    return _rotate_pairs(data, jnp.cos(angles)[None, :, None, :],
                         jnp.sin(angles)[None, :, None, :], interleaved)


# ---------------------------------------------------------------------------
# Multi-head latent attention (MLA): keys and values of all heads are
# up-projections of ONE low-rank latent per token, and one rotary key is
# shared by every head. The cache holds (latent, rotated k_rope) only.
# ---------------------------------------------------------------------------

# tokens whose (H, L, L) scores one step of prefill attention holds at once
_MLA_TOKEN_BUDGET = 2048


def _row_chunk(b, l):
    """Largest divisor of ``b`` whose rows hold <= the token budget."""
    rows = max(1, min(b, _MLA_TOKEN_BUDGET // max(l, 1)))
    while b % rows:
        rows -= 1
    return rows


@register("_contrib_mla_attention", aliases=["mla_attention"])
def mla_attention(query, latent, k_rope, kvb_weight, *, nope_dim, v_dim,
                  scale):
    """Causal MLA over whole sequences, keys and values EXPANDED from the
    latent (the prefill form: every query attends only to tokens of its
    own row, so nothing is read from a cache).

    ``query`` (B, L, H, nope + rope), rotated and scaled by the caller;
    ``latent`` (B, L, R) normalised latent ``c'``; ``k_rope`` (B, L, rope)
    the rotated key all heads share; ``kvb_weight`` (H * (nope + v), R)
    in ``Dense`` layout, head-major, each head ``[k_nope | v]``. Softmax
    in float32. Returns (B, L, H * v). Rows are attended a bounded
    number of tokens at a time, so the (rows, H, L, L) scores stay
    bounded whatever the batch bucket."""
    b, l, h, _ = query.shape
    kv = jnp.einsum("blr,or->blo", latent, kvb_weight).reshape(
        b, l, h, nope_dim + v_dim)
    k_nope, v = kv[..., :nope_dim], kv[..., nope_dim:]
    causal = jnp.tril(jnp.ones((l, l), dtype=bool))

    def attend(args):
        q, kn, kr, vv = args
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope_dim], kn,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q[..., nope_dim:], kr,
                               preferred_element_type=jnp.float32)) * scale
        scores = jnp.where(causal, scores, jnp.float32(-1e9))
        probs = jax.nn.softmax(scores, axis=-1).astype(vv.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)

    rows = _row_chunk(b, l)
    if rows == b:
        out = attend((query, k_nope, k_rope, v))
    else:
        def split(x):
            return x.reshape((b // rows, rows) + x.shape[1:])

        out = jax.lax.map(attend, (split(query), split(k_nope),
                                   split(k_rope), split(v)))
    return out.reshape(b, l, h * v_dim)


def _mla_paged_reference(q_full, arena, page_table, lengths, scale):
    """The absorbed attention of :func:`mla_paged_decode` by gather:
    ``q_full`` (B, H, width) against every slot the page tables reach,
    (B, H, width) out. Whole pages are gathered (one contiguous block
    each) and the gathered block is used at its full padded width: the
    query is zero-padded to it and the output cut back by the caller, so
    the block is never sliced or relaid. The oracle of the Pallas kernel
    and the path everywhere off the TPU."""
    b, _, width = q_full.shape
    # every page id of a table is a real page (0: scratch)
    cache = jnp.take(arena, page_table, axis=0, mode="clip")
    cache = cache.reshape(b, -1, width)                     # (B, T, width)
    scores = jnp.einsum("bhc,btc->bht", q_full, cache,
                        preferred_element_type=jnp.float32) * scale
    key_pos = jnp.arange(cache.shape[1], dtype=jnp.int32)
    scores = jnp.where(key_pos[None, None, :] < lengths[:, None, None],
                       scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(q_full.dtype)
    return jnp.einsum("bht,btc->bhc", probs, cache)


@register("_contrib_mla_paged_decode", aliases=["mla_paged_decode"])
def mla_paged_decode(query, arena, page_table, lengths, kvb_weight, *,
                     nope_dim, v_dim, scale):
    """One-token MLA over a paged LATENT cache, in the absorbed form: the
    key up-projection is folded into the query and the value
    up-projection into the output, so attention runs in the latent space
    and no key or value is ever expanded.

    ``query`` (B, H, nope + rope), rotated and scaled; ``arena``
    (pages, page, >= R + rope): ONE sublayer's latent arena
    (:func:`mxnet_tpu.serving.kvcache.make_latent_arena`), each token
    ``[c' | rotated k_rope | lane padding]``; ``page_table`` (B, P),
    ``lengths`` (B,) tokens valid per row, the query's own included;
    ``kvb_weight`` as in :func:`mla_attention`. Returns (B, H * v).

    On the TPU, at eligible shapes, the attention itself is the Pallas
    kernel of pallas_kernels/mla_paged_attention.py, which reads a
    stream's live pages from the arena in place; the folds stay here.
    Otherwise :func:`_mla_paged_reference`, the kernel's oracle. Routed
    by platform and shapes alone, as the experts' grouped matmul is
    (``contrib._grouped_matmul``): the served LongCat configuration
    does not set ``MXNET_PALLAS_FUSED``."""
    b, h, _ = query.shape
    r = kvb_weight.shape[-1]
    width = arena.shape[-1]
    w = kvb_weight.reshape(h, nope_dim + v_dim, r)
    w_uk, w_uv = w[:, :nope_dim], w[:, nope_dim:]
    # q_nope . (W_uk c) = (W_uk^T q_nope) . c
    q_lat = jnp.einsum("bhd,hdr->bhr", query[..., :nope_dim], w_uk)
    q_full = jnp.concatenate([q_lat, query[..., nope_dim:]], axis=-1)
    q_full = jnp.pad(q_full, ((0, 0), (0, 0), (0, width - q_full.shape[-1])))
    from ..pallas_kernels.mla_paged_attention import (
        mla_paged_decode_kernel, mla_paged_supported)

    if mla_paged_supported(q_full, arena):
        from .. import telemetry

        telemetry.record_pallas_dispatch("mla_paged_decode")
        o_lat = mla_paged_decode_kernel(
            q_full, arena, page_table, lengths, scale=scale,
            out_width=r if r % 128 == 0 else width)
    else:
        o_lat = _mla_paged_reference(q_full, arena, page_table, lengths,
                                     scale)
    return jnp.einsum("bhr,hvr->bhv", o_lat[..., :r],
                      w_uv).reshape(b, h * v_dim)
