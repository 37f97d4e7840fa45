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


def _sdp_bounded(query, key, value, mask, kv_len, scale, causal, flash,
                 layout, p_drop):
    """:func:`sdp_attention` with a per-row key length."""
    if causal or p_drop or mask is not None:
        raise ValueError("sdp_attention: kv_len goes with neither causal, "
                         "dropout nor a mask")
    if scale is None:
        scale = 1.0 / math.sqrt(query.shape[-1])
    if flash:
        from ..pallas_kernels import flash_attention, flash_supported

        if flash_supported(query, key, value, layout=layout) == 1:
            from .. import telemetry
            telemetry.record_pallas_dispatch("flash_attention")
            return flash_attention(query, key, value, scale=scale,
                                   layout=layout, kv_len=kv_len)
    lk = key.shape[1 if layout == "blhd" else -2]
    live = jnp.arange(lk)[None, :] < kv_len[:, None]          # (B, Lk)
    return _sdpa_reference(query, key, value, live[:, None, None, :], scale,
                           False, layout=layout)


@register("_contrib_sdp_attention", aliases=["sdp_attention"],
          needs_rng=True, pass_training_flag=True,
          rng_gate=lambda attrs: bool(attrs.get("dropout"))
          and bool(attrs.get("_training")))
def sdp_attention(rng, query, key, value, mask=None, kv_len=None, *,
                  scale=None, causal=False, flash=True, layout="bhld",
                  ring_axis=None, dropout=0.0, _training=False):
    """Scaled dot-product attention.

    ``layout``: "bhld" (batch, heads, seq, head_dim) or "blhd" (batch, seq,
    heads, head_dim). blhd runs the XLA einsum path (head transposes fold
    into the contractions); the Pallas kernel currently takes bhld only —
    Mosaic cannot tile a per-head (seq, head_dim) block of a blhd array
    (squeezed H lands in sublane position), see flash_shape_supported.

    ``flash=True`` routes to the Pallas flash kernel on TPU when the shape
    qualifies (seq multiple of block size), on each batch shard's rows under
    a data-parallel mesh; otherwise the XLA reference path runs.

    ``dropout``: attention-probability dropout (GluonNLP's
    MultiHeadAttentionCell drops attention weights). Training-mode only.
    Generated INSIDE the flash kernels from a stateless position hash
    (_drop_mask) seeded from this op's PRNG key; the reference/scan paths
    use the bitwise-identical mask, so every route drops the same elements.
    Over batch shards the kernel would hash shard-local (batch x head) ids,
    so with dropout the op gives way there.

    ``kv_len`` (B,) int: row ``b`` attends to its first ``kv_len[b]``
    keys only and its queries at or past ``kv_len[b]`` are padding (their
    output is zeros from the kernel and unspecified otherwise): a
    sequence padded to a bucket. Inference only (no causal, no dropout,
    no gradient). The flash kernel skips the key blocks past the length
    and masks the one that holds it; elsewhere the bound becomes a mask.
    """
    if kv_len is not None:
        return _sdp_bounded(query, key, value, mask, kv_len, scale, causal,
                            flash, layout, float(dropout) if _training
                            else 0.0)
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
        from ..parallel.mesh import over_batch_shards
        n = flash_supported(query, key, value, causal=causal, layout=layout)
        if n == 1 or n and not p_drop:
            from .. import telemetry
            telemetry.record_pallas_dispatch("flash_attention")
            return over_batch_shards(flash_attention, n, 3)(
                query, key, value, scale=scale, causal=causal, layout=layout,
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
    Llama blocks adopt the fused-layer path through this seam."""
    from ..pallas_kernels.fused_layers import (fused_layers_enabled,
                                               fused_ln_supported)
    from ..parallel.mesh import over_batch_shards
    shards = fused_layers_enabled() and fused_ln_supported(data)
    if shards:
        from .. import telemetry
        from ..pallas_kernels.fused_layers import fused_rms_norm
        telemetry.record_pallas_dispatch("fused_rms_norm")
        kernel = over_batch_shards(fused_rms_norm, shards, 1)
        return kernel(data, weight, eps=eps)
    x32 = data.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(data.dtype) * weight


def _paged_reference(q, k_arena, v_arena, page_table, lengths,
                     q_positions, page_size, scale, block=1):
    """Eager paged attention: gather K/V rows through the page table,
    then masked f32-softmax attention. The CPU oracle for the Pallas
    paged kernel, and the decode path everywhere off-TPU. ``block``: a
    query sees the keys of its own block of ``block`` positions and of
    every block before it (1: the causal mask)."""
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
    if block == 1:
        mask = key_pos[None, None, None, :] <= \
            q_positions[:, None, :, None]
    else:
        # block-causal: bidirectional inside a block of positions
        mask = key_pos[None, None, None, :] // block <= \
            q_positions[:, None, :, None] // block
    mask = mask & (key_pos[None, None, None, :]
                   < lengths[:, None, None, None])
    scores = jnp.where(mask, scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@register("_contrib_paged_attention", aliases=["paged_attention"])
def paged_attention(query, k_arena, v_arena, page_table, lengths,
                    q_positions=None, *, page_size, scale=None, block=1):
    """Attention over a paged KV cache (serving decode path).

    ``query``: (B, H, Lq, D); ``k_arena``/``v_arena``: (slots, KV, D) —
    a VIEW of ONE layer's (pages, page, KV * D) array (``make_latent_arena``);
    ``page_table``: (B, P) int32 page ids (scratch page 0 pads the
    tail); ``lengths``: (B,) int32 tokens valid per row INCLUDING the
    current query tokens; ``q_positions``: (B, Lq) absolute positions of
    the query rows (default: the trailing positions, i.e.
    ``lengths - Lq + arange(Lq)`` — the decode/prefill common case).

    ``block``: the mask is BLOCK-causal: a query sees every key whose
    block of ``block`` consecutive positions is its own or an earlier one
    (bidirectional inside a block; 1 is the causal mask, key <= query).

    On the TPU the decode shapes route to the Pallas paged kernel
    (``paged_attention_kernel`` of pallas_kernels/paged_attention.py): the
    single query of a causal decode step, and the ``Lq == block`` queries
    of a block step, which are a row's TRAILING block (positions
    ``lengths - block .. lengths - 1``, the caller's contract) and so
    all see every live key: they are folded into the head group (``rep``
    query heads a kv head become ``rep * block`` query rows over ONE walk
    of the stream's live pages). The kernel:
    grid ``(B,)``, a stream's LIVE pages copied from the two arenas by
    page-table-driven DMA a block of up to 512 tokens ahead and folded
    into a float32 online softmax; pages past ``lengths[b]`` are neither
    fetched nor computed and a row of length 0 emits zeros. Its custom
    call's first two operands are the int32 page table ``(B, P)`` and the
    int32 lengths ``(B,)``, in that order: the benchmark's trace readers
    key on them (``benchmarks/kernels/paged_attention.py::PATTERN``).
    Routed by platform and shapes alone (``paged_supported``), as the
    other paged kernels are: the gate refuses every other ``Lq`` (a
    forward of several positions THROUGH the cache: a chunk at an
    offset, a block-causal prefill; a Llama-family prefill that starts
    at position 0 does not come here at all, its layers attend over
    their own fresh keys and values: ``llama.py::_paged_forward``'s
    ``fresh`` form), a head_dim
    that is not whole 128-lane tiles, a page that is not whole sublane
    tiles of the arena's dtype (8 rows of float32, 16 of bfloat16), a
    query of another dtype than the arenas, a trace the SPMD partitioner
    splits, and anything off the TPU; all of that runs the eager gather,
    which doubles as the kernel's oracle.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(query.shape[-1])
    lq = query.shape[2]
    if q_positions is None:
        q_positions = (lengths[:, None] - lq
                       + jnp.arange(lq, dtype=lengths.dtype)[None, :])
    from ..pallas_kernels.paged_attention import (paged_attention_kernel,
                                                  paged_supported)

    if lq == 1 and paged_supported(query, k_arena, page_size):
        from .. import telemetry

        telemetry.record_pallas_dispatch("paged_attention")
        return paged_attention_kernel(query, k_arena, v_arena,
                                      page_table, lengths,
                                      page_size=page_size, scale=scale)
    if lq == block > 1:
        # a row's trailing block: every query sees every live key, so
        # the positions are further heads of their kv group
        b, h, _, d = query.shape
        folded = query.reshape(b, h * lq, 1, d)
        if paged_supported(folded, k_arena, page_size):
            from .. import telemetry

            telemetry.record_pallas_dispatch("paged_attention")
            return paged_attention_kernel(
                folded, k_arena, v_arena, page_table, lengths,
                page_size=page_size, scale=scale).reshape(b, h, lq, d)
    return _paged_reference(query, k_arena, v_arena, page_table, lengths,
                            q_positions, page_size, scale, block)


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


def yarn_inv_freq(d, theta, factor, beta_fast, beta_slow, original_max):
    """YaRN's rotary frequencies (arXiv:2309.00071, as DeepSeek-V3 uses
    it): frequency ``i`` of the ``d / 2`` is a blend of the plain
    ``theta^(-2i/d)`` and the same divided by ``factor``, over a linear
    ramp between the frequencies that make ``beta_fast`` and
    ``beta_slow`` turns in ``original_max`` positions: the fast ones
    stay as trained, the slow ones are interpolated. (d / 2,) float32.
    The softmax-scale correction ``yarn_mscale(factor) ** 2`` is the
    caller's."""
    def turns_dim(turns):
        return d * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    plain = _inv_freq(d, theta)
    return plain / factor * ramp + plain * (1.0 - ramp)


def yarn_mscale(factor, mscale=1.0):
    """``0.1 * mscale * ln(factor) + 1``: the attention logits of a
    YaRN-scaled model are multiplied by its square."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _inv_freq(d, theta, yarn=None):
    """The ``d / 2`` rotary frequencies: plain, or YaRN's."""
    if yarn is not None:
        return yarn_inv_freq(d, theta, *yarn)
    return 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def rope_at(data, positions, *, theta=10000.0, interleaved=False,
            yarn=None):
    """:func:`rope` with explicit per-row absolute positions —
    ``positions`` (B, L) int — the decode-step form, where every row of
    the batch sits at a different depth of its own sequence. Bitwise
    identical to :func:`rope` when
    ``positions == offset + arange(L)`` broadcast over the batch (the
    cos/sin tables are built from positions the same way). ``yarn``:
    ``(factor, beta_fast, beta_slow, original_max)``, the frequencies of
    :func:`yarn_inv_freq` in place of the plain ones."""
    d = data.shape[-1]
    pos = positions.astype(jnp.float32)                  # (B, L)
    inv_freq = _inv_freq(d, theta, yarn)
    angles = pos[:, :, None] * inv_freq[None, None, :]   # (B, L, D/2)
    return _rotate_pairs(data, jnp.cos(angles)[:, :, None, :],
                         jnp.sin(angles)[:, :, None, :], interleaved)


@register("_contrib_rope", aliases=["rope"])
def rope(data, *, theta=10000.0, position_offset=0, interleaved=False,
         yarn=None):
    """Rotary position embedding over (B, L, H, D).

    Default is the true rotate-half convention (Llama / HF checkpoints):
    the head dim is split into first/second halves and rotated as
    ``concat(x1*cos - x2*sin, x2*cos + x1*sin)``, so weights ported from
    Llama-family checkpoints produce identical activations.
    ``interleaved=True`` selects the GPT-J/NeoX even-odd pair convention.
    Computed in-graph from positions — no host-side tables. ``yarn``:
    as :func:`rope_at`."""
    l, d = data.shape[1], data.shape[-1]
    pos = jnp.arange(position_offset, position_offset + l,
                     dtype=jnp.float32)
    inv_freq = _inv_freq(d, theta, yarn)
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


def mla_fresh_attention(query, latent, k_rope, kvb_weight, *, nope_dim,
                        v_dim, scale):
    """:func:`mla_attention` through :func:`sdp_attention`: causal MLA of
    a dispatch's own rows (a prefill that starts at position 0), keys and
    values expanded ONCE from the fresh latents, head-major, and attended
    by the causal flash forward on the TPU (the dense causal reference
    elsewhere). Arguments and result as :func:`mla_attention`.

    The kernel takes ONE head width: scores contract over ``nope +
    rope``, so the values (``v_dim`` <= ``nope + rope``) are zero-padded
    up to that width and the output cut back. ``scale`` is the caller's,
    never the padded width's."""
    b, l, h, width = query.shape
    w = kvb_weight.reshape(h, nope_dim + v_dim, kvb_weight.shape[-1])
    k = jnp.concatenate(
        [jnp.einsum("blr,hdr->bhld", latent, w[:, :nope_dim]),
         jnp.broadcast_to(k_rope[:, None], (b, h, l, width - nope_dim))],
        axis=-1)
    v = jnp.einsum("blr,hdr->bhld", latent, w[:, nope_dim:])
    v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, width - v_dim)))
    out = sdp_attention(None, query.transpose(0, 2, 1, 3), k, v,
                        causal=True, scale=scale)
    return out[..., :v_dim].transpose(0, 2, 1, 3).reshape(b, l, h * v_dim)


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
    latent kernel of pallas_kernels/paged_attention.py, which reads a
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
    from ..pallas_kernels.paged_attention import (
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


# ---------------------------------------------------------------------------
# Learned sparse attention over a latent cache: a small "indexer" scores
# every cached token of a stream for every query (a few narrow heads, one
# cached key of ``index_head_dim`` values a token), the ``top_k`` best are
# picked EXACTLY, and MLA attends over the picked tokens only.
# ---------------------------------------------------------------------------

# queries whose (heads, key block) scores one step of a blocked pass holds
_DSA_QUERY_BLOCK = 256


def _blocked(fn, arrays, block, n_out=1):
    """``fn`` over axis 0 of ``arrays`` (one array per argument, N rows
    each), ``block`` rows at a time under ``lax.map`` so that only one
    block's temporaries are alive; N a multiple of ``block`` or below
    it. ``fn`` returns one array of N rows, or a tuple of ``n_out``."""
    n = arrays[0].shape[0]
    if n <= block or n % block:
        return fn(*arrays)
    out = jax.lax.map(lambda xs: fn(*xs), tuple(
        a.reshape((n // block, block) + a.shape[1:]) for a in arrays))
    if n_out == 1:
        return out.reshape((n,) + out.shape[2:])
    return tuple(o.reshape((n,) + o.shape[2:]) for o in out)


# slots of one key block of a prefill chunk's passes over a stream's cache
_DSA_KEY_BLOCK = 2048


def _key_block(t):
    """The largest multiple of 128 lanes that divides ``t`` slots and is
    at most :data:`_DSA_KEY_BLOCK`; ``t`` itself where there is none (one
    block: short tables, the tests' sizes)."""
    for size in range(_DSA_KEY_BLOCK, 127, -128):
        if t % size == 0:
            return size
    return t


def _live_blocks(live, t, size):
    """Key blocks that hold a slot below ``live`` (all of them when
    ``live`` is None): the trip count of a pass over a stream's cache."""
    if live is None:
        return t // size
    return jnp.clip((live + size - 1) // size, 0, t // size).astype(jnp.int32)


@register("_contrib_dsa_index_scores", aliases=["dsa_index_scores"])
def dsa_index_scores(q_index, head_weights, k_index, live=None):
    """The indexer's score of every cached token for every query:
    ``I[b, t, s] = sum_j w[b, t, j] * relu(q[b, t, j] . k[b, s])``.

    ``q_index`` (B, L, J, D): the J index heads' queries, rotated;
    ``head_weights`` (B, L, J): each query's weight per head, every
    constant factor folded in by the caller; ``k_index`` (B, T, D): the
    stream's cached index keys (garbage past its length: the caller's
    mask); ``live`` (B,) int: slots at or past it are never selected, so
    the key blocks that hold only such slots are SKIPPED and score 0
    (the work follows the stream's length, not the page table's width).
    Returns (B, L, T) float32. Queries are scored a block at a time, so
    the (block, J, key block) products are the largest temporary; a
    decode step (L == 1) is one product over all rows and slots."""
    f32 = jnp.float32
    b, l = q_index.shape[:2]
    t = k_index.shape[1]
    if l == 1:
        # a decode step: one small product over every row and slot; a
        # walk over key blocks would be a chain of tiny ones
        dots = jnp.einsum("bljd,btd->bljt", q_index, k_index,
                          preferred_element_type=f32)
        return jnp.einsum("bljt,blj->blt", jax.nn.relu(dots),
                          head_weights.astype(f32))
    size = _key_block(t)

    def row(args):
        q, w, k, n = args                  # (L, J, D), (L, J), (T, D), ()

        def key_block(i, scores):
            kb = jax.lax.dynamic_slice_in_dim(k, i * size, size, 0)

            def block(qb, wb):
                dots = jnp.einsum("ljd,td->ljt", qb, kb,
                                  preferred_element_type=f32)
                return jnp.einsum("ljt,lj->lt", jax.nn.relu(dots),
                                  wb.astype(f32))

            return jax.lax.dynamic_update_slice_in_dim(
                scores, _blocked(block, (q, w), _DSA_QUERY_BLOCK),
                i * size, 1)

        return jax.lax.fori_loop(0, n, key_block, jnp.zeros((l, t), f32))

    blocks = _live_blocks(live, t, size)
    return jax.lax.map(row, (q_index, head_weights, k_index,
                             jnp.broadcast_to(blocks, (b,))))


def _ordered_uint(x):
    """float32 -> uint32 with the same order (the sign-magnitude bits of
    an IEEE float: a negative one's all flipped, the sign bit of the
    others set; -0.0 as +0.0). No finite float maps to 0."""
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x),
                                        jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


# bits of the k-th largest key that one pass over a row settles. A pass
# costs its read of the row and a fifth of that again per candidate
# (measured on a v5e over 2048 x 17,664 keys: 32 passes of one candidate
# 11.4 ms, 16 of three 8.3, 8 of fifteen 9.9; PERF.md section 6, PR 33)
_DSA_SELECT_BITS = 2


# prefixes of a page table's slots a selection's threshold may be
# searched in (quarters: one compiled search each)
_DSA_SELECT_PREFIXES = 4


@register("_contrib_dsa_select", aliases=["dsa_select"])
def dsa_select(scores, valid, live=None, *, top_k):
    """The EXACT ``top_k`` largest ``scores`` (..., T) among the ``valid``
    (..., T) bool ones, as a bool mask (..., T), equal scores to the
    lower position first (as ``lax.top_k`` orders them); every valid one
    where fewer than ``top_k`` are valid. ``live`` (any shape) int: no
    slot at or past its maximum is valid, so the search for the k-th
    largest score reads only the smallest quarter-prefix of the T slots
    that holds them all (the work follows the streams' lengths).

    Not a sort (``lax.top_k`` at k in the thousands lowers to one on the
    TPU): the k-th largest score is found two bits a pass, in 16 passes
    over the row that each count the ``score >= candidate`` of 3
    candidates, on an integer that orders as the float does. Only where some row has more scores equal to its k-th
    than it has room for (float32 scores: in practice an exact 0 where
    every index head's product was negative) are those ranked by
    position, one running count over the rows."""
    key = jnp.where(valid, _ordered_uint(scores), jnp.uint32(0))
    t = key.shape[-1]

    def kth_largest(width):
        part = key[..., :width]
        # every pass reads the row once and settles _DSA_SELECT_BITS bits
        # of the k-th largest key: the counts of the keys at or above
        # each of the 2 ** bits - 1 candidates that differ in those bits
        # come out of one reduction, and the candidates being in
        # ascending order, as many of them as have ``top_k`` keys at or
        # above them are at or below the k-th largest
        steps = jnp.arange(1, 2 ** _DSA_SELECT_BITS, dtype=jnp.uint32)

        def digit(i, theta):
            shift = (32 - _DSA_SELECT_BITS * (i + 1)).astype(jnp.uint32)
            cands = theta[..., None] + (steps << shift)
            enough = jnp.sum(part[..., None, :] >= cands[..., None],
                             axis=-1, dtype=jnp.int32) >= top_k
            return theta + (jnp.sum(enough, axis=-1).astype(jnp.uint32)
                            << shift)

        return jax.lax.fori_loop(0, 32 // _DSA_SELECT_BITS, digit,
                                 jnp.zeros(key.shape[:-1], jnp.uint32))

    n = _DSA_SELECT_PREFIXES
    if live is None or t % (128 * n):
        theta = kth_largest(t)
    else:
        quarter = t // n
        theta = jax.lax.switch(
            jnp.clip((jnp.max(live) - 1) // quarter, 0, n - 1).astype(
                jnp.int32),
            [lambda w=w: kth_largest(w)
             for w in range(quarter, t + 1, quarter)])
    theta = theta[..., None]
    above = valid & (key > theta)
    tied = valid & (key == theta)
    room = top_k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)

    def first_of_the_tied():
        rank = jnp.cumsum(tied, axis=-1, dtype=jnp.int32) - 1
        return above | (tied & (rank < room))

    crowded = jnp.any(jnp.sum(tied, axis=-1, keepdims=True,
                              dtype=jnp.int32) > room)
    return jax.lax.cond(crowded, first_of_the_tied, lambda: above | tied)


def _one_hot_rows(rows, index, dtype, precision=None):
    """``rows[b, index[b, k]]``, (B, K, W) float32, of ``rows`` (B, N, W)
    through a one-hot product in ``dtype`` with float32 accumulation: no
    gather, exact where ``dtype`` holds every entry, zeros for an index
    outside [0, N)."""
    hot = jnp.arange(rows.shape[1], dtype=jnp.int32) == index[..., None]
    return jnp.einsum("bkn,bnw->bkw", hot.astype(dtype), rows.astype(dtype),
                      precision=precision,
                      preferred_element_type=jnp.float32)


# slots of one block of :func:`_selected_slots`'s two-level count: a
# lane tile, so that a block's running count is one MXU pass
_DSA_SLOT_BLOCK = 128


def _selected_slots(sel, top_k):
    """The first ``top_k`` set slots of each row of ``sel`` (B, T) bool,
    in position order: ``(slot (B, top_k) int32, n_sel (B,) int32)``, as
    ``jnp.nonzero(row, size=top_k)`` lists them; places at or past a
    row's ``n_sel`` hold slot 0.

    A two-level count made of dense compares, reductions and two small
    products, with no scatter, no sort and no gather: the row is cut
    into blocks of 128 slots; the r-th selected slot lies in the first
    block whose inclusive count of selected slots passes r, and inside
    that block at the first slot whose running count does. The block's
    row of running counts is picked by a one-hot product (counts are at
    most 128: exact in bfloat16 with float32 accumulation). On a v5e at
    (8, 35,328) -> (8, 2,048) this is 0.04 ms; the forms it replaced:
    a scatter of one update a SLOT at each slot's rank, 1.4 ms (1.0 in
    the decode program: the costliest operation of a round, serial on
    the TPU), and a bisection on the running count through 16 dependent
    gathers, 2.6 ms (PERF.md section 6, PR 33 and PR 34)."""
    b, t = sel.shape
    i32, bf16, f32 = jnp.int32, jnp.bfloat16, jnp.float32
    size = _DSA_SLOT_BLOCK
    n = -(-t // size)
    blocks = jnp.pad(sel, ((0, 0), (0, n * size - t))).reshape(b, n, size)
    lane = jnp.arange(size, dtype=i32)
    # local[b, j, p]: the selected among slots 0..p of block j (a product
    # with a triangle of ones; a cumsum over the lanes measured 0.09 ms
    # more), count: in the whole block, upto: in blocks 0..j
    local = jnp.einsum("bnl,lp->bnp", blocks.astype(bf16),
                       (lane[:, None] <= lane[None, :]).astype(bf16),
                       preferred_element_type=f32)
    count = local[..., -1].astype(i32)
    upto = jnp.cumsum(count, axis=-1)
    rank = jnp.arange(top_k, dtype=i32)
    # the blocks that end before the rank-th selected slot: a prefix, so
    # their number is that slot's block and their counts' sum its offset
    before = upto[:, None, :] <= rank[None, :, None]         # (B, k, n)
    block = jnp.sum(before, axis=-1, dtype=i32)
    inside = rank[None, :] - jnp.sum(
        jnp.where(before, count[:, None, :], 0), axis=-1)
    row = _one_hot_rows(local, block, bf16)                  # (B, k, 128)
    place = jnp.sum(row <= inside[..., None].astype(f32), axis=-1,
                    dtype=i32)
    n_sel = jnp.minimum(upto[:, -1], top_k)
    return (jnp.where(rank[None, :] < n_sel[:, None],
                      block * size + place, 0), n_sel)


def _one_hot_take(table, index):
    """``table[b, index[b, k]]`` of a small int table (B, N) for
    ``index`` (B, K) in [0, N), without a gather: the table is cut into
    rows of 8, a one-hot product over the rows picks each index's row
    (float32 at the highest precision: exact for entries below 2**24)
    and a compare picks the entry. A decode step's page ids, 8 x 2,048
    of a 2,208-wide page table, cost 0.13 ms a layer through
    ``take_along_axis`` on a v5e and 0.01 ms this way (PERF.md section
    6, PR 34)."""
    b, n = table.shape
    width = 8
    rows = -(-n // width)
    cut = jnp.pad(table, ((0, 0), (0, rows * width - n))).reshape(
        b, rows, width)
    row = _one_hot_rows(cut, index // width, jnp.float32,
                        jax.lax.Precision.HIGHEST)
    return jnp.sum(jnp.where(
        jnp.arange(width, dtype=jnp.int32) == (index % width)[..., None],
        row, 0.0), axis=-1).astype(table.dtype)


def _gather_pages(arena, page_table):
    """A stream's cache through its page table: (B, P * page, width)."""
    b = page_table.shape[0]
    # every page id of a table is a real page (0: scratch)
    rows = jnp.take(arena, page_table, axis=0, mode="clip")
    return rows.reshape(b, -1, arena.shape[-1])


@register("_contrib_mla_sparse_attend", aliases=["mla_sparse_attend"])
def mla_sparse_attend(query, arena, page_table, selected, kvb_weight,
                      live=None, *, nope_dim, v_dim, scale, top_k):
    """MLA over the ``selected`` cached tokens of a paged LATENT cache.

    ``query`` (B, L, H, nope + rope), rotated; ``arena`` (pages, page,
    >= R + rope) one layer's latent arena (``[c' | rotated k_rope | lane
    padding]`` a token); ``page_table`` (B, P); ``selected`` (B, L, T)
    bool over the T = P * page slots the table reaches
    (:func:`dsa_select`: causal and length masks included);
    ``kvb_weight`` as in :func:`mla_attention`; ``live`` (B,) int: no
    slot at or past it is selected (a stream's length), so a chunk's pass
    over the cache stops there. Returns (B, L, H * v); a query that
    selects nothing (padding) gets zeros.

    L == 1 (a decode step): the selected rows, at most ``top_k`` a
    stream, are GATHERED from the arena, ``top_k * width`` values a
    stream whatever its length, and attended in the absorbed form of
    :func:`mla_paged_decode`; which rows is :func:`_selected_slots`'s
    list of the selected slots in position order, counted and not
    scattered. L > 1 (a prefill chunk): every query picks
    its own set, so a gather would be ``L * top_k`` rows; instead the
    stream's cache is walked a key block at a time, as far as it is
    live: the block's keys and values are expanded ONCE from its latents
    and every block of queries attends over them with the selection as
    its mask, the softmax carried across key blocks (running maximum and
    sum, as flash attention carries them). A row at a time, so the
    largest temporaries are one key block's keys and values, one (H,
    query block, key block) score tile and the (L, H, v) accumulator."""
    b, l, h, _ = query.shape
    r = kvb_weight.shape[-1]
    f32 = jnp.float32
    w = kvb_weight.reshape(h, nope_dim + v_dim, r)
    w_uk, w_uv = w[:, :nope_dim], w[:, nope_dim:]
    if l == 1:
        # the selected slots in position order: a slot's rank among the
        # selected is its place in the gathered block
        slot, n_sel = _selected_slots(selected[:, 0], top_k)
        ps = arena.shape[1]
        page = _one_hot_take(page_table, slot // ps)
        rows = arena.reshape(-1, arena.shape[-1])[page * ps + slot % ps]
        width = rows.shape[-1]                                 # (B, k, width)
        q = query[:, 0]
        q_lat = jnp.einsum("bhd,hdr->bhr", q[..., :nope_dim], w_uk)
        q_full = jnp.concatenate([q_lat, q[..., nope_dim:]], axis=-1)
        q_full = jnp.pad(q_full,
                         ((0, 0), (0, 0), (0, width - q_full.shape[-1])))
        scores = jnp.einsum("bhc,bkc->bhk", q_full, rows,
                            preferred_element_type=f32) * scale
        alive = jnp.arange(top_k)[None, :] < n_sel[:, None]
        scores = jnp.where(alive[:, None, :], scores, f32(-1e9))
        probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
        o_lat = jnp.einsum("bhk,bkc->bhc", probs, rows)
        return jnp.einsum("bhr,hvr->bhv", o_lat[..., :r],
                          w_uv).reshape(b, 1, h * v_dim)

    t = selected.shape[-1]
    size = _key_block(t)
    rope_dim = query.shape[-1] - nope_dim

    def row(args):
        q, cache, sel, n = args        # (L, H, D), (T, width), (L, T), ()
        # the softmax scale goes into the queries once, not into every
        # score tile
        q = (q.astype(f32) * scale).astype(q.dtype)

        def key_block(i, carry):
            c = jax.lax.dynamic_slice_in_dim(cache, i * size, size, 0)
            sb = jax.lax.dynamic_slice_in_dim(sel, i * size, size, 1)
            # head-major: what the products below contract over. A key is
            # its head's expanded part beside the rotary part all heads
            # share, so a tile of scores is ONE product over nope + rope
            # (two products would each write a float32 tile to be added)
            k = jnp.concatenate(
                [jnp.einsum("tr,hdr->htd", c[:, :r], w_uk),
                 jnp.broadcast_to(c[None, :, r:r + rope_dim],
                                  (h, size, rope_dim))], axis=-1)
            v = jnp.einsum("tr,hdr->htd", c[:, :r], w_uv)

            def block(qb, mask, m, norm, acc):      # m, norm: (q, H)
                scores = jnp.where(
                    mask[None], jnp.einsum("qhd,hkd->hqk", qb, k,
                                           preferred_element_type=f32),
                    f32(-1e30))
                m_new = jnp.maximum(m.T, jnp.max(scores, axis=-1))
                # an unselected key is 1e30 below any maximum but that of
                # a query that has selected nothing yet, which is held
                # above it: its exp is 0 either way, with no second mask
                p = jnp.exp(scores - jnp.maximum(m_new, f32(-1e20))[..., None])
                old = jnp.exp(m.T - m_new)                  # (H, q)
                pv = jnp.einsum("hqk,hkd->qhd", p.astype(v.dtype), v,
                                preferred_element_type=f32)
                return (m_new.T, (old * norm.T + jnp.sum(p, axis=-1)).T,
                        old.T[..., None] * acc + pv)

            return _blocked(block, (q, sb) + carry, _DSA_QUERY_BLOCK,
                            n_out=3)

        init = (jnp.full((l, h), -1e30, f32), jnp.zeros((l, h), f32),
                jnp.zeros((l, h, v_dim), f32))
        _, norm, acc = jax.lax.fori_loop(0, n, key_block, init)
        return (acc / jnp.maximum(norm[..., None], f32(1e-30))
                ).astype(query.dtype)

    blocks = _live_blocks(live, t, size)
    out = jax.lax.map(row, (query, _gather_pages(arena, page_table),
                            selected, jnp.broadcast_to(blocks, (b,))))
    return out.reshape(b, l, h * v_dim)


@register("_contrib_dsa_mla_attention", aliases=["dsa_mla_attention"])
def dsa_mla_attention(query, latent, k_rope, kvb_weight, q_index,
                      head_weights, k_index, *, nope_dim, v_dim, scale,
                      top_k):
    """Causal sparse MLA over whole sequences (no cache): the three ops
    above on a sequence's own tokens. ``query``, ``latent``, ``k_rope``,
    ``kvb_weight`` as in :func:`mla_attention`; ``q_index``,
    ``head_weights``, ``k_index`` as in :func:`dsa_index_scores` with
    T = L. Query ``t`` attends to the ``min(top_k, t + 1)`` tokens
    ``s <= t`` of largest index score. Returns (B, L, H * v)."""
    b, l = latent.shape[:2]
    pos = jnp.arange(l)
    selected = dsa_select(
        dsa_index_scores(q_index, head_weights, k_index),
        jnp.broadcast_to(pos[None, :] <= pos[:, None], (b, l, l)),
        top_k=top_k)
    # each sequence is one page of its own
    rows = jnp.concatenate([latent, k_rope], axis=-1)
    return mla_sparse_attend(query, rows, jnp.arange(b)[:, None], selected,
                             kvb_weight, nope_dim=nope_dim, v_dim=v_dim,
                             scale=scale, top_k=top_k)
