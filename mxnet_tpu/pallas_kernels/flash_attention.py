"""Flash attention: Pallas TPU kernel + blockwise-scan fallback.

Two implementations of the same O(L) -memory online-softmax algorithm:

* ``flash_attention`` — Pallas kernels both directions. Forward: grid
  (batch*heads, q_blocks, k_blocks), K/V streamed HBM->VMEM one block per
  grid step, f32 accumulators in VMEM scratch, bf16 matmuls on the MXU;
  emits the per-row logsumexp as a residual. Backward (``jax.custom_vjp``):
  a dK/dV kernel (K block resident, Q streams; scores computed transposed
  so row stats broadcast from lane vectors) and a dQ kernel (Q resident,
  K streams), both recomputing probabilities from the saved logsumexp —
  the FlashAttention-2 recompute trade, all matmuls on the MXU.
* ``flash_attention_scan`` — pure-XLA `lax.scan` over K blocks; runs
  anywhere (the CPU-oracle path for check_consistency tests) and is the
  long-sequence fallback when the kernel's shape constraints aren't met.

Shapes: q (B, H, Lq, D), k/v (B, H, Lk, D) -> (B, H, Lq, D).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as _np

BLOCK_Q = 128
BLOCK_K = 128
MAX_BLOCK = 512


def _block_sizes(lq, lk):
    """Largest power-of-two blocks (<= MAX_BLOCK) dividing the seq lengths.

    Bigger blocks mean fewer grid steps and larger MXU matmuls — at seq 512
    a single (512, 512) block turns the whole head into one VMEM-resident
    fused attention, which is what beats XLA's HBM-bound softmax path. 512
    is the VMEM comfort cap: the f32 score tile is bq*bk*4 = 1 MB.
    """
    try:
        # NOTE: an isolated-attention microbench prefers bq=256 at seq 512
        # (~20% on the kernel alone), but the END-TO-END BERT step is
        # consistently FASTER with 512x512 (197-199 vs 182-191 samples/s)
        # — in-context VMEM pressure and step pipelining differ; trust the
        # end-to-end number
        bq = next(b for b in (MAX_BLOCK, 256, 128) if lq % b == 0)
        bk = next(b for b in (MAX_BLOCK, 256, 128) if lk % b == 0)
    except StopIteration:
        raise ValueError(
            f"flash_attention requires sequence lengths that are multiples "
            f"of {BLOCK_Q}; got lq={lq}, lk={lk} (use flash_attention_scan "
            f"or sdp_attention, which fall back automatically)") from None
    return bq, bk

_NEG_INF = -1e30
# np.float32 constants: under global jax_enable_x64 a Python float would be
# promoted to f64 inside the kernel trace, which Mosaic cannot legalize
_NEG_INF32 = _np.float32(-1e30)
_ONE32 = _np.float32(1.0)
_ZERO32 = _np.float32(0.0)
# All kernels run softmax in BASE-2: log2(e) folds into the score scale
# (one multiply that was already there) and exp2 is the VPU's native
# transcendental — exp lowers to exp2 plus a scale per element, so at
# attention sizes (50M+ exps/layer/step, the kernels' dominant VPU cost)
# base-2 removes a full multiply sweep. The saved lse residual is
# therefore in the base-2 domain: p == exp2(s2 - lse2) exactly equals
# exp(s - lse); gradient math (ds = p*(dp-delta)*scale) is unchanged
# because only the representation of p's computation moves, not p.
_LOG2E = _np.float32(1.4426950408889634)

# --- stateless dropout hash (shared by kernels, fallbacks, and oracles) ---
# splitmix/murmur3-finalizer on the element's absolute (head, q, k) id:
# pure elementwise integer code, so the SAME mask is reproducible in any
# kernel orientation/grouping (fwd (BQ, BK) vs transposed bwd (BK, BQ))
# and in the pure-jnp reference path — no PRNG state to thread, no
# fwd-to-bwd mask tensor in HBM. 16 low hash bits vs a u16 threshold =
# the dropout op's keep-rate granularity (ops/nn.py::dropout_op).
_GOLD = _np.uint32(0x9E3779B9)
_MUR1 = _np.uint32(0x85EBCA6B)
_MUR2 = _np.uint32(0xC2B2AE35)
_U16 = _np.uint32(0xFFFF)


def _hash_u32(idx, seed):
    """Murmur3-finalize uint32 ``idx`` (+seed); full 32-bit result."""
    z = idx * _GOLD + seed
    z = z ^ (z >> 16)
    z = z * _MUR1
    z = z ^ (z >> 13)
    z = z * _MUR2
    z = z ^ (z >> 16)
    return z


def _hash_u16(idx, seed):
    """Low 16 bits of the murmur3 finalizer (dropout threshold compare)."""
    return _hash_u32(idx, seed) & _U16


def dropout_thresh(p):
    """u16 keep threshold for drop probability ``p``."""
    return _np.uint32(min(0xFFFF, int(round((1.0 - p) * 65536.0))))


def fold_key_seed(rng):
    """Fold a jax PRNG key's words into one u32 dropout seed — shared by
    every stateless-hash dropout site so all dispatch paths derive the
    identical stream from the same op key."""
    kd = jax.random.key_data(rng).astype(jnp.uint32).reshape(-1)
    seed = kd[0]
    for i in range(1, kd.shape[0]):
        seed = seed ^ (kd[i] * _np.uint32(0x9E3779B9 + i))
    return seed


def _drop_mask(head_idx, q_pos, k_pos, lq, lk, seed, thresh):
    """Keep-mask for absolute (head, q, k) positions (any orientation).

    Two-level hash: the (batch*head) index folds into a per-head seed
    first, then the in-head (q*lk + k) id is hashed under it — a single
    flat (head*lq + q)*lk + k id would wrap uint32 at b*h*lq*lk > 2^32
    (e.g. 32x16 heads at seq 4096) and silently give distinct elements
    identical masks. Per-head ids wrap only at lq*lk > 2^32, i.e. seq
    ~65k even before the head split.
    """
    head_seed = _hash_u32(head_idx.astype(jnp.uint32), seed)
    idx = (q_pos.astype(jnp.uint32) * _np.uint32(lk)
           + k_pos.astype(jnp.uint32))
    return _hash_u16(idx, head_seed) < thresh


def _x32_mode():
    # Mosaic cannot legalize the i64/f64 constants that jax_enable_x64
    # (on globally for MXNet dtype parity) injects into kernel traces and
    # BlockSpec index maps; trace kernels in 32-bit mode.
    return jax.enable_x64(False)


def _prec_for(dtype):
    # f32 inputs get multi-pass MXU matmuls (f32-faithful); bf16 inputs run
    # the native single-pass — the training fast path
    if jnp.dtype(dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def flash_shape_supported(q, k, v, causal=False, layout="bhld") -> bool:
    """Platform-independent kernel shape eligibility.

    Causal with lq > lk is rejected: bottom-right alignment would leave the
    top query rows with no visible keys (a fully-masked, degenerate row the
    dense reference only "answers" with a uniform softmax over masked-out
    scores — not a shape any model in the zoo produces)."""
    if layout == "blhd":
        # Mosaic requires the last two block dims be (8k, 128k)-aligned or
        # span the full array dim; a per-head (bq, d) tile of (B, L, H, D)
        # puts a squeezed H in sublane position, which it rejects. The
        # kernel therefore only takes the bhld layout; blhd callers get the
        # einsum path (whose head transposes fold into the contractions).
        return False
    lq, lk = q.shape[-2], k.shape[-2]
    if causal and lq > lk:
        return False
    return (lq % BLOCK_Q == 0 and lk % BLOCK_K == 0
            and q.shape[-1] <= 256 and q.shape[-1] % 8 == 0)


def flash_supported(q, k, v, causal=False, layout="bhld",
                    manual_axes=()) -> int:
    """Kernel eligibility: TPU execution + block-aligned sequence lengths.
    The answer is ``parallel.mesh.kernel_shards``': 0 give way, 1 the
    kernel, n > 1 the kernel on each of n batch shards (``manual_axes``:
    the mesh axes the caller's own ``shard_map`` holds).
    Platform comes from ``base.current_execution_platform`` — set by the
    framework's jit entry points — so a CPU-context op never takes the
    kernel path just because a TPU exists in the process.
    """
    from ..base import current_execution_platform
    from ..parallel.mesh import kernel_shards

    if current_execution_platform(q) != "tpu" or not flash_shape_supported(
            q, k, v, causal=causal, layout=layout):
        return 0
    return kernel_shards(q.shape[0], manual_axes)


# ---------------------------------------------------------------------------
# scan fallback (runs anywhere; also the VJP recompute path)
# ---------------------------------------------------------------------------


def flash_attention_scan(q, k, v, scale=None, causal=False,
                         block_k=BLOCK_K, dropout=0.0, seed=None):
    """Online-softmax attention via lax.scan over K blocks. O(Lk/block)
    scan steps, never materialises the (Lq, Lk) score matrix.

    ``dropout``/``seed``: same stateless position-hash mask as the Pallas
    kernels (bitwise identical given the same seed) — this path doubles
    as the kernels' CPU oracle."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    dropout = float(dropout)
    if dropout > 0.0 and seed is None:
        raise ValueError("flash_attention_scan: dropout > 0 requires seed")
    dtype = q.dtype
    b, h, lq, d = q.shape
    lk = k.shape[2]
    block_k = min(block_k, lk)
    nk = -(-lk // block_k)
    pad = nk * block_k - lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32).reshape(b, h, nk, block_k, d)
    vf = v.astype(jnp.float32).reshape(b, h, nk, block_k, d)
    # bottom-right causal alignment (matches _sdpa_reference's tril
    # k=lk-lq): the LAST query row sees all lk keys
    q_pos = jnp.arange(lq)[:, None] + (lk - lq)

    def body(carry, blk):
        acc, m, l = carry
        kb, vb, kidx = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb)
        k_pos = kidx * block_k + jnp.arange(block_k)[None, :]
        valid = k_pos < lk
        if causal:
            valid = valid & (k_pos <= q_pos)
        s = jnp.where(valid[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # zero masked probabilities explicitly: for a FULLY-masked row
        # m_new == _NEG_INF and exp(s - m_new) would be 1 for every
        # masked/padded key, silently averaging them in
        p = jnp.where(valid[None, None], jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout > 0.0:
            shp = (b, h, lq, block_k)
            head = (jax.lax.broadcasted_iota(jnp.int32, shp, 0) * h
                    + jax.lax.broadcasted_iota(jnp.int32, shp, 1))
            qp = jax.lax.broadcasted_iota(jnp.int32, shp, 2)
            kp = kidx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, shp, 3)
            # true lk (not the padded extent): padded columns have p == 0
            # regardless, and the kernel oracle hashes with true lk
            keep = _drop_mask(head, qp, kp, lq, lk,
                              jnp.asarray(seed, jnp.uint32).reshape(-1)[0],
                              dropout_thresh(dropout))
            p_acc = jnp.where(keep, p, 0.0)
        else:
            p_acc = p
        acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p_acc, vb)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, h, lq, d), jnp.float32)
    m0 = jnp.full((b, h, lq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, lq, 1), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0),
        (kf.transpose(2, 0, 1, 3, 4), vf.transpose(2, 0, 1, 3, 4),
         jnp.arange(nk)))
    # fully-masked rows (l == 0) emit zeros rather than 0/0 NaN
    if dropout > 0.0:
        acc = acc * _np.float32(1.0 / (1.0 - dropout))
    return (acc / jnp.where(l == 0.0, 1.0, l)).astype(dtype)


# ---------------------------------------------------------------------------
# pallas kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, scale2, causal, nk, causal_offset, prec,
                bq, bk, dropout, lq, lk):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF32)
        l_ref[:] = jnp.zeros_like(l_ref)

    qi = pl.program_id(1)

    def compute():
        # operands stay in the INPUT dtype: casting bf16 to f32 before
        # the dot forces multi-pass f32 MXU matmuls — the bf16 native
        # single-pass with f32 accumulate is the whole fast path. The
        # base-2 scale moves onto the f32 scores (exact there).
        q = q_ref[...]                                     # (BQ, D)
        k = k_ref[...]                                     # (BK, D)
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec) * scale2                       # (BQ, BK) f32
        if causal:
            # bottom-right alignment: offset = lk - lq
            q_pos = causal_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF32)
        m_prev = m_ref[:, 0:1]                             # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        if dropout > 0.0:
            # drop in the PV accumulation only: the online (m, l) stats
            # stay pre-dropout; inv_keep folds into the final normalize
            keep = _drop_mask_2d(seed_ref, bq, bk, qi, ki, lq, lk, dropout)
            pd = jnp.where(keep, p, _ZERO32)
        else:
            pd = p
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            pd.astype(v.dtype), v, preferred_element_type=jnp.float32,
            precision=prec)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    if causal:
        # blocks entirely above the diagonal contribute nothing — skip
        @pl.when(ki * bk <= causal_offset + qi * bq + bq - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _final():
        # fully-masked rows (every K block skipped: l == 0) emit zeros
        l = l_ref[:, 0:1]
        div = jnp.where(l == _ZERO32, _ONE32, l)
        if dropout > 0.0:
            div = div * _np.float32(1.0 - dropout)
        o_ref[...] = (acc_ref[:] / div).astype(o_ref.dtype)
        # per-row base-2 logsumexp residual for the backward kernels,
        # stored as a lane vector broadcast over 8 sublanes — (8, BQ) is
        # the smallest f32 tile, so the (BQ,) column transposes in legally
        m_col = m_ref[:, 0:1]
        l_safe = jnp.where(l == _ZERO32, _ONE32, l)
        lse_col = jnp.where(l == _ZERO32, _NEG_INF32,
                            m_col + jnp.log2(l_safe))
        lse_ref[...] = jnp.broadcast_to(
            lse_col.reshape(1, bq), (8, bq))


def _drop_mask_g(seed_ref, g, bq, bk, qi, ki, lq, lk, dropout):
    """(G, bq, bk) keep-mask for the g-heads-per-step kernels; head ids
    are absolute (program_id(0) * g + local)."""
    from jax.experimental import pallas as pl

    head = (pl.program_id(0) * g + jax.lax.broadcasted_iota(
        jnp.int32, (g, bq, bk), 0))
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (g, bq, bk), 1)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bq, bk), 2)
    return _drop_mask(head, q_pos, k_pos, lq, lk, seed_ref[0],
                      dropout_thresh(dropout))


def _drop_mask_2d(seed_ref, bq, bk, qi, ki, lq, lk, dropout,
                  transposed=False):
    """(bq, bk) keep-mask — or its exact (bk, bq) transpose for the
    score-transposed backward kernels (same absolute ids, so the bits
    match the forward elementwise)."""
    from jax.experimental import pallas as pl

    head = pl.program_id(0)
    if transposed:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    else:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return _drop_mask(head, q_pos, k_pos, lq, lk, seed_ref[0],
                      dropout_thresh(dropout))


def _fwd_kernel_single_g(q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref, *,
                         scale2, causal, causal_offset, prec, bq, bk,
                         dropout, lq, lk):
    """g heads per grid step (refs (G, BQ/BK, D)): amortizes the
    per-grid-step overhead that dominates once the softmax runs in
    base-2 — the dots batch over the leading head dim on the MXU."""
    q = q_ref[...]                                         # (G, BQ, D)
    k = k_ref[...]
    v = v_ref[...]
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec) * scale2
    if causal:
        g = q.shape[0]
        q_pos = causal_offset + jax.lax.broadcasted_iota(
            jnp.int32, (g, bq, bk), 1)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (g, bq, bk), 2)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF32)
    m = jnp.max(s, axis=-1, keepdims=True)                 # (G, BQ, 1)
    p = jnp.exp2(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == _ZERO32, _ONE32, l)
    if dropout > 0.0:
        # mask applied to the PV accumulation only: l (and the lse
        # residual) stay pre-dropout softmax statistics; inv_keep folds
        # into the final normalize
        g = q.shape[0]
        keep = _drop_mask_g(seed_ref, g, bq, bk, 0, 0, lq, lk, dropout)
        pd = jnp.where(keep, p, _ZERO32)
        l_safe = l_safe * _np.float32(1.0 - dropout)
    else:
        pd = p
    o = jax.lax.dot_general(
        pd.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec)
    o_ref[...] = (o / l_safe).astype(o_ref.dtype)
    g = q.shape[0]
    l_norm = jnp.where(l == _ZERO32, _ONE32, l)
    lse_col = jnp.where(l == _ZERO32, _NEG_INF32, m + jnp.log2(l_norm))
    lse_ref[...] = jnp.broadcast_to(
        lse_col.reshape(g, 1, bq), (g, 8, bq))


def _fwd_kernel_single(q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref, *,
                       scale2, causal, causal_offset, prec, bq, bk,
                       dropout, lq, lk):
    """Whole-head-in-one-block forward (nq == nk == 1, e.g. BERT seq 512).

    No streaming means no running statistics: the scratch carries and the
    alpha-rescale sweeps of the online-softmax kernel disappear — at these
    shapes the kernel is VPU-bound, so fewer elementwise passes is the
    win, not matmul shape.
    """
    q = q_ref[...]                                         # (BQ, D)
    k = k_ref[...]
    v = v_ref[...]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec) * scale2
    if causal:
        q_pos = causal_offset + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF32)
    m = jnp.max(s, axis=-1, keepdims=True)                 # (BQ, 1)
    p = jnp.exp2(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == _ZERO32, _ONE32, l)
    if dropout > 0.0:
        keep = _drop_mask_2d(seed_ref, bq, bk, 0, 0, lq, lk, dropout)
        pd = jnp.where(keep, p, _ZERO32)
        div = l_safe * _np.float32(1.0 - dropout)
    else:
        pd = p
        div = l_safe
    o_ref[...] = (jnp.dot(pd.astype(v.dtype), v,
                          preferred_element_type=jnp.float32,
                          precision=prec) / div).astype(o_ref.dtype)
    lse_col = jnp.where(l == _ZERO32, _NEG_INF32, m + jnp.log2(l_safe))
    lse_ref[...] = jnp.broadcast_to(lse_col.reshape(1, bq), (8, bq))


def _dims(x, layout, is_q=True):
    if layout == "blhd":
        b, l, h, d = x.shape
    else:
        b, h, l, d = x.shape
    return b, h, l, d


def _tile_spec(layout, h, blk, d, seq_index):
    """BlockSpec for one (blk, d) Q/K/V/O tile of a head.

    bhld: array is pre-reshaped (B*H, L, D); blhd: array stays native
    (B, L, H, D) and the batch/head grid dim splits in the index map —
    no relayout of the activations at all (None entries squeeze the unit
    dims out of the kernel block).
    """
    from jax.experimental import pallas as pl

    if layout == "blhd":
        return pl.BlockSpec(
            (None, blk, None, d),
            lambda bh_, qi, ki, _h=h, _s=seq_index: (
                bh_ // _h, (qi, ki)[_s], bh_ % _h, 0))
    return pl.BlockSpec(
        (None, blk, d),
        lambda bh_, qi, ki, _s=seq_index: (bh_, (qi, ki)[_s], 0))


def _seed_arr(seed):
    """Normalize the dropout seed to the (1,) u32 SMEM operand the
    kernels read (zeros when dropout is off — the mask code isn't
    traced then, the operand just keeps signatures uniform)."""
    if seed is None:
        return jnp.zeros((1,), jnp.uint32)
    return jnp.asarray(seed, jnp.uint32).reshape((1,))


def _flash_fwd_pallas(q, k, v, scale, causal, interpret=False,
                      layout="bhld", dropout=0.0, seed=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = _dims(q, layout)
    lk = _dims(k, layout)[2]
    bh = b * h
    if layout == "bhld":
        q = q.reshape(bh, lq, d)
        k = k.reshape(bh, lk, d)
        v = v.reshape(bh, lk, d)
        o_shape = jax.ShapeDtypeStruct((bh, lq, d), q.dtype)
    else:
        o_shape = jax.ShapeDtypeStruct((b, lq, h, d), q.dtype)
    bq, bk = _block_sizes(lq, lk)
    nq, nk = lq // bq, lk // bk
    prec = _prec_for(q.dtype)
    scale2 = _np.float32(scale) * _LOG2E
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        _tile_spec(layout, h, bq, d, 0),
        _tile_spec(layout, h, bk, d, 1),
        _tile_spec(layout, h, bk, d, 1),
        smem_spec,
    ]
    out_specs = [
        _tile_spec(layout, h, bq, d, 0),
        pl.BlockSpec((None, None, 8, bq),
                     lambda bh_, qi, ki: (bh_, qi, 0, 0)),
    ]
    out_shape = [
        o_shape,
        jax.ShapeDtypeStruct((bh, nq, 8, bq), jnp.float32),
    ]
    if nq == 1 and nk == 1 and layout == "bhld":
        # g heads per grid step; f32 score tile g*bq*bk*4 caps VMEM
        # f32 score tile gg*bq*bk*4 plus double-buffered operands must
        # fit the 16 MB VMEM scoped limit: g=8 at 512-blocks OOMs (18 MB)
        # and g=6 measures ~1% SLOWER than g=4 end-to-end (BERT-base,
        # PERF_HISTORY.md round 3) — pipelining beats raw occupancy here
        g = next(gg for gg in (4, 3, 2, 1)
                 if bh % gg == 0 and gg * bq * bk * 4 <= 4 << 20)
        kernel = functools.partial(
            _fwd_kernel_single_g, scale2=scale2, causal=causal,
            causal_offset=lk - lq, prec=prec, bq=bq, bk=bk,
            dropout=dropout, lq=lq, lk=lk)
        with _x32_mode():
            out, lse = pl.pallas_call(
                kernel,
                grid=(bh // g, 1, 1),
                in_specs=[
                    pl.BlockSpec((g, bq, d), lambda b, qi, ki: (b, qi, 0)),
                    pl.BlockSpec((g, bk, d), lambda b, qi, ki: (b, ki, 0)),
                    pl.BlockSpec((g, bk, d), lambda b, qi, ki: (b, ki, 0)),
                    smem_spec,
                ],
                out_specs=[
                    pl.BlockSpec((g, bq, d), lambda b, qi, ki: (b, qi, 0)),
                    pl.BlockSpec((g, None, 8, bq),
                                 lambda b, qi, ki: (b, qi, 0, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
                    jax.ShapeDtypeStruct((bh, nq, 8, bq), jnp.float32),
                ],
                interpret=interpret,
            )(q, k, v, _seed_arr(seed))
        return out.reshape(b, h, lq, d), lse
    if nq == 1 and nk == 1:
        kernel = functools.partial(
            _fwd_kernel_single, scale2=scale2, causal=causal,
            causal_offset=lk - lq, prec=prec, bq=bq, bk=bk,
            dropout=dropout, lq=lq, lk=lk)
        scratch = []
    else:
        kernel = functools.partial(
            _fwd_kernel, scale2=scale2, causal=causal, nk=nk,
            causal_offset=lk - lq, prec=prec, bq=bq, bk=bk,
            dropout=dropout, lq=lq, lk=lk)
        scratch = [
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ]
    with _x32_mode():
        out, lse = pl.pallas_call(
            kernel,
            grid=(bh, nq, nk),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(q, k, v, _seed_arr(seed))
    if layout == "bhld":
        out = out.reshape(b, h, lq, d)
    return out, lse


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     seed_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                     scale, scale2, causal, nq, causal_offset, prec, bq, bk,
                     dropout, lq, lk):
    """dK/dV for one K block; Q blocks stream on the innermost grid dim.

    All score math is done TRANSPOSED — s_T = (BK, BQ) — so the per-row
    stats (lse, delta) broadcast from lane vectors (1, BQ) without any
    relayout, and dV/dK contractions take p_T/ds_T directly.
    """
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def compute():
        # native-dtype MXU operands (see _fwd_kernel note); f32
        # intermediates (p, ds) cast down before their dots
        q = q_ref[...]                                     # (BQ, D)
        k = k_ref[...]                                     # (BK, D)
        v = v_ref[...]
        do = do_ref[...]                                   # (BQ, D)
        lse = lse_ref[0:1, :]                               # (1, BQ)
        delta = delta_ref[0:1, :]                           # (1, BQ)
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec) * scale2
        if causal:
            q_pos = causal_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bk, bq), 1)
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bk, bq), 0)
            s_t = jnp.where(k_pos <= q_pos, s_t, _NEG_INF32)
        p_t = jnp.exp2(s_t - lse)                            # (BK, BQ)
        if dropout > 0.0:
            # regenerate the forward's exact mask (same absolute ids,
            # transposed orientation); dV sees P_drop, dP gets the mask
            # before the softmax backward (dS = P ⊙ (dP - delta) — the
            # delta trick survives dropout unchanged, PERF_HISTORY.md round 5)
            keep_t = _drop_mask_2d(seed_ref, bq, bk, qi, ki, lq, lk,
                                   dropout, transposed=True)
            inv_keep = _np.float32(1.0 / (1.0 - dropout))
            pd_t = jnp.where(keep_t, p_t * inv_keep, _ZERO32)
        else:
            pd_t = p_t
        dv_acc[:] += jnp.dot(pd_t.astype(do.dtype), do,
                             preferred_element_type=jnp.float32,
                             precision=prec)
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (BK, BQ)
        if dropout > 0.0:
            dp_t = jnp.where(keep_t, dp_t * inv_keep, _ZERO32)
        ds_t = p_t * (dp_t - delta) * scale
        dk_acc[:] += jnp.dot(ds_t.astype(q.dtype), q,
                             preferred_element_type=jnp.float32,
                             precision=prec)

    if causal:
        @pl.when(ki * bk <= causal_offset + qi * bq + bq - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == nq - 1)
    def _final():
        dk_ref[...] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[:].astype(dv_ref.dtype)


def _drop_mask_g_t(seed_ref, g, bq, bk, lq, lk, dropout):
    """(G, bk, bq) transposed keep-mask for the g-heads fused backward —
    bitwise identical to _drop_mask_g's forward mask."""
    from jax.experimental import pallas as pl

    head = (pl.program_id(0) * g + jax.lax.broadcasted_iota(
        jnp.int32, (g, bk, bq), 0))
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (g, bk, bq), 2)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (g, bk, bq), 1)
    return _drop_mask(head, q_pos, k_pos, lq, lk, seed_ref[0],
                      dropout_thresh(dropout))


def _bwd_fused_kernel_g(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        seed_ref, dq_ref, dk_ref, dv_ref, *, scale, scale2,
                        causal, causal_offset, prec, bq, bk, dropout,
                        lq, lk):
    """g-heads-per-step fused backward (refs (G, ., .)); see
    _bwd_fused_kernel for the math, _fwd_kernel_single_g for why."""
    q = q_ref[...]                                     # (G, BQ, D)
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[:, 0:1, :]                           # (G, 1, BQ)
    delta = delta_ref[:, 0:1, :]
    s_t = jax.lax.dot_general(
        k, q, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec) * scale2
    if causal:
        g = q.shape[0]
        q_pos = causal_offset + jax.lax.broadcasted_iota(
            jnp.int32, (g, bk, bq), 2)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (g, bk, bq), 1)
        s_t = jnp.where(k_pos <= q_pos, s_t, _NEG_INF32)
    p_t = jnp.exp2(s_t - lse)                          # (G, BK, BQ)
    if dropout > 0.0:
        keep_t = _drop_mask_g_t(seed_ref, q.shape[0], bq, bk, lq, lk,
                                dropout)
        inv_keep = _np.float32(1.0 / (1.0 - dropout))
        pd_t = jnp.where(keep_t, p_t * inv_keep, _ZERO32)
    else:
        pd_t = p_t
    p_cast = pd_t.astype(do.dtype)
    dv_ref[...] = jax.lax.dot_general(
        p_cast, do, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=prec).astype(dv_ref.dtype)
    dp_t = jax.lax.dot_general(
        v, do, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec)
    if dropout > 0.0:
        dp_t = jnp.where(keep_t, dp_t * inv_keep, _ZERO32)
    ds_t = (p_t * (dp_t - delta) * scale).astype(q.dtype)
    dk_ref[...] = jax.lax.dot_general(
        ds_t, q, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=prec).astype(dk_ref.dtype)
    dq_ref[...] = jax.lax.dot_general(
        ds_t, k, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=prec).astype(dq_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      seed_ref, dq_ref, dk_ref, dv_ref, *, scale, scale2,
                      causal, causal_offset, prec, bq, bk, dropout, lq, lk):
    """Fused dQ/dK/dV for the single-block case (nq == nk == 1).

    The split dK/dV + dQ kernels each recompute the probability matrix —
    7 MXU matmuls and 2 VPU exp sweeps per head per step. When the whole
    head fits one (bq, bk) block there is nothing to stream, so one kernel
    can share the recompute: 5 matmuls and 1 exp. At BERT shapes the
    attention kernels are VPU(exp)-bound, so the saved exp sweep is the
    dominant win (measured: see PERF_HISTORY.md round-3 attention table).

    Score math transposed (s_t: (BK, BQ)) as in _bwd_dkdv_kernel so the
    per-row stats broadcast from lane vectors.
    """
    q = q_ref[...]                                     # (BQ, D)
    k = k_ref[...]                                     # (BK, D)
    v = v_ref[...]
    do = do_ref[...]                                   # (BQ, D)
    lse = lse_ref[0:1, :]                              # (1, BQ)
    delta = delta_ref[0:1, :]                          # (1, BQ)
    s_t = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec) * scale2
    if causal:
        q_pos = causal_offset + jax.lax.broadcasted_iota(
            jnp.int32, (bk, bq), 1)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        s_t = jnp.where(k_pos <= q_pos, s_t, _NEG_INF32)
    p_t = jnp.exp2(s_t - lse)                           # (BK, BQ) f32
    if dropout > 0.0:
        keep_t = _drop_mask_2d(seed_ref, bq, bk, 0, 0, lq, lk, dropout,
                               transposed=True)
        inv_keep = _np.float32(1.0 / (1.0 - dropout))
        pd_t = jnp.where(keep_t, p_t * inv_keep, _ZERO32)
    else:
        pd_t = p_t
    p_cast = pd_t.astype(do.dtype)
    dv_ref[...] = jnp.dot(p_cast, do,
                          preferred_element_type=jnp.float32,
                          precision=prec).astype(dv_ref.dtype)
    dp_t = jax.lax.dot_general(
        v, do, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)  # (BK, BQ)
    if dropout > 0.0:
        dp_t = jnp.where(keep_t, dp_t * inv_keep, _ZERO32)
    ds_t = (p_t * (dp_t - delta) * scale).astype(q.dtype)
    dk_ref[...] = jnp.dot(ds_t, q,
                          preferred_element_type=jnp.float32,
                          precision=prec).astype(dk_ref.dtype)
    # dq = ds @ k = ds_t^T @ k : contract the BK dim of both
    dq_ref[...] = jax.lax.dot_general(
        ds_t, k, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=prec).astype(dq_ref.dtype)           # (BQ, D)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   seed_ref, dq_ref, dq_acc, *, scale, scale2, causal, nk,
                   causal_offset, prec, bq, bk, dropout, lq, lk):
    """dQ for one Q block; K blocks stream on the innermost grid dim."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def compute():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[0:1, :]                               # (1, BQ)
        delta = delta_ref[0:1, :]                           # (1, BQ)
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec) * scale2
        if causal:
            q_pos = causal_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bk, bq), 1)
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bk, bq), 0)
            s_t = jnp.where(k_pos <= q_pos, s_t, _NEG_INF32)
        p_t = jnp.exp2(s_t - lse)
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        if dropout > 0.0:
            keep_t = _drop_mask_2d(seed_ref, bq, bk, qi, ki, lq, lk,
                                   dropout, transposed=True)
            dp_t = jnp.where(keep_t,
                             dp_t * _np.float32(1.0 / (1.0 - dropout)),
                             _ZERO32)
        ds_t = (p_t * (dp_t - delta) * scale)               # (BK, BQ)
        # dq = ds @ k = ds_t^T @ k : contract the BK dim of both
        dq_acc[:] += jax.lax.dot_general(
            ds_t.astype(k.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (BQ, D)

    if causal:
        @pl.when(ki * bk <= causal_offset + qi * bq + bq - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _final():
        dq_ref[...] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, g, scale, causal, interpret=False,
                      layout="bhld", delta=None, dropout=0.0, seed=None):
    """``delta``: optional precomputed rowsum(dO*O) of shape (B*H, Lq)
    f32 — ring attention passes the GLOBAL delta so per-pair calls don't
    recompute it; ``o`` may then be None."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = _dims(q, layout)
    lk = _dims(k, layout)[2]
    bh = b * h
    if layout == "bhld":
        q = q.reshape(bh, lq, d)
        k = k.reshape(bh, lk, d)
        v = v.reshape(bh, lk, d)
        do = g.reshape(bh, lq, d)
        if delta is None:
            do_f32 = do.astype(jnp.float32)
            o_f32 = o.reshape(bh, lq, d).astype(jnp.float32)
        dq_shape = jax.ShapeDtypeStruct((bh, lq, d), q.dtype)
        dk_shape = jax.ShapeDtypeStruct((bh, lk, d), k.dtype)
        dv_shape = jax.ShapeDtypeStruct((bh, lk, d), v.dtype)
    else:
        do = g
        # (B, L, H, D) -> (BH, L) rowsums for delta
        if delta is None:
            do_f32 = g.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
                bh, lq, d)
            o_f32 = o.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
                bh, lq, d)
        dq_shape = jax.ShapeDtypeStruct((b, lq, h, d), q.dtype)
        dk_shape = jax.ShapeDtypeStruct((b, lk, h, d), k.dtype)
        dv_shape = jax.ShapeDtypeStruct((b, lk, h, d), v.dtype)
    bq, bk = _block_sizes(lq, lk)
    nq, nk = lq // bq, lk // bk
    # delta_i = rowsum(dO_i * O_i) — cheap, fused by XLA outside the
    # kernel; stored in the same sublane-padded layout as lse
    if delta is None:
        delta = jnp.sum(do_f32 * o_f32, axis=-1)
    delta = jnp.broadcast_to(delta.reshape(bh, nq, 1, bq),
                             (bh, nq, 8, bq))
    offset = lk - lq
    prec = _prec_for(q.dtype)
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    if nq == 1 and nk == 1 and layout == "bhld":
        # fused dq/dk/dv kernel, g heads per grid step (f32 score tiles
        # are the VMEM cap: ~3 live (G, BK, BQ) intermediates)
        grp = next(gg for gg in (2, 1)
                   if bh % gg == 0 and 3 * gg * bq * bk * 4 <= 7 << 20)
        gq_spec = pl.BlockSpec((grp, bq, d),
                               lambda b_, qi, ki: (b_, qi, 0))
        gk_spec = pl.BlockSpec((grp, bk, d),
                               lambda b_, qi, ki: (b_, ki, 0))
        grow_spec = pl.BlockSpec((grp, None, 8, bq),
                                 lambda b_, qi, ki: (b_, qi, 0, 0))
        with _x32_mode():
            dq, dk3, dv3 = pl.pallas_call(
                functools.partial(_bwd_fused_kernel_g, scale=scale,
                                  scale2=_np.float32(scale) * _LOG2E,
                                  causal=causal, causal_offset=offset,
                                  prec=prec, bq=bq, bk=bk,
                                  dropout=dropout, lq=lq, lk=lk),
                grid=(bh // grp, 1, 1),
                in_specs=[gq_spec, gk_spec, gk_spec, gq_spec,
                          grow_spec, grow_spec, smem_spec],
                out_specs=[gq_spec, gk_spec, gk_spec],
                out_shape=[dq_shape, dk_shape, dv_shape],
                interpret=interpret,
            )(q, k, v, do, lse, delta, _seed_arr(seed))
        return (dq.reshape(b, h, lq, d), dk3.reshape(b, h, lk, d),
                dv3.reshape(b, h, lk, d))
    if nq == 1 and nk == 1:
        # whole head in one block: fused dq/dk/dv kernel shares the p
        # recompute (5 matmuls + 1 exp instead of 7 + 2)
        q_spec = _tile_spec(layout, h, bq, d, 0)
        k_spec = _tile_spec(layout, h, bk, d, 1)
        row_spec = pl.BlockSpec((None, None, 8, bq),
                                lambda bh_, qi, ki: (bh_, qi, 0, 0))
        with _x32_mode():
            dq, dk3, dv3 = pl.pallas_call(
                functools.partial(_bwd_fused_kernel, scale=scale,
                                  scale2=_np.float32(scale) * _LOG2E,
                                  causal=causal, causal_offset=offset,
                                  prec=prec, bq=bq, bk=bk,
                                  dropout=dropout, lq=lq, lk=lk),
                grid=(bh, 1, 1),
                in_specs=[q_spec, k_spec, k_spec, q_spec,
                          row_spec, row_spec, smem_spec],
                out_specs=[q_spec, k_spec, k_spec],
                out_shape=[dq_shape, dk_shape, dv_shape],
                interpret=interpret,
            )(q, k, v, do, lse, delta, _seed_arr(seed))
        return dq, dk3, dv3

    # grid (bh, nk, nq): q/do/lse/delta stream on the inner (j) dim, so
    # their tiles index by grid dim 2 (seq_index=1) and K/V by dim 1
    q_spec_j = _tile_spec(layout, h, bq, d, 1)
    k_spec_i = _tile_spec(layout, h, bk, d, 0)
    row_spec_j = pl.BlockSpec((None, None, 8, bq),
                              lambda bh_, i, j: (bh_, j, 0, 0))
    with _x32_mode():
        dk3, dv3 = pl.pallas_call(
            functools.partial(_bwd_dkdv_kernel, scale=scale,
                              scale2=_np.float32(scale) * _LOG2E,
                              causal=causal, nq=nq, causal_offset=offset,
                              prec=prec, bq=bq, bk=bk,
                              dropout=dropout, lq=lq, lk=lk),
            grid=(bh, nk, nq),
            in_specs=[q_spec_j, k_spec_i, k_spec_i, q_spec_j,
                      row_spec_j, row_spec_j, smem_spec],
            out_specs=[k_spec_i, k_spec_i],
            out_shape=[dk_shape, dv_shape],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v, do, lse, delta, _seed_arr(seed))

        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale,
                              scale2=_np.float32(scale) * _LOG2E,
                              causal=causal, nk=nk, causal_offset=offset,
                              prec=prec, bq=bq, bk=bk,
                              dropout=dropout, lq=lq, lk=lk),
            grid=(bh, nq, nk),
            in_specs=[
                _tile_spec(layout, h, bq, d, 0),
                _tile_spec(layout, h, bk, d, 1),
                _tile_spec(layout, h, bk, d, 1),
                _tile_spec(layout, h, bq, d, 0),
                pl.BlockSpec((None, None, 8, bq),
                             lambda bh_, i, j: (bh_, i, 0, 0)),
                pl.BlockSpec((None, None, 8, bq),
                             lambda bh_, i, j: (bh_, i, 0, 0)),
                smem_spec,
            ],
            out_specs=_tile_spec(layout, h, bq, d, 0),
            out_shape=dq_shape,
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
        )(q, k, v, do, lse, delta, _seed_arr(seed))
    if layout == "bhld":
        return (dq.reshape(b, h, lq, d), dk3.reshape(b, h, lk, d),
                dv3.reshape(b, h, lk, d))
    return dq, dk3, dv3


# ---------------------------------------------------------------------------
# forward with a per-row key length (inference: a sequence padded to a
# bucket attends to its live keys only)
# ---------------------------------------------------------------------------


def _bounded_blocks(lq, lk, d, itemsize):
    """``(bq, bkv, bk, rows)`` of the length-bounded forward: query rows
    and keys of a grid step, keys and query rows of a tile. Each is the
    largest of its kind that divides the length (both lengths are
    multiples of 128: ``flash_shape_supported``)."""
    bq = next(b for b in (1024, 512, 256, 128) if lq % b == 0)
    bk = next(b for b in _BOUNDED_KEYS if lk % b == 0)
    chunks = lk // bk
    fit = max(_BOUNDED_KV_BYTES // (4 * bk * d * itemsize), 1)
    bkv = bk * max(c for c in range(1, min(fit, chunks) + 1)
                   if chunks % c == 0)
    return bq, bkv, bk, min(bq, _BOUNDED_ROWS)


# A tile is ``_BOUNDED_ROWS`` query rows against ``_BOUNDED_KEYS`` keys:
# one 128-key weight tile for each of the v5e's four MXUs, which then
# return a row group's 512 scores together, and few enough rows that the
# tile's scores, between the QK^T and the PV products, stay in registers
# and the compiler's own spill slots (the custom call's scoped VMEM is its
# pipelined blocks, its scratch and < 256 KB: tests/test_tpu_compile.py).
# A grid step walks its query block's tiles over every LIVE 512 keys of a
# key block of up to ``_BOUNDED_KV_BYTES`` (K and V, double-buffered), so
# a step's fixed cost (~0.35 us) is paid once for several tiles and a dead
# key chunk costs nothing.
_BOUNDED_ROWS = 256
_BOUNDED_KEYS = (512, 256, 128)
_BOUNDED_KV_BYTES = 4 << 20


def _fwd_kernel_bounded(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                        l_ref, *, scale2, nkv, prec, bq, bkv, bk, rows, h):
    """:func:`_fwd_kernel`, neither causal nor dropped, for a row with
    ``len_ref[b]`` live keys (and as many live queries): key chunks past
    the length and query blocks past it are skipped, the key chunk that
    holds the length is masked, whole chunks run unmasked.

    The running max and sum are kept 128 lanes wide, the max replicated
    and the sum as 128 partial sums a row (key ``j`` adds into lane ``j %
    128``; the lanes are added up once, at the end). Every step of a
    tile's chain is then a plain vector operation on whole registers but
    the one cross-lane max a row group: a ``(rows, 1)`` max or rescale
    factor has to be spread over the lanes again by the permute unit, and
    that unit's turn-around, not the products or the exponentials, bound
    a tile (PERF.md section 6, PR 45)."""
    from jax.experimental import pallas as pl

    d = acc_ref.shape[1]
    n = len_ref[pl.program_id(0) // h]
    qi, ki = pl.program_id(1), pl.program_id(2)
    lo = ki * bkv
    n_here = jnp.clip(n - lo, 0, bkv)       # live keys of this key block
    whole = n_here // bk

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF32)
        l_ref[:] = jnp.zeros_like(l_ref)

    def chunk(c, masked):
        k = k_ref[pl.ds(c, bk), :]
        v = v_ref[pl.ds(c, bk), :]
        for r in range(0, bq, rows):
            tile = slice(r, r + rows)
            s = jax.lax.dot_general(
                q_ref[tile, :], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=prec) * scale2                   # (rows, bk) f32
            if masked:
                k_pos = lo + c + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, bk), 1)
                s = jnp.where(k_pos < n, s, _NEG_INF32)
            m_prev = m_ref[tile, :]                        # (rows, 128)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            p = [jnp.exp2(s[:, j:j + 128] - m_new)
                 for j in range(0, bk, 128)]
            l_ref[tile, :] = l_ref[tile, :] * alpha + sum(p[1:], p[0])
            # alpha over the head dim: its lanes all hold the row's factor
            a_d = (alpha if d <= 128 else jnp.concatenate(
                [alpha] * -(-d // 128), axis=1))[:, :d]
            acc_ref[tile, :] = acc_ref[tile, :] * a_d + jnp.dot(
                jnp.concatenate(p, axis=1).astype(v.dtype), v,
                preferred_element_type=jnp.float32, precision=prec)
            m_ref[tile, :] = m_new

    @pl.when(qi * bq < n)
    def _live_queries():
        def body(j, carry):
            chunk(pl.multiple_of(j * bk, bk), False)
            return carry

        jax.lax.fori_loop(0, whole, body, 0)

        @pl.when(whole * bk < n_here)
        def _edge():
            chunk(pl.multiple_of(whole * bk, bk), True)

    @pl.when(ki == nkv - 1)
    def _final():
        # a skipped query block (l == 0) emits zeros
        l = jnp.sum(l_ref[...], axis=-1, keepdims=True)
        o_ref[...] = (acc_ref[:] / jnp.where(l == _ZERO32, _ONE32, l)
                      ).astype(o_ref.dtype)


def _flash_fwd_bounded(q, k, v, kv_len, scale, interpret=False):
    """(B, H, L, D) attention of row ``b`` over its first ``kv_len[b]``
    keys. The lengths ride as a scalar-prefetch operand: the K/V index
    maps clamp a skipped block to the last live one, so it costs no
    copy."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    bh = b * h
    bq, bkv, bk, rows = _bounded_blocks(lq, lk, d, q.dtype.itemsize)
    nq, nkv = lq // bq, lk // bkv

    def kv_map(i, qi, ki, len_ref):
        last = jnp.maximum((len_ref[i // h] + (bkv - 1)) // bkv - 1, 0)
        return (i, jnp.minimum(ki, last), 0)

    kernel = functools.partial(
        _fwd_kernel_bounded, scale2=_np.float32(scale) * _LOG2E, nkv=nkv,
        prec=_prec_for(q.dtype), bq=bq, bkv=bkv, bk=bk, rows=rows, h=h)
    with _x32_mode():
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(bh, nq, nkv),
                in_specs=[
                    pl.BlockSpec((None, bq, d),
                                 lambda i, qi, ki, len_ref: (i, qi, 0)),
                    pl.BlockSpec((None, bkv, d), kv_map),
                    pl.BlockSpec((None, bkv, d), kv_map),
                ],
                out_specs=pl.BlockSpec(
                    (None, bq, d), lambda i, qi, ki, len_ref: (i, qi, 0)),
                scratch_shapes=[
                    pltpu.VMEM((bq, d), jnp.float32),
                    pltpu.VMEM((bq, 128), jnp.float32),
                    pltpu.VMEM((bq, 128), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            # the HLO instruction and the operation's metadata carry it:
            # benchmarks/kernels/vit_flash_attention.py::PATTERN
            name="flash_fwd_bounded",
        )(jnp.asarray(kv_len, jnp.int32).reshape(b),
          q.reshape(bh, lq, d), k.reshape(bh, lk, d), v.reshape(bh, lk, d))
    return out.reshape(b, h, lq, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, seed, scale, causal, interpret, layout, dropout):
    return _flash_fwd_pallas(q, k, v, scale, causal, interpret, layout,
                             dropout, seed)[0]


def _flash_fwd(q, k, v, seed, scale, causal, interpret, layout, dropout):
    o, lse = _flash_fwd_pallas(q, k, v, scale, causal, interpret, layout,
                               dropout, seed)
    return o, (q, k, v, o, lse, seed)


def _flash_bwd(scale, causal, interpret, layout, dropout, res, g):
    # Pallas dq/dk/dv kernels recomputing p from the saved logsumexp —
    # training-mode attention runs on the MXU in BOTH directions (round-1
    # weakness #5: the old bwd re-differentiated the XLA scan). The
    # dropout mask is REGENERATED from (seed, positions) — nothing beyond
    # the (1,) seed crosses fwd->bwd.
    from .. import telemetry

    telemetry.record_pallas_dispatch("flash_attention_bwd")
    q, k, v, o, lse, seed = res
    dq, dk, dv = _flash_bwd_pallas(q, k, v, o, lse, g, scale, causal,
                                   interpret, layout, dropout=dropout,
                                   seed=seed)
    return dq, dk, dv, _np.zeros((1,), jax.dtypes.float0)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, scale=None, causal=False, interpret=False,
                    layout="bhld", dropout=0.0, seed=None, kv_len=None):
    """Pallas flash attention (differentiable).

    ``layout``: "bhld" (B, H, L, D) — the classic attention layout — or
    "blhd" (B, L, H, D), the projection-native layout. blhd currently
    lowers only in interpret mode (tests / CPU oracle): Mosaic rejects the
    squeezed-H sublane tile — groundwork for a (B, L, H*D) 128-aligned
    view once a head_dim % 128 model needs it. On-hardware callers go
    through ``sdp_attention``, which gates on ``flash_supported``.

    ``dropout``: attention-probability drop rate (reference capability:
    GluonNLP MultiHeadAttentionCell's dropout on the attention weights).
    The keep-mask is a stateless position hash (see _drop_mask) applied
    to the post-softmax P inside the kernels, pre-PV-matmul; ``seed``
    (uint32 scalar/(1,) array, may be traced) selects the stream and
    MUST be supplied when dropout > 0.

    ``kv_len`` (B,) int32 (may be traced): row ``b`` has ``kv_len[b]``
    live keys AND queries, the rest of its ``L`` being padding to a
    bucket. Key blocks past the length are skipped without a copy, the
    block that holds it is masked, query blocks past it are skipped and
    emit zeros (a padding query inside the last live block attends to the
    live keys; its row is the caller's to drop). Forward only, bhld,
    neither causal nor dropped: the padding costs none of the quadratic
    work. Without it the call is the one it has always been.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if kv_len is not None:
        if causal or dropout or layout != "bhld":
            raise ValueError("flash_attention: kv_len goes with neither "
                             "causal, dropout nor the blhd layout")
        return _flash_fwd_bounded(q, k, v, kv_len, float(scale),
                                  bool(interpret))
    dropout = float(dropout)
    if dropout > 0.0 and seed is None:
        raise ValueError("flash_attention: dropout > 0 requires a seed")
    return _flash(q, k, v, _seed_arr(seed), float(scale), bool(causal),
                  bool(interpret), str(layout), dropout)
