"""Ring attention — sequence/context parallelism over a mesh axis
(reference capability: long-context training; design follows the Ring
Attention construction — arXiv:2310.01889 — expressed TPU-natively as
``shard_map`` + ``lax.ppermute`` over ICI).

Each device holds a sequence shard of Q/K/V. K/V blocks rotate around the
ring while every device folds them into a running softmax merge for its
local Q shard, so

* memory per device is O(L_local) — no device ever materializes the full
  (L, L) score matrix or the full K/V, in the FORWARD **and** the
  BACKWARD: a ``jax.custom_vjp`` saves only (q, k, v, out, lse) shards
  and re-walks the ring in the backward pass, rotating a
  (q, dO, lse, delta, dQ) bundle while each device accumulates dK/dV for
  its resident shard — probabilities are recomputed per pair from the
  global logsumexp, the FlashAttention recompute trade stretched over
  the ring (round-2 weakness #3: the old scan saved every rotating K/V
  carry, making training memory O(L));
* communication is nearest-neighbor ``ppermute`` riding ICI, overlapping
  with the per-block attention math;
* the math is EXACTLY softmax(QK^T)V — per-pair partials merge through
  their base-2 logsumexp (the same domain the Pallas kernels emit);
* on TPU, each per-pair block attention runs the Pallas flash kernels in
  both directions when the shard shapes qualify (``flash_supported``);
  anywhere else an einsum path computes the identical algebra.
"""
from __future__ import annotations

import functools
import math

import jax

__all__ = ["ring_attention", "ring_attention_sharded", "ring_active"]

_NEG = -1e30
_LOG2E = 1.4426950408889634


def _pair_fwd(q, k, v, scale, pair_causal, use_kernel, interpret=False):
    """One (q-shard, k-shard) block attention -> (out f32, lse2 f32).

    ``out`` is normalized within the pair; ``lse2`` is the pair's base-2
    logsumexp of the SCALED scores, shaped (B, H, Lq). Fully-masked rows
    emit out = 0, lse2 = -inf, which merge as zero weight.
    """
    import jax.numpy as jnp

    if use_kernel:
        from ..pallas_kernels.flash_attention import _flash_fwd_pallas

        out, lse = _flash_fwd_pallas(q, k, v, scale, pair_causal,
                                     interpret=interpret)
        b, h, lq, d = q.shape
        nq = lse.shape[1]
        lse2 = lse[:, :, 0, :].reshape(b, h, lq)
        return out.astype(jnp.float32), lse2

    qf = q.astype(jnp.float32) * jnp.float32(scale * _LOG2E)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, k.astype(jnp.float32))
    if pair_causal:
        lq, lk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        s = jnp.where(mask[None, None], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp2(s - m)
    if pair_causal:
        p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    out = out / jnp.where(l == 0.0, 1.0, l)
    lse2 = jnp.where(l == 0.0, _NEG, m + jnp.log2(jnp.where(
        l == 0.0, 1.0, l)))[..., 0]
    return out, lse2


def _merge(out_a, lse_a, out_b, lse_b):
    """Merge two normalized partial attentions via base-2 logsumexp."""
    import jax.numpy as jnp

    m = jnp.maximum(lse_a, lse_b)
    # fully-masked partials carry lse = -inf -> weight 0 (guard m=-inf)
    m_safe = jnp.where(m <= _NEG, 0.0, m)
    wa = jnp.exp2(lse_a - m_safe)
    wb = jnp.exp2(lse_b - m_safe)
    tot = wa + wb
    tot_safe = jnp.where(tot == 0.0, 1.0, tot)
    out = (out_a * wa[..., None] + out_b * wb[..., None]) / tot_safe[..., None]
    lse = jnp.where(tot == 0.0, _NEG, m_safe + jnp.log2(tot_safe))
    return out, lse


def _pair_bwd(q, k, v, do, lse2, delta, scale, pair_causal, use_kernel,
              interpret=False):
    """Gradients of one block pair given the GLOBAL lse2/delta.

    Returns (dq, dk, dv) contributions in f32. p recomputed as
    exp2(s2 - lse2) — rows of q fully masked within this pair produce
    zero contributions (s2 = -inf).
    """
    import jax.numpy as jnp

    b, h, lq, d = q.shape
    if use_kernel:
        from ..pallas_kernels.flash_attention import (_block_sizes,
                                                      _flash_bwd_pallas)

        bh = b * h
        bq = _block_sizes(lq, k.shape[2])[0]
        nq = lq // bq
        lse_k = jnp.broadcast_to(
            lse2.reshape(bh, nq, 1, bq), (bh, nq, 8, bq))
        dq, dk, dv = _flash_bwd_pallas(
            q, k, v, None, lse_k, do, scale, pair_causal,
            interpret=interpret, delta=delta.reshape(bh, lq))
        return (dq.astype(jnp.float32), dk.astype(jnp.float32),
                dv.astype(jnp.float32))

    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s2 = jnp.einsum("bhqd,bhkd->bhqk", qf * jnp.float32(scale * _LOG2E), kf)
    if pair_causal:
        lk = k.shape[2]
        mask = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        s2 = jnp.where(mask[None, None], s2, _NEG)
    p = jnp.exp2(s2 - lse2[..., None])                    # (B,H,Lq,Lk)
    dv_c = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    ds = p * (dp - delta[..., None]) * jnp.float32(scale)
    dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    dq_c = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    return dq_c, dk_c, dv_c


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring(q, k, v, axis_name, causal, scale):
    return _ring_fwd(q, k, v, axis_name, causal, scale)[0]


def _use_kernel(q, k, v, causal, axis_name):
    from ..pallas_kernels.flash_attention import flash_supported

    return flash_supported(q, k, v, causal=causal,
                           manual_axes=(axis_name,))


def _ring_fwd(q, k, v, axis_name, causal, scale):
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, lq, d = q.shape
    lk = k.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]
    kernel_ok = _use_kernel(q, k, v, causal, axis_name)

    def step(carry, s):
        out, lse, kb, vb = carry
        k_idx = (idx - s) % n

        def attend(args):
            out, lse = args
            # diagonal pair: lq == lk blocks, standard causal; strictly
            # past pair: full attention
            if causal:
                o_i, l_i = lax.cond(
                    k_idx == idx,
                    lambda: _pair_fwd(q, kb, vb, scale, True, kernel_ok),
                    lambda: _pair_fwd(q, kb, vb, scale, False, kernel_ok))
            else:
                o_i, l_i = _pair_fwd(q, kb, vb, scale, False, kernel_ok)
            return _merge(out, lse, o_i, l_i)

        if causal:
            # skip blocks entirely in this shard's future
            visible = k_idx <= idx
            out, lse = lax.cond(visible, attend, lambda a: a, (out, lse))
        else:
            out, lse = attend((out, lse))
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (out, lse, kb, vb), None

    out0 = jnp.zeros((b, h, lq, d), jnp.float32)
    lse0 = jnp.full((b, h, lq), _NEG, jnp.float32)
    mark = getattr(lax, "pcast", None)
    if mark is not None:
        out0 = mark(out0, (axis_name,), to="varying")
        lse0 = mark(lse0, (axis_name,), to="varying")
    (out, lse, _, _), _ = lax.scan(step, (out0, lse0, k, v),
                                   jnp.arange(n))
    return out.astype(q.dtype), (q, k, v, out.astype(q.dtype), lse)


def _ring_bwd(axis_name, causal, scale, res, g):
    """One reverse ring pass: the (q, dO, lse, delta, dQ) bundle rotates;
    each device folds the visiting shard into its resident dK/dV."""
    import jax.numpy as jnp
    from jax import lax

    q, k, v, out, lse = res
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    kernel_ok = _use_kernel(q, k, v, causal, axis_name)

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                               # (B,H,Lq)

    def step(carry, s):
        qb, dob, lseb, deltab, dqb, dk_acc, dv_acc = carry
        # the visiting bundle originated on device (idx - s) % n; its q
        # block index is that origin — local k block index is idx
        q_idx = (idx - s) % n

        def attend(args):
            dqb, dk_acc, dv_acc = args
            if causal:
                dq_c, dk_c, dv_c = lax.cond(
                    q_idx == idx,
                    lambda: _pair_bwd(qb, k, v, dob, lseb, deltab, scale,
                                      True, kernel_ok),
                    lambda: _pair_bwd(qb, k, v, dob, lseb, deltab, scale,
                                      False, kernel_ok))
            else:
                dq_c, dk_c, dv_c = _pair_bwd(qb, k, v, dob, lseb, deltab,
                                             scale, False, kernel_ok)
            return dqb + dq_c, dk_acc + dk_c, dv_acc + dv_c

        if causal:
            visible = idx <= q_idx  # local keys not in visiting q's future
            dqb, dk_acc, dv_acc = lax.cond(
                visible, attend, lambda a: a, (dqb, dk_acc, dv_acc))
        else:
            dqb, dk_acc, dv_acc = attend((dqb, dk_acc, dv_acc))
        qb = lax.ppermute(qb, axis_name, perm)
        dob = lax.ppermute(dob, axis_name, perm)
        lseb = lax.ppermute(lseb, axis_name, perm)
        deltab = lax.ppermute(deltab, axis_name, perm)
        dqb = lax.ppermute(dqb, axis_name, perm)
        return (qb, dob, lseb, deltab, dqb, dk_acc, dv_acc), None

    b, h, lq, d = q.shape
    dq0 = jnp.zeros((b, h, lq, d), jnp.float32)
    dk0 = jnp.zeros_like(dq0)
    dv0 = jnp.zeros_like(dq0)
    mark = getattr(lax, "pcast", None)
    if mark is not None:
        # constants start device-invariant; the scan carries become
        # varying per shard
        dq0 = mark(dq0, (axis_name,), to="varying")
        dk0 = mark(dk0, (axis_name,), to="varying")
        dv0 = mark(dv0, (axis_name,), to="varying")
    (_, _, _, _, dq, dk, dv), _ = lax.scan(
        step, (q, g, lse, delta, dq0, dk0, dv0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(_ring_fwd, _ring_bwd)


def ring_attention_sharded(q, k, v, axis_name, causal=False, scale=None):
    """Per-shard body: call inside ``shard_map`` over ``axis_name``.

    q/k/v: (B, H, L_local, D) — this device's sequence shard.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if causal and q.shape[2] != k.shape[2]:
        # the per-pair diagonal masks and shard-index visibility tests
        # assume equal q/k shard lengths; unequal-length causal ring
        # (chunked scoring against a longer cache) needs global-position
        # masks — fail loudly rather than attend to the future
        raise ValueError(
            f"causal ring attention requires equal q/k shard lengths, "
            f"got lq={q.shape[2]}, lk={k.shape[2]}")
    return _ring(q, k, v, axis_name, causal, float(scale))


def ring_attention(q, k, v, mesh=None, axis="sp", causal=False, scale=None):
    """Sequence-parallel exact attention over ``mesh[axis]``.

    q/k/v: GLOBAL (B, H, L, D) arrays (sharded or replicated — the
    shard_map in_spec lays them on the axis). Returns (B, H, L, D) with
    the same sequence sharding. Falls back to dense attention when the
    mesh axis has a single device.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .mesh import current_mesh

    mesh = mesh or current_mesh()
    if not ring_active(axis, mesh):
        from ..ops.attention import _sdpa_reference

        if scale is None:
            scale = 1.0 / math.sqrt(q.shape[-1])
        return _sdpa_reference(q, k, v, None, scale, causal)
    # ONLY the ring axis is manual (axis_names): batch (dp) and head (tp)
    # shardings stay with GSPMD — making every axis manual would
    # all-gather q/k/v over the other mesh axes and replicate the
    # attention compute per dp/tp shard
    spec = P(None, None, axis, None)
    # check_vma=False: the Pallas per-pair kernels' out_shapes carry no
    # varying-mesh-axes annotation (jax would demand `vma` on every
    # ShapeDtypeStruct inside the manual region otherwise)
    fn = shard_map(
        lambda a, b_, c: ring_attention_sharded(a, b_, c, axis,
                                                causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset({axis}), check_vma=False)
    return fn(q, k, v)


def ring_active(axis, mesh=None):
    """True when ring attention would actually run over ``axis`` (a mesh
    is active and the axis spans more than one device)."""
    from .mesh import current_mesh

    mesh = mesh or current_mesh()
    return (mesh is not None and axis in mesh.axis_names
            and mesh.shape[axis] > 1)
