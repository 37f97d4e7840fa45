"""Fused transformer layer kernels: LayerNorm/RMSNorm + residual +
dropout, and the bias+GELU matmul epilogue.

Reference counterpart: MXNet's hand-fused transformer ops
(``src/operator/contrib/transformer.cc``) and the NVRTC runtime fusion
that welded bias/activation/residual epilogues onto the GEMMs. On TPU,
XLA fuses elementwise chains on its own but the earlier installation's
batch-32 trace (PERF_HISTORY.md) showed the residue it leaves on the
transformer step:
fusion epilogues re-reading the residual stream, RNG + bool mask traffic
for dropout, and bandwidth-bound LayerNorm sweeps. These kernels close
that gap the same way flash attention did for softmax:

* ``fused_layer_norm`` — ONE VMEM pass computing
  ``LN(dropout(x) + residual)``. The dropout keep-mask is the stateless
  position hash shared with the flash kernels (no RNG state, no mask
  tensor in HBM — regenerated bit-identically in the backward), and the
  ``jax.custom_vjp`` backward recomputes ``xhat`` from the saved per-row
  ``(mean, rstd)`` statistics — the same residual trick
  ``flash_attention.py`` uses for the logsumexp. Nothing but two f32
  row-vectors crosses forward->backward beyond the step's own inputs.
* ``fused_rms_norm`` — the same kernel family in RMS mode (no mean, no
  beta): the Llama-path norm, routed from ``ops/attention.py::rms_norm``.
* ``fused_bias_gelu`` — the Dense epilogue ``gelu(x + bias)`` (exact erf
  form, matching ``Activation(act_type='gelu')``); the backward
  recomputes the activation derivative from the (already-live) matmul
  output instead of saving erf/cdf intermediates.

Routing contract (mirrors ``flash_supported``): kernels engage only when
``MXNET_PALLAS_FUSED=1`` AND the executing platform is TPU AND the shape
gate passes; every caller falls back to the eager jnp composition
otherwise, and the *reference* implementations here double as the CPU
oracles for the bit-/tolerance-identity tests
(``tests/test_pallas_fused_layers.py``).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as _np

from .flash_attention import (_hash_u16, _x32_mode, dropout_thresh,
                              fold_key_seed)

__all__ = [
    "fused_layer_norm", "fused_rms_norm", "fused_bias_gelu",
    "fused_layer_norm_reference", "fused_rms_norm_reference",
    "fused_bias_gelu_reference", "fused_layers_enabled",
    "fused_ln_shape_supported", "fused_ln_supported",
]

# VMEM comfort cap for one (rows, D) f32 tile; with ~4 live f32
# intermediates per row-block the backward stays well under the 16 MB
# scoped limit at 2 MB per operand tile
_TILE_BYTES = 2 << 20
_MAX_D = 8192
_INV_SQRT2 = _np.float32(0.7071067811865476)
_INV_SQRT2PI = _np.float32(0.3989422804014327)
_ONE32 = _np.float32(1.0)
_HALF32 = _np.float32(0.5)


def fused_layers_enabled() -> bool:
    """The routing knob: ``MXNET_PALLAS_FUSED=1`` opts the ops/nn.py and
    model-zoo seams into the fused-kernel dispatch (shape/platform gates
    still apply per call). Read per call so tests can toggle it."""
    return os.environ.get("MXNET_PALLAS_FUSED", "0") == "1"


def fused_ln_shape_supported(x, shards=1) -> bool:
    """Platform-independent shape eligibility for the row kernels.

    Rows (product of leading dims; those of ONE of ``shards`` equal parts)
    must tile into 8-sublane f32 blocks, the feature dim be lane-aligned
    and VMEM-resident; anything else takes the eager path."""
    if x.ndim < 2:
        return False
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    return (d % 128 == 0 and d <= _MAX_D and rows > 0
            and rows % (8 * shards) == 0)


def fused_ln_supported(x) -> int:
    """Kernel eligibility (the ``flash_supported`` twin): TPU execution
    platform, and the shape gate on what ONE batch shard holds. The answer
    is ``kernel_shards``': 0 give way, 1 the kernel, n it on n shards."""
    from ..base import current_execution_platform
    from ..parallel.mesh import kernel_shards

    n = kernel_shards(x.shape[0]) if x.ndim >= 2 else 0
    ok = n and current_execution_platform(x) == "tpu"
    return n if ok and fused_ln_shape_supported(x, n) else 0


def _block_rows(rows: int, d: int) -> int:
    """Largest 8-multiple row-block whose f32 tile fits the VMEM cap."""
    cap = max(8, _TILE_BYTES // (d * 4))
    for br in (1024, 512, 256, 128, 64, 32, 16, 8):
        if br <= cap and rows % br == 0:
            return br
    return 8


def _seed_arr(seed):
    if seed is None:
        return jnp.zeros((1,), jnp.uint32)
    return jnp.asarray(seed, jnp.uint32).reshape(-1)


def _row_keep_mask(seed_ref, block_idx, br, d, dropout):
    """(br, d) keep-mask for a row block: the flash kernels' murmur
    finalizer over the element's absolute flat (row, col) id (the call's
    first row in the whole array is ``seed_ref[1]``), same in the backward."""
    base = seed_ref[1] + (block_idx * br).astype(jnp.uint32)
    row = base + jax.lax.broadcasted_iota(jnp.uint32, (br, d), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (br, d), 1)
    flat = row * _np.uint32(d) + col
    return _hash_u16(flat, seed_ref[0]) < dropout_thresh(dropout)


def _ref_keep_mask(shape2d, seed, dropout):
    """The oracle's mask over a flattened (rows, d) view — bitwise
    identical to the in-kernel mask."""
    rows, d = shape2d
    row = jax.lax.broadcasted_iota(jnp.uint32, (rows, d), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (rows, d), 1)
    flat = row * _np.uint32(d) + col
    seed_u = jnp.asarray(seed, jnp.uint32).reshape(-1)[0]
    return _hash_u16(flat, seed_u) < dropout_thresh(dropout)


# ---------------------------------------------------------------------------
# reference implementations (eager fallback path AND the CPU oracle)
# ---------------------------------------------------------------------------


def _apply_ref_dropout(x, dropout, seed):
    if not dropout:
        return x
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    keep = _ref_keep_mask((rows, x.shape[-1]), seed, dropout).reshape(
        x.shape)
    inv_keep = jnp.asarray(1.0 / (1.0 - dropout), x.dtype)
    return jnp.where(keep, x * inv_keep, jnp.zeros_like(x))


def fused_layer_norm_reference(x, gamma, beta, residual=None, *, eps=1e-5,
                               dropout=0.0, seed=None):
    """Eager composition of ``LN(dropout(x) + residual)`` — the same
    math as ``ops/nn.py::layer_norm`` over the summed input, with the
    kernels' stateless-hash dropout so both paths draw identical masks
    for a given seed."""
    h = _apply_ref_dropout(x, float(dropout), seed)
    if residual is not None:
        h = h + residual
    h32 = h.astype(jnp.float32)
    mean = jnp.mean(h32, axis=-1, keepdims=True)
    var = jnp.var(h32, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    out = (h32 - mean) * inv
    bshape = (1,) * (x.ndim - 1) + (x.shape[-1],)
    out = out * gamma.astype(jnp.float32).reshape(bshape) \
        + beta.astype(jnp.float32).reshape(bshape)
    return out.astype(x.dtype)


def fused_rms_norm_reference(x, weight, *, eps=1e-6):
    """Identical math to ``ops/attention.py::rms_norm``."""
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * weight


def fused_bias_gelu_reference(x, bias):
    """Identical math to the eager Dense path: ``out + bias`` in the
    matmul dtype, then exact-erf GELU."""
    return jax.nn.gelu(x + bias.astype(x.dtype), approximate=False)


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------


def _norm_fwd_kernel(*refs, eps, dropout, d, br, rms, has_res):
    """One row-block: h = dropout(x) + residual; out = norm(h).

    Writes the per-row statistics (mean, rstd — rstd only in RMS mode)
    as (8, br) sublane-broadcast f32 tiles, the backward's residuals.
    """
    from jax.experimental import pallas as pl

    it = iter(refs)
    x_ref = next(it)
    res_ref = next(it) if has_res else None
    g_ref = next(it)
    b_ref = None if rms else next(it)
    seed_ref = next(it)
    o_ref = next(it)
    mean_ref = None if rms else next(it)
    rstd_ref = next(it)

    h = x_ref[...].astype(jnp.float32)                    # (br, d)
    if dropout > 0.0:
        keep = _row_keep_mask(seed_ref, pl.program_id(0), br, d, dropout)
        h = jnp.where(keep, h * _np.float32(1.0 / (1.0 - dropout)),
                      _np.float32(0.0))
    if has_res:
        h = h + res_ref[...].astype(jnp.float32)
    if rms:
        var = jnp.mean(h * h, axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(var + _np.float32(eps))
        # eager parity (ops/attention.py::rms_norm): the normalized
        # value is rounded to the INPUT dtype before the weight multiply
        # — with f32 norm weights over bf16 activations the output
        # promotes to f32, and the rounding is observable
        xhat = (h * rstd).astype(x_ref.dtype).astype(jnp.float32)
        out = xhat * g_ref[...].astype(jnp.float32)
    else:
        mean = jnp.mean(h, axis=-1, keepdims=True)
        hc = h - mean
        var = jnp.mean(hc * hc, axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(var + _np.float32(eps))
        xhat = hc * rstd
        out = xhat * g_ref[...].astype(jnp.float32) \
            + b_ref[...].astype(jnp.float32)
        mean_ref[...] = jnp.broadcast_to(mean.reshape(1, br), (8, br))
    o_ref[...] = out.astype(o_ref.dtype)
    rstd_ref[...] = jnp.broadcast_to(rstd.reshape(1, br), (8, br))


def _partial_rows(t, d):
    """Column sums of one (br, d) block as an (8, d) sublane-broadcast
    tile (the per-block partial of a bias/scale gradient)."""
    return jnp.broadcast_to(jnp.sum(t, axis=0).reshape(1, d), (8, d))


def _sum_partials(part):
    """(nb, 8, d) sublane-broadcast partials -> (d,) total."""
    return jnp.sum(part[:, 0, :], axis=0)


def _norm_bwd_kernel(*refs, eps, dropout, d, br, rms, has_res):
    """Backward for one row-block, recomputing ``xhat`` from the saved
    (mean, rstd) row statistics — no activation tensor was saved.

    dgamma/dbeta contributions are emitted as per-block partial rows
    and summed outside the kernel: the grid is embarrassingly
    row-parallel, and the partials are tiny next to the activations.
    Each partial is an (8, d) sublane-broadcast tile of an (nb, 8, d)
    output, like the row statistics — a (1, d) block of an (nb, d)
    array is not a legal TPU block.
    """
    from jax.experimental import pallas as pl

    it = iter(refs)
    x_ref = next(it)
    res_ref = next(it) if has_res else None
    g_ref = next(it)
    mean_ref = None if rms else next(it)
    rstd_ref = next(it)
    dy_ref = next(it)
    seed_ref = next(it)
    dx_ref = next(it)
    dres_ref = next(it) if (has_res and dropout > 0.0) else None
    dg_ref = next(it)
    db_ref = None if rms else next(it)

    h = x_ref[...].astype(jnp.float32)
    if dropout > 0.0:
        keep = _row_keep_mask(seed_ref, pl.program_id(0), br, d, dropout)
        inv_keep = _np.float32(1.0 / (1.0 - dropout))
        h = jnp.where(keep, h * inv_keep, _np.float32(0.0))
    if has_res:
        h = h + res_ref[...].astype(jnp.float32)
    rstd = rstd_ref[0:1, :].reshape(br, 1)                # (br, 1)
    if rms:
        xhat = h * rstd
    else:
        mean = mean_ref[0:1, :].reshape(br, 1)
        xhat = (h - mean) * rstd
    dy = dy_ref[...].astype(jnp.float32)
    g32 = g_ref[...].astype(jnp.float32)                  # (1, d)
    wdy = dy * g32
    m2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    if rms:
        dh = rstd * (wdy - xhat * m2)
    else:
        m1 = jnp.mean(wdy, axis=-1, keepdims=True)
        dh = rstd * (wdy - m1 - xhat * m2)
        db_ref[...] = _partial_rows(dy, d)
    dg_ref[...] = _partial_rows(dy * xhat, d)
    if dropout > 0.0:
        dx = jnp.where(keep, dh * inv_keep, _np.float32(0.0))
    else:
        dx = dh
    dx_ref[...] = dx.astype(dx_ref.dtype)
    if dres_ref is not None:
        dres_ref[...] = dh.astype(dres_ref.dtype)


def _norm_fwd_pallas(x2, res2, gamma, beta, seed, eps, dropout, rms,
                     interpret):
    """x2/res2: (rows, d); gamma/beta: (1, d). Returns (out, mean, rstd)
    with stats shaped (nb, 8, br) f32 (mean is None in RMS mode)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, d = x2.shape
    br = _block_rows(rows, d)
    nb = rows // br
    has_res = res2 is not None
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((None, 8, br), lambda i: (i, 0, 0))
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [row_spec] + ([row_spec] if has_res else []) + [vec_spec] \
        + ([] if rms else [vec_spec]) + [smem_spec]
    out_specs = [row_spec] + ([] if rms else [stat_spec]) + [stat_spec]
    # RMS mode promotes by the weight dtype, like the eager
    # `.astype(x.dtype) * weight` (f32 norm weights -> f32 output)
    out_dtype = jnp.result_type(x2.dtype, gamma.dtype) if rms else x2.dtype
    out_shape = [jax.ShapeDtypeStruct((rows, d), out_dtype)] \
        + ([] if rms else [jax.ShapeDtypeStruct((nb, 8, br), jnp.float32)]) \
        + [jax.ShapeDtypeStruct((nb, 8, br), jnp.float32)]
    args = [x2] + ([res2] if has_res else []) + [gamma] \
        + ([] if rms else [beta]) + [_seed_arr(seed)]
    kernel = functools.partial(_norm_fwd_kernel, eps=eps, dropout=dropout,
                               d=d, br=br, rms=rms, has_res=has_res)
    with _x32_mode():
        outs = pl.pallas_call(kernel, grid=(nb,), in_specs=in_specs,
                              out_specs=out_specs, out_shape=out_shape,
                              interpret=interpret)(*args)
    if rms:
        out, rstd = outs
        return out, None, rstd
    return outs


def _norm_bwd_pallas(x2, res2, gamma, mean, rstd, dy2, seed, eps, dropout,
                     rms, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import telemetry

    telemetry.record_pallas_dispatch(
        "fused_rms_norm_bwd" if rms else "fused_layer_norm_bwd")
    rows, d = x2.shape
    br = _block_rows(rows, d)
    nb = rows // br
    has_res = res2 is not None
    emit_dres = has_res and dropout > 0.0
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((None, 8, br), lambda i: (i, 0, 0))
    part_spec = pl.BlockSpec((None, 8, d), lambda i: (i, 0, 0))
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [row_spec] + ([row_spec] if has_res else []) + [vec_spec] \
        + ([] if rms else [stat_spec]) + [stat_spec, row_spec, smem_spec]
    out_specs = [row_spec] + ([row_spec] if emit_dres else []) \
        + [part_spec] + ([] if rms else [part_spec])
    out_shape = [jax.ShapeDtypeStruct((rows, d), x2.dtype)] \
        + ([jax.ShapeDtypeStruct((rows, d), x2.dtype)] if emit_dres
           else []) \
        + [jax.ShapeDtypeStruct((nb, 8, d), jnp.float32)] \
        + ([] if rms else [jax.ShapeDtypeStruct((nb, 8, d), jnp.float32)])
    args = [x2] + ([res2] if has_res else []) + [gamma] \
        + ([] if rms else [mean]) + [rstd, dy2, _seed_arr(seed)]
    kernel = functools.partial(_norm_bwd_kernel, eps=eps, dropout=dropout,
                               d=d, br=br, rms=rms, has_res=has_res)
    with _x32_mode():
        outs = pl.pallas_call(kernel, grid=(nb,), in_specs=in_specs,
                              out_specs=out_specs, out_shape=out_shape,
                              interpret=interpret)(*args)
    outs = list(outs)
    dx = outs.pop(0)
    dres = outs.pop(0) if emit_dres else (dx if has_res else None)
    dg_part = outs.pop(0)
    db_part = None if rms else outs.pop(0)
    dgamma = _sum_partials(dg_part)
    dbeta = None if rms else _sum_partials(db_part)
    return dx, dres, dgamma, dbeta


# -- layer norm with residual ------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ln_res(x2, res2, gamma, beta, seed, eps, dropout, interpret):
    out, _, _ = _norm_fwd_pallas(x2, res2, gamma, beta, seed, eps,
                                 dropout, False, interpret)
    return out


def _ln_res_fwd(x2, res2, gamma, beta, seed, eps, dropout, interpret):
    out, mean, rstd = _norm_fwd_pallas(x2, res2, gamma, beta, seed, eps,
                                       dropout, False, interpret)
    return out, (x2, res2, gamma, mean, rstd, seed)


def _ln_res_bwd(eps, dropout, interpret, resids, dy):
    x2, res2, gamma, mean, rstd, seed = resids
    dx, dres, dgamma, dbeta = _norm_bwd_pallas(
        x2, res2, gamma, mean, rstd, dy, seed, eps, dropout, False,
        interpret)
    return (dx, dres.astype(res2.dtype),
            dgamma.reshape(gamma.shape).astype(gamma.dtype),
            dbeta.reshape(gamma.shape).astype(gamma.dtype),
            _np.zeros(seed.shape, jax.dtypes.float0))


_ln_res.defvjp(_ln_res_fwd, _ln_res_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ln_plain(x2, gamma, beta, seed, eps, dropout, interpret):
    out, _, _ = _norm_fwd_pallas(x2, None, gamma, beta, seed, eps,
                                 dropout, False, interpret)
    return out


def _ln_plain_fwd(x2, gamma, beta, seed, eps, dropout, interpret):
    out, mean, rstd = _norm_fwd_pallas(x2, None, gamma, beta, seed, eps,
                                       dropout, False, interpret)
    return out, (x2, gamma, mean, rstd, seed)


def _ln_plain_bwd(eps, dropout, interpret, resids, dy):
    x2, gamma, mean, rstd, seed = resids
    dx, _, dgamma, dbeta = _norm_bwd_pallas(
        x2, None, gamma, mean, rstd, dy, seed, eps, dropout, False,
        interpret)
    return (dx, dgamma.reshape(gamma.shape).astype(gamma.dtype),
            dbeta.reshape(gamma.shape).astype(gamma.dtype),
            _np.zeros(seed.shape, jax.dtypes.float0))


_ln_plain.defvjp(_ln_plain_fwd, _ln_plain_bwd)


def fused_layer_norm(x, gamma, beta, residual=None, *, eps=1e-5,
                     dropout=0.0, seed=None, interpret=False, first_row=0):
    """Fused ``LayerNorm(dropout(x) + residual)`` over the last axis.

    ``gamma``/``beta``: (D,). ``residual``: same shape as ``x`` or None.
    ``dropout`` applies to ``x`` only (the post-LN transformer pattern);
    the mask is the stateless hash of an element's position, seeded by
    ``seed`` (uint32, required when dropout > 0). ``first_row``: where
    ``x``'s rows start in the array the mask is drawn over (a batch shard's
    offset; 0 for the whole). Differentiable via ``jax.custom_vjp``: the
    backward recomputes ``xhat`` from the saved per-row (mean, rstd).
    """
    dropout = float(dropout)
    if dropout > 0.0 and seed is None:
        raise ValueError("fused_layer_norm: dropout > 0 requires a seed")
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    g2 = gamma.reshape(1, d)
    b2 = beta.reshape(1, d)
    if residual is not None:
        out = _ln_res(x2, residual.reshape(rows, d), g2, b2,
                      _seed_and_first_row(seed, first_row), float(eps),
                      dropout, bool(interpret))
    else:
        out = _ln_plain(x2, g2, b2, _seed_and_first_row(seed, first_row),
                        float(eps), dropout, bool(interpret))
    return out.reshape(shape)


# -- rms norm ----------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms(x2, weight, eps, interpret):
    out, _, _ = _norm_fwd_pallas(x2, None, weight, None, None, eps, 0.0,
                                 True, interpret)
    return out


def _rms_fwd(x2, weight, eps, interpret):
    out, _, rstd = _norm_fwd_pallas(x2, None, weight, None, None, eps,
                                    0.0, True, interpret)
    return out, (x2, weight, rstd)


def _rms_bwd(eps, interpret, resids, dy):
    x2, weight, rstd = resids
    dx, _, dw, _ = _norm_bwd_pallas(x2, None, weight, None, rstd, dy,
                                    None, eps, 0.0, True, interpret)
    return dx, dw.reshape(weight.shape).astype(weight.dtype)


_rms.defvjp(_rms_fwd, _rms_bwd)


def fused_rms_norm(x, weight, *, eps=1e-6, interpret=False):
    """Fused RMSNorm over the last axis (the Llama-path norm); stats in
    f32, backward recomputes ``xhat`` from the saved rstd row-vector."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    out = _rms(x.reshape(rows, d), weight.reshape(1, d), float(eps),
               bool(interpret))
    return out.reshape(shape)


# -- bias + gelu epilogue ----------------------------------------------------


# f32 erf as the rational polynomial XLA itself evaluates for lax.erf
# (x * P(x^2) / Q(x^2) on the clamped argument; ~3e-7 absolute error):
# Mosaic has no lowering for the erf primitive.
_ERF_CLAMP = _np.float32(3.7439211627767994)
_ERF_ALPHA = tuple(_np.float32(c) for c in (
    0.00022905065861350646, 0.0034082910107109506, 0.050955695062380861,
    0.18520832239976145, 1.128379143519084))
_ERF_BETA = tuple(_np.float32(c) for c in (
    -1.1791602954361697e-7, 0.000023547966471313185,
    0.0010179625278914885, 0.014070470171167667, 0.11098505178285362,
    0.49746925110067538, 1.0))


def _erf32(x):
    x = jnp.clip(x, -_ERF_CLAMP, _ERF_CLAMP)
    x2 = x * x
    num = jnp.full_like(x, _ERF_ALPHA[0])
    for c in _ERF_ALPHA[1:]:
        num = num * x2 + c
    den = jnp.full_like(x, _ERF_BETA[0])
    for c in _ERF_BETA[1:]:
        den = den * x2 + c
    return x * num / den


# The two bodies walk a block in strips: ``_GELU_STRIP_ROWS`` rows at a
# time, each strip in pieces of at most ``_GELU_PIECE_COLS`` columns. One
# value of the chain is then 8 float32 vregs of the chip's 64, so the
# ~35 values between a piece's load and its store live in registers. As
# whole-block expressions every one of them was a block-sized float32
# temporary that Mosaic wrote to VMEM and read back (7.75 MB of compiler
# scratch beside 3.1 MB of pipelined blocks at BERT's FFN width), and
# the kernel was bound by those stores, not by HBM.
_GELU_STRIP_ROWS = 16
_GELU_PIECE_COLS = (512, 384, 256, 128)
# what the pipelined blocks of one call may take of the 16 MB of scoped
# VMEM; nothing else of block size is left to hold
_GELU_BLOCK_BYTES = 10 << 20


def _gelu_strip(br: int, d: int):
    """(rows of a strip, columns of a piece) for a ``(br, d)`` block: a
    whole packed bf16 tile of rows where the block has them, and the
    widest piece that divides ``d`` (a multiple of 128 by the gate)."""
    sr = _GELU_STRIP_ROWS if br % _GELU_STRIP_ROWS == 0 else 8
    return sr, next(w for w in _GELU_PIECE_COLS if d % w == 0)


def _bias_gelu_block_rows(rows: int, d: int, itemsize: int,
                          n_blocks: int) -> int:
    """Rows of a block when ``n_blocks`` row blocks are pipelined (in and
    out, each double-buffered): the tallest that divides ``rows`` and
    keeps the buffers inside ``_GELU_BLOCK_BYTES``."""
    cap = _GELU_BLOCK_BYTES // (2 * n_blocks * d * itemsize)
    for br in (256, 128, 64, 32, 16):
        if br <= cap and rows % br == 0:
            return br
    return 8


def _bias_gelu_fwd_kernel(x_ref, b_ref, o_ref, *, d, br):
    from jax.experimental import pallas as pl

    sr, pw = _gelu_strip(br, d)

    def strip(i, carry):
        r = pl.multiple_of(i * sr, sr)
        for c in range(0, d, pw):
            u = x_ref[pl.ds(r, sr), c:c + pw].astype(jnp.float32) \
                + b_ref[:, c:c + pw].astype(jnp.float32)
            cdf = _HALF32 * (_ONE32 + _erf32(u * _INV_SQRT2))
            o_ref[pl.ds(r, sr), c:c + pw] = (u * cdf).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, br // sr, strip, 0)


def _bias_gelu_bwd_kernel(x_ref, b_ref, dy_ref, dx_ref, db_ref, *, d, br):
    from jax.experimental import pallas as pl

    sr, pw = _gelu_strip(br, d)
    # the bias gradient's partial gathers in its (8, d) float32 output
    # block: per piece a load and a store of that block's columns beside
    # the ~40 register operations of each of the piece's vregs
    db_ref[...] = jnp.zeros((8, d), jnp.float32)

    def strip(i, carry):
        r = pl.multiple_of(i * sr, sr)
        for c in range(0, d, pw):
            u = x_ref[pl.ds(r, sr), c:c + pw].astype(jnp.float32) \
                + b_ref[:, c:c + pw].astype(jnp.float32)
            cdf = _HALF32 * (_ONE32 + _erf32(u * _INV_SQRT2))
            pdf = jnp.exp(-_HALF32 * u * u) * _INV_SQRT2PI
            deriv = cdf + u * pdf
            dy = dy_ref[pl.ds(r, sr), c:c + pw].astype(jnp.float32)
            dx = dy * deriv
            dx_ref[pl.ds(r, sr), c:c + pw] = dx.astype(dx_ref.dtype)
            part = dx[0:8]
            for k in range(8, sr, 8):
                part = part + dx[k:k + 8]
            db_ref[:, c:c + pw] += part
        return carry

    jax.lax.fori_loop(0, br // sr, strip, 0)
    db_ref[...] = _partial_rows(db_ref[...], d)


# jitted, so that sites of one shape share one trace and one lowering of
# a body: the unrolled strips are ~6x a whole-block body's operations to
# trace, and every process pays a kernel's tracing as set-up. The name
# reaches the trace: the custom calls are ``_fused_bias_gelu_pallas.N``.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_bias_gelu_pallas(x2, b2, interpret, backward_dy=None):
    from jax.experimental import pallas as pl

    rows, d = x2.shape
    br = _bias_gelu_block_rows(rows, d, x2.dtype.itemsize,
                               2 if backward_dy is None else 3)
    nb = rows // br
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    part_spec = pl.BlockSpec((None, 8, d), lambda i: (i, 0, 0))
    with _x32_mode():
        if backward_dy is None:
            return pl.pallas_call(
                functools.partial(_bias_gelu_fwd_kernel, d=d, br=br),
                grid=(nb,), in_specs=[row_spec, vec_spec],
                out_specs=row_spec,
                out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
                interpret=interpret)(x2, b2)
        dx, db_part = pl.pallas_call(
            functools.partial(_bias_gelu_bwd_kernel, d=d, br=br),
            grid=(nb,), in_specs=[row_spec, vec_spec, row_spec],
            out_specs=[row_spec, part_spec],
            out_shape=[jax.ShapeDtypeStruct((rows, d), x2.dtype),
                       jax.ShapeDtypeStruct((nb, 8, d), jnp.float32)],
            interpret=interpret)(x2, b2, backward_dy)
    return dx, _sum_partials(db_part)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _bias_gelu(x2, b2, interpret):
    return _fused_bias_gelu_pallas(x2, b2, interpret)


def _bias_gelu_fwd(x2, b2, interpret):
    return _fused_bias_gelu_pallas(x2, b2, interpret), (x2, b2)


def _bias_gelu_bwd(interpret, resids, dy):
    from .. import telemetry

    telemetry.record_pallas_dispatch("fused_bias_gelu_bwd")
    x2, b2 = resids
    dx, db = _fused_bias_gelu_pallas(x2, b2, interpret, backward_dy=dy)
    return dx, db.reshape(b2.shape).astype(b2.dtype)


_bias_gelu.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


def fused_bias_gelu(x, bias, *, interpret=False):
    """Fused ``gelu(x + bias)`` (exact erf form) — the Dense matmul
    epilogue. ``bias``: (D,). The backward recomputes the activation
    derivative from (x, bias); no erf/cdf intermediate is saved.

    Both kernels are elementwise chains of ~35 float32 operations, so
    their bodies loop over strips of a block (``_gelu_strip``) and take
    each strip from load to store in registers: what a site costs is
    then its HBM traffic and its arithmetic, not the VMEM round trips of
    block-sized temporaries. Per element the operations and their order
    are those of the whole-block expressions; only the bias gradient's
    float32 row sum is taken in another order."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    out = _bias_gelu(x.reshape(rows, d), bias.reshape(1, d),
                     bool(interpret))
    return out.reshape(shape)


def _seed_and_first_row(seed, first_row):
    """The dropout kernels' SMEM operand ``(seed, first_row)``: the mask
    hashes a row's place in the GLOBAL array, and a call on one batch
    shard's rows (``parallel.mesh.over_batch_shards``) starts at that
    shard's first row, not at 0. Without a seed (no dropout) the operand
    is the ``(1,)`` placeholder it always was."""
    if seed is None:
        return _seed_arr(None)
    return jnp.stack([_seed_arr(seed)[0],
                      jnp.asarray(first_row, jnp.uint32)])
