"""The key-length-bounded flash forward's schedule (a grid step walks the
live 512-key chunks of a wide key block; a tile's running max and sum are
kept 128 lanes wide) against the plain softmax with a live-key mask, in
interpret mode, and against the whole-tile body it replaced."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu  # noqa: F401  (x64 on, as every real trace has it)
from mxnet_tpu.ops.attention import _sdpa_reference
from mxnet_tpu.pallas_kernels import flash_attention
from mxnet_tpu.pallas_kernels.flash_attention import (
    _LOG2E, _NEG_INF32, _ONE32, _ZERO32, _bounded_blocks, _prec_for,
    _x32_mode)

pytestmark = pytest.mark.pallas

F32, BF16 = jnp.float32, jnp.bfloat16


def _qkv(rows, d, dtype, batch=2, heads=2, key=0):
    return tuple(jax.random.normal(x, (batch, heads, rows, d), F32)
                 .astype(dtype)
                 for x in jax.random.split(jax.random.key(key), 3))


def _lengths(rows, d, dtype, where):
    """Two lengths of a batch that sit ``where`` the schedule has a seam
    (the second row always differs from the first)."""
    _, bkv, bk, _ = _bounded_blocks(rows, rows, d, jnp.dtype(dtype).itemsize)
    return {
        "zero_and_one": (0, 1),
        "inside_a_chunk": (bk // 2 + 3, max(rows - 5, 1)),
        "on_a_chunk_edge": (bk, max(rows - bk, bk)),
        "on_a_grid_step_edge": (bkv, max(bkv - 1, 1)),
        "whole": (rows, max(rows // 2 + 1, 1)),
    }[where]


# rows x head dim x dtype: 128 and 384 are the rows only 128 divides
# (navit_tiny's buckets), 3,072 has three 512-key chunks in ONE grid step,
# float32 at 3,072 rows has two grid steps of three chunks (its key block
# is half as many keys), head dim 64 spreads the rescale over half a
# register and 256 over two
SHAPES = [(128, 128, F32), (384, 128, F32), (384, 64, BF16),
          (1024, 128, BF16), (1024, 64, F32), (1024, 256, BF16),
          (3072, 128, BF16), (3072, 128, F32), (3072, 64, BF16)]
SEAMS = ["zero_and_one", "inside_a_chunk", "on_a_chunk_edge",
         "on_a_grid_step_edge", "whole"]


@pytest.mark.parametrize("where", SEAMS)
@pytest.mark.parametrize("rows,d,dtype", SHAPES,
                         ids=[f"{r}x{d}-{jnp.dtype(t).name}"
                              for r, d, t in SHAPES])
def test_bounded_schedule_against_the_masked_softmax(rows, d, dtype, where):
    lengths = _lengths(rows, d, dtype, where)
    q, k, v = _qkv(rows, d, dtype, heads=1 if rows > 1024 else 2)
    n = jnp.asarray(lengths, jnp.int32)
    out = flash_attention(q, k, v, interpret=True, kv_len=n)
    assert out.shape == q.shape and out.dtype == q.dtype
    live = jnp.arange(rows)[None, :] < n[:, None]
    ref = _sdpa_reference(q.astype(F32), k.astype(F32), v.astype(F32),
                          live[:, None, None, :], d ** -0.5, False)
    # bfloat16: the probabilities are rounded before PV and the output
    # after it, 2^-8 each of values below 1 in size
    tol = 3e-6 if dtype == F32 else 2e-2
    bq = _bounded_blocks(rows, rows, d, jnp.dtype(dtype).itemsize)[0]
    for row, nb in enumerate(lengths):
        got = np.asarray(out[row].astype(F32))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got[:, :nb], np.asarray(ref[row, :, :nb]),
                                   atol=tol)
        # query blocks wholly past the length: exactly zero
        past = -(-nb // bq) * bq
        assert np.abs(got[:, past:]).max(initial=0.0) == 0.0


# ---------------------------------------------------------------------------
# a private oracle: the whole-tile body this schedule replaced (one grid
# step a (1024, 512) tile, the max and rescale factor as (rows, 1)
# columns, the row sum reduced every key step), kept as it was
# ---------------------------------------------------------------------------


def _old_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                *, scale2, nk, prec, bq, bk, h):
    from jax.experimental import pallas as pl

    n = len_ref[pl.program_id(0) // h]
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF32)
        l_ref[:] = jnp.zeros_like(l_ref)

    def compute(masked):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec) * scale2
        if masked:
            k_pos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos < n, s, _NEG_INF32)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32,
            precision=prec)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    live_q = qi * bq < n

    @pl.when(live_q & ((ki + 1) * bk <= n))
    def _whole():
        compute(False)

    @pl.when(live_q & (ki * bk < n) & ((ki + 1) * bk > n))
    def _edge():
        compute(True)

    @pl.when(ki == nk - 1)
    def _final():
        l = l_ref[:, 0:1]
        o_ref[...] = (acc_ref[:] / jnp.where(l == _ZERO32, _ONE32, l)
                      ).astype(o_ref.dtype)


def _old_flash_fwd_bounded(q, k, v, kv_len, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lq, d = q.shape
    lk = k.shape[2]
    bh = b * h
    bq = next(x for x in (1024, 512, 256, 128) if lq % x == 0)
    bk = next(x for x in (512, 256, 128) if lk % x == 0)
    nq, nk = lq // bq, lk // bk

    def kv_map(i, qi, ki, len_ref):
        last = jnp.maximum((len_ref[i // h] + (bk - 1)) // bk - 1, 0)
        return (i, jnp.minimum(ki, last), 0)

    kernel = functools.partial(
        _old_kernel, scale2=np.float32(scale) * _LOG2E, nk=nk,
        prec=_prec_for(q.dtype), bq=bq, bk=bk, h=h)
    with _x32_mode():
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(bh, nq, nk),
                in_specs=[
                    pl.BlockSpec((None, bq, d),
                                 lambda i, qi, ki, len_ref: (i, qi, 0)),
                    pl.BlockSpec((None, bk, d), kv_map),
                    pl.BlockSpec((None, bk, d), kv_map)],
                out_specs=pl.BlockSpec(
                    (None, bq, d), lambda i, qi, ki, len_ref: (i, qi, 0)),
                scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                                pltpu.VMEM((bq, 128), jnp.float32),
                                pltpu.VMEM((bq, 128), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
            interpret=True,
        )(jnp.asarray(kv_len, jnp.int32).reshape(b),
          q.reshape(bh, lq, d), k.reshape(bh, lk, d), v.reshape(bh, lk, d))
    return out.reshape(b, h, lq, d)


@pytest.mark.parametrize("dtype,tol", [(F32, 2e-6), (BF16, 2 ** -7)],
                         ids=["float32", "bfloat16"])
def test_bounded_schedule_is_the_old_tile_to_rounding(dtype, tol):
    """Same mathematics: float32 scores, max, sum and accumulator, exp2 on
    scaled scores, p cast to the value dtype before PV. What differs is
    the order the float32 row sums are added in (128 partial sums a row,
    added up once), so float32 agrees to a few ulps; bfloat16 outputs may
    land on the neighbouring bfloat16 (one step of 2^-8 below 1)."""
    rows, d = 2048, 128
    q, k, v = _qkv(rows, d, dtype, heads=1, key=3)
    n = jnp.asarray([1500, 2048], jnp.int32)
    new = flash_attention(q, k, v, interpret=True, kv_len=n)
    old = _old_flash_fwd_bounded(q, k, v, n, d ** -0.5)
    np.testing.assert_allclose(np.asarray(new.astype(F32)),
                               np.asarray(old.astype(F32)), atol=tol)
