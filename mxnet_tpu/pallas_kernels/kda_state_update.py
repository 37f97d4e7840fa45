"""The decode step of the delta-rule recurrence (Kimi Delta Attention:
``ops/linear_attention.py``), in place on an engine's slot array.

As ``ssd_state_update.py`` does for Mamba-2's scan state: a stream's
state is a ``(heads, d_k, d_v)`` float32 block of its STATE SLOT (2 MB at
Ling's widths), the round's ``B`` streams sit in ``B`` arbitrary slots,
the slot ids are scalar-prefetched and steer each grid step's block
straight at the stream's slot, the array is aliased input to output, and
every live stream's state is read once and written once. The two kernels
share that frame and nothing else (a second kernel, not one walker behind
both): the SSD body scales a head's whole state by ONE scalar and adds an
outer product of a group's ``B`` row, so it never has to look at the state
before it writes; here the decay multiplies ROWS (a key channel each) and
the write is a correction, ``beta k (v - k^T S')^T``, that needs ``k^T
S'`` of the whole decayed state first. So the body makes two passes over
a head's rows: the first decays them and sums ``k^T S'``; the second adds
the outer product, stores, and sums ``q^T S``.

A head's state lies keys down the sublanes and values along the lanes.
What is indexed by the key channel (``alpha``, ``k``, ``q``) has to stand
as COLUMNS beside it: the caller hands the three in ONE array laid out
``(B, heads / hb, d_k, 3 * hb)``, the key channel in the sublanes and
(kind, head) in the lanes, so that a head's column is a static lane of
the step's block and the broadcast along the lanes is the only relayout
(3% of the state's bytes; a ``(.., d_k, 1)`` array a head would be padded
to whole lane tiles and double the traffic). What is indexed by the
value channel (``beta v``, ``beta``, ``o``) stays rows.

Grid ``(B, heads / hb)``. Padding rows of a batch bucket carry slot 0,
the scratch slot: they all write it, in grid order, and nobody reads it.
``kda_update_supported`` gates on TPU execution plus Mosaic-friendly
shapes; ``ops/linear_attention.py::kda_step`` over ``states[slots]`` is
the reference and the path everywhere else, and CPU tests run this kernel
with ``interpret=True`` against it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _x32_mode

__all__ = ["kda_state_update_kernel", "kda_update_shape_supported",
           "kda_update_supported"]

# state rows of one piece of a head's walk: (64, 128) float32 is 8 vregs
_ROWS = 64
# heads of one grid step at most: (16, 128, 128) float32 is 1 MB, in and
# out, two buffers each
_HEADS = 16


def _heads_per_step(n_heads: int) -> int:
    return next(hb for hb in range(min(_HEADS, n_heads), 0, -1)
                if n_heads % hb == 0)


def kda_update_shape_supported(states, q) -> bool:
    """Platform-independent shape eligibility: float32 ``states`` (slots,
    H, Dk, Dv) whose heads are one lane tile wide and whole sublane
    tiles deep, and ``q`` (B, H, Dk) to match."""
    if states.ndim != 4 or q.ndim != 3 or states.dtype != jnp.float32:
        return False
    _, h, dk, dv = states.shape
    return (dv == 128 and dk % 8 == 0 and q.shape[1:] == (h, dk)
            and _heads_per_step(h) % 8 == 0)


def kda_update_supported(states, q) -> bool:
    """TPU execution, a trace the SPMD partitioner does not have to
    split, and the shape gate."""
    from ..base import current_execution_platform
    from ..parallel.mesh import auto_partitioned

    if current_execution_platform(q) != "tpu" or auto_partitioned():
        return False
    return kda_update_shape_supported(states, q)


def _update_kernel(slots_ref, s_ref, cols_ref, bv_ref, beta_ref, s_out,
                   o_ref, *, rows):
    """One (stream, block of ``hb`` heads) grid step. ``s_ref`` / ``s_out``
    (1, hb, Dk, Dv): the block of the stream's slot, before and after;
    ``cols_ref`` (1, 1, Dk, 3 hb): ``alpha | k | q`` as columns, head
    ``i``'s at lanes ``i``, ``hb + i`` and ``2 hb + i``; ``bv_ref`` /
    ``beta_ref`` / ``o_ref`` (1, hb, Dv) rows. Every index is static: the
    heads are written out."""
    del slots_ref                       # read by the index maps alone
    hb, dk = s_ref.shape[1], s_ref.shape[2]
    for i in range(hb):
        def col(kind, r, i=i):
            return cols_ref[0, 0, r:r + rows, kind * hb + i:kind * hb + i + 1]

        seen = jnp.zeros((rows, s_ref.shape[3]), jnp.float32)
        for r in range(0, dk, rows):
            alpha = col(0, r)
            # a zero decay drops the old state whatever the slot holds
            decayed = jnp.where(alpha > 0, s_ref[0, i, r:r + rows, :] * alpha,
                                0.0)
            s_out[0, i, r:r + rows, :] = decayed
            seen = seen + decayed * col(1, r)
        # u = beta (v - k^T S'), a row of values
        u = bv_ref[0, i:i + 1, :] - beta_ref[0, i:i + 1, :] * jnp.sum(
            seen, axis=0, keepdims=True)
        out = jnp.zeros((rows, s_ref.shape[3]), jnp.float32)
        for r in range(0, dk, rows):
            new = s_out[0, i, r:r + rows, :] + col(1, r) * u
            s_out[0, i, r:r + rows, :] = new
            out = out + new * col(2, r)
        o_ref[0, i:i + 1, :] = jnp.sum(out, axis=0, keepdims=True)


def kda_state_update_kernel(states, slots, q, k, v, alpha, beta, *,
                            interpret: bool = False):
    """``S' = Diag(alpha) S``, ``S = S' + beta k (v - k^T S')^T`` and ``o
    = S^T q`` for each row's slot.

    ``states`` (slots, H, Dk, Dv) float32, donated by the caller's
    program for the update to be in place; ``slots`` (B,) int32, a live
    row's own and 0 for a padding row; ``q``, ``k`` (B, H, Dk); ``v`` (B,
    H, Dv); ``alpha`` (B, H, Dk) the decay ``exp(g)``, 0 where the row
    starts a stream; ``beta`` (B, H). Returns ``o`` (B, H, Dv) and the
    slot array with each named slot advanced. Float32 throughout."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    _, h, dk, dv = states.shape
    bsz = q.shape[0]
    hb = _heads_per_step(h)
    rows = min(_ROWS, dk)
    kernel = functools.partial(_update_kernel, rows=rows)
    # (kind, B, H, Dk) -> (B, H / hb, Dk, kind x hb): the key channel in
    # the sublanes, a step's heads' columns side by side in the lanes
    cols = jnp.stack([alpha.astype(f32), k.astype(f32), q.astype(f32)])
    cols = cols.reshape(3, bsz, h // hb, hb, dk).transpose(1, 2, 4, 0, 3)
    cols = cols.reshape(bsz, h // hb, dk, 3 * hb)
    beta = beta.astype(f32)[..., None]
    state_spec = pl.BlockSpec((1, hb, dk, dv),
                              lambda i, j, slot: (slot[i], j, 0, 0))
    row_spec = pl.BlockSpec((1, hb, dv), lambda i, j, slot: (i, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, h // hb),
        in_specs=[state_spec,
                  pl.BlockSpec((1, 1, dk, 3 * hb),
                               lambda i, j, slot: (i, j, 0, 0)),
                  row_spec, row_spec],
        out_specs=[state_spec, row_spec],
    )
    with _x32_mode():
        states, o = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(states.shape, f32),
                       jax.ShapeDtypeStruct((bsz, h, dv), f32)],
            # operand 0 is the prefetched slot ids
            input_output_aliases={1: 0},
            name="kda_state_update",
            interpret=interpret,
        )(slots.astype(jnp.int32), states, cols, beta * v.astype(f32),
          jnp.broadcast_to(beta, (bsz, h, dv)))
    return o, states
