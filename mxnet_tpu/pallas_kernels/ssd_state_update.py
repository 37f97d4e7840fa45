"""The decode step of the SSD recurrence (Mamba-2: ``ops/ssm.py``), in
place on an engine's slot array.

A decode round advances one token a stream. A stream's scan state is a
``(heads, d_state, head_dim)`` float32 block of its STATE SLOT
(``serving/kvcache.py::StateSlots``), 4 MB at Falcon-H1's widths, and
the round's ``B`` streams sit in ``B`` arbitrary slots of a ``(slots,
heads, d_state, head_dim)`` array. Through XLA that is a gather of the
``B`` blocks, the update, and a scatter back: the state crosses HBM
three times each way. Here the slot ids are scalar-prefetched and steer
each grid step's block straight at the stream's slot, the array is
aliased input to output, and every live stream's state is read once and
written once; slots no row names are not touched.

Grid ``(B, heads / hb)``: one step holds ``hb`` heads of one stream's
state (``hb`` heads of ONE group, so ``B`` and ``C`` are the step's
own). A head's state is kept transposed, ``(d_state, head_dim)``: the
head's channels lie in the lanes, so ``dt x`` and the decay are rows that
broadcast down the sublanes, ``y`` is a sum over the sublanes that comes
out as a row, and only ``B`` and ``C`` (a row of ``d_state`` each, once a
step) have to be stood up as columns, by whole-tile transposes. The body
walks a head ``_ROWS`` state rows at a time so that its values stay in
registers.

Padding rows of a batch bucket carry slot 0, the scratch slot: they all
write it, in grid order, and nobody reads it.
``ssd_update_supported`` gates on TPU execution plus Mosaic-friendly
shapes; ``ops/ssm.py::ssd_step`` over ``states[slots]`` is the reference
and the path everywhere else, and CPU tests run this kernel with
``interpret=True`` against it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _x32_mode

__all__ = ["ssd_state_update_kernel", "ssd_update_shape_supported",
           "ssd_update_supported"]

# state rows of one piece of a head's walk: (64, 128) float32 is 8 vregs
_ROWS = 64
# heads of one grid step at most: (16, 256, 128) float32 is 2 MB, in and
# out, two buffers each
_HEADS = 16


def _heads_per_step(n_heads: int, n_groups: int) -> int:
    per_group = n_heads // n_groups
    return next(hb for hb in range(min(_HEADS, per_group), 0, -1)
                if per_group % hb == 0)


def ssd_update_shape_supported(states, x, b) -> bool:
    """Platform-independent shape eligibility: float32 ``states``
    (slots, H, N, P) whose heads are one lane tile wide, whose state
    rows stand up as whole (P, P) tiles, and whose heads divide into the
    groups of ``b`` (B, G, N)."""
    if states.ndim != 4 or x.ndim != 3 or b.ndim != 3 \
            or states.dtype != jnp.float32:
        return False
    _, h, n, p = states.shape
    g = b.shape[1]
    hb = _heads_per_step(h, g) if g and h % g == 0 else 0
    return (p == 128 and n % p == 0 and x.shape[1:] == (h, p)
            and b.shape[2] == n and hb % 8 == 0)


def ssd_update_supported(states, x, b) -> bool:
    """TPU execution, a trace the SPMD partitioner does not have to
    split, and the shape gate."""
    from ..base import current_execution_platform
    from ..parallel.mesh import auto_partitioned

    if current_execution_platform(x) != "tpu" or auto_partitioned():
        return False
    return ssd_update_shape_supported(states, x, b)


def _columns(ref, width):
    """The row ``ref`` (1, 1, N) stood up as (N, width), every column
    the row: (width, width) tiles, each a lane tile of the row broadcast
    down and transposed."""
    return jnp.concatenate(
        [jnp.broadcast_to(ref[0, :, i:i + width], (width, width)).T
         for i in range(0, ref.shape[2], width)], axis=0)


def _update_kernel(slots_ref, s_ref, dtx_ref, decay_ref, b_ref, c_ref,
                   o_ref, y_ref, bcol, ccol, *, rows):
    """One (stream, block of ``hb`` heads) grid step. ``s_ref`` / ``o_ref``
    (1, hb, N, P): the block of the stream's slot, before and after;
    ``dtx_ref`` / ``decay_ref`` / ``y_ref`` (1, hb, P) rows; ``b_ref`` /
    ``c_ref`` (1, 1, N), the block's group's; ``bcol`` / ``ccol`` (N, P)
    scratch. Every index is static (Mosaic loads no row at a dynamic
    sublane offset): the heads are written out."""
    del slots_ref                       # read by the index maps alone
    n, p = bcol.shape
    bcol[...] = _columns(b_ref, p)
    ccol[...] = _columns(c_ref, p)
    for i in range(s_ref.shape[1]):
        decay = decay_ref[0, i:i + 1, :]                    # (1, P)
        dtx = dtx_ref[0, i:i + 1, :]
        acc = jnp.zeros((rows, p), jnp.float32)
        for r in range(0, n, rows):
            # a zero decay drops the old state whatever the slot holds
            new = (jnp.where(decay > 0, s_ref[0, i, r:r + rows, :] * decay,
                             0.0)
                   + bcol[r:r + rows, :] * dtx)
            o_ref[0, i, r:r + rows, :] = new
            acc = acc + new * ccol[r:r + rows, :]
        y_ref[0, i:i + 1, :] = jnp.sum(acc, axis=0, keepdims=True)


def ssd_state_update_kernel(states, slots, dtx, decay, b, c, *,
                            interpret: bool = False):
    """``S = decay S + B (dt x)^T`` and ``y = S^T C`` for each row's slot.

    ``states`` (slots, H, N, P) float32, donated by the caller's program
    for the update to be in place; ``slots`` (B,) int32, a live row's own
    and 0 for a padding row; ``dtx`` (B, H, P) ``dt x``; ``decay`` (B, H)
    ``exp(dt a)``, 0 where the row starts a stream; ``b``, ``c`` (B, G,
    N). Returns the slot array with each named slot advanced and ``y``
    (B, H, P), without the ``D x`` term. Float32 throughout."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    _, h, n, p = states.shape
    bsz, g = b.shape[0], b.shape[1]
    hb = _heads_per_step(h, g)
    rows = min(_ROWS, n)
    kernel = functools.partial(_update_kernel, rows=rows)
    steps_per_group = h // g // hb
    state_spec = pl.BlockSpec((1, hb, n, p),
                              lambda i, j, slot: (slot[i], j, 0, 0))
    row_spec = pl.BlockSpec((1, hb, p), lambda i, j, slot: (i, j, 0))
    # b and c as (B * G, 1, N): a step's block is its own group's row
    group_spec = pl.BlockSpec(
        (1, 1, n), lambda i, j, slot: (i * g + j // steps_per_group, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, h // hb),
        in_specs=[state_spec, row_spec, row_spec, group_spec, group_spec],
        out_specs=[state_spec, row_spec],
        scratch_shapes=[pltpu.VMEM((n, p), f32), pltpu.VMEM((n, p), f32)],
    )
    with _x32_mode():
        states, y = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(states.shape, f32),
                       jax.ShapeDtypeStruct((bsz, h, p), f32)],
            # operand 0 is the prefetched slot ids
            input_output_aliases={1: 0},
            name="ssd_state_update",
            interpret=interpret,
        )(slots.astype(jnp.int32), states, dtx.astype(f32),
          jnp.broadcast_to(decay.astype(f32)[..., None], (bsz, h, p)),
          b.astype(f32).reshape(bsz * g, 1, n),
          c.astype(f32).reshape(bsz * g, 1, n))
    return states, y
