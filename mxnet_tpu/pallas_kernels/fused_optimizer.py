"""Fused multi-tensor optimizer sweep kernel.

Reference counterpart: MXNet's horizontally-fused ``multi_sgd_update`` /
``multi_mp_sgd_mom_update`` kernels (``src/operator/optimizer_op.cc``) —
one launch updating a whole parameter list. Here the bucket's
(param, grad, state) leaves arrive PRE-PACKED into flat buffers
(``optimizer/multi_tensor.py``) and the kernel is a single VMEM
elementwise pass over them: each (block, 128) tile of every operand is
read once, the family formula runs on the VPU in f32, and each output
tile is written once — no per-parameter kernel launches, no HBM
round-trips between the Adam moments.

The kernel body CALLS the same formula function as the pure-``lax``
fallback (``multi_tensor._adam_elem`` et al.), so the two paths are
bit-identical by construction; what the kernel adds on TPU is explicit
tiling (one fused loop regardless of how XLA would have scheduled the
unpacked update) — the same contract as ``fused_layers.py``.

Routing (mirrors ``fused_ln_supported``): ``MXNET_PALLAS_FUSED=1`` AND
the execution platform is TPU; every caller falls back to the identical
jnp composition otherwise. Non-elementwise residue (LAMB's trust-ratio
norms, AdamW's per-param overflow scan) is reduced OUTSIDE the kernel on
the packed buffer and re-enters as a per-element vector.
"""
from __future__ import annotations

import os

import numpy as _np

from .flash_attention import _x32_mode

__all__ = ["fused_opt_enabled", "fused_opt_supported", "sweep_pallas"]

# flat buffers are padded to a whole number of (sublane, 128) tiles; 32
# sublanes covers the f32/bf16/int8 minimum-tile table in one granule
_GRANULE = 32 * 128
# VMEM comfort cap per operand tile (same budget as fused_layers)
_TILE_BYTES = 2 << 20


def fused_opt_enabled() -> bool:
    """Same knob family as the layer kernels: ``MXNET_PALLAS_FUSED=1``
    opts the packed optimizer sweep into the Pallas kernel (platform
    gate still applies per call). Read per call so tests can toggle."""
    return os.environ.get("MXNET_PALLAS_FUSED", "0") == "1"


def fused_opt_supported(platform) -> bool:
    """Kernel eligibility for a sweep lowered for ``platform``, in a
    trace the SPMD partitioner does not have to split. The packed
    layout is padded inside :func:`sweep_pallas`, so unlike the row
    kernels there is no shape gate — any bucket size qualifies."""
    from ..parallel.mesh import auto_partitioned

    return fused_opt_enabled() and platform == "tpu" \
        and not auto_partitioned()


def _block_rows(rows: int, width_bytes: int) -> int:
    """Largest 32-multiple row block whose widest operand tile fits the
    VMEM cap (32 keeps every dtype's sublane minimum satisfied)."""
    cap = max(32, _TILE_BYTES // max(width_bytes, 1))
    for br in (1024, 512, 256, 128, 64, 32):
        if br <= cap and rows % br == 0:
            return br
    return 32


def sweep_pallas(fn, static, flats, vec_el, scalars, out_specs,
                 interpret=False):
    """Run one elementwise sweep stage as a Pallas kernel.

    ``fn(env, static)``: the shared formula — sees each flat operand and
    per-element vector as a (block, 128) f32-or-original-dtype tile and
    each scalar as a 0-d value; returns a dict of output arrays.
    ``flats`` / ``vec_el``: name -> (L,) arrays (equal lengths);
    ``scalars``: name -> 0-d values; ``out_specs``: ordered
    ``(name, dtype)`` outputs. Returns name -> (L,) arrays.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    flat_names = sorted(flats)
    vec_names = sorted(vec_el)
    scalar_names = sorted(scalars)
    L = int(flats[flat_names[0]].shape[0])
    Lp = ((L + _GRANULE - 1) // _GRANULE) * _GRANULE
    rows = Lp // 128
    width = max(_np.dtype(flats[n].dtype).itemsize * 128
                for n in flat_names)
    br = _block_rows(rows, width)
    nb = rows // br

    def to2d(a):
        if Lp != L:
            # zero padding is formula-safe: every family's math maps the
            # all-zeros element to a finite value (eps guards the
            # divisions), and the pad region is sliced off below
            a = jnp.pad(a, (0, Lp - L))
        return a.reshape(rows, 128)

    args = [to2d(flats[n]) for n in flat_names]
    args += [to2d(vec_el[n]) for n in vec_names]
    args += [jnp.asarray(scalars[n], jnp.float32).reshape((1,))
             for n in scalar_names]

    row_spec = pl.BlockSpec((br, 128), lambda i: (i, 0))
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [row_spec] * (len(flat_names) + len(vec_names)) \
        + [smem_spec] * len(scalar_names)
    out_shape = [jax.ShapeDtypeStruct((rows, 128), dtype)
                 for _, dtype in out_specs]

    def kernel(*refs):
        it = iter(refs)
        env = {}
        for name in flat_names:
            env[name] = next(it)[...]
        for name in vec_names:
            env[name] = next(it)[...]
        for name in scalar_names:
            env[name] = next(it)[0]
        outs = fn(env, static)
        for name, _ in out_specs:
            o_ref = next(it)
            o_ref[...] = outs[name].astype(o_ref.dtype)

    with _x32_mode():
        results = pl.pallas_call(
            kernel, grid=(nb,), in_specs=in_specs,
            out_specs=[row_spec] * len(out_specs), out_shape=out_shape,
            interpret=interpret)(*args)
    if not isinstance(results, (list, tuple)):
        results = (results,)
    return {name: r.reshape(-1)[:L]
            for (name, _), r in zip(out_specs, results)}
