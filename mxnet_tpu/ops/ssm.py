"""State-space ops of hybrid decoders, TWO recurrences: Mamba-1's selective
scan + gated memory unit (SambaY, arXiv:2507.06607; `selective_scan`,
`mamba_forward`; written out in ``benchmarks/references/phi4flash.py``)
and, below them, Mamba-2's SSD (arXiv:2405.21060; `ssd_step`,
`ssd_chunk_scan`, `mamba2_forward`; ``benchmarks/references/falcon_h1.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

# tokens of one block of the chunk form: the loop over a chunk runs
# L / block iterations of ``block`` unrolled steps, the state carried
# between them, so no (L, state, channels) tensor ever lives whole
_SCAN_BLOCK = 16


def _scan_step(s, u_t, dt_t, b_t, c_t, a, d):
    """One token: ``s`` (B, N, D) float32 -> (new s, y (B, D) float32)."""
    da = jnp.exp(dt_t[:, None, :] * a[None])
    s = da * s + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
    return s, jnp.sum(s * c_t[:, :, None], axis=1) + d[None] * u_t


@register("_contrib_selective_scan", aliases=["selective_scan"],
          num_outputs=2)
def selective_scan(u, dt, a, b, c, d, state, *, block=_SCAN_BLOCK):
    """The selective state-space recurrence of a Mamba layer, from a
    given state:

        s_t = exp(dt_t * A) * s_{t-1} + (dt_t * u_t) B_t^T
        y_t = s_t C_t + D * u_t

    ``u`` (B, L, D) the convolved, activated input; ``dt`` (B, L, D) the
    step sizes after their softplus (a position with ``dt == 0`` is an
    identity step: the state passes through it, which is how a padded
    position is written); ``a`` (N, D) = ``-exp(A_log)`` with the state
    axis leading (channels in the lanes); ``b``, ``c`` (B, L, N); ``d``
    (D,); ``state`` (B, N, D) float32. Returns ``y`` (B, L, D) in ``u``'s
    dtype and the state after the last token, float32. The recurrence
    runs in float32 whatever the inputs are.

    ``L == 1`` is the step form (one decode token). Otherwise the chunk
    form: a loop over blocks of ``block`` tokens with the state carried
    from block to block, each block ``block`` steps written out."""
    f32 = jnp.float32
    a, d, state = a.astype(f32), d.astype(f32), state.astype(f32)
    if u.shape[1] == 1:
        state, y = _scan_step(state, u[:, 0].astype(f32),
                              dt[:, 0].astype(f32), b[:, 0].astype(f32),
                              c[:, 0].astype(f32), a, d)
        return y[:, None].astype(u.dtype), state

    def step(s, xs):
        u_t, dt_t, b_t, c_t = (x.astype(f32) for x in xs)
        s, y = _scan_step(s, u_t, dt_t, b_t, c_t, a, d)
        return s, y.astype(u.dtype)

    xs = tuple(jnp.swapaxes(x, 0, 1) for x in (u, dt, b, c))
    state, y = jax.lax.scan(step, state, xs,
                            unroll=max(1, min(int(block), u.shape[1])))
    return jnp.swapaxes(y, 0, 1), state


def causal_conv(tail, x, weight):
    """The causal depthwise convolution every recurrent mixer here runs
    over its inputs, from a stream's carried tail: ``tail`` (B, K - 1, D)
    the convolution's last ``K - 1`` inputs (zeros at a stream's start),
    ``x`` (B, L, D) float32, ``weight`` (D, K), tap ``K - 1`` on the
    current token. Returns the convolution (B, L, D) float32, without a
    bias, and its input with the tail before it (B, K - 1 + L, D), which
    :func:`conv_tail` cuts the next tail from."""
    f32 = jnp.float32
    l = x.shape[1]
    ext = jnp.concatenate([tail.astype(f32), x], axis=1)
    conv = sum(ext[:, j:j + l] * weight[:, j].astype(f32)
               for j in range(weight.shape[1]))
    return conv, ext


def conv_tail(ext, n_real, k):
    """The ``k - 1`` inputs of :func:`causal_conv` that end at each row's
    last REAL token (``n_real`` (B,) int32: the row's real positions,
    which lead): what a stream's slot carries to its next dispatch. A row
    with no real token keeps the tail it came with."""
    keep = n_real[:, None] + jnp.arange(k - 1)[None]
    return jnp.take_along_axis(ext, keep[:, :, None], axis=1)


def mamba_forward(h, p, tail, state, real):
    """A Mamba mixer over ``h`` (B, L, U) from a stream's carried state:
    ``tail`` (B, K - 1, D) the convolution's last inputs, ``state`` (B,
    N, D) float32; ``real`` (B, L) marks the real positions (a padded one
    is an identity step, ``dt = 0``). ``p``: ``in`` (2D, U), ``conv_w``
    (D, K), ``conv_b``, ``x`` (R + 2N, D), ``dt_w`` (D, R), ``dt_b``,
    ``a_log`` (D, N), ``d``, ``out`` (U, D). Returns the mixer's output
    (B, L, U), the scan's output ``y`` (B, L, D) BEFORE the gate (what a
    memory-publishing layer hands on), the convolution's input with the
    tail before it (B, K - 1 + L, D) and the new state, all float32:
    every matrix product takes its operands in the weights' dtype and
    hands its result on in float32, and what lies between two products
    (convolution, activations, scan, gate) is float32. Device work under
    ``ssm.proj`` and ``ssm.scan``."""
    f32 = jnp.float32
    n = p["a_log"].shape[1]
    r = p["dt_w"].shape[1]

    def mm(x, w):
        # operands in the weights' dtype, the product handed on in
        # float32: one rounding a matrix product, on its input
        return jnp.matmul(x.astype(w.dtype), w.T, preferred_element_type=f32)

    with jax.named_scope("ssm.proj"):
        u, z = jnp.split(mm(h, p["in"]), 2, axis=-1)
        conv, ext = causal_conv(tail, u, p["conv_w"])
        u = jax.nn.silu(conv + p["conv_b"].astype(f32))
        rbc = mm(u, p["x"])
        dt = jax.nn.softplus(mm(rbc[..., :r], p["dt_w"])
                             + p["dt_b"].astype(f32))
        dt = jnp.where(real[..., None], dt, f32(0.0))
    with jax.named_scope("ssm.scan"):
        y, state = selective_scan(
            u, dt, -jnp.exp(p["a_log"].astype(f32)).T, rbc[..., r:r + n],
            rbc[..., r + n:], p["d"], state)
    with jax.named_scope("ssm.proj"):
        out = mm(y * jax.nn.silu(z), p["out"])
    return out, y, ext, state


@register("_contrib_mamba_mixer", aliases=["mamba_mixer"], num_outputs=2)
def mamba_mixer(data, in_weight, conv_weight, conv_bias, x_weight,
                dt_weight, dt_bias, a_log, d, out_weight):
    """A Mamba mixer over whole sequences ``data`` (B, L, U) from a zero
    state (no cache): the mixer's output and the scan's output ``y``
    before the gate. Weights as :func:`mamba_forward` names them."""
    b, l, _ = data.shape
    dim, k = conv_weight.shape
    p = {"in": in_weight, "conv_w": conv_weight, "conv_b": conv_bias,
         "x": x_weight, "dt_w": dt_weight, "dt_b": dt_bias, "a_log": a_log,
         "d": d, "out": out_weight}
    out, y, _, _ = mamba_forward(
        data, p, jnp.zeros((b, k - 1, dim), data.dtype),
        jnp.zeros((b, a_log.shape[1], dim), jnp.float32),
        jnp.ones((b, l), bool))
    return out, y


@register("_contrib_gated_memory_unit", aliases=["gated_memory_unit"])
def gated_memory_unit(data, memory, in_weight, out_weight):
    """``W_2 (m * silu(W_1 h))``: ``data`` (..., U) gates ``memory``
    (..., M), another layer's state-space output at the same token;
    ``in_weight`` (M, U), ``out_weight`` (U, M). The products take
    operands in the weights' dtype; the gate and the result are float32."""
    f32 = jnp.float32
    gate = jax.nn.silu(jnp.matmul(data.astype(in_weight.dtype), in_weight.T,
                                  preferred_element_type=f32))
    return jnp.matmul((memory.astype(f32) * gate).astype(out_weight.dtype),
                      out_weight.T, preferred_element_type=f32)


# ---------------------------------------------------------------------------
# Mamba-2 / SSD (state-space duality, as Falcon-H1's parallel hybrid blocks
# use it): a SCALAR decay a head over a matrix state of d_state x head_dim a
# head, ``B`` and ``C`` shared by the heads of a group, and a chunk form
# that is matrix products. Pure JAX but for the decode step's in-place
# update of an engine's slots (``pallas_kernels/ssd_state_update.py``). The
# state of a head is kept TRANSPOSED, (d_state, head_dim): the state index
# leads and the head's channels lie in the lanes, as ``selective_scan``
# keeps Mamba-1's, so that ``y`` (a sum over the state index) comes out as
# a row of channels with no relayout.
# ---------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST


def _per_head(g, n_heads):
    """A group's vectors ``g`` (..., G, N) as every head's (..., H, N):
    head ``h`` reads group ``h // (H / G)``."""
    return jnp.repeat(g, n_heads // g.shape[-2], axis=-2)


@register("_contrib_ssd_step", aliases=["ssd_step"], num_outputs=2)
def ssd_step(x, dt, a, b, c, d, state):
    """One token of the SSD recurrence from a given state:

        S_h = exp(dt_h a_h) S_h + B_g (dt_h x_h)^T
        y_h = S_h^T C_g + D_h x_h          (g = h // (H / G))

    ``x`` (B, H, P) the convolved, activated input a head; ``dt`` (B, H)
    after its softplus (``dt == 0``: an identity step); ``a`` (H,) =
    ``-exp(A_log)``; ``b``, ``c`` (B, G, N); ``d`` (H,); ``state`` (B, H,
    N, P) float32. Returns ``y`` (B, H, P) in ``x``'s dtype and the new
    state; float32 throughout. The oracle of
    ``pallas_kernels/ssd_state_update.py``."""
    f32 = jnp.float32
    h, dtype = x.shape[1], x.dtype
    x, dt = x.astype(f32), dt.astype(f32)
    bh, ch = (_per_head(v.astype(f32), h) for v in (b, c))
    decay = jnp.exp(dt * a.astype(f32))
    state = (decay[..., None, None] * state.astype(f32)
             + bh[..., None] * (dt[..., None] * x)[:, :, None, :])
    y = jnp.sum(state * ch[..., None], axis=2) + d.astype(f32)[:, None] * x
    return y.astype(dtype), state


@register("_contrib_ssd_chunk_scan", aliases=["ssd_chunk_scan"],
          num_outputs=2)
def ssd_chunk_scan(x, dt, a, b, c, d, state, *, chunk=128):
    """The SSD recurrence over ``L`` tokens from a given state, in its
    chunk form: matrix products inside a chunk of ``chunk`` tokens, one
    state hand-over between chunks. With ``cum_t`` the running sum of
    ``dt_r a`` inside a chunk (so ``exp(cum_t - cum_s)`` is the decay from
    token ``s`` to token ``t``):

        Y_diag = (L o (C B^T)) (dt x),   L[t, s] = exp(cum_t - cum_s), s <= t
        Y_off  = exp(cum_t) C_t S_prev
        S_next = exp(cum_last) S_prev + sum_s exp(cum_last - cum_s) B_s (dt_s x_s)^T

    ``x`` (B, L, H, P); ``dt`` (B, L, H) (``dt == 0`` at a padded
    position: an identity step, as ``selective_scan`` writes them);
    ``a``, ``d`` (H,); ``b``, ``c`` (B, L, G, N); ``state`` (B, H, N, P)
    float32. Returns ``y`` (B, L, H, P) in ``x``'s dtype and the state
    after the last token. Float32 throughout, the products at the
    highest precision (on a TPU a float32 product is otherwise one
    bfloat16 pass, which would round the carried state on every read);
    ``L`` is padded to whole chunks with identity steps."""
    f32 = jnp.float32
    bsz, l, h, p = x.shape
    q = int(chunk)
    pad = -l % q
    a, d = a.astype(f32), d.astype(f32)

    def chunks(v):
        v = jnp.pad(v.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) *
                    (v.ndim - 2))
        return jnp.swapaxes(v.reshape((bsz, -1, q) + v.shape[2:]), 0, 1)

    causal = jnp.tril(jnp.ones((q, q), bool))

    def one(s, xs):
        x_c, dt_c, b_c, c_c = xs                # (B, Q, ...)
        cum = jnp.cumsum(dt_c * a, axis=1)                       # (B, Q, H)
        gap = cum[:, :, None, :] - cum[:, None, :, :]            # [t, s]
        decay = jnp.exp(jnp.where(causal[None, :, :, None], gap, -jnp.inf))
        cb = jnp.repeat(jnp.einsum("btgn,bsgn->btsg", c_c, b_c,
                                   precision=_HIGHEST),
                        h // b_c.shape[2], axis=-1)              # (B,Q,Q,H)
        dtx = dt_c[..., None] * x_c                              # (B,Q,H,P)
        y = jnp.einsum("btsh,bshp->bthp", decay * cb, dtx,
                       precision=_HIGHEST)
        ch, bh = _per_head(c_c, h), _per_head(b_c, h)            # (B,Q,H,N)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bthn,bhnp->bthp", ch, s, precision=_HIGHEST)
        to_end = jnp.exp(cum[:, -1:, :] - cum)                   # (B, Q, H)
        s = (jnp.exp(cum[:, -1])[..., None, None] * s
             + jnp.einsum("bshn,bshp->bhnp", bh * to_end[..., None], dtx,
                          precision=_HIGHEST))
        return s, y + d[:, None] * x_c

    state, y = jax.lax.scan(one, state.astype(f32),
                            tuple(chunks(v) for v in (x, dt, b, c)))
    y = jnp.swapaxes(y, 0, 1).reshape(bsz, -1, h, p)[:, :l]
    return y.astype(x.dtype), state


def gated_group_norm(y, z, gain, n_groups, eps):
    """Mamba-2's gated norm with the gate FIRST: ``RMSNorm(y * silu(z))``
    with the statistics of each of ``n_groups`` groups of channels and a
    gain a channel; ``y``, ``z`` (..., D) float32."""
    f32 = jnp.float32
    v = (y * jax.nn.silu(z)).reshape(y.shape[:-1] + (n_groups, -1))
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return v.reshape(y.shape) * gain.astype(f32)


def ssd_slot_update(states, slots, fresh, x, dt, a, b, c, d):
    """One decode token a row, ON an engine's slot array: row ``i``'s
    state is ``states[slots[i]]`` (``states`` (S, H, N, P) float32;
    ``slots`` (B,) int32, 0 for a padding row; ``fresh`` (B,) bool: the
    row starts a stream, its slot's content counts as zeros); the other
    operands as :func:`ssd_step` takes them. Returns ``y`` (B, H, P) and
    the slot array with the rows' slots advanced.

    On a TPU, where the shapes allow (``ssd_update_supported``: routed by
    platform and shapes alone), the Pallas kernel updates the slots in
    place, each read once and written once
    (``pallas_kernels/ssd_state_update.py``); everywhere else
    :func:`ssd_step` runs over the gathered rows and the result is
    scattered back, which is also the kernel's oracle."""
    from ..pallas_kernels.ssd_state_update import (ssd_state_update_kernel,
                                                   ssd_update_supported)

    f32 = jnp.float32
    if ssd_update_supported(states, x, b):
        from .. import telemetry

        telemetry.record_pallas_dispatch("ssd_state_update")
        x32, dt32 = x.astype(f32), dt.astype(f32)
        decay = jnp.where(fresh[:, None], f32(0.0),
                          jnp.exp(dt32 * a.astype(f32)))
        states, y = ssd_state_update_kernel(
            states, slots, dt32[..., None] * x32, decay, b, c)
        return (y + d.astype(f32)[:, None] * x32).astype(x.dtype), states
    state = jnp.where(fresh[:, None, None, None], f32(0.0), states[slots])
    y, state = ssd_step(x, dt, a, b, c, d, state)
    return y, states.at[slots].set(state)


def mamba2_forward(h, p, tail, state, real, *, n_groups, d_state, eps,
                   chunk=128, scan=None):
    """A Mamba-2 mixer over ``h`` (B, L, U) from a stream's carried state:
    ``tail`` (B, K - 1, D + 2GN) the convolution's last inputs, ``state``
    the scan's (B, H, N, P) float32; ``real`` (B, L) marks the real
    positions (a padded one is an identity step, ``dt = 0``). ``p``:
    ``in`` (2D + 2GN + H, U) whose rows make ``z | x | B | C | dt``,
    ``mup`` (2D + 2GN + H,) the multiplier of each of those rows' outputs
    (muP; ones for none), ``conv_w`` (D + 2GN, K), ``conv_b``, ``dt_b``,
    ``a_log``, ``d`` (H,), ``norm`` (D,) the gated norm's gain, ``out``
    (U, D). The convolution runs over ``x | B | C`` together; the gate
    comes BEFORE the norm, whose statistics are a group's (D / G
    channels). Returns the mixer's output (B, L, U), the convolution's
    input with the tail before it (B, K - 1 + L, D + 2GN) and the new
    state, float32: matrix products take operands in the weights' dtype
    and hand float32 on, everything between them is float32.

    ``scan(x, dt, a, b, c, d, state) -> (y, state)`` replaces the
    recurrence (:func:`ssd_chunk_scan`): a decode engine hands in one
    that updates its slot array in place, and ``state`` is then whatever
    that callable takes. Device work under ``ssd.proj`` (the two
    projections) and ``ssd.scan`` (convolution, recurrence, gated norm),
    as :func:`mamba_forward` names Mamba-1's ``ssm.proj`` / ``ssm.scan``."""
    f32 = jnp.float32
    bsz, l, _ = h.shape
    n_heads = p["a_log"].shape[0]
    dim = p["out"].shape[1]
    gn = n_groups * d_state

    def mm(x, w):
        return jnp.matmul(x.astype(w.dtype), w.T, preferred_element_type=f32)

    with jax.named_scope("ssd.proj"):
        zxbcdt = mm(h, p["in"]) * p["mup"].astype(f32)
    with jax.named_scope("ssd.scan"):
        z = zxbcdt[..., :dim]
        conv, ext = causal_conv(tail, zxbcdt[..., dim:2 * dim + 2 * gn],
                                p["conv_w"])
        xbc = jax.nn.silu(conv + p["conv_b"].astype(f32))
        dt = jax.nn.softplus(zxbcdt[..., 2 * dim + 2 * gn:]
                             + p["dt_b"].astype(f32))
        dt = jnp.where(real[..., None], dt, f32(0.0))
        x = xbc[..., :dim].reshape(bsz, l, n_heads, dim // n_heads)
        b = xbc[..., dim:dim + gn].reshape(bsz, l, n_groups, d_state)
        c = xbc[..., dim + gn:].reshape(bsz, l, n_groups, d_state)
        a = -jnp.exp(p["a_log"].astype(f32))
        if scan is None:
            y, state = ssd_chunk_scan(x, dt, a, b, c, p["d"], state,
                                      chunk=min(chunk, l))
        else:
            y, state = scan(x, dt, a, b, c, p["d"], state)
        gated = gated_group_norm(y.reshape(bsz, l, dim), z, p["norm"],
                                 n_groups, eps)
    with jax.named_scope("ssd.proj"):
        out = mm(gated, p["out"])
    return out, ext, state


def mamba2_mup(multipliers, d_ssm, group_width, n_heads):
    """The muP factor of each in-projection row, float32: the five
    ``multipliers`` over the ``z | x | B | C | dt`` segments (``d_ssm``,
    ``d_ssm``, ``group_width``, ``group_width``, ``n_heads`` rows)."""
    import numpy as np

    return jnp.asarray(np.repeat(
        np.asarray(multipliers, np.float32),
        (d_ssm, d_ssm, group_width, group_width, n_heads)))


@register("_contrib_mamba2_mixer", aliases=["mamba2_mixer"])
def mamba2_mixer(data, in_weight, conv_weight, conv_bias, dt_bias, a_log, d,
                 norm_weight, out_weight, *, n_groups=1, d_state=128,
                 chunk=128, eps=1e-5, multipliers=(1.0, 1.0, 1.0, 1.0, 1.0)):
    """A Mamba-2 mixer over whole sequences ``data`` (B, L, U) from a
    zero state (no cache). Weights as :func:`mamba2_forward` names them;
    ``multipliers``: the muP factors of the in-projection's ``z | x | B |
    C | dt`` segments."""
    b, l, _ = data.shape
    width, k = conv_weight.shape
    n_heads = a_log.shape[0]
    mup = mamba2_mup(multipliers, out_weight.shape[1], n_groups * d_state,
                     n_heads)
    p = {"in": in_weight, "mup": mup, "conv_w": conv_weight,
         "conv_b": conv_bias, "dt_b": dt_bias, "a_log": a_log, "d": d,
         "norm": norm_weight, "out": out_weight}
    out, _, _ = mamba2_forward(
        data, p, jnp.zeros((b, k - 1, width), data.dtype),
        jnp.zeros((b, n_heads, d_state, out_weight.shape[1] // n_heads),
                  jnp.float32),
        jnp.ones((b, l), bool), n_groups=n_groups, d_state=d_state,
        eps=eps, chunk=chunk)
    return out
