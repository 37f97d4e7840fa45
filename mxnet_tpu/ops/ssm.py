"""State-space and memory-gating ops of hybrid decoders (SambaY,
arXiv:2507.06607): the selective scan of a Mamba layer and the gated
memory unit that re-reads one layer's scan output further up the stack.
Pure JAX; the equations are written out in
``benchmarks/references/phi4flash.py``.
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
    l = h.shape[1]
    n = p["a_log"].shape[1]
    r = p["dt_w"].shape[1]

    def mm(x, w):
        # operands in the weights' dtype, the product handed on in
        # float32: one rounding a matrix product, on its input
        return jnp.matmul(x.astype(w.dtype), w.T, preferred_element_type=f32)

    with jax.named_scope("ssm.proj"):
        u, z = jnp.split(mm(h, p["in"]), 2, axis=-1)
        ext = jnp.concatenate([tail.astype(f32), u], axis=1)
        k = p["conv_w"].shape[1]
        conv = sum(ext[:, j:j + l] * p["conv_w"][:, j].astype(f32)
                   for j in range(k))
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
