"""Linear attention with a DELTA RULE: Kimi Delta Attention (KDA,
arXiv:2510.26692), the third recurrence of this repo beside the two of
``ops/ssm.py``. A head's state is a MATRIX ``S`` (keys x values, float32)
that every token first decays a KEY CHANNEL at a time and then corrects
by a rank-one update of itself:

    S' = Diag(alpha_t) S_{t-1}                  alpha_t = exp(g_t), g_t <= 0
    S_t = S' + beta_t k_t (v_t - k_t^T S')^T
    o_t = S_t^T q_t

(written out token by token in ``benchmarks/references/ling_linear.py``,
the plain reference the tests hold this file to). Three forms of one
function family: :func:`kda_step` (one token a row, the decode round),
:func:`kda_chunk_scan` (a prefill chunk from a carried state: the WY / UT
form, matrix products inside a chunk of 64 tokens and one state hand-over
between chunks) and :func:`kda_slot_update` (the step ON an engine's slot
array: the Pallas kernel ``pallas_kernels/kda_state_update.py`` on a TPU).
The causal convolution in front of ``q``, ``k`` and ``v`` and its tail are
``ops/ssm.py``'s (:func:`~.ssm.causal_conv`, :func:`~.ssm.conv_tail`).

A padded position is an identity step: ``g = 0`` (no decay) and ``beta =
0`` (no write); :func:`kda_gates` writes them so.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register
from .ssm import causal_conv

_HIGHEST = jax.lax.Precision.HIGHEST

# tokens of one block of the chunk form: the unit-triangular system a
# head solves is CHUNK x CHUNK
CHUNK = 64


def l2_normalize(x, eps=1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_gates(a, b, a_log, dt_bias, real, *, lower_bound=-5.0, safe=True):
    """The log-decay ``g`` (..., H, D) and the write strength ``beta``
    (..., H) of KDA from their projections ``a`` (..., H * D) and ``b``
    (..., H): ``beta = sigmoid(b)``; with ``safe`` (``kda_safe_gate``)
    ``g = lower_bound * sigmoid(exp(A_log_h) * (a + dt_bias))``, bounded
    to ``[lower_bound, 0]``, else the unbounded ``-exp(A_log_h) *
    softplus(a + dt_bias)``. ``real`` (...,) bool: a padded position gets
    ``g = 0`` and ``beta = 0``, an identity step. Float32."""
    f32 = jnp.float32
    h = a_log.shape[0]
    a = (a.astype(f32) + dt_bias.astype(f32)).reshape(a.shape[:-1] + (h, -1))
    rate = jnp.exp(a_log.astype(f32))[:, None]
    if safe:
        g = f32(lower_bound) * jax.nn.sigmoid(rate * a)
    else:
        g = -rate * jax.nn.softplus(a)
    beta = jax.nn.sigmoid(b.astype(f32))
    return (jnp.where(real[..., None, None], g, f32(0.0)),
            jnp.where(real[..., None], beta, f32(0.0)))


@register("_contrib_kda_step", aliases=["kda_step"], num_outputs=2)
def kda_step(q, k, v, g, beta, state):
    """One token of the recurrence from a given state: ``q``, ``k`` (B,
    H, Dk) normalised (``q`` scaled), ``v`` (B, H, Dv), ``g`` (B, H, Dk)
    the log-decay, ``beta`` (B, H), ``state`` (B, H, Dk, Dv) float32.
    Returns ``o`` (B, H, Dv) and the new state, float32. The oracle of
    ``pallas_kernels/kda_state_update.py``."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    s = jnp.exp(g)[..., None] * state.astype(f32)
    seen = jnp.sum(k[..., None] * s, axis=2)                 # k^T S'
    u = beta[..., None] * (v - seen)
    s = s + k[..., None] * u[:, :, None, :]
    return jnp.sum(q[..., None] * s, axis=2), s


def _unit_lower_inverse(n):
    """``(I + n)^-1`` for strictly lower-triangular ``n`` (..., C, C), C a
    power of two: ``n`` is nilpotent, so the inverse is the finite product
    ``(I - n)(I + n^2)(I + n^4) ...``, all matrix products."""
    c = n.shape[-1]
    eye = jnp.eye(c, dtype=n.dtype)
    inv, power, span = eye - n, n, 1
    while 2 * span < c:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=_HIGHEST)
        span *= 2
    return inv


@register("_contrib_kda_chunk_scan", aliases=["kda_chunk_scan"],
          num_outputs=2)
def kda_chunk_scan(q, k, v, g, beta, state, *, chunk=CHUNK):
    """The recurrence over ``L`` tokens from a given state, in its chunk
    form. Inside a chunk, with ``G_t`` the running sum of ``g`` (so
    ``exp(G_t - G_i)`` is the decay a key channel takes from token ``i``
    to token ``t``) and ``S_0`` the state the chunk starts from:

        A[t, i] = sum_d k_t k_i exp(G_t - G_i)      (i <  t)
        B[t, i] = sum_d q_t k_i exp(G_t - G_i)      (i <= t)
        (I + Diag(beta) A) U = Diag(beta) (V - (K exp(G)) S_0)
        O = (Q exp(G)) S_0 + B U
        S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

    ``U`` holds each token's correction ``beta_t (v_t - k_t^T S')``: the
    unit-triangular system is the delta rule's dependence of a token on
    the tokens of its chunk before it (the WY / UT form of the paper).
    Every exponent is a difference that is <= 0, so nothing overflows
    whatever the decay. What does not depend on ``S_0`` (``A``, ``B``,
    the inverse) is made for all chunks at once; the loop over chunks
    carries the state through three products a chunk.

    ``q``, ``k`` (B, L, H, Dk), ``v`` (B, L, H, Dv), ``g`` (B, L, H, Dk),
    ``beta`` (B, L, H); ``state`` (B, H, Dk, Dv) float32. Returns ``o``
    (B, L, H, Dv) and the state after the last token. Float32
    throughout, the products at the highest precision (on a TPU a float32
    product is otherwise one bfloat16 pass, which would round the carried
    state on every read); ``L`` is padded to whole chunks with identity
    steps."""
    f32 = jnp.float32
    bsz, l, h, _ = q.shape
    c = int(chunk)
    pad = -l % c
    n = (l + pad) // c

    def chunks(x):
        # (B, L, H, ...) -> (N, B, H, C, ...)
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) *
                    (x.ndim - 2))
        x = x.reshape((bsz, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 2, 3), 1, 0)

    q, k, v, g = (chunks(x) for x in (q, k, v, g))
    beta = chunks(beta[..., None])                            # (N,B,H,C,1)
    cum = jnp.cumsum(g, axis=3)                               # G_t
    causal = jnp.tril(jnp.ones((c, c), bool))

    def pair_products(xs):
        # one (row, chunk) at a time, so that exp(G_t - G_i) for i <= t
        # (H, C[t], C[i], Dk; 0 elsewhere) never lives for all of them
        q_c, k_c, cum_c = xs
        decay = jnp.exp(jnp.where(
            causal[:, :, None], cum_c[:, :, None, :] - cum_c[:, None, :, :],
            -jnp.inf)) * k_c[:, None, :, :]
        return (jnp.sum(k_c[:, :, None, :] * decay, axis=-1),
                jnp.sum(q_c[:, :, None, :] * decay, axis=-1))

    a, b = jax.lax.map(pair_products, tuple(
        x.reshape((n * bsz,) + x.shape[2:]) for x in (q, k, cum)))
    a, b = (x.reshape((n, bsz) + x.shape[1:]) for x in (a, b))
    solve = _unit_lower_inverse(
        beta * jnp.where(causal & ~jnp.eye(c, dtype=bool), a, 0.0))
    solve = solve * jnp.swapaxes(beta, -1, -2)                # T Diag(beta)
    gate_in = jnp.exp(cum)                                    # exp(G_t)
    w = jnp.matmul(solve, k * gate_in, precision=_HIGHEST)    # (…, C, Dk)
    u0 = jnp.matmul(solve, v, precision=_HIGHEST)             # (…, C, Dv)
    q_in = q * gate_in
    k_out = k * jnp.exp(cum[..., -1:, :] - cum)               # to the end
    last = gate_in[..., -1, :]                                # (N, B, H, Dk)

    def one(s, xs):
        w_c, u0_c, q_c, b_c, k_c, last_c = xs
        u = u0_c - jnp.matmul(w_c, s, precision=_HIGHEST)
        o = (jnp.matmul(q_c, s, precision=_HIGHEST)
             + jnp.matmul(b_c, u, precision=_HIGHEST))
        s = last_c[..., None] * s + jnp.matmul(
            jnp.swapaxes(k_c, -1, -2), u, precision=_HIGHEST)
        return s, o

    state, o = jax.lax.scan(one, state.astype(f32),
                            (w, u0, q_in, b, k_out, last))
    # (N, B, H, C, Dv) -> (B, L, H, Dv)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(
        bsz, n * c, h, -1)[:, :l]
    return o, state


def kda_slot_update(states, slots, fresh, q, k, v, g, beta):
    """One decode token a row, ON an engine's slot array: row ``i``'s
    state is ``states[slots[i]]`` (``states`` (S, H, Dk, Dv) float32;
    ``slots`` (B,) int32, 0 for a padding row; ``fresh`` (B,) bool: the
    row starts a stream, its slot's content counts as zeros); the other
    operands as :func:`kda_step` takes them. Returns ``o`` (B, H, Dv)
    float32 and the slot array with the rows' slots advanced.

    On a TPU, where the shapes allow (``kda_update_supported``: routed by
    platform and shapes alone), the Pallas kernel updates the slots in
    place, each read once and written once
    (``pallas_kernels/kda_state_update.py``); everywhere else
    :func:`kda_step` runs over the gathered rows and the result is
    scattered back, which is also the kernel's oracle."""
    from ..pallas_kernels.kda_state_update import (kda_state_update_kernel,
                                                   kda_update_supported)

    f32 = jnp.float32
    if kda_update_supported(states, q):
        from .. import telemetry

        telemetry.record_pallas_dispatch("kda_state_update")
        # a zero decay drops the old state whatever the slot holds
        alpha = jnp.where(fresh[:, None, None], f32(0.0),
                          jnp.exp(g.astype(f32)))
        return kda_state_update_kernel(states, slots, q, k, v, alpha, beta)
    state = jnp.where(fresh[:, None, None, None], f32(0.0), states[slots])
    o, state = kda_step(q, k, v, g, beta, state)
    return o, states.at[slots].set(state)


def gated_head_norm(o, gate, gain, eps):
    """KDA's output norm: ``RMSNorm`` of each head's ``o`` (..., H, Dv)
    with one gain of ``Dv`` values every head shares, times
    ``sigmoid(gate)`` (..., H, Dv). Float32."""
    f32 = jnp.float32
    o = o.astype(f32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * gain.astype(f32) * jax.nn.sigmoid(gate.astype(f32))


def kda_forward(h, p, tails, state, real, *, scan=None, lower_bound=-5.0,
                safe_gate=True, eps=1e-6):
    """A KDA mixer over ``h`` (B, L, U) from a stream's carried state:
    ``tails`` (B, K - 1, 3 * H * D) the three convolutions' last inputs
    (``q | k | v``), ``state`` the recurrence's (B, H, D, D) float32;
    ``real`` (B, L) marks the real positions (a padded one is an identity
    step). ``p``: ``qkv`` (3 H D, U), ``conv`` (3 H D, K) the depthwise
    taps (no bias), ``f`` (H D, U) the decay's projection with ``dt_b``
    (H D,) and ``a_log`` (H,), ``b`` (H, U) the write strength's, ``g``
    (H D, U) the output gate's, ``o_norm`` (D,), ``o`` (U, H D).

    ``q``, ``k``, ``v`` pass the causal convolution and SiLU; ``q`` and
    ``k`` are L2-normalised a head, ``q`` also times ``D^-0.5``. Returns
    the mixer's output (B, L, U), the convolutions' input with the tails
    before it (B, K - 1 + L, 3 H D) and the new state, float32: matrix
    products take operands in the weights' dtype and hand float32 on,
    everything between them is float32.

    ``scan(q, k, v, g, beta, state) -> (o, state)`` replaces the
    recurrence (:func:`kda_chunk_scan`): a decode engine hands in one
    that updates its slot array in place, and ``state`` is then whatever
    that callable takes. Device work under ``kda.proj`` (the projections
    and the convolution), ``kda.chunk`` or ``kda.update`` (the
    recurrence: a chunk, or ``L == 1``) and ``kda.out`` (norm, gate,
    ``W_o``)."""
    f32 = jnp.float32
    bsz, l, _ = h.shape
    n_heads = p["a_log"].shape[0]
    d = p["o_norm"].shape[0]

    def mm(x, w):
        return jnp.matmul(x.astype(w.dtype), w.T, preferred_element_type=f32)

    with jax.named_scope("kda.proj"):
        conv, ext = causal_conv(tails, mm(h, p["qkv"]), p["conv"])
        q, k, v = (x.reshape(bsz, l, n_heads, d) for x in
                   jnp.split(jax.nn.silu(conv), 3, axis=-1))
        q = l2_normalize(q) * f32(d ** -0.5)
        k = l2_normalize(k)
        g, beta = kda_gates(mm(h, p["f"]), mm(h, p["b"]), p["a_log"],
                            p["dt_b"], real, lower_bound=lower_bound,
                            safe=safe_gate)
        gate = mm(h, p["g"]).reshape(bsz, l, n_heads, d)
    with jax.named_scope("kda.update" if l == 1 else "kda.chunk"):
        if scan is None:
            o, state = kda_chunk_scan(q, k, v, g, beta, state)
        else:
            o, state = scan(q, k, v, g, beta, state)
    with jax.named_scope("kda.out"):
        y = gated_head_norm(o, gate, p["o_norm"], eps)
        out = mm(y.reshape(bsz, l, n_heads * d), p["o"])
    return out, ext, state


@register("_contrib_kda_mixer", aliases=["kda_mixer"])
def kda_mixer(data, qkv_weight, conv_weight, f_weight, dt_bias, a_log,
              b_weight, g_weight, norm_weight, out_weight, *,
              lower_bound=-5.0, safe_gate=True, eps=1e-6):
    """A KDA mixer over whole sequences ``data`` (B, L, U) from a zero
    state (no cache). Weights as :func:`kda_forward` names them."""
    b, l, _ = data.shape
    width, k = conv_weight.shape
    n_heads, d = a_log.shape[0], norm_weight.shape[0]
    p = {"qkv": qkv_weight, "conv": conv_weight, "f": f_weight,
         "dt_b": dt_bias, "a_log": a_log, "b": b_weight, "g": g_weight,
         "o_norm": norm_weight, "o": out_weight}
    out, _, _ = kda_forward(
        data, p, jnp.zeros((b, k - 1, width), jnp.float32),
        jnp.zeros((b, n_heads, d, d), jnp.float32), jnp.ones((b, l), bool),
        lower_bound=lower_bound, safe_gate=safe_gate, eps=eps)
    return out.astype(data.dtype)
