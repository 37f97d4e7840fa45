"""Plain reference: LongCat-Flash's full causal forward in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")`` — no
cache, no kernels, no batching, one sequence at a time.

For double layer ``l``, input ``x``, sublayer ``i`` in (0, 1); every
projection without bias; ``RMS`` = RMSNorm with gain, eps ``rms_norm_eps``:

* ``MLA(h)``: ``q = W_qb RMS(W_qa h)`` as heads of ``[q_nope | q_rope]``,
  both multiplied by ``s_q = sqrt(hidden / q_lora_rank)``;
  ``[c | k_rope] = W_kva h``; ``c' = RMS(c) * s_kv``,
  ``s_kv = sqrt(hidden / kv_lora_rank)``; per head
  ``[k_nope | v] = W_kvb c'``; interleaved rotary (pairs ``(2j, 2j+1)``)
  on ``q_rope`` and on the ONE ``k_rope`` all heads share; scores
  ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``, causal
  softmax, output ``W_o concat_heads(P v)``.
* ``a_i = x + MLA_i(RMS_in,i(x))``; ``h_i = RMS_post,i(a_i)``; if
  ``i == 0``: ``m = MoE(h_0)``; ``x = a_i + FFN_i(h_i)`` (SwiGLU); after
  ``i == 1``: ``x = x + m`` (the shortcut-connected expert layer).
* ``MoE(h)``: ``p = softmax(W_r h)`` over all ``n_routed + n_zero``
  outputs; the ``moe_topk`` largest of ``p + b`` are picked (``b``: the
  per-expert selection bias); ``w_e = routed_scaling_factor * p_e`` for
  the picked, not renormalised, bias not in the weight;
  ``MoE(h) = sum over picked e < n_routed of w_e SwiGLU_e(h)
  + sum over picked e >= n_routed of w_e h``.
* Model: embedding, the double layers, final RMSNorm, untied head.

**The share.** ``weights`` hold the experts ``first_held ..
first_held + held - 1`` of each layer and a slice of the vocabulary. The
sum over routed experts runs over the HELD ones only (the absent experts'
part is left out, here as in the program); the zero-expert sum is whole.
``held == n_routed`` gives the uncut layer.

Departures from ``transformers``' ``modeling_longcat_flash.py``, which
could not be read here (no network; written from memory, listed under
``assumed`` in the config file): untied head; ``norm_topk_prob`` false;
the router a bias-free linear in float32; rotary pairs interleaved.

The weights arrive in the dtype they are served in and are cast up ONE
MATRIX AT A TIME (a double layer in float32 would be 5 GB beside the
served copy).
"""
from __future__ import annotations

import functools
import math

PAD_TO = 1280


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * g.astype(jnp.float32)


def _rope(x, theta):
    """x: (L, H, D), positions 0..L-1; pairs (2j, 2j+1) rotated."""
    import jax.numpy as jnp

    l, h, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(l, h, d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(l, h, d)


def _mm(x, w):
    """x @ w.T with ``w`` (out, in) cast up here, one matrix at a time."""
    import jax.numpy as jnp

    return x @ w.astype(jnp.float32).T


def _swiglu(x, gate_up, down):
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(_mm(x, gate_up), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, down)


def mla(h, p, c):
    import jax
    import jax.numpy as jnp

    l, u = h.shape
    heads, nope, rope, v_dim = c["heads"], c["nope"], c["rope"], c["v_dim"]
    r = c["kv_rank"]
    s_q = math.sqrt(u / c["q_rank"])
    s_kv = math.sqrt(u / r)
    q = _mm(_rms(_mm(h, p["qa"]), p["qnorm"], c["eps"]), p["qb"]) * s_q
    q = q.reshape(l, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], c["theta"])
    ckr = _mm(h, p["kva"])
    latent = _rms(ckr[:, :r], p["kvnorm"], c["eps"]) * s_kv
    k_rope = _rope(ckr[:, None, r:], c["theta"])[:, 0]
    kv = _mm(latent, p["kvb"]).reshape(l, heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) \
        / math.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((l, l), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return _mm(att.reshape(l, heads * v_dim), p["out"])


def router(h, m, c):
    """(picked expert ids (L, k), their weights (L, k), p + b (L, E))."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(_mm(h, m["router"]), axis=-1)
    biased = p + m["router_bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(biased, c["top_k"])
    return idx, c["moe_scale"] * jnp.take_along_axis(p, idx, axis=-1), biased


def moe(h, m, c):
    """The held experts' part plus the whole zero-expert part."""
    import jax
    import jax.numpy as jnp

    idx, w, _ = router(h, m, c)
    zero_w = jnp.sum(jnp.where(idx >= c["n_routed"], w, 0.0), axis=-1)
    out = zero_w[:, None] * h
    for j in range(m["gate_up"].shape[0]):
        e = c["first_held"] + j
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)     # (L,)
        gate, up = jnp.split(h @ m["gate_up"][j].astype(jnp.float32), 2,
                             axis=-1)
        y = (jax.nn.silu(gate) * up) @ m["down"][j].astype(jnp.float32)
        out = out + w_e[:, None] * y
    return out


def double_layer(x, lw, c):
    shortcut = None
    for i in (0, 1):
        p = lw["sub"][i]
        a = x + mla(_rms(x, p["in_norm"], c["eps"]), p, c)
        h = _rms(a, p["post_norm"], c["eps"])
        if i == 0:
            shortcut = moe(h, lw["moe"], c)
        x = a + _swiglu(h, p["ffn_gate_up"], p["ffn_down"])
    return x + shortcut


def constants(config: dict) -> tuple:
    """The numbers of the config file the equations use, hashable."""
    return tuple(sorted({
        "heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "kv_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "v_dim": config["v_head_dim"],
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "n_routed": config["router_outputs"] - config["zero_expert_num"],
        "top_k": config["moe_topk"],
        "moe_scale": float(config["routed_scaling_factor"]),
        "first_held": config.get("first_held_expert", 0)}.items()))


@functools.lru_cache(maxsize=None)
def _jitted(consts):
    import jax

    c = dict(consts)
    layer = jax.jit(functools.partial(double_layer, c=c))

    def head(x, norm_w, head_w, rows):
        return _mm(_rms(x[rows], norm_w, c["eps"]), head_w)

    return layer, jax.jit(head)


def logits_at(weights: dict, config: dict, tokens, rows):
    """float32 logits (len(rows), vocab slice) of ONE sequence ``tokens``
    (1-D int array) at the positions ``rows``: row i scores token i + 1.
    Padding after the last row of interest is harmless (causal)."""
    import jax
    import jax.numpy as jnp

    layer, head = _jitted(constants(config))
    # one compiled length per PAD_TO tokens (causal: padding is harmless)
    tokens = jnp.asarray(tokens)
    tokens = jnp.pad(tokens, (0, -tokens.shape[0] % PAD_TO))
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(jnp.float32)
        for lw in weights["layers"]:
            x = layer(x, lw)
        return head(x, weights["norm"], weights["lm_head"], jnp.asarray(rows))
