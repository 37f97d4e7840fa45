"""Plain reference: a Llama-family decoder's full causal forward in
float32 ``jax.numpy`` — no kernels, no cache, no batching, one sequence
at a time.

Pre-norm residual blocks: RMSNorm, rotary position embedding in the
rotate-half (Hugging Face) convention, grouped-query attention (each key
/ value head serves ``heads / kv_heads`` query heads), SwiGLU, a final
RMSNorm and an untied vocabulary projection. The fused ``kv`` weight
holds the key heads first, then the value heads; ``gate_up`` holds gate
first, then up — the layout the served net is built with.

The weights arrive in the dtype they are served in; each layer's are
cast up as the layer runs (a float32 copy of all of them does not fit
beside the served copy).
"""
from __future__ import annotations

import functools


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * g


def _rope(x, theta):
    """x: (L, H, D); positions 0..L-1; rotate-half convention."""
    import jax.numpy as jnp

    l, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _layer(x, lw, *, heads, kv_heads, head_dim, theta, eps):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    lw = {k: v.astype(f32) for k, v in lw.items()}
    l = x.shape[0]
    h = _rms(x, lw["attn_norm"], eps)
    q = (h @ lw["q"].T).reshape(l, heads, head_dim)
    kv = (h @ lw["kv"].T).reshape(l, 2 * kv_heads, head_dim)
    k, v = kv[:, :kv_heads], kv[:, kv_heads:]
    q, k = _rope(q, theta), _rope(k, theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(f32(head_dim))
    causal = jnp.tril(jnp.ones((l, l), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + att.reshape(l, heads * head_dim) @ lw["out"].T
    hm = _rms(x, lw["mlp_norm"], eps)
    gate, up = jnp.split(hm @ lw["gate_up"].T, 2, axis=-1)
    return x + (jax.nn.silu(gate) * up) @ lw["down"].T


@functools.lru_cache(maxsize=None)
def _jitted(heads, kv_heads, head_dim, theta, eps):
    import jax

    layer = jax.jit(functools.partial(
        _layer, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        theta=theta, eps=eps))

    def head(x, norm_w, head_w, rows):
        import jax.numpy as jnp

        f32 = jnp.float32
        return _rms(x[rows], norm_w.astype(f32), eps) @ head_w.astype(f32).T

    return layer, jax.jit(head)


def logits_at(weights: dict, config: dict, tokens, rows):
    """float32 logits (len(rows), vocab) of ONE sequence ``tokens`` (1-D
    int array) at the positions ``rows``: row i scores token i + 1.
    Padding after the last row of interest is harmless (causal)."""
    import jax
    import jax.numpy as jnp

    h = config["num_attention_heads"]
    layer, head = _jitted(
        h, config["num_key_value_heads"],
        config.get("head_dim") or config["hidden_size"] // h,
        float(config["rope_theta"]), float(config["rms_norm_eps"]))
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for lw in weights["layers"]:
            x = layer(x, lw)
        return head(x, weights["norm"], weights["lm_head"], jnp.asarray(rows))
