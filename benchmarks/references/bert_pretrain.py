"""Plain reference: BERT masked-LM forward and loss in float32
``jax.numpy`` — no kernels, no fusion, full (B, L, vocab) logits.

Follows Devlin et al. 2018 (post-LN encoder, exact-erf GELU, learned
positions, masked-LM head = dense + GELU + LayerNorm + projection tied to
the word embedding + bias). Departures, all following what the
configuration file states:

* no segment (token-type) embedding is added: the repo's pretraining net
  never passes token types, and a reference that added the row-0
  embedding would not be comparing the same function;
* the loss is the mean cross entropy over EVERY position, not only the
  masked ones (``assumed.loss`` of the config);
* ``layer_norm_eps`` is the config's, not the paper's 1e-12.
"""
from __future__ import annotations


def _ln(x, g, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def per_position_loss(weights: dict, config: dict, tokens, labels):
    """(B, L) float32 cross entropy of the masked-LM head at each position."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = config["layer_norm_eps"]
    heads = config["num_attention_heads"]
    with jax.default_matmul_precision("highest"):
        wt = jax.tree_util.tree_map(lambda a: jnp.asarray(a, f32), weights)
        b, l = tokens.shape
        x = wt["word_embed"][tokens] + wt["pos_embed"][:l][None]
        x = _ln(x, wt["embed_ln_g"], wt["embed_ln_b"], eps)
        d = x.shape[-1] // heads
        for lw in wt["layers"]:
            qkv = x @ lw["qkv_w"].T + lw["qkv_b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q, k, v = (t.reshape(b, l, heads, d).transpose(0, 2, 1, 3)
                       for t in (q, k, v))
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(f32(d))
            probs = jax.nn.softmax(scores, axis=-1)
            att = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
            att = att.transpose(0, 2, 1, 3).reshape(b, l, heads * d)
            x = _ln(x + att @ lw["out_w"].T + lw["out_b"],
                    lw["ln1_g"], lw["ln1_b"], eps)
            h = jax.nn.gelu(x @ lw["ffn1_w"].T + lw["ffn1_b"],
                            approximate=False)
            x = _ln(x + h @ lw["ffn2_w"].T + lw["ffn2_b"],
                    lw["ln2_g"], lw["ln2_b"], eps)
        h = jax.nn.gelu(x @ wt["head_w"].T + wt["head_b"], approximate=False)
        h = _ln(h, wt["head_ln_g"], wt["head_ln_b"], eps)
        logits = h @ wt["word_embed"].T + wt["vocab_bias"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return lse - picked


def loss(weights: dict, config: dict, tokens, labels) -> float:
    import jax.numpy as jnp

    return float(jnp.mean(per_position_loss(
        weights, config, jnp.asarray(tokens), jnp.asarray(labels))))
