"""Plain reference: Falcon-H1's (``falcon_h1``; parallel hybrid blocks)
full causal forward in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no cache, no kernels, no
chunk form, no batching, one sequence at a time, the recurrence token by
token (it is the definition).

``x`` (tokens, hidden); config keys in backticks; **assumed** marks what
``config.json`` does not settle (each is listed under ``assumed`` in the
configuration file; there is no network here, so they are the published
description as remembered: the Falcon-H1 report and the model's own
modelling file). ``eps`` is ``rms_norm_eps``; ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * g``.

* ``x_0 = E[token] * embedding_multiplier``.
* Every layer: ``u = RMSNorm_in(x)``; **both mixers read the same u and
  their outputs are added**:
  ``x = x + ssm_out_multiplier * Mixer(ssm_in_multiplier * u)
  + attention_out_multiplier * Attn(attention_in_multiplier * u)``;
  then ``v = RMSNorm_ff(x)``, ``x = x + mlp_multipliers[1] *
  W_down(silu(mlp_multipliers[0] * W_gate v) * W_up v)``
  (``intermediate_size``; ``gate_up`` holds gate first, then up; no
  bias).
* **Mixer** (Mamba-2 / SSD; ``mamba_d_ssm`` D = ``mamba_n_heads`` H x
  ``mamba_d_head`` P, ``mamba_d_state`` N, ``mamba_n_groups`` G,
  ``mamba_d_conv`` K; ``mamba_d_ssm`` overrides ``mamba_expand``):
  ``[z | x | B | C | dt] = W_in u'`` (D, D, GN, GN, H wide), each
  segment times its entry of ``ssm_multipliers`` (z, x, B, C, dt in
  that order); ``[x | B | C] = silu(conv1d_causal_depthwise([x | B | C])
  + b_conv)``, the convolution over the three together (**assumed**),
  tap ``K - 1`` on the current token; ``dt_h = softplus(dt_h +
  dt_bias_h)`` (**assumed** not clamped above); ``a_h = -exp(A_log_h)``,
  one scalar a head. For head ``h`` of group ``g = h // (H / G)``, with
  ``S_h`` (P x N) from zeros:
  ``S_h = exp(dt_h a_h) S_h + dt_h x_h B_g^T``,
  ``y_h = S_h C_g + D_h x_h``. The gate FIRST, then a GROUPED norm
  (``mamba_norm_before_gate`` false, ``mamba_rms_norm`` true;
  **assumed**: the statistics over each group's D / G channels):
  ``y = RMSNorm_groups(y * silu(z))`` with a gain of D values;
  ``Mixer = W_out y``. No biases but the convolution's
  (``mamba_proj_bias`` false, ``mamba_conv_bias`` true).
* **Attn** (``num_attention_heads`` Hq, ``num_key_value_heads`` Hkv,
  ``head_dim`` d): ``q = W_q u'``, ``k = W_k u' * key_multiplier``, ``v =
  W_v u'``; rotary over the whole head in half-split pairs
  (**assumed**), ``rope_theta``, ``rope_scaling`` null; causal softmax
  of ``q k^T / sqrt(d)``; query head ``i`` reads key / value head ``i //
  (Hq / Hkv)``; ``W_o``; no bias, no q / k norm.
* **Ends**: ``logits = lm_head_multiplier * W_head RMSNorm_final(x)``;
  the head is untied (``tie_word_embeddings`` false).

A 1,024-token sequence has to fit beside 10.5 GB of served weights:
per-token products run ``TOKEN_BLOCK`` tokens at a time, attention
``QUERY_BLOCK`` queries at a time, the head ``HEAD_BLOCKS`` slices of the
vocabulary at a time (the whole head in float32 would be 5.3 GB); weights
arrive in the dtype they are served in and are cast up one matrix (one
slice) at a time.
"""
from __future__ import annotations

import functools
import math

PAD_TO = 256
TOKEN_BLOCK = 1024
QUERY_BLOCK = 128
HEAD_BLOCKS = 8


def _f32(w):
    import jax.numpy as jnp

    return w.astype(jnp.float32)


def _mm(x, w):
    """x @ w.T with ``w`` (out, in) cast up here."""
    return x @ _f32(w).T


def _rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _blocks(fn, xs, block):
    """``fn`` over the leading axis of ``xs`` (arrays of N rows) ``block``
    rows at a time; N must be a multiple of ``block`` or below it."""
    import jax

    n = xs[0].shape[0]
    if n <= block:
        return fn(*xs)
    split = tuple(x.reshape((n // block, block) + x.shape[1:]) for x in xs)
    out = jax.lax.map(lambda a: fn(*a), split)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n,) + o.shape[2:]), out)


def mixer(u, lw, c):
    """The Mamba-2 mixer of ``u`` (L, U) from a zero state; the
    recurrence token by token, state (H, P, N)."""
    import jax
    import jax.numpy as jnp

    l = u.shape[0]
    dim, h, n, g, k = (c["d_ssm"], c["ssm_heads"], c["d_state"],
                       c["n_groups"], c["d_conv"])
    p = dim // h
    zxbcdt = _blocks(lambda ub: _mm(ub, lw["in"]), (u,), TOKEN_BLOCK)
    edges = [dim, 2 * dim, 2 * dim + g * n, 2 * dim + 2 * g * n]
    z, x, b, cc, dt = (seg * m for seg, m in zip(
        jnp.split(zxbcdt, edges, axis=-1), c["ssm_multipliers"]))
    xbc = jnp.concatenate([x, b, cc], axis=-1)
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    conv_w = _f32(lw["conv_w"])
    xbc = jax.nn.silu(sum(padded[j:j + l] * conv_w[:, j] for j in range(k))
                      + _f32(lw["conv_b"]))
    x = xbc[:, :dim].reshape(l, h, p)
    b = jnp.repeat(xbc[:, dim:dim + g * n].reshape(l, g, n), h // g, axis=1)
    cc = jnp.repeat(xbc[:, dim + g * n:].reshape(l, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + _f32(lw["dt_b"]))          # (L, H)
    a = -jnp.exp(_f32(lw["a_log"]))                      # (H,)
    d = _f32(lw["d"])

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs                         # (H, P), (H,), (H, N)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + d[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                        (x, dt, b, cc))
    y = (y.reshape(l, dim) * jax.nn.silu(z)).reshape(l, g, dim // g)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + c["eps"])
    return _mm(y.reshape(l, dim) * _f32(lw["norm"]), lw["out"])


def _rotary(x, theta):
    """Half-split rotary of ``x`` (L, H, d) at positions 0 .. L - 1."""
    import jax.numpy as jnp

    l, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(u, lw, c):
    """Causal grouped-query attention of ``u`` (L, U), ``QUERY_BLOCK``
    queries at a time against every key (masked dense products)."""
    import jax
    import jax.numpy as jnp

    l = u.shape[0]
    hq, hkv, d = c["heads"], c["kv_heads"], c["head_dim"]
    q = _rotary(_mm(u, lw["q"]).reshape(l, hq, d), c["rope_theta"])
    k = _rotary((_mm(u, lw["k"]) * c["key_multiplier"]).reshape(l, hkv, d),
                c["rope_theta"])
    v = _mm(u, lw["v"]).reshape(l, hkv, d)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    key_pos = jnp.arange(l)

    def rows(qb, pos):
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        seen = key_pos[None, :] <= pos[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", prob, v).reshape(qb.shape[0],
                                                           hq * d)

    return _mm(_blocks(rows, (q, key_pos), QUERY_BLOCK), lw["o"])


def layer(x, lw, *, c):
    """One parallel hybrid block over ``x`` (L, U)."""
    import jax
    import jax.numpy as jnp

    u = _rms_norm(x, lw["ln1"], c["eps"])
    x = (x + c["ssm_out_multiplier"] * mixer(c["ssm_in_multiplier"] * u,
                                             lw, c)
         + c["attention_out_multiplier"]
         * attention(c["attention_in_multiplier"] * u, lw, c))
    gate_mult, out_mult = c["mlp_multipliers"]

    def rows(xb):
        gate, up = jnp.split(
            _mm(_rms_norm(xb, lw["ln2"], c["eps"]), lw["gate_up"]), 2,
            axis=-1)
        return _mm(jax.nn.silu(gate_mult * gate) * up, lw["down"])

    return x + out_mult * _blocks(rows, (x,), TOKEN_BLOCK)


def constants(config: dict) -> tuple:
    """The numbers of the config file the equations use, hashable."""
    return tuple(sorted({
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rope_theta": float(config["rope_theta"]),
        "d_ssm": config["mamba_d_ssm"], "ssm_heads": config["mamba_n_heads"],
        "d_state": config["mamba_d_state"],
        "n_groups": config["mamba_n_groups"],
        "d_conv": config["mamba_d_conv"],
        "eps": float(config["rms_norm_eps"]),
        "ssm_in_multiplier": float(config["ssm_in_multiplier"]),
        "ssm_out_multiplier": float(config["ssm_out_multiplier"]),
        "attention_in_multiplier": float(config["attention_in_multiplier"]),
        "attention_out_multiplier":
            float(config["attention_out_multiplier"]),
        "key_multiplier": float(config["key_multiplier"]),
        "ssm_multipliers": tuple(float(m)
                                 for m in config["ssm_multipliers"]),
        "mlp_multipliers": tuple(float(m)
                                 for m in config["mlp_multipliers"]),
    }.items()))


@functools.lru_cache(maxsize=None)
def _jitted(consts):
    import jax

    return jax.jit(functools.partial(layer, c=dict(consts)))


@functools.lru_cache(maxsize=None)
def _head(eps, multiplier):
    import jax
    import jax.numpy as jnp

    def head(x, rows, g, w):
        # HEAD_BLOCKS slices of the vocabulary, each cast up alone
        n = rows.shape[0]
        padded = -(-n // QUERY_BLOCK) * QUERY_BLOCK
        xs = _rms_norm(x[jnp.pad(rows, (0, padded - n))], g, eps)
        vocab = w.shape[0]
        blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
        parts = jax.lax.map(lambda wb: _mm(xs, wb),
                            w.reshape(blocks, vocab // blocks, w.shape[1]))
        return multiplier * jnp.moveaxis(parts, 0, 1).reshape(
            padded, vocab)[:n]

    return jax.jit(head)


def _padded(tokens):
    import jax.numpy as jnp

    # few distinct compiled lengths (causal: padding is harmless)
    tokens = jnp.asarray(tokens)
    return jnp.pad(tokens, (0, -tokens.shape[0] % PAD_TO))


def _run(weights, config, tokens, collect=None):
    consts = constants(config)
    x = _f32(weights["embed"][_padded(tokens)]) \
        * float(config["embedding_multiplier"])
    for lw in weights["layers"]:
        y = _jitted(consts)(x, lw)
        if collect is not None:
            collect.append({"x": x, "output": y})
        x = y
    return x


def logits_at(weights: dict, config: dict, tokens, rows):
    """float32 logits (len(rows), vocab) of ONE sequence ``tokens`` (1-D
    int array) at the positions ``rows``: row i scores token i + 1.
    Padding after the last row of interest is harmless (causal)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _run(weights, config, tokens)
        return _head(float(config["rms_norm_eps"]),
                     float(config["lm_head_multiplier"]))(
            x, jnp.asarray(rows, jnp.int32), weights["norm"],
            weights["head"])


def layer_io(weights: dict, config: dict, tokens) -> list:
    """Per layer of ONE sequence its input ``x`` and its ``output``
    (padded length, hidden), float32; rows past ``len(tokens)`` are
    padding."""
    import jax

    out = []
    with jax.default_matmul_precision("highest"):
        _run(weights, config, tokens, collect=out)
    return out


def mixer_io(weights: dict, config: dict, x, layer_index: int, which: str):
    """One mixer of one layer ALONE on a given layer input ``x`` (L, U)
    float32: ``which`` ``ssm`` or ``attention``; its output before the
    out-multiplier. For comparisons the harness's ``correct`` cannot make
    (``tools/falcon_h1_chip_check.py``)."""
    import jax

    c = dict(constants(config))
    lw = weights["layers"][layer_index]
    with jax.default_matmul_precision("highest"):
        u = _rms_norm(x, lw["ln1"], c["eps"])
        if which == "ssm":
            return mixer(c["ssm_in_multiplier"] * u, lw, c)
        return attention(c["attention_in_multiplier"] * u, lw, c)
