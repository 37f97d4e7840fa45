"""Plain reference: Phi-4-mini-flash-reasoning's (``phi4flash``; SambaY,
arXiv:2507.06607) full causal forward in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no cache, no kernels, no
ring, no batching, one sequence at a time, every layer on every position.

``x`` (tokens, hidden); config keys in backticks; **assumed** marks what
``config.json`` does not carry (each is listed under ``assumed`` in the
configuration file; there is no network here, so they are the published
description as remembered: the paper and the model's own modelling file).

Every layer: ``x = x + mixer(LN1(x))``, ``x = x + MLP(LN2(x))``. ``LN``
is LayerNorm with gain and bias, ``layer_norm_eps``. MLP is SwiGLU
``down(silu(gate) * up)``, ``intermediate_size``, no bias (``mlp_bias``
false); ``gate_up`` holds gate first, then up. **No positional encoding
anywhere** (assumed; the config has no rotary key). ``n =
num_hidden_layers``, ``mb_per_layer`` 2: layer ``i`` has a Mamba-kind
mixer if ``i`` is even, an attention-kind mixer if odd. Layers ``0 ..
n/2 - 1``: the self-decoder (Mamba, windowed attention). Layer ``n/2``:
Mamba that also publishes its memory. Layer ``n/2 + 1``: full attention
that also publishes its keys and values. Layers above (the
cross-decoder): even = gated memory unit, odd = cross-attention.

* **Mamba** (assumed: ``d_state`` 16, ``d_conv`` 4, ``expand`` 2 so
  ``d_inner`` 5120, ``dt_rank`` = hidden / 16; in / out projections
  without bias, convolution and ``dt`` projection with):
  ``[u | z] = W_in h``; ``u = silu(conv1d_causal_depthwise(u) + b_c)``
  (kernel ``d_conv``, tap ``d_conv - 1`` on the current token);
  ``[r | B | C] = W_x u``; ``dt = softplus(W_dt r + b_dt)``;
  ``A = -exp(A_log)`` (``d_inner`` x ``d_state``);
  ``s_t = exp(dt_t * A) * s_{t-1} + (dt_t * u_t) B_t^T``;
  ``y_t = s_t C_t + D * u_t``; output ``W_out (y * silu(z))``. The layer
  at ``n/2`` publishes ``m = y`` (the scan's output with the ``D`` term,
  BEFORE its own gate; assumed).
* **Differential attention** (``num_attention_heads`` Hq,
  ``num_key_value_heads`` Hkv, head width d = hidden / Hq; assumed: the
  differential form, its pairing, its lambdas, biases on ``Wqkv`` and on
  the out-projection): ``[q | k | v] = W_qkv h + b``; heads pair as
  ``(i, j)``, ``j`` in {0, 1}: query head ``2i + j`` (``i`` < Hq / 2),
  key / value head ``2p + j`` (``p`` < Hkv / 2); query pair ``i`` reads
  key / value pair ``p = i // (Hq / Hkv)``; ``V_p = [v_(p,0) |
  v_(p,1)]`` (2d wide);
  ``O_(i,j) = softmax(q_(i,j) K_(p,j)^T / sqrt(d) + mask) V_p``;
  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``,
  ``lam0 = 0.8 - 0.6 exp(-0.3 l)`` for layer index ``l``, four learned
  d-vectors a layer;
  ``o_i = (1 - lam0) * rmsnorm_2d(O_(i,0) - lam * O_(i,1))`` (gain of
  2d values, eps ``layer_norm_eps``); the ``o_i`` side by side, then the
  out-projection. Masks: self-decoder layers (``sliding_window`` W):
  query ``t`` sees keys ``t - W < s <= t`` (W keys, the query's own
  among them; assumed inclusive count); the full layer: every ``s <= t``.
* **Cross-attention**: ``q = W_q h + b`` only; keys and values are the
  FULL layer's, every ``s <= t``; the same differential form with the
  layer's own lambdas and sub-norm. No K/V weights.
* **Gated memory unit**: ``out = W_2 (m * silu(W_1 h))``, ``m`` the
  memory at the same token.
* **Ends**: embedding, final LayerNorm, head tied to the embedding
  (``tie_word_embeddings``).

**No departure from the published inference path.** Serving runs the
full layer's attention, the cross-decoder and the head on a prompt's
LAST token only (its keys and values on every token): that IS the
published prefill; this reference runs every layer on every position,
and the two agree at the positions serving reads.

A 19k-token sequence has to fit beside the served weights: per-token
work runs ``TOKEN_BLOCK`` tokens at a time, attention ``QUERY_BLOCK``
queries at a time, the head ``QUERY_BLOCK`` rows at a time; weights
arrive in the dtype they are served in and are cast up one matrix at a
time.
"""
from __future__ import annotations

import functools
import math

PAD_TO = 256
# a sequence past one token block: each of the six layer programs takes
# ~7 s to compile a length, so the cell's 3k-19k sequences share two
LONG_PAD_TO = 10240
TOKEN_BLOCK = 2048
QUERY_BLOCK = 128


def _f32(w):
    import jax.numpy as jnp

    return w.astype(jnp.float32)


def _mm(x, w, b=None):
    """x @ w.T (+ b) with ``w`` (out, in) cast up here."""
    y = x @ _f32(w).T
    return y if b is None else y + _f32(b)


def _layer_norm(x, g, b, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(g) + _f32(b)


def _blocks(fn, xs, block):
    """``fn`` over the leading axis of ``xs`` (arrays of N rows) ``block``
    rows at a time; N must be a multiple of ``block`` or below it."""
    import jax
    import jax.numpy as jnp

    n = xs[0].shape[0]
    if n <= block:
        return fn(*xs)
    split = tuple(x.reshape((n // block, block) + x.shape[1:]) for x in xs)
    out = jax.lax.map(lambda a: fn(*a), split)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n,) + o.shape[2:]), out)


def mlp(x, lw, c):
    import jax
    import jax.numpy as jnp

    def rows(xb):
        gate, up = jnp.split(
            _mm(_layer_norm(xb, lw["ln2_g"], lw["ln2_b"], c["eps"]),
                lw["gate_up"]), 2, axis=-1)
        return _mm(jax.nn.silu(gate) * up, lw["down"])

    return x + _blocks(rows, (x,), TOKEN_BLOCK)


def mamba(h, lw, c):
    """The Mamba mixer of ``h`` (L, U) from a zero state: its output and
    the scan's output ``y`` (L, d_inner) before the gate. The recurrence
    token by token, state (d_inner, d_state)."""
    import jax
    import jax.numpy as jnp

    l = h.shape[0]
    k = lw["conv_w"].shape[1]
    n, r = c["d_state"], c["dt_rank"]
    u, z = jnp.split(_blocks(lambda hb: _mm(hb, lw["in"]), (h,),
                             TOKEN_BLOCK), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u],
                             axis=0)
    conv_w = _f32(lw["conv_w"])
    u = jax.nn.silu(sum(padded[j:j + l] * conv_w[:, j] for j in range(k))
                    + _f32(lw["conv_b"]))
    rbc = _mm(u, lw["x"])
    dt = jax.nn.softplus(_mm(rbc[:, :r], lw["dt_w"], lw["dt_b"]))
    a = -jnp.exp(_f32(lw["a_log"]))                     # (D, N)
    d = _f32(lw["d"])

    def step(s, xs):
        u_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, s @ c_t + d * u_t

    _, y = jax.lax.scan(step, jnp.zeros_like(a),
                        (u, dt, rbc[:, r:r + n], rbc[:, r + n:]))
    return _mm(y * jax.nn.silu(z), lw["out"]), y


def diff_attention(q, k, v, lw, layer, c, window=0):
    """The differential attention of queries ``q`` (L, Hq * d) over keys
    and values ``k``, ``v`` (L, Hkv * d), causal, within ``window`` where
    it is above 0; the out-projection applied. ``QUERY_BLOCK`` queries
    at a time against every key (masked dense products)."""
    import jax
    import jax.numpy as jnp

    l = q.shape[0]
    hq, hkv, d = c["heads"], c["kv_heads"], c["head_dim"]
    pairs, g = hq // 2, hq // hkv
    i = jnp.arange(pairs)
    q = q.reshape(l, hq, d)[:, 2 * i[:, None] + jnp.arange(2)[None]]
    # pair i reads key / value pair i // g
    k = k.reshape(l, hkv, d)[:, 2 * (i // g)[:, None] + jnp.arange(2)[None]]
    v = v.reshape(l, hkv // 2, 2 * d)[:, i // g]         # (L, pairs, 2d)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(_f32(lw["lq1"]) * _f32(lw["lk1"])))
           - jnp.exp(jnp.sum(_f32(lw["lq2"]) * _f32(lw["lk2"]))) + lam0)
    key_pos = jnp.arange(l)

    def rows(qb, pos):
        s = jnp.einsum("qijd,kijd->ijqk", qb, k) / math.sqrt(d)
        seen = key_pos[None, :] <= pos[:, None]
        if window:
            seen &= key_pos[None, :] > pos[:, None] - window
        prob = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf),
                              axis=-1)
        o = jnp.einsum("ijqk,kie->qije", prob, v)        # (Q, pairs, 2, 2d)
        diff = o[:, :, 0] - lam * o[:, :, 1]
        norm = diff / jnp.sqrt(jnp.mean(diff * diff, axis=-1, keepdims=True)
                               + c["eps"])
        return ((1.0 - lam0) * norm * _f32(lw["subln"])).reshape(
            qb.shape[0], pairs * 2 * d)

    att = _blocks(rows, (q, key_pos), QUERY_BLOCK)
    return _mm(att, lw["o"], lw["o_b"])


def _qkv(h, lw, c):
    hq, hkv, d = c["heads"], c["kv_heads"], c["head_dim"]
    qkv = _blocks(lambda hb: _mm(hb, lw["qkv"], lw["qkv_b"]), (h,),
                  TOKEN_BLOCK)
    return (qkv[:, :hq * d], qkv[:, hq * d:(hq + hkv) * d],
            qkv[:, (hq + hkv) * d:])


def layer(x, memory, k, v, lw, index, *, kind, c):
    """One layer of kind ``mamba`` / ``publish`` (Mamba that publishes
    its memory) / ``window`` / ``full`` / ``gmu`` / ``cross`` at layer
    index ``index`` (a float32 scalar, so that one program serves every
    layer of a kind): (x, memory, k, v) in and out; ``publish`` replaces
    the memory, ``full`` the keys and values."""
    import jax

    h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"], c["eps"])
    if kind in ("mamba", "publish"):
        out, y = mamba(h, lw, c)
        memory = y if kind == "publish" else memory
    elif kind in ("window", "full"):
        q, k_own, v_own = _qkv(h, lw, c)
        out = diff_attention(q, k_own, v_own, lw, index, c,
                             c["window"] if kind == "window" else 0)
        if kind == "full":
            k, v = k_own, v_own
    elif kind == "gmu":
        out = _mm(memory * jax.nn.silu(_mm(h, lw["gmu_in"])), lw["gmu_out"])
    else:
        out = diff_attention(_mm(h, lw["q"], lw["q_b"]), k, v, lw, index, c)
    return mlp(x + out, lw, c), memory, k, v


def kinds(n_layers: int) -> list:
    half = n_layers // 2
    return [("publish" if i == half else "mamba") if i % 2 == 0 and i <= half
            else "window" if i < half else "full" if i == half + 1
            else "gmu" if i % 2 == 0 else "cross" for i in range(n_layers)]


def constants(config: dict) -> tuple:
    """The numbers of the config file the equations use, hashable."""
    a = config.get("assumed_sizes", {})
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    return tuple(sorted({
        "heads": heads, "kv_heads": config["num_key_value_heads"],
        "head_dim": hidden // heads, "window": config["sliding_window"],
        "d_state": a.get("d_state", 16),
        "dt_rank": a.get("dt_rank", hidden // 16),
        "eps": float(config["layer_norm_eps"])}.items()))


@functools.lru_cache(maxsize=None)
def _jitted(consts, kind):
    import jax

    return jax.jit(functools.partial(layer, kind=kind, c=dict(consts)))


@functools.lru_cache(maxsize=None)
def _head(eps):
    import jax

    def head(x, rows, g, b, embed):
        # the tied head QUERY_BLOCK rows at a time: 2,560 rows of 200,064
        # float32 logits would be 2 GB at once
        w = _f32(embed)
        n = rows.shape[0]
        padded = -(-n // QUERY_BLOCK) * QUERY_BLOCK
        xs = _layer_norm(x[jax.numpy.pad(rows, (0, padded - n))], g, b, eps)
        return _blocks(lambda xb: xb @ w.T, (xs,), QUERY_BLOCK)[:n]

    return jax.jit(head)


def _padded(tokens):
    import jax.numpy as jnp

    # few distinct compiled lengths (causal: padding is harmless); whole
    # token and query blocks
    tokens = jnp.asarray(tokens)
    n = tokens.shape[0]
    to = PAD_TO if n <= TOKEN_BLOCK else LONG_PAD_TO
    return jnp.pad(tokens, (0, -n % to))


def _run(weights, config, tokens, collect=None, keep=None):
    import jax.numpy as jnp

    consts = constants(config)
    x = _f32(weights["embed"][_padded(tokens)])
    memory = k = v = jnp.zeros((1, 1), jnp.float32)
    for i, (kind, lw) in enumerate(zip(kinds(len(weights["layers"])),
                                       weights["layers"])):
        y, memory, k, v = _jitted(consts, kind)(x, memory, k, v, lw,
                                                jnp.float32(i))
        if collect is not None:
            collect.append({"x": x, "output": y}
                           if keep is None or i in keep else None)
        x = y
    return x, memory, k, v


def logits_at(weights: dict, config: dict, tokens, rows):
    """float32 logits (len(rows), vocab) of ONE sequence ``tokens`` (1-D
    int array) at the positions ``rows``: row i scores token i + 1.
    Padding after the last row of interest is harmless (causal)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x, _, _, _ = _run(weights, config, tokens)
        return _head(float(config["layer_norm_eps"]))(
            x, jnp.asarray(rows, jnp.int32), weights["norm_g"],
            weights["norm_b"], weights["embed"])


def layer_io(weights: dict, config: dict, tokens, layers=None) -> list:
    """Per layer of ONE sequence its input ``x`` and its ``output``
    (padded length, hidden), float32; rows past ``len(tokens)`` are
    padding. ``layers``: keep these layers' only (None elsewhere): 32
    layers of a long sequence do not fit beside the served weights."""
    import jax

    out = []
    with jax.default_matmul_precision("highest"):
        _run(weights, config, tokens, collect=out, keep=layers)
    return out


def memory_and_kv(weights: dict, config: dict, tokens) -> tuple:
    """What the cross-decoder reads of ONE sequence: the memory (padded
    length, d_inner) the middle Mamba layer publishes, and the full
    layer's keys and values (padded length, Hkv * d), float32."""
    import jax

    with jax.default_matmul_precision("highest"):
        _, memory, k, v = _run(weights, config, tokens)
    return memory, k, v
