"""Plain reference: GLM-5's (``glm_moe_dsa``) full causal forward in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
no cache, no kernels, no batching, one sequence at a time.

``x`` (tokens, hidden); every projection without bias; ``RMS`` = RMSNorm
with gain, eps ``rms_norm_eps``; config keys in backticks.

* **MLA** (``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
  ``qk_rope_head_dim``, ``v_head_dim``, ``num_attention_heads``):
  ``c_q = RMS(W_qa h)``; ``q = W_qb c_q`` as heads of ``[q_nope |
  q_rope]``; ``[c_kv | k_r] = W_kva h``, ``c' = RMS(c_kv)``; per head
  ``[k_nope | v] = W_kvb c'``; interleaved rotary (pairs ``(2j, 2j+1)``,
  ``rope_interleave``, ``rope_theta``) on ``q_rope`` and on the ONE
  ``k_r`` all heads share; scores ``(q_nope . k_nope + q_rope . k_r) *
  qk_head_dim^-0.5``. None of LongCat's ``mla_scale_*`` factors. (A
  serving cache holds ``[c' | rotated k_r]`` a token.)
* **Indexer** (``index_n_heads`` J, ``index_head_dim`` D,
  ``index_topk``): ``q_I = W_Iq c_q`` as J heads of D (from the SAME
  ``c_q``); ``k_I = LayerNorm(W_Ik h)`` (gain and bias, eps 1e-6), one a
  token; interleaved rotary (``indexer_rope_interleave``) on the FIRST
  ``qk_rope_head_dim`` of the D dims of both, same positions and theta;
  ``w = W_Iw h * J^-0.5 * D^-0.5``;
  ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` for ``s <= t``;
  ``S_t`` = the ``min(index_topk, t + 1)`` positions of largest
  ``I[t, .]`` (equal scores: the lower position first). Attention of
  query ``t`` is the softmax over ``s in S_t``
  of the MLA scores, values from the same ``S_t``; for
  ``t < index_topk`` plain causal MLA.
* **Layer**: ``a = x + W_o MLA(RMS_in(x))``; ``x' = a + FFN(RMS_post(a))``.
  The first ``first_k_dense_replace`` layers: ``FFN`` = SwiGLU of
  ``intermediate_size``. The others: ``s = sigmoid(W_r h)`` in float32
  over ``router_outputs`` experts; the ``num_experts_per_tok`` largest of
  ``s + b`` are picked (``b``: ``e_score_correction_bias``, picks only;
  ``n_group`` = ``topk_group`` = 1: no group limit); weights
  ``routed_scaling_factor * s_e / sum of the picked s`` (``norm_topk_prob``);
  ``FFN(h) = sum over picked e of w_e SwiGLU_e(h) + SwiGLU_shared(h)``
  (``n_shared_experts`` x ``moe_intermediate_size`` wide).
* Model: embedding, the layers, final RMSNorm, untied head.

**The share.** ``weights`` hold the experts ``first_held_expert ..
+ n_routed_experts - 1`` of each expert layer and a slice of the
vocabulary. The sum over picked experts runs over the HELD ones only
(what the absent experts would add is left out, here as in the program);
the shared expert is whole. Holding all ``router_outputs`` experts gives
the uncut layer.

**Departures** from the published inference code (which could not be
read here: no network; equations from memory of DeepSeek-V3.2's
``inference/model.py``, whose indexer GLM-5 adopts, and listed under
``assumed`` in the config file): that code rotates ``q_I`` and ``k_I`` by
a Hadamard matrix and stores ``k_I`` in fp8 with a per-row scale. An
orthogonal rotation of both sides leaves every dot product as it is, so
it is left out; the index keys stay in the configuration's dtype. The
multi-token-prediction block (``num_nextn_predict_layers``) drafts token
t + 2 and does not enter the model's logits: it is not computed.

A 35k-token sequence has to fit beside the served weights: per-token
work runs ``TOKEN_BLOCK`` tokens at a time, the selection ``QUERY_BLOCK``
queries at a time, attention a group of ``HEAD_GROUP`` heads and
``QUERY_BLOCK`` queries at a time; weights arrive in the dtype they are
served in and are cast up one matrix at a time.
"""
from __future__ import annotations

import functools
import math

PAD_TO = 4096
TOKEN_BLOCK = 1024
QUERY_BLOCK = 128
HEAD_GROUP = 8
INDEX_NORM_EPS = 1e-6


def _f32(w):
    import jax.numpy as jnp

    return w.astype(jnp.float32)


def _mm(x, w):
    """x @ w.T with ``w`` (out, in) cast up here, one matrix at a time."""
    return x @ _f32(w).T


def _rms(x, g, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * _f32(g)


def _layer_norm(x, g, b, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(g) + _f32(b)


def _rope(x, theta, pos=None):
    """x: (L, H, D) at positions ``pos`` (default 0..L-1); pairs
    (2j, 2j+1) rotated."""
    import jax.numpy as jnp

    l, h, d = x.shape
    pos = jnp.arange(l) if pos is None else pos
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(l, h, d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(l, h, d)


def _blocks(fn, xs, block):
    """``fn`` over the leading axis of ``xs`` (a tuple of arrays of N
    rows), ``block`` rows at a time; outputs joined again."""
    import jax

    n = xs[0].shape[0]
    if n <= block or n % block:
        return fn(*xs)
    out = jax.lax.map(lambda a: fn(*a), tuple(
        x.reshape((n // block, block) + x.shape[1:]) for x in xs))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n,) + o.shape[2:]), out)


def _swiglu(x, gate_up, down):
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(_mm(x, gate_up), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, down)


def _rope_head(x, c, pos=None):
    """Rotary on the first ``qk_rope_head_dim`` dims of (L, H, D)."""
    import jax.numpy as jnp

    rope = c["rope"]
    return jnp.concatenate([_rope(x[..., :rope], c["theta"], pos),
                            x[..., rope:]], axis=-1)


def select(c_q, k_i, w, p, c):
    """``S_t`` as a bool (L, L): row ``t`` marks the ``min(index_topk,
    t + 1)`` positions ``s <= t`` of largest index score. ``k_i`` (L, D)
    the rotated index keys, ``w`` (L, J) the head weights with their
    constant factors; the index queries are made here from ``c_q``, a
    block of queries at a time."""
    import jax
    import jax.numpy as jnp

    l = k_i.shape[0]
    k = min(c["index_topk"], l)
    key_pos = jnp.arange(l)

    def block(cb, wb, pos):
        qb = _rope_head(_mm(cb, p["iq"]).reshape(
            -1, c["index_heads"], c["index_dim"]), c, pos)
        dots = jnp.einsum("qjd,sd->qjs", qb, k_i)
        score = jnp.einsum("qjs,qj->qs", jax.nn.relu(dots), wb)
        causal = key_pos[None, :] <= pos[:, None]
        # equal scores: the lower position first, as top_k orders them
        # (-0.0, which it would put below 0.0, made 0.0 first)
        score = jnp.where(score == 0, 0.0, score)
        _, idx = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), k)
        picked = jnp.zeros(causal.shape, bool).at[
            jnp.arange(idx.shape[0])[:, None], idx].set(True)
        return picked & causal                  # t + 1 < k: all causal

    return _blocks(block, (c_q, w, key_pos), QUERY_BLOCK)


def attend(c_q, latent, k_rope, selected, p, c, collect=False):
    """MLA over the selected positions and its output projection,
    (L, hidden); with ``collect`` also the attention output before the
    projection, (L, H * v). A group of heads and a block of queries at a
    time, each group's part of the projection added as it is made."""
    import jax
    import jax.numpy as jnp

    l = c_q.shape[0]
    heads, nope, rope, v_dim = c["heads"], c["nope"], c["rope"], c["v_dim"]
    g = min(HEAD_GROUP, heads)
    n_g = heads // g
    qb_w = p["qb"].reshape(n_g, g * (nope + rope), -1)
    kvb_w = p["kvb"].reshape(n_g, g * (nope + v_dim), -1)
    out_w = p["out"].reshape(-1, n_g, g * v_dim).transpose(1, 0, 2)
    scale = (nope + rope) ** -0.5

    def group(projected, ws):
        w_q, w_kv, w_o = ws
        q = _mm(c_q, w_q).reshape(l, g, nope + rope)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], c["theta"])
        kv = _mm(latent, w_kv).reshape(l, g, nope + v_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def block(qn, qr, sel):
            scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                      + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * scale
            scores = jnp.where(sel[None], scores, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd",
                              jax.nn.softmax(scores, axis=-1), v)

        att = _blocks(block, (q_nope, q_rope, selected),
                      QUERY_BLOCK).reshape(l, g * v_dim)
        return projected + _mm(att, w_o), att if collect else None

    projected, att = jax.lax.scan(
        group, jnp.zeros((l, p["out"].shape[0]), jnp.float32),
        (qb_w, kvb_w, out_w))
    if collect:                                       # (G, L, g * v)
        att = att.transpose(1, 0, 2).reshape(l, heads * v_dim)
    return projected, att


def attention(x, p, c, collect=False):
    """``W_o MLA(RMS_in(x))`` over the selected positions, and (with
    ``collect``) what it was made of, by name: the chip check compares
    them one by one."""
    n_j, d_j = c["index_heads"], c["index_dim"]

    def per_token(xb):
        h = _rms(xb, p["in_norm"], c["eps"])
        c_q = _rms(_mm(h, p["qa"]), p["qnorm"], c["eps"])
        ckr = _mm(h, p["kva"])
        k_i = _layer_norm(_mm(h, p["ik"]), p["ik_gain"], p["ik_bias"],
                          INDEX_NORM_EPS)
        w = _mm(h, p["iw"]) * (n_j ** -0.5 * d_j ** -0.5)
        return c_q, _rms(ckr[:, :c["kv_rank"]], p["kvnorm"],
                         c["eps"]), ckr[:, c["kv_rank"]:], k_i, w

    c_q, latent, k_r, k_i, w = _blocks(per_token, (x,), TOKEN_BLOCK)
    k_rope = _rope(k_r[:, None, :], c["theta"])[:, 0]
    k_i = _rope_head(k_i[:, None, :], c)[:, 0]
    selected = select(c_q, k_i, w, p, c)
    projected, att = attend(c_q, latent, k_rope, selected, p, c, collect)
    return projected, {"latent": latent, "k_rope": k_rope, "k_index": k_i,
                       "selected": selected, "att": att} if collect else None


def router(h, m, c):
    """(picked expert ids (L, k), their weights (L, k), s + b (L, E))."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(_mm(h, m["router"]))
    biased = s + _f32(m["router_bias"])
    _, idx = jax.lax.top_k(biased, c["top_k"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    w = c["moe_scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                                   + 1e-20)
    return idx, w, biased


def routed(h, m, c):
    """The held experts' part of the routed sum, an expert at a time."""
    import jax
    import jax.numpy as jnp

    idx, w, _ = router(h, m, c)

    def add(out, expert):
        e, gate_up, down = expert
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        gate, up = jnp.split(h @ _f32(gate_up), 2, axis=-1)
        return out + w_e[:, None] * ((jax.nn.silu(gate) * up)
                                     @ _f32(down)), None

    held = m["gate_up"].shape[0]
    return jax.lax.scan(add, jnp.zeros_like(h), (
        c["first_held"] + jnp.arange(held), m["gate_up"], m["down"]))[0]


def ffn(a, lw, c):
    """The FFN half's contribution (dense, or routed share + shared)."""
    def per_token(ab):
        h = _rms(ab, lw["post_norm"], c["eps"])
        if "moe" not in lw:
            return _swiglu(h, lw["ffn_gate_up"], lw["ffn_down"])
        return routed(h, lw["moe"], c) + _swiglu(
            h, lw["shared_gate_up"], lw["shared_down"])

    return _blocks(per_token, (a,), TOKEN_BLOCK)


def layer(x, lw, c, collect=False):
    projected, parts = attention(x, lw, c, collect)
    a = x + projected
    out = a + ffn(a, lw, c)
    return (out, dict(parts, post_attention=a)) if collect else out


def constants(config: dict) -> tuple:
    """The numbers of the config file the equations use, hashable."""
    return tuple(sorted({
        "heads": config["num_attention_heads"],
        "kv_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "v_dim": config["v_head_dim"],
        "index_heads": config["index_n_heads"],
        "index_dim": config["index_head_dim"],
        "index_topk": config["index_topk"],
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "top_k": config["num_experts_per_tok"],
        "moe_scale": float(config["routed_scaling_factor"]),
        "first_held": config.get("first_held_expert", 0)}.items()))


@functools.lru_cache(maxsize=None)
def _jitted(consts, collect=False):
    import jax

    c = dict(consts)

    def head(x, norm_w, head_w, rows):
        return _mm(_rms(x[rows], norm_w, c["eps"]), head_w)

    return (jax.jit(functools.partial(layer, c=c, collect=collect)),
            jax.jit(head))


def _padded(tokens):
    import jax.numpy as jnp

    # few distinct compiled lengths (causal: padding is harmless)
    tokens = jnp.asarray(tokens)
    return jnp.pad(tokens, (0, -tokens.shape[0] % PAD_TO))


def logits_at(weights: dict, config: dict, tokens, rows):
    """float32 logits (len(rows), vocab slice) of ONE sequence ``tokens``
    (1-D int array) at the positions ``rows``: row i scores token i + 1.
    Padding after the last row of interest is harmless (causal)."""
    import jax
    import jax.numpy as jnp

    run, head = _jitted(constants(config))
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["embed"][_padded(tokens)])
        for lw in weights["layers"]:
            x = run(x, lw)
        return head(x, weights["norm"], weights["lm_head"], jnp.asarray(rows))


def layer_io(weights: dict, config: dict, tokens) -> list:
    """Per layer of ONE sequence: its input ``x`` and what
    :func:`attention` names (``selected``: the sets ``S_t``),
    ``post_attention`` and its ``output``, float32; rows past
    ``len(tokens)`` are padding."""
    import jax

    run, _ = _jitted(constants(config), collect=True)
    out = []
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["embed"][_padded(tokens)])
        for lw in weights["layers"]:
            y, parts = run(x, lw)
            out.append(dict(parts, x=x, output=y))
            x = y
    return out


def selected_sets(weights: dict, config: dict, tokens) -> list:
    """Per layer the bool (L, L) selection of ONE sequence (row ``t``:
    the positions query ``t`` attends to)."""
    return [io["selected"] for io in layer_io(weights, config, tokens)]
