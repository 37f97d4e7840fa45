"""Plain reference: the language model of Ling-3.0-flash-VL
(``inclusionAI/Ling-3.0-flash-VL``) as a full causal forward in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: no cache,
no slots, no kernels, no chunk form, no batching, one sequence at a time,
the delta rule TOKEN BY TOKEN (it is the definition).

``x`` is a token's residual stream (``hidden_size``); config keys in
backticks; **assumed** marks what ``config.json`` does not settle (each is
listed under ``assumed`` in the configuration file). ``RMSNorm(x) = x /
sqrt(mean(x^2) + rms_norm_eps) * g``; ``H = num_attention_heads``, ``d =
head_dim``.

* ``x_0 = E[token]``. A layer is ``x += Mixer(RMSNorm_in(x)); x +=
  FFN(RMSNorm_post(x))``. ``layer_kinds`` names each held layer's mixer
  (published: MLA where ``(i + 1) % layer_group_size == 0``, KDA
  otherwise); the FFN is a dense SwiGLU of ``intermediate_size`` for the
  first ``first_k_dense_replace`` layers and the expert layer otherwise.
* **KDA mixer** (``u = RMSNorm_in(x)``, a row a token ``t``):
  ``q~, k~, v~ = W_q u, W_k u, W_v u`` (``H x d`` each; ``qkv`` holds q,
  then k, then v); each passes a causal depthwise convolution of
  ``short_conv_kernel_size`` taps (no bias, the last tap on the current
  token, zeros before the sequence) and SiLU (``linear_silu``); ``q`` and
  ``k`` are L2-normalised a head (eps 1e-6, **assumed** reading of
  ``use_qk_norm``), ``q`` also times ``d^-0.5``; ``v`` as it is
  (``value_norm`` false). Decay, a value a KEY CHANNEL a head: ``a_t =
  W_f u + dt_bias``, ``g_t = kda_lower_bound * sigmoid(exp(A_log_h) *
  a_t)`` (``kda_safe_gate`` true: the log-decay lies in
  ``[kda_lower_bound, 0]``; ``safe_gate`` false gives the unbounded
  ``-exp(A_log_h) * softplus(a_t)`` the config turns off), ``alpha_t =
  exp(g_t)``. Write strength a head: ``beta_t = sigmoid(W_beta u)``.
  State ``S`` (H, d keys, d values) float32, zeros at the start:
  ``S' = alpha_t[:, None] * S_{t-1}``; ``S_t = S' + beta_t k_t (v_t -
  k_t^T S')^T``; ``o_t = S_t^T q_t``. Out: ``y_t = W_o concat_h(
  RMSNorm_d(o_t,h) * sigmoid(W_g u)_h)``, one gain of ``d`` values for
  every head (**assumed**).
* **MLA mixer**: ``q = W_q u`` as H x (``qk_nope_head_dim`` +
  ``qk_rope_head_dim``) (``q_lora_rank`` null: no bottleneck); ``[c, k_r]
  = W_kva u`` (``kv_lora_rank`` + rope), ``c = RMSNorm(c)``; ``[k_nope |
  v]_h = W_kvb c``; rotary (``rope_theta``, half-split pairs, **assumed**)
  on ``q_rope`` and on the one ``k_r`` every head shares; scores
  ``(q_nope . k_nope + q_rope . k_r) * (nope + rope)^-0.5``, causal
  softmax, ``o_h = P v_h``; a gate a head: ``o_h *= sigmoid(W_gate u)_h``
  (``gated_attention_proj_granularity_type`` head_wise); ``y = W_o
  concat_h(o_h)``.
* **Expert layer**: as ``references/dots_vlm.py`` writes it (sigmoid
  scores over ``router_outputs`` in ``n_group`` groups, a group scores the
  sum of its 2 largest ``s + b``, the ``topk_group`` best stay, top
  ``num_experts_per_tok`` among theirs, weights renormalised and times
  ``routed_scaling_factor``) plus one shared SwiGLU expert of
  ``moe_shared_expert_intermediate_size``. No SwiGLU clamp: the held
  layers have none (``expert_swiglu_limit_list`` 0).
* ``logits = W_head RMSNorm_final(x)``.

**The share**: ``weights`` hold the experts ``first_held_expert .. +
num_experts - 1`` of each expert layer and a slice of the vocabulary; the
routed sum runs over the HELD experts only; the shared expert is whole.

**Not computed**: the vision tower and the multi-token-prediction block
(``not_served`` in the configuration file).

Per-token work runs ``TOKEN_BLOCK`` tokens at a time, attention a group
of heads and a block of queries at a time, so that 7k tokens fit beside
the served weights; weights arrive in the dtype they are served in and
are cast up one matrix at a time.
"""
from __future__ import annotations

import functools

from benchmarks.references.dots_vlm import routed
from benchmarks.references.glm_moe_dsa import (_blocks, _f32, _mm, _rms,
                                               _swiglu)

# a sequence longer than SHORT is padded to LONG (one compiled length
# for every request of the cell), a shorter one to whole SHORTs
SHORT, LONG = 256, 7168
TOKEN_BLOCK = 1024
QUERY_BLOCK = 128
HEAD_GROUP = 8
QK_NORM_EPS = 1e-6


def _round(x, fmt):
    """``x`` rounded to ``fmt`` = (exponent bits, mantissa bits), or as
    it is (None); not a pair of converts, which the compiler may drop as
    excess precision."""
    import jax

    return x if fmt is None else jax.lax.reduce_precision(x, *fmt)


def _l2(x):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + QK_NORM_EPS)


def kda(u, lw, c):
    """The KDA mixer of ``u`` (L, U) from a zero state, the recurrence
    token by token. ``c["state"]``: the format the state is rounded to
    after every token (None: float32)."""
    import jax
    import jax.numpy as jnp

    l = u.shape[0]
    h, k = c["heads"], c["conv"]
    qkv = _blocks(lambda ub: _mm(ub, lw["qkv"]), (u,), TOKEN_BLOCK)
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, qkv.shape[1]), qkv.dtype), qkv], axis=0)
    conv_w = _f32(lw["conv"])
    qkv = jax.nn.silu(sum(padded[j:j + l] * conv_w[:, j] for j in range(k)))
    q, key, v = (t.reshape(l, h, -1) for t in jnp.split(qkv, 3, axis=-1))
    d = q.shape[-1]
    q, key = _l2(q) * d ** -0.5, _l2(key)
    a = (_blocks(lambda ub: _mm(ub, lw["f"]), (u,), TOKEN_BLOCK)
         + _f32(lw["dt_b"])).reshape(l, h, d)
    rate = jnp.exp(_f32(lw["a_log"]))[:, None]
    if c["safe_gate"]:
        g = c["lower_bound"] * jax.nn.sigmoid(rate * a)
    else:
        g = -rate * jax.nn.softplus(a)
    alpha = jnp.exp(g)                                     # (L, H, d)
    beta = jax.nn.sigmoid(_mm(u, lw["b"]))                 # (L, H)

    def step(s, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        s = a_t[:, :, None] * s
        seen = jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + jnp.einsum("hk,hv->hkv", k_t,
                           b_t[:, None] * (v_t - seen))
        s = _round(s, c["state"])
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    _, o = jax.lax.scan(step, jnp.zeros((h, d, d), jnp.float32),
                        (q, key, v, alpha, beta))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c["eps"]) \
        * _f32(lw["o_norm"])
    gate = jax.nn.sigmoid(_blocks(lambda ub: _mm(ub, lw["g"]), (u,),
                                  TOKEN_BLOCK)).reshape(l, h, d)
    return _blocks(lambda yb: _mm(yb, lw["o"]),
                   ((o * gate).reshape(l, h * d),), TOKEN_BLOCK)


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


def mla(u, lw, c):
    """The MLA mixer of ``u`` (L, U), dense and causal; a group of heads
    and a block of queries at a time."""
    import jax
    import jax.numpy as jnp

    l = u.shape[0]
    heads, nope, rope, v_dim = c["heads"], c["nope"], c["rope"], c["v_dim"]
    ckr = _blocks(lambda ub: _mm(ub, lw["kva"]), (u,), TOKEN_BLOCK)
    latent = _rms(ckr[:, :c["kv_rank"]], lw["kvnorm"], c["eps"])
    k_rope = _rotary(ckr[:, None, c["kv_rank"]:], c["theta"])[:, 0]
    gate = jax.nn.sigmoid(_mm(u, lw["gate"]))              # (L, H)
    g = min(HEAD_GROUP, heads)
    n_g = heads // g
    q_w = lw["q"].reshape(n_g, g * (nope + rope), -1)
    kvb_w = lw["kvb"].reshape(n_g, g * (nope + v_dim), -1)
    out_w = lw["out"].reshape(-1, n_g, g * v_dim).transpose(1, 0, 2)
    key_pos = jnp.arange(l)

    def group(projected, ws):
        w_q, w_kv, w_o, gate_g = ws
        q = _blocks(lambda ub: _mm(ub, w_q), (u,), TOKEN_BLOCK).reshape(
            l, g, nope + rope)
        q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], c["theta"])
        kv = _mm(latent, w_kv).reshape(l, g, nope + v_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def block(qn, qr, pos):
            scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                      + jnp.einsum("qhd,kd->hqk", qr, k_rope)) \
                * (nope + rope) ** -0.5
            scores = jnp.where(key_pos[None, None, :] <= pos[None, :, None],
                               scores, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd",
                              jax.nn.softmax(scores, axis=-1), v)

        att = _blocks(block, (q_nope, q_rope, key_pos), QUERY_BLOCK)
        att = (att * gate_g[:, :, None]).reshape(l, g * v_dim)
        return projected + _mm(att, w_o), None

    return jax.lax.scan(
        group, jnp.zeros((l, lw["out"].shape[0]), jnp.float32),
        (q_w, kvb_w, out_w, gate.reshape(l, n_g, g).transpose(1, 0, 2)))[0]


def ffn(a, lw, c):
    """The FFN half's contribution (dense, or routed share + shared)."""
    def per_token(ab):
        h = _rms(ab, lw["post_norm"], c["eps"])
        if "moe" not in lw:
            return _swiglu(h, lw["ffn_gate_up"], lw["ffn_down"])
        return routed(h, lw["moe"], c) + _swiglu(
            h, lw["shared_gate_up"], lw["shared_down"])

    return _blocks(per_token, (a,), TOKEN_BLOCK)


def mixer(x, lw, c):
    """A layer's mixer on the layer input ``x`` (L, U): KDA where the
    layer's weights have ``qkv``, MLA otherwise."""
    u = _rms(x, lw["in_norm"], c["eps"])
    return kda(u, lw, c) if "qkv" in lw else mla(u, lw, c)


def layer(x, lw, *, c):
    a = x + mixer(x, lw, c)
    return a + ffn(a, lw, c)


def constants(config: dict, state=None) -> tuple:
    """The numbers of the config file the equations use, hashable.
    ``state``: the format the KDA state is rounded to after every token
    (None: float32), the precision below the configuration's that the
    float32-state pin of the tests reads."""
    return tuple(sorted({
        "heads": config["num_attention_heads"],
        "conv": config["short_conv_kernel_size"],
        "lower_bound": float(config["kda_lower_bound"]),
        "safe_gate": bool(config["kda_safe_gate"]),
        "kv_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "v_dim": config["v_head_dim"],
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "top_k": config["num_experts_per_tok"],
        "n_group": config["n_group"], "topk_group": config["topk_group"],
        "moe_scale": float(config["routed_scaling_factor"]),
        "first_held": config.get("first_held_expert", 0),
        "state": state}.items(), key=lambda kv: kv[0]))


@functools.lru_cache(maxsize=None)
def _jitted(consts):
    import jax

    c = dict(consts)

    def head(x, norm_w, head_w, rows):
        return _mm(_rms(x[rows], norm_w, c["eps"]), head_w)

    return (jax.jit(functools.partial(layer, c=c)), jax.jit(head),
            jax.jit(functools.partial(mixer, c=c)))


def _padded(tokens):
    import numpy as np

    tokens = np.asarray(tokens)
    n = tokens.shape[0]
    to = -(-n // SHORT) * SHORT if n <= 8 * SHORT else -(-n // LONG) * LONG
    return np.pad(tokens, (0, to - n))


def _run(weights, consts, tokens, collect=None):
    import jax.numpy as jnp

    run = _jitted(consts)[0]
    x = _f32(weights["embed"][jnp.asarray(_padded(tokens))])
    for lw in weights["layers"]:
        y = run(x, lw)
        if collect is not None:
            collect.append({"x": x, "output": y})
        x = y
    return x


def logits_at(weights: dict, config: dict, tokens, rows, **controls):
    """float32 logits (len(rows), vocab slice) of ONE sequence ``tokens``
    (1-D int array) at the positions ``rows``: row i scores token i + 1.
    Padding after the last row of interest is harmless (causal).
    ``controls``: :func:`constants`' (``state``)."""
    import jax
    import jax.numpy as jnp

    consts = constants(config, **controls)
    with jax.default_matmul_precision("highest"):
        x = _run(weights, consts, tokens)
        return _jitted(consts)[1](x, weights["norm"], weights["lm_head"],
                                  jnp.asarray(rows, jnp.int32))


def layer_io(weights: dict, config: dict, tokens) -> list:
    """Per layer of ONE sequence its input ``x`` and its ``output``
    (padded length, hidden), float32; rows past ``len(tokens)`` are
    padding."""
    import jax

    out = []
    with jax.default_matmul_precision("highest"):
        _run(weights, constants(config), tokens, collect=out)
    return out


def mixer_io(weights: dict, config: dict, x, layer_index: int):
    """One layer's mixer ALONE on a given layer input ``x`` (L, U)
    float32: what it adds to the stream. For comparisons the harness's
    ``correct`` cannot make (``tools/ling_chip_check.py``)."""
    import jax

    with jax.default_matmul_precision("highest"):
        return _jitted(constants(config))[2](
            x, weights["layers"][layer_index])


def expert_layer_io(weights: dict, config: dict, h, layer_index: int):
    """The held routed experts' part ALONE of one expert layer on normed
    tokens ``h`` (L, U) float32 (without the shared expert)."""
    import jax

    c = dict(constants(config))
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda hb, m: routed(hb, m, c))(
            h, weights["layers"][layer_index]["moe"])
