"""Plain reference: dots.vlm1's (``dots_vlm``) vision tower and full
causal language forward in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no cache, no kernels, no
batching, no padding buckets, one request at a time.

**Vision tower** (``vision_config`` of the config file; the widths and
equations are ``assumed``, from memory of the family's published
modelling code). An image of ``rows x cols`` patches (both even), each
14 x 14 x 3 = 588 values, ordered so that the four patches of a 2 x 2
merge group lie side by side (:func:`patch_positions` gives each patch's
row and column). ``RMS`` = RMSNorm with gain, eps ``rms_norm_eps``.

* ``x = RMS(W_pe p + b_pe)``, ``embed_dim`` wide.
* ``num_hidden_layers`` blocks, no biases:
  ``x += W_o Attn(R(W_qkv RMS_1(x)))``, ``num_attention_heads`` heads,
  every patch attends to every patch of ITS image and nothing else,
  scale ``head_dim^-0.5``; ``x += W_2 (silu(W_1 h) * W_3 h)``,
  ``h = RMS_2(x)``, ``intermediate_size`` wide (``W_1 | W_3`` stacked).
* ``R``, the 2-D rotary: ``head_dim / 4`` frequencies
  ``theta^(-2i / (head_dim / 2))`` times the patch's row, as many times
  its column; the ``head_dim / 2`` angles repeated over the head's dims,
  rotate-half (dim ``j`` pairs with ``j + head_dim / 2``).
* ``x = RMS_post(x)``; the merger, a merge group a row:
  ``y = W_b gelu(W_a [LN(x_1) | LN(x_2) | LN(x_3) | LN(x_4)] + b_a) +
  b_b`` (LayerNorm eps 1e-6, exact gelu, ``4 embed_dim -> 4 embed_dim ->
  hidden_size``).

**Language model** (DeepSeek-V3's layer; every key from the catalog row).
The input at a position that holds ``image_token_id`` is, in order, the
next row of the request's ``y`` (its images in order); every other
position its token's embedding. Positions are plain 1-D positions.

* MLA as ``references/glm_moe_dsa.py`` writes it, DENSE (every query
  attends to every earlier token: no indexer), rotary frequencies
  YaRN's (:func:`yarn_inv_freq`: ``rope_scaling``), softmax scale
  ``qk_head_dim^-0.5 * mscale^2``, ``mscale = 0.1 mscale_all_dim
  ln(factor) + 1``.
* ``first_k_dense_replace`` dense SwiGLU layers, then expert layers:
  ``s = sigmoid(W_r h)`` in float32 over ``router_outputs`` experts in
  ``n_group`` equal groups; a group scores the sum of its 2 largest
  ``s + b``; the ``topk_group`` best groups stay; the
  ``num_experts_per_tok`` largest ``s + b`` among their experts are
  picked; weights ``routed_scaling_factor * s_e / sum of the picked s``;
  plus the shared expert.

**The share**: as in the GLM-5 reference, ``weights`` hold the experts
``first_held_expert .. + n_routed_experts - 1`` of each expert layer and
a slice of the vocabulary; the routed sum runs over the HELD experts
only; the shared expert and the tower are whole.

**Not computed**: the multi-token-prediction block
(``num_nextn_predict_layers``) does not enter the model's logits.

Per-token work runs ``TOKEN_BLOCK`` tokens at a time, attention a group
of heads and a block of queries at a time, so that 12k patches and 6.6k
tokens fit beside the served weights.
"""
from __future__ import annotations

import functools
import math

from benchmarks.references.glm_moe_dsa import (_blocks, _f32, _layer_norm,
                                               _mm, _rms, _swiglu)

PAD_TO = 2048
# an image's patches are padded to a multiple of this (the padding masked
# out of every softmax): few distinct compiled sizes
PATCH_PAD = 1024
TOKEN_BLOCK = 1024
QUERY_BLOCK = 128
HEAD_GROUP = 8
VISION_QUERY_BLOCK = 512
MERGER_NORM_EPS = 1e-6


# -- vision tower -------------------------------------------------------------

def patch_positions(rows: int, cols: int):
    """(rows * cols, 2) int32: the (row, column) of each patch in the
    order the tower takes them, merge group by merge group (row-major
    over the ``rows / 2 x cols / 2`` groups, the group's four patches
    row-major)."""
    import numpy as np

    gi, gj, di, dj = np.meshgrid(np.arange(rows // 2), np.arange(cols // 2),
                                 (0, 1), (0, 1), indexing="ij")
    return np.stack([2 * gi + di, 2 * gj + dj], axis=-1).reshape(
        -1, 2).astype(np.int32)


def _rope_2d(x, pos, theta):
    """x (N, H, D) at patch positions ``pos`` (N, 2)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d // 2, 2, dtype=jnp.float32)
                           / (d // 2)))
    p = pos.astype(jnp.float32)
    ang = jnp.concatenate([p[:, 0:1] * inv, p[:, 1:2] * inv], axis=-1)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def vision_block(x, bw, pos, n_live, v):
    """One tower block over ONE image's patches ``x`` (N, embed), of
    which the first ``n_live`` are the image's (the rest is padding that
    no query attends to)."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    heads, eps = v["heads"], v["eps"]
    d = x.shape[1] // heads
    qkv = _mm(_rms(x, bw["norm1"], eps), bw["qkv"]).reshape(n, 3, heads, d)
    q = _rope_2d(qkv[:, 0], pos, v["theta"])
    k = _rope_2d(qkv[:, 1], pos, v["theta"])
    val = qkv[:, 2]

    live = jnp.arange(n) < n_live

    def block(qb):
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * d ** -0.5
        scores = jnp.where(live[None, None, :], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          val)

    att = _blocks(block, (q,), VISION_QUERY_BLOCK).reshape(n, heads * d)
    x = x + _mm(att, bw["proj"])
    return _stream(x + _swiglu(_rms(x, bw["norm2"], eps), bw["fc13"],
                               bw["fc2"]), v.get("stream"))


def _vision_constants(config: dict, stream=None) -> tuple:
    vc = config["vision_config"]
    return tuple(sorted({"heads": vc["num_attention_heads"],
                         "eps": float(vc["rms_norm_eps"]),
                         "theta": float(vc["rope_theta"]),
                         "stream": stream}.items(), key=lambda kv: kv[0]))


@functools.lru_cache(maxsize=None)
def _vision_jitted(consts):
    import jax

    v = dict(consts)

    def embed(patches, vw):
        return _rms(_mm(_f32(patches), vw["patch_w"]) + _f32(vw["patch_b"]),
                    vw["patch_norm"], v["eps"])

    def merge(x, vw):
        import jax.numpy as jnp

        x = _layer_norm(_rms(x, vw["post_norm"], v["eps"]), vw["ln_g"],
                        vw["ln_b"], MERGER_NORM_EPS)
        x = x.reshape(x.shape[0] // 4, -1)
        hid = jax.nn.gelu(_mm(x, vw["merger_a"]) + _f32(vw["merger_a_b"]),
                          approximate=False)
        return _mm(hid, vw["merger_b"]) + _f32(vw["merger_b_b"])

    return (jax.jit(embed), jax.jit(functools.partial(vision_block, v=v)),
            jax.jit(merge))


def vision_encode(vw: dict, config: dict, patches, grid, collect=False,
                  stream=None):
    """float32 ``y`` (rows * cols / 4, hidden_size) of ONE image:
    ``patches`` (rows * cols, 588) in merge-group order, ``grid`` =
    (rows, cols). ``vw``: the tower's weights, the blocks' stacked along
    a leading layer axis. With ``collect`` also the tower's state after
    each block."""
    import jax
    import jax.numpy as jnp

    import numpy as np

    rows, cols = int(grid[0]), int(grid[1])
    n = rows * cols
    pad = -n % PATCH_PAD
    pos = jnp.asarray(np.pad(patch_positions(rows, cols), ((0, pad), (0, 0))))
    embed, block, merge = _vision_jitted(_vision_constants(config, stream))
    states = []
    with jax.default_matmul_precision("highest"):
        x = embed(jnp.pad(jnp.asarray(patches), ((0, pad), (0, 0))), vw)
        n_layers = vw["blocks"]["qkv"].shape[0]
        for li in range(n_layers):
            x = block(x, jax.tree_util.tree_map(lambda a: a[li],
                                                vw["blocks"]), pos,
                      jnp.int32(n))
            if collect:
                states.append(x[:n])
        y = merge(x, vw)[:n // 4]
    return (y, states) if collect else y


# -- language model -----------------------------------------------------------

def yarn_inv_freq(d, theta, factor, beta_fast, beta_slow, original_max):
    """The ``d / 2`` rotary frequencies under YaRN: frequency ``i`` is
    ``theta^(-2i/d)`` where it makes more than ``beta_fast`` turns in
    ``original_max`` positions, that over ``factor`` where it makes fewer
    than ``beta_slow``, and a linear blend between the two dims."""
    import numpy as np

    def turns_dim(turns):
        return d * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def _rope(x, inv_freq, pos=None):
    """x: (L, H, D) at positions ``pos`` (default 0..L-1); pairs
    (2j, 2j+1) rotated by ``pos * inv_freq[j]``."""
    import jax.numpy as jnp

    l, h, d = x.shape
    pos = jnp.arange(l) if pos is None else pos
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(l, h, d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(l, h, d)


def attention(x, p, c):
    """``W_o MLA(RMS_in(x))``, dense and causal; a group of heads and a
    block of queries at a time."""
    import jax
    import jax.numpy as jnp

    l = x.shape[0]
    heads, nope, rope, v_dim = c["heads"], c["nope"], c["rope"], c["v_dim"]
    inv_freq = yarn_inv_freq(rope, c["theta"], *c["yarn"]) if c["yarn"] \
        else c["theta"] ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)

    def per_token(xb):
        h = _rms(xb, p["in_norm"], c["eps"])
        c_q = _rms(_mm(h, p["qa"]), p["qnorm"], c["eps"])
        ckr = _mm(h, p["kva"])
        return c_q, _rms(ckr[:, :c["kv_rank"]], p["kvnorm"],
                         c["eps"]), ckr[:, c["kv_rank"]:]

    c_q, latent, k_r = _blocks(per_token, (x,), TOKEN_BLOCK)
    k_rope = _rope(k_r[:, None, :], inv_freq)[:, 0]
    g = min(HEAD_GROUP, heads)
    n_g = heads // g
    qb_w = p["qb"].reshape(n_g, g * (nope + rope), -1)
    kvb_w = p["kvb"].reshape(n_g, g * (nope + v_dim), -1)
    out_w = p["out"].reshape(-1, n_g, g * v_dim).transpose(1, 0, 2)
    key_pos = jnp.arange(l)

    def group(projected, ws):
        w_q, w_kv, w_o = ws
        q = _mm(c_q, w_q).reshape(l, g, nope + rope)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], inv_freq)
        kv = _mm(latent, w_kv).reshape(l, g, nope + v_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def block(qn, qr, pos):
            scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                      + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * c["scale"]
            scores = jnp.where(key_pos[None, None, :] <= pos[None, :, None],
                               scores, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd",
                              jax.nn.softmax(scores, axis=-1), v)

        att = _blocks(block, (q_nope, q_rope, key_pos),
                      QUERY_BLOCK).reshape(l, g * v_dim)
        return projected + _mm(att, w_o), None

    return jax.lax.scan(group, jnp.zeros((l, p["out"].shape[0]),
                                         jnp.float32),
                        (qb_w, kvb_w, out_w))[0]


def router(h, m, c):
    """(picked expert ids (L, k), their weights (L, k)): sigmoid scores,
    the pick by ``s + b`` inside the ``topk_group`` best of ``n_group``
    groups."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(_mm(h, m["router"]))
    biased = s + _f32(m["router_bias"])
    if c["n_group"] > 1:
        grouped = biased.reshape(h.shape[0], c["n_group"], -1)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, c["topk_group"])
        kept = jnp.zeros(group_score.shape, bool).at[
            jnp.arange(h.shape[0])[:, None], keep].set(True)
        biased = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(
            biased.shape)
    _, idx = jax.lax.top_k(biased, c["top_k"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    return idx, c["moe_scale"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def routed(h, m, c):
    """The held experts' part of the routed sum, an expert at a time."""
    import jax
    import jax.numpy as jnp

    idx, w = router(h, m, c)

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


def _stream(x, dtype):
    """The residual stream as the reference carries it (float32), or
    rounded to ``dtype`` = (exponent bits, mantissa bits) after every
    block: the nearest precision below the configuration's, which
    ``correct`` has to refuse."""
    import jax

    # (exponent bits, mantissa bits); not a pair of converts, which the
    # compiler may drop as excess precision
    return x if dtype is None else jax.lax.reduce_precision(x, *dtype)


def layer(x, lw, c):
    a = x + attention(x, lw, c)
    return _stream(a + ffn(a, lw, c), c.get("stream"))


def constants(config: dict, stream=None) -> tuple:
    """The numbers of the config file the equations use, hashable."""
    rs = config.get("rope_scaling")
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    yarn, mscale = None, 1.0
    if rs:
        yarn = (float(rs["factor"]), float(rs["beta_fast"]),
                float(rs["beta_slow"]),
                float(rs["original_max_position_embeddings"]))
        mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return tuple(sorted({
        "heads": config["num_attention_heads"],
        "kv_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "v_dim": config["v_head_dim"],
        "theta": float(config["rope_theta"]), "yarn": yarn,
        "scale": qk ** -0.5 * mscale * mscale,
        "eps": float(config["rms_norm_eps"]),
        "top_k": config["num_experts_per_tok"],
        "n_group": config["n_group"], "topk_group": config["topk_group"],
        "moe_scale": float(config["routed_scaling_factor"]),
        "first_held": config.get("first_held_expert", 0),
        "stream": stream}.items(), key=lambda kv: kv[0]))


@functools.lru_cache(maxsize=None)
def _jitted(consts):
    import jax

    c = dict(consts)

    def head(x, norm_w, head_w, rows):
        return _mm(_rms(x[rows], norm_w, c["eps"]), head_w)

    return jax.jit(functools.partial(layer, c=c)), jax.jit(head)


def input_rows(weights: dict, config: dict, tokens, images=None,
               stream=None):
    """The language model's float32 input (len(tokens), hidden): each
    token's embedding, the positions that hold ``image_token_id`` taking,
    in order, the rows of the images' ``y``."""
    import jax.numpy as jnp
    import numpy as np

    tokens = np.asarray(tokens)
    x = _f32(weights["embed"][jnp.asarray(tokens)])
    if images:
        y = jnp.concatenate([vision_encode(weights["vision"], config, p, g,
                                           stream=stream)
                             for p, g in images], axis=0)
        # the prompt's placeholders come first; a GENERATED id that
        # happens to be the placeholder's is a token like any other
        at = np.flatnonzero(tokens == config["image_token_id"])
        if at.size < y.shape[0]:
            raise ValueError(f"{at.size} placeholder ids for "
                             f"{y.shape[0]} rows of image embeddings")
        at = at[:y.shape[0]]
        x = x.at[jnp.asarray(at)].set(y)
    return x


def logits_at(weights: dict, config: dict, tokens, rows, images=None,
              stream=None):
    """float32 logits (len(rows), vocab slice) of ONE request: ``tokens``
    (1-D int array: prompt with its placeholder ids, then generated ids),
    ``images`` a list of ``(patches, (rows, cols))``; row i scores token
    i + 1. Padding after the last row of interest is harmless (causal).
    ``stream``: (exponent bits, mantissa bits) the residual stream is
    rounded to after every block, tower and language model (None:
    float32 throughout)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tokens = np.asarray(tokens)
    run, head = _jitted(constants(config, stream))
    with jax.default_matmul_precision("highest"):
        x = input_rows(weights, config, tokens, images, stream)
        x = jnp.pad(x, ((0, -x.shape[0] % PAD_TO), (0, 0)))
        for lw in weights["layers"]:
            x = run(x, lw)
        return head(x, weights["norm"], weights["lm_head"], jnp.asarray(rows))
