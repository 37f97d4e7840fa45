"""GLM-5 (``zai-org/GLM-5``, ``model_type: glm_moe_dsa``): multi-head
latent attention whose queries each attend to a LEARNED SELECTION of the
cached tokens, and a sigmoid-routed mixture of experts with a shared
expert (the layer equations are written out in
``benchmarks/references/glm_moe_dsa.py``, the plain reference the tests
hold this file to).

* **MLA** as in :mod:`.longcat_flash` (one latent of ``kv_lora_rank``
  values and one rotary key a token, shared by every head), without
  that model's scale factors.
* **The indexer**: ``index_n_heads`` narrow heads score every cached
  token for every query from one cached key of ``index_head_dim`` values
  a token; the ``index_topk`` best are picked exactly and attention runs
  over the picked tokens only (``ops/attention.py``:
  ``dsa_index_scores``, ``dsa_select``, ``mla_sparse_attend``). The
  serving cache therefore holds TWO rows a token a layer on ONE page
  table: the latent row and the index key.
* **Layers**: ``first_k_dense`` leading layers with a dense SwiGLU FFN,
  then expert layers: a router of ``n_routed_experts`` sigmoid scores,
  ``num_experts_per_tok`` picks by score + correction bias, weights
  renormalised over the picks and scaled, plus a shared expert every
  token takes. As in LongCat the block is told which routed experts it
  HOLDS and leaves out what the absent ones would add.

The decode engine prefills a prompt of any length a chunk at a time
(``chunked_prefill``): a forward writes its rows into both arenas and
attends through the page table at whatever ``positions`` it is given, so
a chunk at offset 30k and a prompt's first chunk are one executable.

Device work is named with ``jax.named_scope``: ``mla.proj``,
``dsa.indexer``, ``dsa.select``, ``dsa.attend``, ``moe.router``,
``moe.experts``, ``moe.shared``, ``ffn.dense`` and ``lm_head``, in layer
programs named ``glm_dsa_<prefill|decode>_<dense|moe>`` (one run per
layer). With telemetry on the engine records, after every forward, the
keys scored and selected (``telemetry.record_dsa_keys``) and the expert
picks (``telemetry.record_moe_picks`` and the ``moe.picks:`` trace mark,
as LongCat's engine writes it; ``zero`` is 0).
"""
from __future__ import annotations

import math

from ....serving.engine import PagedDecodeEngine
from ...block import HybridBlock
from ... import nn
from .llama import RMSNorm
from .longcat_flash import (PICKS_MARK, LongcatFFN, LongcatMoE, _dense,
                            _embed, _head, _named, _swiglu)

__all__ = ["GlmDsaAttention", "GlmDsaMoE", "GlmDsaLayer", "GlmDsaModel",
           "GlmDsaDecodeEngine", "glm_moe_dsa_tiny"]

_INDEX_NORM_EPS = 1e-6


class GlmDsaAttention(HybridBlock):
    """MLA with the sparse-attention indexer, over whole sequences (no
    cache)."""

    def __init__(self, units, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 index_n_heads, index_head_dim, index_topk, rope_theta=1e6,
                 eps=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._h, self._r = num_heads, kv_lora_rank
        self._nope, self._rope, self._v = (qk_nope_head_dim,
                                           qk_rope_head_dim, v_head_dim)
        self._j, self._d, self._topk = (index_n_heads, index_head_dim,
                                        index_topk)
        self._theta = rope_theta
        qk = qk_nope_head_dim + qk_rope_head_dim
        self._scale = 1.0 / math.sqrt(qk)
        with self.name_scope():
            self.q_a = _dense(q_lora_rank, "qa_")
            self.q_norm = RMSNorm(q_lora_rank, eps, prefix="qnorm_")
            self.q_b = _dense(num_heads * qk, "qb_")
            self.kv_a = _dense(kv_lora_rank + qk_rope_head_dim, "kva_")
            self.kv_norm = RMSNorm(kv_lora_rank, eps, prefix="kvnorm_")
            self.kvb_weight = self.params.get(
                "kvb_weight", init="xavier", shape=(
                    num_heads * (qk_nope_head_dim + v_head_dim),
                    kv_lora_rank))
            self.out_proj = _dense(units, "out_")
            # the indexer: queries from the attention's own c_q
            self.index_q = _dense(index_n_heads * index_head_dim, "iq_")
            self.index_k = _dense(index_head_dim, "ik_")
            self.index_k_norm = nn.LayerNorm(
                epsilon=_INDEX_NORM_EPS, in_channels=index_head_dim,
                prefix="iknorm_")
            self.index_w = _dense(index_n_heads, "iw_")

    def _rotate(self, F, x, begin, end):
        """Interleaved rotary on ``x[..., begin:end]`` of (B, L, H, D)."""
        d = x.shape[-1]
        parts = []
        if begin:
            parts.append(F.slice_axis(x, axis=-1, begin=0, end=begin))
        parts.append(F._contrib_rope(
            F.slice_axis(x, axis=-1, begin=begin, end=end),
            theta=self._theta, interleaved=True))
        if end < d:
            parts.append(F.slice_axis(x, axis=-1, begin=end, end=d))
        return F.concat(*parts, dim=-1) if len(parts) > 1 else parts[0]

    def hybrid_forward(self, F, x, kvb_weight):
        b, l = x.shape[0], x.shape[1]
        h, nope, rope, r = self._h, self._nope, self._rope, self._r
        c_q = self.q_norm(self.q_a(x))
        q = self._rotate(F, self.q_b(c_q).reshape((b, l, h, nope + rope)),
                         nope, nope + rope)
        ckr = self.kv_a(x)
        latent = self.kv_norm(F.slice_axis(ckr, axis=-1, begin=0, end=r))
        k_rope = self._rotate(
            F, F.slice_axis(ckr, axis=-1, begin=r,
                            end=r + rope).reshape((b, l, 1, rope)),
            0, rope).reshape((b, l, rope))
        q_i = self._rotate(
            F, self.index_q(c_q).reshape((b, l, self._j, self._d)), 0, rope)
        k_i = self._rotate(
            F, self.index_k_norm(self.index_k(x)).reshape(
                (b, l, 1, self._d)), 0, rope).reshape((b, l, self._d))
        w_i = self.index_w(x) * (1.0 / math.sqrt(self._j * self._d))
        att = F._contrib_dsa_mla_attention(
            q, latent, k_rope, kvb_weight, q_i, w_i, k_i, nope_dim=nope,
            v_dim=self._v, scale=self._scale, top_k=self._topk)
        return self.out_proj(att)


class GlmDsaMoE(HybridBlock):
    """This chip's share of the routed experts (sigmoid scores,
    renormalised weights, no zero-compute experts) plus the shared
    expert, which every chip computes for its own tokens."""

    def __init__(self, units, hidden_size, n_routed, top_k, scale,
                 n_shared=1, first_held=0, held=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.routed = LongcatMoE(units, hidden_size, n_routed, 0, top_k,
                                     scale, first_held, held,
                                     prefix="routed_")
            self.routed._cfg.update(score="sigmoid", renormalize=True)
            self.shared = LongcatFFN(units, n_shared * hidden_size,
                                     prefix="shared_")

    def hybrid_forward(self, F, x):
        return self.routed(x) + self.shared(x)


class GlmDsaLayer(HybridBlock):
    """Pre-norm attention + residual, pre-norm FFN + residual; the FFN
    is dense (``moe`` None) or :class:`GlmDsaMoE`."""

    def __init__(self, units, attn, ffn_hidden_size=None, moe=None, eps=1e-5,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.in_norm = RMSNorm(units, eps, prefix="innorm_")
            self.attn = GlmDsaAttention(units, eps=eps, prefix="attn_",
                                        **attn)
            self.post_norm = RMSNorm(units, eps, prefix="postnorm_")
            self.ffn = (LongcatFFN(units, ffn_hidden_size, prefix="ffn_")
                        if moe is None else
                        GlmDsaMoE(units, prefix="moe_", **moe))
        self.is_moe = moe is not None

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.in_norm(x))
        return x + self.ffn(self.post_norm(x))


class GlmDsaModel(HybridBlock):
    """``held_experts`` of the ``n_routed_experts`` live here, from
    ``first_held``; ``vocab_size`` is the slice of the vocabulary held
    here. Defaults are the published widths with this repo's benchmark
    share (16 experts, an eighth of the vocabulary, 1 dense + 4 expert
    layers)."""

    def __init__(self, vocab_size=19360, num_layers=5, first_k_dense=1,
                 units=6144, ffn_hidden_size=12288,
                 moe_ffn_hidden_size=2048, num_heads=64, q_lora_rank=2048,
                 kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
                 v_head_dim=256, index_n_heads=32, index_head_dim=128,
                 index_topk=2048, n_routed_experts=256,
                 num_experts_per_tok=8, n_shared_experts=1,
                 routed_scaling_factor=2.5, first_held=0, held_experts=16,
                 rope_theta=1e6, eps=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        attn = dict(num_heads=num_heads, q_lora_rank=q_lora_rank,
                    kv_lora_rank=kv_lora_rank,
                    qk_nope_head_dim=qk_nope_head_dim,
                    qk_rope_head_dim=qk_rope_head_dim,
                    v_head_dim=v_head_dim, index_n_heads=index_n_heads,
                    index_head_dim=index_head_dim, index_topk=index_topk,
                    rope_theta=rope_theta)
        moe = dict(hidden_size=moe_ffn_hidden_size, n_routed=n_routed_experts,
                   top_k=num_experts_per_tok, scale=routed_scaling_factor,
                   n_shared=n_shared_experts, first_held=first_held,
                   held=held_experts)
        # what the pure cache-aware forward needs beside the weights
        self._decode_cfg = {
            "vocab_size": int(vocab_size), "num_layers": int(num_layers),
            "first_k_dense": int(first_k_dense), "units": int(units),
            "num_heads": int(num_heads), "q_lora_rank": int(q_lora_rank),
            "kv_lora_rank": int(kv_lora_rank),
            "nope": int(qk_nope_head_dim), "rope": int(qk_rope_head_dim),
            "v_dim": int(v_head_dim), "index_heads": int(index_n_heads),
            "index_dim": int(index_head_dim), "index_topk": int(index_topk),
            "rope_theta": float(rope_theta), "eps": float(eps),
            "scale": 1.0 / math.sqrt(qk_nope_head_dim + qk_rope_head_dim),
            "n_routed": int(n_routed_experts),
            "top_k": int(num_experts_per_tok),
            "moe_scale": float(routed_scaling_factor),
            "first_held": int(first_held), "held": int(held_experts),
        }
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = []
            for i in range(num_layers):
                blk = GlmDsaLayer(
                    units, attn, ffn_hidden_size,
                    None if i < first_k_dense else moe, eps,
                    prefix=f"layer{i}_")
                self.blocks.append(blk)
                self.register_child(blk, f"layer{i}")
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.lm_head = _dense(vocab_size, "lm_head_")

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        for blk in self.blocks:
            x = blk(x)
        return self.lm_head(self.norm(x))

    def decode_engine(self, pool) -> "GlmDsaDecodeEngine":
        """The seam ``serving.Server`` asks for ``submit_generate``: a
        paged latent cache and a paged index-key cache over ``pool``, on
        the device and in the dtype of the parameters."""
        return GlmDsaDecodeEngine.build(self, pool)


# ---------------------------------------------------------------------------
# serving: the cache-aware pure forward and its engine
# ---------------------------------------------------------------------------

def _scatter_rows(arena, rows, page, offset):
    """``rows`` (N, w) into ``arena`` (pages, page, >= w) at the N
    (page, offset) slots, zero-padded to the arena's lane width."""
    import jax.numpy as jnp

    pad = arena.shape[2] - rows.shape[1]
    return arena.at[page, offset].set(jnp.pad(rows, ((0, 0), (0, pad))))


def _index_and_cache(x, p, arena, iarena, positions, page_table, lengths,
                     cfg):
    """The first half of a layer's attention: the MLA query, this
    forward's rows written into both arenas, and the index score of every
    slot the page tables reach for every query. ``x`` (B, L, U) at
    ``positions``, of which row ``b`` has ``lengths[b]`` tokens in the
    cache once this forward's are written (a position at or beyond it, or
    below 0, is padding and goes to the scratch page). Returns the query
    (B, L, H, nope + rope), both arenas, the scores (B, L, T), which of
    them a query may select (causal, inside the stream's length, a real
    query) and which queries are real."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import (_gather_pages, dsa_index_scores, rms_norm,
                                   rope_at)
    from ....ops.nn import layer_norm

    b, l, _ = x.shape
    eps, theta = cfg["eps"], cfg["rope_theta"]
    nope, rope, r = cfg["nope"], cfg["rope"], cfg["kv_lora_rank"]
    heads, n_j, d_j = cfg["num_heads"], cfg["index_heads"], cfg["index_dim"]
    f32 = jnp.float32
    ps = arena.shape[1]
    real = (positions >= 0) & (positions < lengths[:, None])
    page_of = jnp.clip(positions // ps, 0, page_table.shape[1] - 1)
    page = jnp.where(real, jnp.take_along_axis(page_table, page_of, axis=1),
                     0).reshape(-1)                     # padding -> scratch
    offset = (positions % ps).reshape(-1)

    def rot(v):
        return rope_at(v, positions, theta=theta, interleaved=True)

    h = rms_norm(x, p["in_norm"], eps=eps)
    with jax.named_scope("mla.proj"):
        c_q = rms_norm(h @ p["qa"].T, p["qnorm"], eps=eps)
        q = (c_q @ p["qb"].T).reshape(b, l, heads, nope + rope)
        q = jnp.concatenate([q[..., :nope], rot(q[..., nope:])], axis=-1)
        ckr = h @ p["kva"].T
        latent = rms_norm(ckr[..., :r], p["kvnorm"], eps=eps)
        k_rope = rot(ckr[..., r:].reshape(b, l, 1, rope)).reshape(b, l, rope)
        arena = _scatter_rows(
            arena, jnp.concatenate([latent, k_rope], axis=-1).reshape(
                b * l, -1), page, offset)
    with jax.named_scope("dsa.indexer"):
        q_i = (c_q @ p["iq"].T).reshape(b, l, n_j, d_j)
        q_i = jnp.concatenate([rot(q_i[..., :rope]), q_i[..., rope:]],
                              axis=-1)
        k_i = layer_norm(h @ p["ik"].T, p["ik_gain"], p["ik_bias"],
                         eps=_INDEX_NORM_EPS)
        k_i = jnp.concatenate(
            [rot(k_i[..., :rope].reshape(b, l, 1, rope)).reshape(b, l, rope),
             k_i[..., rope:]], axis=-1)
        w_i = jnp.einsum("blu,ju->blj", h, p["iw"],
                         preferred_element_type=f32) \
            * f32(1.0 / math.sqrt(n_j * d_j))
        iarena = _scatter_rows(iarena, k_i.reshape(b * l, -1), page, offset)
        scores = dsa_index_scores(
            q_i, w_i, _gather_pages(iarena, page_table)[..., :d_j], lengths)
    with jax.named_scope("dsa.select"):
        key_pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)
        valid = (real[:, :, None]
                 & (key_pos[None, None, :] <= positions[:, :, None])
                 & (key_pos[None, None, :] < lengths[:, None, None]))
    return q, arena, iarena, scores, valid, real


def _sparse_attention(x, p, arena, iarena, positions, page_table, lengths,
                      cfg):
    """The attention half of a layer, cache-aware and pure: every real
    query scores the index keys of its stream's cache, this forward's
    among them (:func:`_index_and_cache`), selects and attends: one form
    for a decode step, a prompt's first chunk and a chunk at any offset.
    Returns the residual stream after attention, both arenas, (keys
    scored, keys selected) over the real queries, and the real
    queries."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import dsa_select, mla_sparse_attend

    q, arena, iarena, scores, valid, real = _index_and_cache(
        x, p, arena, iarena, positions, page_table, lengths, cfg)
    with jax.named_scope("dsa.select"):
        selected = dsa_select(scores, valid, lengths,
                              top_k=cfg["index_topk"])
        counts = jnp.stack([jnp.sum(valid, dtype=jnp.int32),
                            jnp.sum(selected, dtype=jnp.int32)])
    with jax.named_scope("dsa.attend"):
        att = mla_sparse_attend(
            q, arena, page_table, selected, p["kvb"], lengths,
            nope_dim=cfg["nope"],
            v_dim=cfg["v_dim"], scale=cfg["scale"], top_k=cfg["index_topk"])
    with jax.named_scope("mla.proj"):
        x = x + att @ p["out"].T
    return x, arena, iarena, counts, real


def _layer_forward(x, lp, arena, iarena, positions, page_table, lengths, *,
                   cfg, moe):
    """One layer (dense FFN, or ``moe``: routed share + shared expert).
    Returns the output, both arenas, the indexer's (scored, selected)
    and the expert layer's pick counts (zeros in a dense layer)."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import rms_norm
    from ....ops.contrib import moe_routed_experts

    b, l, _ = x.shape
    a, arena, iarena, keys, real = _sparse_attention(
        x, lp, arena, iarena, positions, page_table, lengths, cfg)
    h = rms_norm(a, lp["post_norm"], eps=cfg["eps"])
    if not moe:
        with jax.named_scope("ffn.dense"):
            out = a + _swiglu(h, lp["ffn_gate_up"], lp["ffn_down"])
        return out, arena, iarena, keys, jnp.zeros((4,), jnp.int32)
    m = lp["moe"]
    routed, picks = moe_routed_experts(
        h.reshape(b * l, -1), m["router"], m["router_bias"], m["gate_up"],
        m["down"], real.reshape(-1), first_held=cfg["first_held"],
        n_routed=cfg["n_routed"], top_k=cfg["top_k"],
        scale=cfg["moe_scale"], score="sigmoid", renormalize=True)
    with jax.named_scope("moe.shared"):
        out = a + routed.reshape(b, l, -1) + _swiglu(
            h, lp["shared_gate_up"], lp["shared_down"])
    return out, arena, iarena, keys, picks


class GlmDsaDecodeEngine(PagedDecodeEngine):
    """The decode engine over one :class:`GlmDsaModel`: per layer a
    latent arena (``arenas[2 * li]``) and an index-key arena
    (``arenas[2 * li + 1]``) on ONE page table and pool, under its own
    identity at the ``serving_decode`` cache site.

    A forward is ``2 + num_layers`` dispatches: the embedding lookup,
    TWO layer programs (dense, expert), each compiled once per signature
    and run once per layer of its kind, and the head. They are named
    ``glm_dsa_<decode|prefill>_<dense|moe>``. After every forward
    ``last_counts`` holds, per layer, the indexer's (scored, selected)
    and the expert layer's picks as device arrays; with telemetry on
    they are read back and recorded."""

    family = "glm_moe_dsa"
    chunked_prefill = True
    last_counts = ()

    def _extract(self, model, w):
        def layer(blk):
            a = blk.attn
            out = {"in_norm": w(blk.in_norm.weight), "qa": w(a.q_a.weight),
                   "qnorm": w(a.q_norm.weight), "qb": w(a.q_b.weight),
                   "kva": w(a.kv_a.weight), "kvnorm": w(a.kv_norm.weight),
                   "kvb": w(a.kvb_weight), "out": w(a.out_proj.weight),
                   "iq": w(a.index_q.weight), "ik": w(a.index_k.weight),
                   "ik_gain": w(a.index_k_norm.gamma),
                   "ik_bias": w(a.index_k_norm.beta),
                   "iw": w(a.index_w.weight),
                   "post_norm": w(blk.post_norm.weight)}
            if blk.is_moe:
                r, s = blk.ffn.routed, blk.ffn.shared
                out.update(
                    moe={"router": w(r.router_weight),
                         "router_bias": w(r.router_bias),
                         "gate_up": w(r.gate_up_weight),
                         "down": w(r.down_weight)},
                    shared_gate_up=w(s.gate_up.weight),
                    shared_down=w(s.down.weight))
            else:
                out.update(ffn_gate_up=w(blk.ffn.gate_up.weight),
                           ffn_down=w(blk.ffn.down.weight))
            return out

        return (w(model.embed.weight),
                tuple(layer(blk) for blk in model.blocks),
                w(model.norm.weight), w(model.lm_head.weight))

    def _make_arenas(self, pool):
        from ....serving.kvcache import make_latent_arena

        n = self.cfg["num_layers"]
        latent = make_latent_arena(
            n, pool, self.cfg["kv_lora_rank"] + self.cfg["rope"],
            self.dtype, device=self._device)
        index = make_latent_arena(n, pool, self.cfg["index_dim"],
                                  self.dtype, device=self._device)
        return [a for pair in zip(latent, index) for a in pair]

    def _run(self, b, l, w_pages, tokens, positions, page_table, lengths):
        import jax
        import numpy as _np

        from .... import telemetry

        sig = (b, l, w_pages)
        phase = "decode" if l == 1 else "prefill"
        embed_w, layers, norm_w, head_w = self._params
        # one transfer of each host array for all the dispatches
        tokens, positions, page_table, lengths = jax.device_put(
            (tokens, positions, page_table, lengths), self._device)
        x = self._fn("embed", *sig, lambda: (_embed, ()))(embed_w, tokens)

        def program(kind):
            return self._fn(kind, *sig, lambda: (_named(
                _layer_forward, f"glm_dsa_{phase}_{kind}", cfg=self.cfg,
                moe=kind == "moe"), (2, 3)))

        counts = []
        for li, lp in enumerate(layers):
            kind = "moe" if "moe" in lp else "dense"
            x, self.arenas[2 * li], self.arenas[2 * li + 1], keys, picks = \
                program(kind)(x, lp, self.arenas[2 * li],
                              self.arenas[2 * li + 1], positions, page_table,
                              lengths)
            counts.append((keys, picks))
        picked = self._fn("head", *sig, lambda: (_named(
            _head, "glm_dsa_head", eps=self.cfg["eps"]), ()))(
                x, norm_w, head_w, positions, lengths)
        self.last_counts = tuple(counts)
        if telemetry._state.enabled:
            keys, picks = (_np.asarray([c[i] for c in counts]).sum(axis=0)
                           for i in (0, 1))
            telemetry.record_dsa_keys(int(keys[0]), int(keys[1]), phase)
            held, zero, absent, touched = (int(v) for v in picks)
            n_moe = self.cfg["num_layers"] - self.cfg["first_k_dense"]
            telemetry.record_moe_picks(held, zero, absent, touched, n_moe,
                                       phase=phase)
            # the same counts as a host event of a running profiler
            # trace, so that a traced slice carries its own rounds' picks
            with jax.profiler.TraceAnnotation(
                    f"{PICKS_MARK}{phase}:{held}:{zero}:{absent}:{touched}"
                    f":{n_moe}"):
                pass
        return picked


def glm_moe_dsa_tiny(**kwargs):
    """Test-sized config of the same kinds: one dense and two expert
    layers, 8 routed experts of which 2 are held, top-2, a shared
    expert, 2 index heads that pick 8 cached tokens a query."""
    cfg = dict(vocab_size=128, num_layers=3, first_k_dense=1, units=32,
               ffn_hidden_size=64, moe_ffn_hidden_size=16, num_heads=4,
               q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
               qk_rope_head_dim=4, v_head_dim=8, index_n_heads=2,
               index_head_dim=8, index_topk=8, n_routed_experts=8,
               num_experts_per_tok=2, n_shared_experts=1,
               routed_scaling_factor=2.5, first_held=0, held_experts=2,
               rope_theta=1e6)
    cfg.update(kwargs)
    return GlmDsaModel(**cfg)
