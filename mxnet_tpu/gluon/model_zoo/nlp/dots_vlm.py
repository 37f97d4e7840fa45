"""dots.vlm1 (``rednote-hilab/dots.vlm1.inst``, ``model_type: dots_vlm``):
a NaViT vision tower (:mod:`..vision.navit`) in front of a
DeepSeek-V3-shaped language model (the layer equations are written out in
``benchmarks/references/dots_vlm.py``, the plain reference the tests hold
this file to).

* **MLA** as in :mod:`.glm_moe_dsa`, DENSE (every query attends to every
  earlier token of its stream: no indexer), with YaRN's rotary
  frequencies and its softmax-scale correction
  (``ops.attention.yarn_inv_freq`` / ``yarn_mscale``).
* **Layers**: ``first_k_dense`` leading dense SwiGLU layers, then expert
  layers whose sigmoid router picks GROUP-LIMITED
  (``ops.contrib.moe_routed_experts(n_group=, topk_group=)``), plus a
  shared expert; as in LongCat and GLM-5 the block is told which routed
  experts it HOLDS.
* **Images**: the language model's input at a position that holds
  ``image_token_id`` is a row of the tower's output, not a row of the
  embedding. The decode engine takes such rows through the
  ``embeds=`` / ``embed_rows=`` seam of
  :class:`~mxnet_tpu.serving.engine.PagedDecodeEngine` and declares the
  tower as its ``vision`` (:class:`..vision.navit.NavitEncodeEngine`),
  which ``serving.Server`` runs before a request's first prefill chunk.

The decode engine serves through ONE latent paged cache at any
``positions`` (``chunked_prefill``): a forward writes its rows into the
arena, then a chunk (L > 1) whose rows all start at position 0 attends
over its OWN fresh latents, expanded once, with the causal flash forward
(``ops.attention.mla_fresh_attention``); a chunk at an offset attends to
the stream's cached latents expanded a key block at a time
(``ops.attention.mla_sparse_attend`` with the causal-valid mask where
GLM-5 has its selection); the program picks between the two by its
``positions`` (``lax.cond``: one program a signature); and a decode step
(L = 1) attends in the absorbed form (``ops.attention.mla_paged_decode``:
the Pallas kernel on the TPU).

Device work is named with ``jax.named_scope``: ``mla.proj``,
``mla.attend``, ``moe.router``, ``moe.experts``, ``moe.shared``,
``ffn.dense`` and ``lm_head``, in layer programs named
``dots_lm_<prefill|decode>_<dense|moe>`` (one run per layer); the
tower's are ``dots_vit_encode_<bucket>``. With telemetry on the engine
records the expert picks after every forward
(``telemetry.record_moe_picks`` and the ``moe.picks:`` trace mark, as
LongCat's engine writes it; ``zero`` is 0).
"""
from __future__ import annotations

import math

from ....serving.engine import PagedDecodeEngine
from ...block import HybridBlock
from ... import nn
from ..vision.navit import NavitEncodeEngine, NavitTower
from .glm_moe_dsa import GlmDsaMoE, _scatter_rows
from .llama import RMSNorm
from .longcat_flash import (PICKS_MARK, LongcatFFN, LongcatMLA, _dense,
                            _embed, _head, _named, _swiglu)

__all__ = ["DotsMLA", "DotsVlmLayer", "DotsVlmModel", "DotsVlmDecodeEngine",
           "dots_vlm_tiny"]


class DotsMLA(LongcatMLA):
    """Dense causal MLA over whole sequences (no cache): LongCat's
    parameters without its scale factors, YaRN's rotary (``yarn`` None:
    plain), on interleaved pairs or (``interleaved`` false) half-split
    ones."""

    def __init__(self, units, yarn=None, softmax_scale=None,
                 interleaved=True, **kw):
        super().__init__(units, **kw)
        self._yarn = yarn
        self._interleaved = bool(interleaved)
        if softmax_scale is not None:
            self._scale = softmax_scale

    def _project_out(self, F, x, att):
        """The attention's heads (B, L, H * v) back to the stream."""
        return self.out_proj(att)

    def hybrid_forward(self, F, x, kvb_weight):
        b, l = x.shape[0], x.shape[1]
        h, nope, rope, r = self._h, self._nope, self._rope, self._r
        rot = dict(theta=self._theta, interleaved=self._interleaved,
                   yarn=self._yarn)
        q = self._query(x).reshape((b, l, h, nope + rope))
        q = F.concat(F.slice_axis(q, axis=-1, begin=0, end=nope),
                     F._contrib_rope(F.slice_axis(
                         q, axis=-1, begin=nope, end=nope + rope), **rot),
                     dim=-1)
        ckr = self.kv_a(x)
        latent = self.kv_norm(F.slice_axis(ckr, axis=-1, begin=0, end=r))
        k_rope = F._contrib_rope(
            F.slice_axis(ckr, axis=-1, begin=r,
                         end=r + rope).reshape((b, l, 1, rope)),
            **rot).reshape((b, l, rope))
        att = F._contrib_mla_attention(q, latent, k_rope, kvb_weight,
                                       nope_dim=nope, v_dim=self._v,
                                       scale=self._scale)
        return self._project_out(F, x, att)


class DotsVlmLayer(HybridBlock):
    """Pre-norm attention + residual, pre-norm FFN + residual; the FFN
    is dense (``moe`` None) or a routed share + shared expert."""

    def __init__(self, units, attn, ffn_hidden_size=None, moe=None, eps=1e-6,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.in_norm = RMSNorm(units, eps, prefix="innorm_")
            self.attn = DotsMLA(units, eps=eps, prefix="attn_", **attn)
            self.post_norm = RMSNorm(units, eps, prefix="postnorm_")
            if moe is None:
                self.ffn = LongcatFFN(units, ffn_hidden_size, prefix="ffn_")
            else:
                groups = {k: moe.pop(k) for k in ("n_group", "topk_group")}
                self.ffn = GlmDsaMoE(units, prefix="moe_", **moe)
                self.ffn.routed._cfg.update(groups)
        self.is_moe = moe is not None

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.in_norm(x))
        return x + self.ffn(self.post_norm(x))


class DotsVlmModel(HybridBlock):
    """``held_experts`` of the ``n_routed_experts`` live here, from
    ``first_held``; ``vocab_size`` is the slice of the vocabulary held
    here; ``vision``: the tower's keyword arguments
    (:class:`..vision.navit.NavitTower`; its ``out_dim`` is ``units``).
    Defaults are the published widths with this repo's benchmark share
    (16 experts, an eighth of the vocabulary, 1 dense + 4 expert layers,
    the tower whole). ``forward(tokens)`` is the language model over
    token ids alone; images enter through the decode engine."""

    def __init__(self, vocab_size=16160, num_layers=5, first_k_dense=1,
                 units=7168, ffn_hidden_size=18432,
                 moe_ffn_hidden_size=2048, num_heads=128, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, n_routed_experts=256,
                 num_experts_per_tok=8, n_shared_experts=1, n_group=8,
                 topk_group=4, routed_scaling_factor=2.5, first_held=0,
                 held_experts=16, rope_theta=1e4,
                 yarn=(40.0, 32.0, 1.0, 4096.0), yarn_mscale_all_dim=1.0,
                 eps=1e-6, image_token_id=1, vision=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        from ....ops.attention import yarn_mscale

        yarn = tuple(float(v) for v in yarn) if yarn else None
        mscale = yarn_mscale(yarn[0], yarn_mscale_all_dim) if yarn else 1.0
        scale = mscale * mscale / math.sqrt(qk_nope_head_dim
                                            + qk_rope_head_dim)
        attn = dict(num_heads=num_heads, q_lora_rank=q_lora_rank,
                    kv_lora_rank=kv_lora_rank,
                    qk_nope_head_dim=qk_nope_head_dim,
                    qk_rope_head_dim=qk_rope_head_dim,
                    v_head_dim=v_head_dim, rope_theta=rope_theta, yarn=yarn,
                    softmax_scale=scale)
        moe = dict(hidden_size=moe_ffn_hidden_size, n_routed=n_routed_experts,
                   top_k=num_experts_per_tok, scale=routed_scaling_factor,
                   n_shared=n_shared_experts, first_held=first_held,
                   held=held_experts, n_group=n_group, topk_group=topk_group)
        if not 0 <= image_token_id < vocab_size:
            raise ValueError(f"image_token_id {image_token_id} is not an id "
                             f"of the {vocab_size}-row vocabulary slice")
        # what the pure cache-aware forward needs beside the weights
        self._decode_cfg = {
            "vocab_size": int(vocab_size), "num_layers": int(num_layers),
            "first_k_dense": int(first_k_dense), "units": int(units),
            "num_heads": int(num_heads), "q_lora_rank": int(q_lora_rank),
            "kv_lora_rank": int(kv_lora_rank),
            "nope": int(qk_nope_head_dim), "rope": int(qk_rope_head_dim),
            "v_dim": int(v_head_dim), "rope_theta": float(rope_theta),
            "yarn": yarn, "eps": float(eps), "scale": float(scale),
            "n_routed": int(n_routed_experts),
            "top_k": int(num_experts_per_tok), "n_group": int(n_group),
            "topk_group": int(topk_group),
            "moe_scale": float(routed_scaling_factor),
            "first_held": int(first_held), "held": int(held_experts),
            "image_token_id": int(image_token_id),
        }
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = []
            for i in range(num_layers):
                blk = DotsVlmLayer(
                    units, dict(attn), ffn_hidden_size,
                    None if i < first_k_dense else dict(moe), eps,
                    prefix=f"layer{i}_")
                self.blocks.append(blk)
                self.register_child(blk, f"layer{i}")
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.lm_head = _dense(vocab_size, "lm_head_")
            self.vision = NavitTower(out_dim=units, prefix="vision_",
                                     **(vision or {}))

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        for blk in self.blocks:
            x = blk(x)
        return self.lm_head(self.norm(x))

    def decode_engine(self, pool) -> "DotsVlmDecodeEngine":
        """The seam ``serving.Server`` asks for ``submit_generate``: a
        paged latent cache over ``pool`` and the tower as the engine's
        ``vision``, on the device and in the dtype of the parameters."""
        return DotsVlmDecodeEngine.build(self, pool)


# ---------------------------------------------------------------------------
# serving: the cache-aware pure forward and its engine
# ---------------------------------------------------------------------------

def _mix_embeds(x, embeds, embed_rows):
    """``x`` (B, L, U) with the rows where ``embed_rows`` (B, L) is not
    negative replaced by ``embeds[b, embed_rows[b, l]]``."""
    import jax.numpy as jnp

    rows = jnp.take_along_axis(
        embeds, jnp.maximum(embed_rows, 0)[:, :, None], axis=1)
    return jnp.where((embed_rows >= 0)[:, :, None], rows.astype(x.dtype), x)


def _mla_mix(x, p, arena, positions, page_table, lengths, cfg):
    """What MLA adds to the residual stream ``x`` (B, L, U) from its
    normed self (in the weights' dtype, whatever the stream's),
    cache-aware and pure: this forward's latent rows go into
    the arena (a position at or beyond a row's ``lengths``, or below 0,
    is padding and goes to the scratch page), then every real query
    attends to its stream's cache up to its own position: a chunk (L >
    1) whose every row is at ``arange(L)`` over the rows it has just
    computed (a real query's keys are all real and all its own
    dispatch's; a padding query's output is never read), any other chunk
    through the page table. The variants a model's weights and ``cfg``
    name: no query bottleneck (``p`` has ``q`` and not ``qa | qnorm |
    qb``), rotary on half-split pairs (``cfg["rope_interleaved"]``
    false), no YaRN (``cfg`` has no ``yarn``), a sigmoid gate a head
    on the attention's output (``p`` has ``gate`` (H, U)). Returns ``W_o
    att``, the arena and the real queries."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import (mla_fresh_attention, mla_paged_decode,
                                   mla_sparse_attend, rms_norm, rope_at)

    b, l, _ = x.shape
    eps, nope, rope, r = (cfg["eps"], cfg["nope"], cfg["rope"],
                          cfg["kv_lora_rank"])
    ps = arena.shape[1]
    real = (positions >= 0) & (positions < lengths[:, None])
    page_of = jnp.clip(positions // ps, 0, page_table.shape[1] - 1)
    page = jnp.where(real, jnp.take_along_axis(page_table, page_of, axis=1),
                     0).reshape(-1)                     # padding -> scratch
    offset = (positions % ps).reshape(-1)

    def rot(v):
        return rope_at(v, positions, theta=cfg["rope_theta"],
                       interleaved=cfg.get("rope_interleaved", True),
                       yarn=cfg.get("yarn"))

    h = rms_norm(x, p["in_norm"], eps=eps).astype(p["kva"].dtype)
    with jax.named_scope("mla.proj"):
        if "qa" in p:
            c_q = rms_norm(h @ p["qa"].T, p["qnorm"], eps=eps)
            q = c_q @ p["qb"].T
        else:
            q = h @ p["q"].T
        q = q.reshape(b, l, cfg["num_heads"], nope + rope)
        q = jnp.concatenate([q[..., :nope], rot(q[..., nope:])], axis=-1)
        ckr = h @ p["kva"].T
        latent = rms_norm(ckr[..., :r], p["kvnorm"], eps=eps)
        k_rope = rot(ckr[..., r:].reshape(b, l, 1, rope)).reshape(b, l, rope)
        arena = _scatter_rows(
            arena, jnp.concatenate([latent, k_rope], axis=-1).reshape(
                b * l, -1), page, offset)
    kw = dict(nope_dim=nope, v_dim=cfg["v_dim"], scale=cfg["scale"])
    with jax.named_scope("mla.attend"):
        if l == 1:
            att = mla_paged_decode(q[:, 0], arena, page_table, lengths,
                                   p["kvb"], **kw)[:, None]
        else:
            def fresh():
                return mla_fresh_attention(q, latent, k_rope, p["kvb"], **kw)

            def walk():
                key_pos = jnp.arange(page_table.shape[1] * ps,
                                     dtype=jnp.int32)
                valid = (real[:, :, None]
                         & (key_pos[None, None, :] <= positions[:, :, None])
                         & (key_pos[None, None, :] < lengths[:, None, None]))
                return mla_sparse_attend(q, arena, page_table, valid,
                                         p["kvb"], lengths, top_k=0, **kw)

            # every row at arange(L): all a query may see is this
            # dispatch's own rows, so nothing is read back from the arena
            att = jax.lax.cond(
                jnp.all(positions == jnp.arange(l, dtype=positions.dtype)),
                fresh, walk)
    with jax.named_scope("mla.proj"):
        if "gate" in p:
            gate = jax.nn.sigmoid((h @ p["gate"].T).astype(jnp.float32))
            att = (att.reshape(b, l, cfg["num_heads"], -1)
                   * gate[..., None].astype(att.dtype)).reshape(att.shape)
        return att @ p["out"].T, arena, real


def _attention(x, p, arena, positions, page_table, lengths, cfg):
    """The attention half of a layer: :func:`_mla_mix` of the stream,
    added to it. Returns the residual stream after attention, the arena
    and the real queries."""
    out, arena, real = _mla_mix(x, p, arena, positions, page_table, lengths,
                                cfg)
    return x + out, arena, real


def _layer_forward(x, lp, arena, positions, page_table, lengths, *, cfg,
                   moe):
    """One layer (dense FFN, or ``moe``: routed share + shared expert).
    Returns the output, the arena and the expert layer's pick counts
    (zeros in a dense layer)."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import rms_norm
    from ....ops.contrib import moe_routed_experts

    b, l, _ = x.shape
    a, arena, real = _attention(x, lp, arena, positions, page_table, lengths,
                                cfg)
    h = rms_norm(a, lp["post_norm"], eps=cfg["eps"])
    if not moe:
        with jax.named_scope("ffn.dense"):
            out = a + _swiglu(h, lp["ffn_gate_up"], lp["ffn_down"])
        return out, arena, jnp.zeros((4,), jnp.int32)
    m = lp["moe"]
    routed, picks = moe_routed_experts(
        h.reshape(b * l, -1), m["router"], m["router_bias"], m["gate_up"],
        m["down"], real.reshape(-1), first_held=cfg["first_held"],
        n_routed=cfg["n_routed"], top_k=cfg["top_k"],
        scale=cfg["moe_scale"], score="sigmoid", renormalize=True,
        n_group=cfg["n_group"], topk_group=cfg["topk_group"])
    with jax.named_scope("moe.shared"):
        out = a + routed.reshape(b, l, -1) + _swiglu(
            h, lp["shared_gate_up"], lp["shared_down"])
    return out, arena, picks


class DotsVlmDecodeEngine(PagedDecodeEngine):
    """The decode engine over one :class:`DotsVlmModel`: a latent arena a
    layer, under its own identity at the ``serving_decode`` cache site,
    and the tower as ``vision``.

    A forward is ``2 + num_layers`` dispatches (the embedding lookup, two
    layer programs run once per layer of their kind, the head), and one
    more, ``embed_mix``, where the rows carry embeddings
    (``takes_embeds``): a forward over ids alone runs the programs it
    would run without the seam. A forward of more than one position
    counts in ``mxnet_serving_prefill_dispatch_total{path}``: ``fresh``
    where every row's positions are ``arange(L)`` (the layers attend over
    the dispatch's own latents), ``gather`` otherwise."""

    family = "dots_vlm"
    chunked_prefill = True
    takes_embeds = True
    last_counts = ()

    def __init__(self, model, pool):
        super().__init__(model, pool)
        self.vision = NavitEncodeEngine(model.vision)
        self.image_token_id = self.cfg["image_token_id"]

    def refresh_params(self, model) -> None:
        super().refresh_params(model)
        if getattr(self, "vision", None) is not None:
            self.vision.refresh_params()

    def _extract(self, model, w):
        def layer(blk):
            a = blk.attn
            out = {"in_norm": w(blk.in_norm.weight), "qa": w(a.q_a.weight),
                   "qnorm": w(a.q_norm.weight), "qb": w(a.q_b.weight),
                   "kva": w(a.kv_a.weight), "kvnorm": w(a.kv_norm.weight),
                   "kvb": w(a.kvb_weight), "out": w(a.out_proj.weight),
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

        return make_latent_arena(
            self.cfg["num_layers"], pool,
            self.cfg["kv_lora_rank"] + self.cfg["rope"], self.dtype,
            device=self._device)

    def _run(self, b, l, w_pages, tokens, positions, page_table, lengths,
             embeds=None, embed_rows=None):
        import jax
        import numpy as _np

        from .... import telemetry

        sig = (b, l, w_pages)
        phase = "decode" if l == 1 else "prefill"
        if l > 1 and telemetry._state.enabled:
            # which way the layer programs' `lax.cond` goes
            fresh = bool((positions == _np.arange(l)).all())
            telemetry.record_prefill_dispatch("fresh" if fresh else "gather")
        embed_w, layers, norm_w, head_w = self._params
        # one transfer of each host array for all the dispatches
        tokens, positions, page_table, lengths = jax.device_put(
            (tokens, positions, page_table, lengths), self._device)
        x = self._fn("embed", *sig, lambda: (_embed, ()))(embed_w, tokens)
        if embeds is not None:
            x = self._fn("embed_mix", *sig, lambda: (_named(
                _mix_embeds, "dots_lm_embed_mix"), ()))(
                    x, embeds, jax.device_put(embed_rows, self._device))

        def program(kind):
            return self._fn(kind, *sig, lambda: (_named(
                _layer_forward, f"dots_lm_{phase}_{kind}", cfg=self.cfg,
                moe=kind == "moe"), (2,)))

        counts = []
        for li, lp in enumerate(layers):
            kind = "moe" if "moe" in lp else "dense"
            x, self.arenas[li], picks = program(kind)(
                x, lp, self.arenas[li], positions, page_table, lengths)
            counts.append(picks)
        picked = self._fn("head", *sig, lambda: (_named(
            _head, "dots_lm_head", eps=self.cfg["eps"]), ()))(
                x, norm_w, head_w, positions, lengths)
        self.last_counts = tuple(counts)
        if telemetry._state.enabled:
            held, zero, absent, touched = (
                int(v) for v in _np.asarray(counts).sum(axis=0))
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


def dots_vlm_tiny(**kwargs):
    """Test-sized config of the same kinds: one dense and two expert
    layers, 16 routed experts in 4 groups of which 2 stay, top-2, 4 held,
    a shared expert, YaRN over an original length of 32, a two-layer
    tower."""
    cfg = dict(vocab_size=128, num_layers=3, first_k_dense=1, units=32,
               ffn_hidden_size=64, moe_ffn_hidden_size=16, num_heads=4,
               q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
               qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=16,
               num_experts_per_tok=2, n_shared_experts=1, n_group=4,
               topk_group=2, routed_scaling_factor=2.5, first_held=0,
               held_experts=4, rope_theta=1e4, yarn=(4.0, 4.0, 1.0, 32.0),
               image_token_id=1,
               vision=dict(embed_dim=32, num_layers=2, num_heads=2,
                           intermediate_size=48))
    cfg.update(kwargs)
    return DotsVlmModel(**cfg)
