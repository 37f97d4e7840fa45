"""Ling-3.0-flash's language model (``inclusionAI/Ling-3.0-flash-VL``): a
hybrid decoder of LINEAR-attention layers with a delta rule (Kimi Delta
Attention, KDA: ``ops/linear_attention.py``) and, one layer in
``layer_group_size``, multi-head latent attention, over sigmoid-routed
experts with a shared one (the layer equations are written out in
``benchmarks/references/ling_linear.py``, the plain reference the tests
hold this file to).

* **KDA**: ``q``, ``k``, ``v`` through a causal depthwise convolution and
  SiLU, ``q`` and ``k`` L2-normalised a head; a decay a KEY CHANNEL a head
  bounded to ``[kda_lower_bound, 0]`` in the log, a write strength a
  head; a matrix state a head (keys x values, float32) that each token
  decays and corrects by a rank-one update; a per-head RMSNorm of the
  read-out times a full-rank sigmoid gate.
* **MLA** as :mod:`.dots_vlm` serves it (one latent of ``kv_lora_rank``
  values and one rotary key a token), WITHOUT a query bottleneck
  (``q_lora_rank`` null), rotary on half-split pairs, no YaRN, and a
  sigmoid gate a head on the attention's output.
* **Layers**: ``layer_kinds`` says which mixer each layer has;
  ``first_k_dense`` leading layers with a dense SwiGLU FFN, then expert
  layers as dots.vlm1's (group-limited sigmoid routing, a shared expert,
  the block told which routed experts it HOLDS).

So a stream owns two kinds of cache: pages of latents for its MLA layers
only (``serving/kvcache.py::make_latent_arena`` of as many sublayers as
there are MLA layers: one in six) and, in its STATE SLOT
(``StateSlots``; the engine declares ``state_slots``), a KDA layer's
``heads x 128 x 128`` float32 state (2 MB) and its three convolutions'
last ``K - 1`` inputs. A decode round moves every live stream's states
once in and once out, in place on the slot arrays
(``pallas_kernels/kda_state_update.py`` through
``ops/linear_attention.py::kda_slot_update``); a prefill chunk at any
offset (``chunked_prefill``) carries state and tails from the slot
through the chunk form of the recurrence.

**Precision of the served forward.** The residual stream, norms, the
convolution, gates, the recurrence with its state and tails are float32;
matrix products take their operands in the weights' dtype (bfloat16 as
served); the latent pages hold the weights' dtype.

Device work is named with ``jax.named_scope``: ``kda.proj``,
``kda.update`` / ``kda.chunk``, ``kda.out`` (``ops/linear_attention.py::
kda_forward``), ``mla.proj``, ``mla.attend``, ``moe.router``,
``moe.experts``, ``moe.shared``, ``ffn.dense`` and the head's ``h1.head``
(Falcon-H1's head function), in layer programs named
``ling_<prefill|decode>_layer_<kda|mla>`` (``_dense`` appended for a layer
with a dense FFN; one run per layer) and ``ling_head``. With telemetry on
the engine records the expert picks of the forward BEFORE the one it has
just dispatched (``telemetry.record_moe_picks`` and the ``moe.picks:``
trace mark): the host reads counts the device has long made, and never
waits for the round in flight.
"""
from __future__ import annotations

import math

import numpy as _np

from ....serving.engine import PagedDecodeEngine
from ...block import HybridBlock
from ... import nn
from .dots_vlm import DotsMLA, _mla_mix
from .falcon_h1 import _embed_rows, _head, _norm
from .glm_moe_dsa import GlmDsaMoE
from .llama import RMSNorm
from .longcat_flash import (PICKS_MARK, LongcatFFN, _dense, _named,
                            _swiglu)

__all__ = ["LingKDA", "LingMLA", "LingLayer", "LingLinearModel",
           "LingLinearDecodeEngine", "ling_linear_tiny"]


class LingKDA(HybridBlock):
    """A KDA mixer over whole sequences (no cache); the weights as
    ``ops/linear_attention.py::kda_forward`` names them."""

    def __init__(self, units, num_heads, head_dim, conv_kernel, lower_bound,
                 safe_gate, eps, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        from .... import initializer as init

        self._attrs = dict(lower_bound=float(lower_bound),
                           safe_gate=bool(safe_gate), eps=float(eps))
        width = num_heads * head_dim
        get = self.params.get
        with self.name_scope():
            self.qkv_weight = get("qkv_weight", init="xavier",
                                  shape=(3 * width, units))
            self.conv_weight = get("conv_weight", init="xavier",
                                   shape=(3 * width, conv_kernel))
            self.f_weight = get("f_weight", init="xavier",
                                shape=(width, units))
            # a decay of ~exp(-5 sigmoid(-3)) = 0.79 a token at a zero
            # projection: a state that holds a few tokens
            self.dt_bias = get("dt_bias", shape=(width,),
                               init=init.Constant(-3.0))
            self.a_log = get("a_log", shape=(num_heads,), init="zeros")
            self.b_weight = get("b_weight", init="xavier",
                                shape=(num_heads, units))
            self.g_weight = get("g_weight", init="xavier",
                                shape=(width, units))
            self.norm_weight = get("norm_weight", init="ones",
                                   shape=(head_dim,))
            self.out_weight = get("out_weight", init="xavier",
                                  shape=(units, width))

    def hybrid_forward(self, F, x, qkv_weight, conv_weight, f_weight,
                       dt_bias, a_log, b_weight, g_weight, norm_weight,
                       out_weight):
        return F._contrib_kda_mixer(
            x, qkv_weight, conv_weight, f_weight, dt_bias, a_log, b_weight,
            g_weight, norm_weight, out_weight, **self._attrs)


class LingMLA(DotsMLA):
    """Dense causal MLA over whole sequences (no cache) without a query
    bottleneck, with a sigmoid gate a head on the attention's output."""

    def __init__(self, units, num_heads, **kw):
        super().__init__(units, num_heads=num_heads, q_lora_rank=None, **kw)
        with self.name_scope():
            self.gate = _dense(num_heads, "gate_")

    def _project_out(self, F, x, att):
        b, l = x.shape[0], x.shape[1]
        gate = F.sigmoid(self.gate(x)).reshape((b, l, self._h, 1))
        return self.out_proj(
            (att.reshape((b, l, self._h, self._v)) * gate).reshape(
                (b, l, self._h * self._v)))


class LingLayer(HybridBlock):
    """Pre-norm mixer (``kind``: ``kda`` or ``mla``) + residual, pre-norm
    FFN + residual; the FFN is dense (``moe`` None) or a routed share +
    shared expert."""

    def __init__(self, units, kind, kda, mla, ffn_hidden_size=None, moe=None,
                 eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if kind not in ("kda", "mla"):
            raise ValueError(f"a layer's mixer is 'kda' or 'mla', not "
                             f"{kind!r}")
        with self.name_scope():
            self.in_norm = RMSNorm(units, eps, prefix="innorm_")
            self.mixer = (
                LingKDA(units, eps=eps, prefix="kda_", **kda)
                if kind == "kda" else
                LingMLA(units, eps=eps, prefix="attn_", **mla))
            self.post_norm = RMSNorm(units, eps, prefix="postnorm_")
            if moe is None:
                self.ffn = LongcatFFN(units, ffn_hidden_size, prefix="ffn_")
            else:
                moe = dict(moe)
                groups = {k: moe.pop(k) for k in ("n_group", "topk_group")}
                self.ffn = GlmDsaMoE(units, prefix="moe_", **moe)
                self.ffn.routed._cfg.update(groups)
        self.kind, self.is_moe = kind, moe is not None

    def hybrid_forward(self, F, x):
        x = x + self.mixer(self.in_norm(x))
        return x + self.ffn(self.post_norm(x))


class LingLinearModel(HybridBlock):
    """``layer_kinds``: each layer's mixer, in order (the published 42
    layers have ``mla`` where ``(i + 1) % 6 == 0`` and ``kda``
    otherwise); ``held_experts`` of the ``n_routed_experts`` live here,
    from ``first_held``; ``vocab_size`` is the slice of the vocabulary
    held here. Defaults are the published widths with this repo's
    benchmark share (64 experts, an eighth of the vocabulary, 1 dense
    layer + one period of 5 KDA and 1 MLA expert layers)."""

    def __init__(self, vocab_size=19648,
                 layer_kinds=("kda", "kda", "kda", "kda", "mla", "kda",
                              "kda"),
                 first_k_dense=1, units=2560, ffn_hidden_size=6144,
                 moe_ffn_hidden_size=768, num_heads=32, head_dim=128,
                 conv_kernel=4, kda_lower_bound=-5.0, kda_safe_gate=True,
                 kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, rope_theta=6e6, rope_interleaved=False,
                 n_routed_experts=512, num_experts_per_tok=8,
                 n_shared_experts=1, n_group=8, topk_group=4,
                 routed_scaling_factor=2.5, first_held=0, held_experts=64,
                 eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        layer_kinds = tuple(str(k) for k in layer_kinds)
        kda = dict(num_heads=num_heads, head_dim=head_dim,
                   conv_kernel=conv_kernel, lower_bound=kda_lower_bound,
                   safe_gate=kda_safe_gate)
        mla = dict(num_heads=num_heads, kv_lora_rank=kv_lora_rank,
                   qk_nope_head_dim=qk_nope_head_dim,
                   qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
                   rope_theta=rope_theta, interleaved=rope_interleaved)
        moe = dict(hidden_size=moe_ffn_hidden_size, n_routed=n_routed_experts,
                   top_k=num_experts_per_tok, scale=routed_scaling_factor,
                   n_shared=n_shared_experts, first_held=first_held,
                   held=held_experts, n_group=n_group, topk_group=topk_group)
        # what the pure cache-aware forward needs beside the weights
        self._decode_cfg = {
            "vocab_size": int(vocab_size), "layer_kinds": layer_kinds,
            "first_k_dense": int(first_k_dense), "units": int(units),
            "num_heads": int(num_heads), "head_dim": int(head_dim),
            "conv_kernel": int(conv_kernel),
            "kda_lower_bound": float(kda_lower_bound),
            "kda_safe_gate": bool(kda_safe_gate),
            "kv_lora_rank": int(kv_lora_rank),
            "nope": int(qk_nope_head_dim), "rope": int(qk_rope_head_dim),
            "v_dim": int(v_head_dim), "rope_theta": float(rope_theta),
            "rope_interleaved": bool(rope_interleaved), "eps": float(eps),
            "scale": 1.0 / math.sqrt(qk_nope_head_dim + qk_rope_head_dim),
            "n_routed": int(n_routed_experts),
            "top_k": int(num_experts_per_tok), "n_group": int(n_group),
            "topk_group": int(topk_group),
            "moe_scale": float(routed_scaling_factor),
            "first_held": int(first_held), "held": int(held_experts),
        }
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = []
            for i, kind in enumerate(layer_kinds):
                blk = LingLayer(units, kind, kda, mla, ffn_hidden_size,
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

    def decode_engine(self, pool) -> "LingLinearDecodeEngine":
        """The seam ``serving.Server`` asks for ``submit_generate``: a
        latent page arena an MLA layer over ``pool`` and the pool's state
        slots for the KDA layers, on the device and in the dtype of the
        parameters."""
        return LingLinearDecodeEngine.build(self, pool)


# ---------------------------------------------------------------------------
# serving: the cache-aware pure forward and its engine
# ---------------------------------------------------------------------------

def _kda_mix(x, p, tails, states, positions, lengths, slots, cfg):
    """What a KDA mixer adds to the stream ``x`` (B, L, U) from the rows'
    slots, and both slot arrays advanced to each row's last real token. A
    padded position is an identity step; a padding row (slot 0) changes
    scratch only; a row at position 0 starts a stream, whatever its slot
    holds. One token a stream updates the states in place on the slot
    array; a chunk gathers its rows' states, runs the chunk form and
    scatters them back."""
    import jax.numpy as jnp

    from ....ops.linear_attention import (kda_chunk_scan, kda_forward,
                                          kda_slot_update)
    from ....ops.ssm import conv_tail

    l = x.shape[1]
    real = (positions >= 0) & (positions < lengths[:, None])
    fresh = positions[:, 0] == 0
    tail = jnp.where(fresh[:, None, None], 0, tails[slots])

    def scan(q, k, v, g, beta, slot_states):
        if l > 1:
            state = jnp.where(fresh[:, None, None, None], 0,
                              slot_states[slots])
            o, state = kda_chunk_scan(q, k, v, g, beta, state)
            return o, slot_states.at[slots].set(state)
        o, slot_states = kda_slot_update(
            slot_states, slots, fresh, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
            beta[:, 0])
        return o[:, None], slot_states

    out, ext, states = kda_forward(
        _norm(x, p["in_norm"], cfg["eps"]), p, tail, states, real, scan=scan,
        lower_bound=cfg["kda_lower_bound"], safe_gate=cfg["kda_safe_gate"],
        eps=cfg["eps"])
    tail = conv_tail(ext, jnp.sum(real, axis=1, dtype=jnp.int32),
                     cfg["conv_kernel"])
    return out, tails.at[slots].set(tail.astype(tails.dtype)), states, real


def _ffn(x, lp, real, cfg, moe):
    """The FFN half's contribution to the float32 stream ``x`` and the
    expert layer's pick counts (zeros in a dense layer)."""
    import jax
    import jax.numpy as jnp

    from ....ops.contrib import moe_routed_experts

    b, l, _ = x.shape
    h = _norm(x, lp["post_norm"], cfg["eps"])
    if not moe:
        with jax.named_scope("ffn.dense"):
            h = h.astype(lp["ffn_gate_up"].dtype)
            return (_swiglu(h, lp["ffn_gate_up"], lp["ffn_down"]),
                    jnp.zeros((4,), jnp.int32))
    m = lp["moe"]
    h = h.astype(m["router"].dtype)
    routed, picks = moe_routed_experts(
        h.reshape(b * l, -1), m["router"], m["router_bias"], m["gate_up"],
        m["down"], real.reshape(-1), first_held=cfg["first_held"],
        n_routed=cfg["n_routed"], top_k=cfg["top_k"],
        scale=cfg["moe_scale"], score="sigmoid", renormalize=True,
        n_group=cfg["n_group"], topk_group=cfg["topk_group"])
    with jax.named_scope("moe.shared"):
        out = routed.reshape(b, l, -1).astype(jnp.float32) + _swiglu(
            h, lp["shared_gate_up"], lp["shared_down"])
    return out, picks


def _kda_layer(x, lp, tails, states, positions, lengths, slots, *, cfg, moe):
    """One KDA layer, cache-aware and pure: ``x`` (B, L, U) float32 at
    ``positions``; the layer's convolution tails and states in and out."""
    out, tails, states, real = _kda_mix(x, lp, tails, states, positions,
                                        lengths, slots, cfg)
    x = x + out
    out, picks = _ffn(x, lp, real, cfg, moe)
    return x + out, tails, states, picks


def _mla_layer(x, lp, arena, positions, page_table, lengths, *, cfg, moe):
    """One MLA layer, cache-aware and pure: the layer's latent arena in
    and out."""
    out, arena, real = _mla_mix(x, lp, arena, positions, page_table, lengths,
                                cfg)
    x = x + out
    out, picks = _ffn(x, lp, real, cfg, moe)
    return x + out, arena, picks


class LingLinearDecodeEngine(PagedDecodeEngine):
    """The decode engine over one :class:`LingLinearModel`: the MLA
    layers' pages AND the KDA layers' slot state on one page table and
    one slot a stream.

    ``arenas``: a latent arena an MLA layer, in the layers' order
    (``(pages, page, 640)``: ``[c | k_r]`` in whole lane tiles), so a
    stream's pages cost one layer in ``layer_group_size`` of what an
    all-attention model's would. ``slot_arrays``: per KDA layer, in the
    layers' order, the convolutions' tail ``(slots, K - 1, 3 heads x
    head_dim)`` and the state ``(slots, heads, head_dim, head_dim)``, both
    float32; defrag never touches them.

    A forward is the embedding lookup, ONE program a kind of layer (mixer
    x FFN: a signature compiles each kind once whatever the depth) run
    once per layer of its kind and, where a row of the dispatch ends its
    prompt or decodes, the head. ``last_counts`` holds the expert layers'
    picks of the last forward as device arrays; with telemetry on the
    picks of the forward BEFORE it are read back and recorded, so that
    the host never waits for the forward it has just dispatched."""

    family = "ling_linear"
    chunked_prefill = True
    state_slots = True
    last_counts = ()
    _pending_picks = None

    def _extract(self, model, w):
        def layer(blk):
            m = blk.mixer
            out = {"in_norm": w(blk.in_norm.weight),
                   "post_norm": w(blk.post_norm.weight)}
            if blk.kind == "kda":
                out.update(
                    qkv=w(m.qkv_weight), conv=w(m.conv_weight),
                    f=w(m.f_weight), dt_b=w(m.dt_bias), a_log=w(m.a_log),
                    b=w(m.b_weight), g=w(m.g_weight),
                    o_norm=w(m.norm_weight), o=w(m.out_weight))
            else:
                out.update(
                    q=w(m.q_proj.weight), kva=w(m.kv_a.weight),
                    kvnorm=w(m.kv_norm.weight), kvb=w(m.kvb_weight),
                    gate=w(m.gate.weight), out=w(m.out_proj.weight))
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
        import jax
        import jax.numpy as jnp

        from ....serving.kvcache import make_latent_arena

        cfg = self.cfg
        s = pool.state_slots.n_slots
        heads, d = cfg["num_heads"], cfg["head_dim"]

        def zeros(shape):
            return jax.device_put(
                jnp.zeros(shape, jnp.float32, device=self._device),
                self._device)

        n_kda = cfg["layer_kinds"].count("kda")
        self.slot_arrays = {
            "tails": [zeros((s, cfg["conv_kernel"] - 1, 3 * heads * d))
                      for _ in range(n_kda)],
            "states": [zeros((s, heads, d, d)) for _ in range(n_kda)],
        }
        # bytes of recurrent state a live stream holds in its slot
        self.state_bytes_per_stream = 4 * n_kda * (
            heads * d * d + (cfg["conv_kernel"] - 1) * 3 * heads * d)
        return list(make_latent_arena(
            cfg["layer_kinds"].count("mla"), pool,
            cfg["kv_lora_rank"] + cfg["rope"], self.dtype,
            device=self._device))

    def _record_picks(self, counts, phase):
        """The picks of a forward whose device work is long done, into
        telemetry and as a host event of a running profiler trace (so
        that a traced slice carries its rounds' picks)."""
        import jax

        from .... import telemetry

        held, zero, absent, touched = (
            int(v) for v in _np.sum(_np.asarray(counts), axis=0))
        telemetry.record_moe_picks(held, zero, absent, touched, len(counts),
                                   phase=phase)
        with jax.profiler.TraceAnnotation(
                f"{PICKS_MARK}{phase}:{held}:{zero}:{absent}:{touched}"
                f":{len(counts)}"):
            pass

    def _run(self, b, l, w_pages, tokens, positions, page_table, lengths,
             slots, final):
        import jax

        from .... import telemetry

        phase = "decode" if l == 1 else "prefill"
        sig = (b, l, w_pages)
        embed_w, layers, norm_w, head_w = self._params
        cfg, st = self.cfg, self.slot_arrays
        reads = (lengths > positions[:, 0]) & (final | (l == 1))
        tokens, positions, page_table, lengths, slots = jax.device_put(
            (tokens, positions, page_table, lengths, slots), self._device)
        x = self._fn("embed", *sig, lambda: (_named(
            _embed_rows, "ling_embed", multiplier=1.0), ()))(embed_w, tokens)

        def program(kind, moe):
            name = kind + ("" if moe else "_dense")
            fn, donate = ((_kda_layer, (2, 3)) if kind == "kda"
                          else (_mla_layer, (2,)))
            return self._fn(name, *sig, lambda: (_named(
                fn, f"ling_{phase}_layer_{name}", cfg=cfg, moe=moe), donate))

        counts = []
        i_kda = i_mla = 0
        for lp in layers:
            moe = "moe" in lp
            if "qkv" in lp:
                x, st["tails"][i_kda], st["states"][i_kda], picks = program(
                    "kda", moe)(x, lp, st["tails"][i_kda],
                                st["states"][i_kda], positions, lengths,
                                slots)
                i_kda += 1
            else:
                x, self.arenas[i_mla], picks = program("mla", moe)(
                    x, lp, self.arenas[i_mla], positions, page_table,
                    lengths)
                i_mla += 1
            if moe:
                counts.append(picks)
        self.last_counts = tuple(counts)
        if not reads.any():
            # no row ends its prompt here: nobody reads the ids
            out = _np.zeros((b,), _np.int32), None
        else:
            out = self._fn("head", *sig, lambda: (_named(
                _head, "ling_head", eps=cfg["eps"], multiplier=1.0), ()))(
                    x, norm_w, head_w, positions, lengths)
        if telemetry._state.enabled:
            telemetry.set_state_bytes_live(
                self.pool.state_slots.stats()["used"]
                * self.state_bytes_per_stream)
            # a forward late: the counts read are the device's finished
            # work, the forward just dispatched is not waited for
            pending, self._pending_picks = (self._pending_picks,
                                            (self.last_counts, phase))
            if pending is not None and pending[0]:
                self._record_picks(*pending)
        return out


def ling_linear_tiny(**kwargs):
    """Test-sized config of the same kinds: a dense KDA layer, then KDA,
    MLA and KDA expert layers; 4 heads of 16, 16 routed experts in 4
    groups of which 2 stay, top-2, 4 held, a shared expert."""
    cfg = dict(vocab_size=128, layer_kinds=("kda", "kda", "mla", "kda"),
               first_k_dense=1, units=32, ffn_hidden_size=64,
               moe_ffn_hidden_size=16, num_heads=4, head_dim=16,
               conv_kernel=4, kv_lora_rank=8, qk_nope_head_dim=8,
               qk_rope_head_dim=8, v_head_dim=8, rope_theta=1e4,
               n_routed_experts=16, num_experts_per_tok=2,
               n_shared_experts=1, n_group=4, topk_group=2,
               routed_scaling_factor=2.5, first_held=0, held_experts=4)
    cfg.update(kwargs)
    return LingLinearModel(**cfg)
