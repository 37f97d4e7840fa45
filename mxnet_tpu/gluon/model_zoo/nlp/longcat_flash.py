"""LongCat-Flash: multi-head latent attention (MLA) and a
shortcut-connected mixture of experts with zero-compute experts
(``meituan-longcat/LongCat-Flash-Chat``; the layer equations are written
out in ``benchmarks/references/longcat_flash.py``, the plain reference
the tests hold this file to).

Every layer is a DOUBLE layer: two MLA attentions, two dense SwiGLU FFNs
and one routed expert layer whose output, computed from the first half's
post-attention activations, is added after the second half — the expert
layer runs beside the second attention and FFN, not between them.

* **MLA**: queries through a low-rank bottleneck (``q_lora_rank``); keys
  and values of all heads are up-projections of ONE latent of
  ``kv_lora_rank`` values per token, and one rotary key of
  ``qk_rope_head_dim`` values is shared by every head. The serving cache
  holds ``(latent, rotated k_rope)`` per token per attention sublayer
  (:func:`~mxnet_tpu.serving.kvcache.make_latent_arena`). Prefill expands
  keys and values from the latent; decode folds the up-projections into
  the query and the output and attends in the latent space.
* **Experts**: ONE router over ``n_routed_experts`` SwiGLU experts and
  ``zero_expert_num`` identity experts, ``moe_topk`` picks a token, weights
  ``routed_scaling_factor * softmax`` without renormalisation. The block
  is told which routed experts it HOLDS (``first_held``, ``held_experts``):
  it routes over all of them, computes the held experts' part and the
  zero experts' part, and leaves out what the absent experts (on other
  chips of an expert-parallel deployment) would add. On one chip the
  layer runs without its exchange.

Device work is named with ``jax.named_scope``: ``mla.prefill``,
``mla.decode``, ``moe.router``, ``moe.experts``, ``moe.zero`` (the three
inside ``ops/contrib.py::moe_routed_experts``), ``ffn.dense`` and
``lm_head``; the decode engine's double-layer program is named
``longcat_prefill`` or ``longcat_decode`` (one run per layer), which a
profiler trace shows as the first element of every operation's name.
With telemetry on the engine also writes, after every forward, one host
annotation ``moe.picks:<phase>:<held>:<zero>:<absent>:<touched>:<layers>``
(the counts ``telemetry.record_moe_picks`` takes, summed over the
``layers`` expert layers of the forward) into a running profiler trace.
"""
from __future__ import annotations

import math

from ....serving.engine import PagedDecodeEngine, greedy_pick
from ...block import HybridBlock
from ... import nn
from .llama import RMSNorm

__all__ = ["LongcatFFN", "LongcatMLA", "LongcatMoE", "LongcatDoubleLayer",
           "LongcatFlashModel", "LongcatFlashDecodeEngine",
           "longcat_flash_tiny"]


def _dense(units, prefix):
    # input width deferred to the first forward or ``set_data``, as in the
    # Llama blocks: a server's builder then never allocates an
    # initializer's copy of a weight it is about to overwrite
    return nn.Dense(units, flatten=False, use_bias=False, prefix=prefix)


class LongcatFFN(HybridBlock):
    """SwiGLU, gate and up in one matmul."""

    def __init__(self, units, hidden_size, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.gate_up = _dense(2 * hidden_size, "gateup_")
            self.down = _dense(units, "down_")

    def hybrid_forward(self, F, x):
        gate, up = F.split(self.gate_up(x), num_outputs=2, axis=-1)
        return self.down(F.Activation(gate, act_type="silu") * up)


class LongcatMLA(HybridBlock):
    """Multi-head latent attention over whole sequences (no cache).
    ``q_lora_rank`` None: no query bottleneck, the queries are ONE
    projection of the input (``q_proj``)."""

    def __init__(self, units, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=1e7, eps=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._h = num_heads
        self._nope, self._rope, self._v = (qk_nope_head_dim,
                                           qk_rope_head_dim, v_head_dim)
        self._r = kv_lora_rank
        self._theta = rope_theta
        # mla_scale_q_lora / mla_scale_kv_lora of the published config;
        # without a bottleneck there is nothing to rescale
        self._s_q = math.sqrt(units / q_lora_rank) if q_lora_rank else 1.0
        self._s_kv = math.sqrt(units / kv_lora_rank)
        qk = qk_nope_head_dim + qk_rope_head_dim
        self._scale = 1.0 / math.sqrt(qk)
        with self.name_scope():
            if q_lora_rank:
                self.q_a = _dense(q_lora_rank, "qa_")
                self.q_norm = RMSNorm(q_lora_rank, eps, prefix="qnorm_")
                self.q_b = _dense(num_heads * qk, "qb_")
            else:
                self.q_proj = _dense(num_heads * qk, "q_")
            self.kv_a = _dense(kv_lora_rank + qk_rope_head_dim, "kva_")
            self.kv_norm = RMSNorm(kv_lora_rank, eps, prefix="kvnorm_")
            # used as a weight, never as a layer: prefill expands keys and
            # values with it, decode folds it into query and output
            self.kvb_weight = self.params.get(
                "kvb_weight", init="xavier", shape=(
                    num_heads * (qk_nope_head_dim + v_head_dim),
                    kv_lora_rank))
            self.out_proj = _dense(units, "out_")

    def _query(self, x):
        """Every head's query, (B, L, H * (nope + rope)), before rotary:
        through the bottleneck where there is one."""
        if hasattr(self, "q_proj"):
            return self.q_proj(x)
        return self.q_b(self.q_norm(self.q_a(x)))

    def hybrid_forward(self, F, x, kvb_weight):
        b, l = x.shape[0], x.shape[1]
        h, nope, rope = self._h, self._nope, self._rope
        q = self._query(x) * self._s_q
        q = q.reshape((b, l, h, nope + rope))
        q_rope = F._contrib_rope(
            F.slice_axis(q, axis=-1, begin=nope, end=nope + rope),
            theta=self._theta, interleaved=True)
        q = F.concat(F.slice_axis(q, axis=-1, begin=0, end=nope), q_rope,
                     dim=-1)
        ckr = self.kv_a(x)
        latent = self.kv_norm(
            F.slice_axis(ckr, axis=-1, begin=0, end=self._r)) * self._s_kv
        k_rope = F._contrib_rope(
            F.slice_axis(ckr, axis=-1, begin=self._r,
                         end=self._r + rope).reshape((b, l, 1, rope)),
            theta=self._theta, interleaved=True).reshape((b, l, rope))
        att = F._contrib_mla_attention(q, latent, k_rope, kvb_weight,
                                       nope_dim=nope, v_dim=self._v,
                                       scale=self._scale)
        return self.out_proj(att)


class LongcatMoE(HybridBlock):
    """This chip's share of the routed expert layer: the router over all
    ``n_routed + n_zero`` outputs, the ``held`` SwiGLU experts
    ``first_held ..`` as stacked ``(held, in, out)`` weights, the
    identity experts for free. ``router_bias`` is the selection bias the
    published model keeps as a buffer."""

    def __init__(self, units, hidden_size, n_routed, n_zero, top_k,
                 scale, first_held=0, held=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        held = n_routed - first_held if held is None else held
        if not 0 <= first_held <= first_held + held <= n_routed:
            raise ValueError(
                f"held experts {first_held}..{first_held + held - 1} are "
                f"not among the {n_routed} routed experts")
        self._cfg = dict(first_held=first_held, n_routed=n_routed,
                         n_zero=n_zero, top_k=top_k, scale=float(scale))
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(n_routed + n_zero, units),
                init="xavier")
            self.router_bias = self.params.get(
                "router_bias", shape=(n_routed + n_zero,), init="zeros",
                grad_req="null")
            self.gate_up_weight = self.params.get(
                "gate_up_weight", shape=(held, 0, 2 * hidden_size),
                init="xavier", allow_deferred_init=True)
            self.down_weight = self.params.get(
                "down_weight", shape=(held, hidden_size, 0),
                init="xavier", allow_deferred_init=True)

    def _infer_param_shapes(self, x, *rest):
        held, _, two_h = self.gate_up_weight.shape
        u = x.shape[-1]
        self.gate_up_weight._finish_deferred_init((held, u, two_h))
        self.down_weight._finish_deferred_init((held, two_h // 2, u))

    def hybrid_forward(self, F, x, router_weight, router_bias,
                       gate_up_weight, down_weight):
        b, l, u = x.shape
        out, _counts = F._contrib_moe_routed_experts(
            x.reshape((b * l, u)), router_weight,
            router_bias, gate_up_weight, down_weight, **self._cfg)
        return out.reshape((b, l, u))


class LongcatDoubleLayer(HybridBlock):
    def __init__(self, units, ffn_hidden_size, expert_hidden_size, mla,
                 moe, eps=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.in_norms, self.attns, self.post_norms, self.ffns = \
                [], [], [], []
            for i in (0, 1):
                for group, blk in (
                        (self.in_norms,
                         RMSNorm(units, eps, prefix=f"innorm{i}_")),
                        (self.attns, LongcatMLA(units, eps=eps,
                                                prefix=f"attn{i}_", **mla)),
                        (self.post_norms,
                         RMSNorm(units, eps, prefix=f"postnorm{i}_")),
                        (self.ffns, LongcatFFN(units, ffn_hidden_size,
                                               prefix=f"ffn{i}_"))):
                    group.append(blk)
                    self.register_child(blk, blk.prefix.rstrip("_"))
            self.moe = LongcatMoE(units, expert_hidden_size, prefix="moe_",
                                  **moe)

    def hybrid_forward(self, F, x):
        shortcut = None
        for i in (0, 1):
            a = x + self.attns[i](self.in_norms[i](x))
            h = self.post_norms[i](a)
            if i == 0:
                shortcut = self.moe(h)
            x = a + self.ffns[i](h)
        return x + shortcut


class LongcatFlashModel(HybridBlock):
    """``held_experts`` of the ``n_routed_experts`` live here, from
    ``first_held``; ``vocab_size`` is the slice of the vocabulary held
    here. Defaults are the published widths with this repo's benchmark
    share (16 experts, an eighth of the vocabulary, 4 double layers)."""

    def __init__(self, vocab_size=16384, num_layers=4, units=6144,
                 ffn_hidden_size=12288, expert_ffn_hidden_size=2048,
                 num_heads=64, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 n_routed_experts=512, zero_expert_num=256, moe_topk=12,
                 routed_scaling_factor=6.0, first_held=0, held_experts=16,
                 rope_theta=1e7, eps=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        mla = dict(num_heads=num_heads, q_lora_rank=q_lora_rank,
                   kv_lora_rank=kv_lora_rank,
                   qk_nope_head_dim=qk_nope_head_dim,
                   qk_rope_head_dim=qk_rope_head_dim,
                   v_head_dim=v_head_dim, rope_theta=rope_theta)
        moe = dict(n_routed=n_routed_experts, n_zero=zero_expert_num,
                   top_k=moe_topk, scale=routed_scaling_factor,
                   first_held=first_held, held=held_experts)
        qk = qk_nope_head_dim + qk_rope_head_dim
        # what the pure cache-aware forward needs beside the weights
        self._decode_cfg = {
            "vocab_size": int(vocab_size), "num_layers": int(num_layers),
            "units": int(units), "num_heads": int(num_heads),
            "q_lora_rank": int(q_lora_rank),
            "kv_lora_rank": int(kv_lora_rank),
            "nope": int(qk_nope_head_dim), "rope": int(qk_rope_head_dim),
            "v_dim": int(v_head_dim), "rope_theta": float(rope_theta),
            "eps": float(eps), "scale": 1.0 / math.sqrt(qk),
            "n_routed": int(n_routed_experts),
            "n_zero": int(zero_expert_num), "top_k": int(moe_topk),
            "moe_scale": float(routed_scaling_factor),
            "first_held": int(first_held), "held": int(held_experts),
        }
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = []
            for i in range(num_layers):
                blk = LongcatDoubleLayer(units, ffn_hidden_size,
                                         expert_ffn_hidden_size, mla, moe,
                                         eps, prefix=f"layer{i}_")
                self.blocks.append(blk)
                self.register_child(blk, f"layer{i}")
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.lm_head = _dense(vocab_size, "lm_head_")

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        for blk in self.blocks:
            x = blk(x)
        return self.lm_head(self.norm(x))

    def decode_engine(self, pool) -> "LongcatFlashDecodeEngine":
        """The seam ``serving.Server`` asks for ``submit_generate``:
        a paged LATENT cache over ``pool`` (a
        :class:`~mxnet_tpu.serving.kvcache.PagePool`), on the device and
        in the dtype of the parameters."""
        return LongcatFlashDecodeEngine.build(self, pool)


# ---------------------------------------------------------------------------
# serving: the cache-aware pure forward and its engine
# ---------------------------------------------------------------------------

PICKS_MARK = "moe.picks:"


def _mla_inputs(h, p, positions, cfg):
    """Query (B, L, H, nope + rope), latent ``c'`` (B, L, R) and the
    shared rotated key (B, L, rope) of one attention sublayer."""
    import jax.numpy as jnp

    from ....ops.attention import rms_norm, rope_at

    b, l, u = h.shape
    nope, rope, r = cfg["nope"], cfg["rope"], cfg["kv_lora_rank"]
    eps, theta = cfg["eps"], cfg["rope_theta"]
    s_q = math.sqrt(u / cfg["q_lora_rank"])
    s_kv = math.sqrt(u / r)
    f32 = jnp.float32
    q = rms_norm(h @ p["qa"].T, p["qnorm"], eps=eps) @ p["qb"].T
    # the scales are applied in float32: sqrt(12) rounded to bf16 would
    # be a systematic 0.13% error on every latent
    q = (q.astype(f32) * s_q).astype(q.dtype).reshape(
        b, l, cfg["num_heads"], nope + rope)
    q = jnp.concatenate(
        [q[..., :nope],
         rope_at(q[..., nope:], positions, theta=theta, interleaved=True)],
        axis=-1)
    ckr = h @ p["kva"].T
    latent = (rms_norm(ckr[..., :r], p["kvnorm"], eps=eps).astype(f32)
              * s_kv).astype(ckr.dtype)
    k_rope = rope_at(ckr[..., r:].reshape(b, l, 1, rope), positions,
                     theta=theta, interleaved=True).reshape(b, l, rope)
    return q, latent, k_rope


def _swiglu(x, gate_up, down):
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(x @ gate_up.T, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ down.T


def _layer_forward(x, lp, arena0, arena1, positions, page_table, lengths,
                   *, cfg):
    """One double layer, cache-aware and pure; the latent-cache sibling
    of a ``llama._paged_forward`` layer with the same padding contract.
    ``x`` (B, L, U) at ``positions``; each attention sublayer's
    ``(c', k_rope)`` is scattered into its arena (positions at or beyond
    a row's ``lengths`` go to the scratch page) and attended — L > 1:
    expanded keys and values of the row's own tokens (a prompt has
    nothing cached before it); L == 1: the absorbed form through the
    page table. Returns the layer's output, both arenas, and the expert
    layer's pick counts."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import (mla_attention, mla_paged_decode,
                                   rms_norm)
    from ....ops.contrib import moe_routed_experts

    eps = cfg["eps"]
    b, l, _ = x.shape
    ps = arena0.shape[1]
    attn_kw = dict(nope_dim=cfg["nope"], v_dim=cfg["v_dim"],
                   scale=cfg["scale"])
    # a padding row of a decode batch has length 0 and position -1
    real = (positions >= 0) & (positions < lengths[:, None])
    page_of = jnp.clip(positions // ps, 0, page_table.shape[1] - 1)
    page = jnp.where(real, jnp.take_along_axis(page_table, page_of, axis=1),
                     0).reshape(-1)                     # padding -> scratch
    offset = (positions % ps).reshape(-1)
    arenas = [arena0, arena1]
    shortcut = counts = None
    for i in (0, 1):
        p = lp["sub"][i]
        h = rms_norm(x, p["in_norm"], eps=eps)
        q, latent, k_rope = _mla_inputs(h, p, positions, cfg)
        row = jnp.concatenate([latent, k_rope], axis=-1).reshape(b * l, -1)
        pad = arenas[i].shape[2] - row.shape[1]         # lane padding
        arenas[i] = arenas[i].at[page, offset].set(
            jnp.pad(row, ((0, 0), (0, pad))))
        if l == 1:
            with jax.named_scope("mla.decode"):
                att = mla_paged_decode(q[:, 0], arenas[i], page_table,
                                       lengths, p["kvb"], **attn_kw)[:, None]
        else:
            with jax.named_scope("mla.prefill"):
                att = mla_attention(q, latent, k_rope, p["kvb"], **attn_kw)
        a = x + att @ p["out"].T
        h = rms_norm(a, p["post_norm"], eps=eps)
        if i == 0:
            m = lp["moe"]
            shortcut, counts = moe_routed_experts(
                h.reshape(b * l, -1), m["router"], m["router_bias"],
                m["gate_up"], m["down"], real.reshape(-1),
                first_held=cfg["first_held"], n_routed=cfg["n_routed"],
                n_zero=cfg["n_zero"], top_k=cfg["top_k"],
                scale=cfg["moe_scale"])
        with jax.named_scope("ffn.dense"):
            x = a + _swiglu(h, p["ffn_gate_up"], p["ffn_down"])
    return x + shortcut.reshape(b, l, -1), arenas[0], arenas[1], counts


def _embed(embed_w, tokens):
    import jax.numpy as jnp

    return jnp.take(embed_w, tokens, axis=0)


def _head(x, norm_w, head_w, positions, lengths, *, eps):
    """The greedy token id and the float32 logits of the last REAL input
    row (prefill: lengths - 1; decode L = 1: always row 0)."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import rms_norm

    with jax.named_scope("lm_head"):
        l = x.shape[1]
        last = jnp.clip(lengths - 1 - positions[:, 0], 0, l - 1)
        x_last = jnp.take_along_axis(
            x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = jnp.einsum("bu,vu->bv", rms_norm(x_last, norm_w, eps=eps),
                            head_w, preferred_element_type=jnp.float32)
        return greedy_pick(logits), logits


def _named(fn, name, **kw):
    """``fn`` with ``kw`` bound, under ``name``: what ``jax.jit`` calls
    the program, and so what a device trace calls its operations."""
    def call(*args):
        return fn(*args, **kw)

    call.__name__ = call.__qualname__ = name
    return call


class LongcatFlashDecodeEngine(PagedDecodeEngine):
    """The decode engine over one :class:`LongcatFlashModel`: a latent
    paged cache of two sublayers per double layer (``arenas[2 * li]``,
    ``arenas[2 * li + 1]``), under its own identity at the
    ``serving_decode`` cache site.

    A forward is ``2 + num_layers`` dispatches: the embedding lookup,
    ONE double-layer program run once per layer (every layer has the
    same shapes, so a signature compiles one layer, not the stack: a
    quarter of the compile time at four layers, which is what a cold
    server start is made of) and the head. The layer program is named
    ``longcat_decode`` (L = 1) or ``longcat_prefill``. After every
    forward ``last_counts`` holds the expert layers' picks (held, zero,
    absent, held experts touched) per layer as device arrays; with
    telemetry on they are read back and recorded."""

    family = "longcat_flash"
    last_counts = ()

    def _extract(self, model, w):
        def sub(blk, i):
            a, f = blk.attns[i], blk.ffns[i]
            return {"in_norm": w(blk.in_norms[i].weight),
                    "qa": w(a.q_a.weight), "qnorm": w(a.q_norm.weight),
                    "qb": w(a.q_b.weight), "kva": w(a.kv_a.weight),
                    "kvnorm": w(a.kv_norm.weight), "kvb": w(a.kvb_weight),
                    "out": w(a.out_proj.weight),
                    "post_norm": w(blk.post_norms[i].weight),
                    "ffn_gate_up": w(f.gate_up.weight),
                    "ffn_down": w(f.down.weight)}

        return (
            w(model.embed.weight),
            tuple({"sub": (sub(blk, 0), sub(blk, 1)),
                   "moe": {"router": w(blk.moe.router_weight),
                           "router_bias": w(blk.moe.router_bias),
                           "gate_up": w(blk.moe.gate_up_weight),
                           "down": w(blk.moe.down_weight)}}
                  for blk in model.blocks),
            w(model.norm.weight), w(model.lm_head.weight))

    def _make_arenas(self, pool):
        from ....serving.kvcache import make_latent_arena

        return make_latent_arena(
            2 * self.cfg["num_layers"], pool,
            self.cfg["kv_lora_rank"] + self.cfg["rope"], self.dtype,
            device=self._device)

    def _run(self, b, l, w_pages, tokens, positions, page_table, lengths):
        import jax
        import numpy as _np

        from .... import telemetry

        sig = (b, l, w_pages)
        embed_w, layers, norm_w, head_w = self._params
        # one transfer of each host array for all the dispatches
        tokens, positions, page_table, lengths = jax.device_put(
            (tokens, positions, page_table, lengths), self._device)
        x = self._fn("embed", *sig, lambda: (_embed, ()))(embed_w, tokens)
        layer = self._fn("layer", *sig, lambda: (_named(
            _layer_forward, "longcat_decode" if l == 1 else "longcat_prefill",
            cfg=self.cfg), (2, 3)))
        counts = []
        for li, lp in enumerate(layers):
            x, self.arenas[2 * li], self.arenas[2 * li + 1], c = layer(
                x, lp, self.arenas[2 * li], self.arenas[2 * li + 1],
                positions, page_table, lengths)
            counts.append(c)
        picked = self._fn("head", *sig, lambda: (_named(
            _head, "longcat_head", eps=self.cfg["eps"]), ()))(
                x, norm_w, head_w, positions, lengths)
        self.last_counts = tuple(counts)
        if telemetry._state.enabled:
            held, zero, absent, touched = (
                int(v) for v in _np.sum(_np.asarray(counts), axis=0))
            phase = "decode" if l == 1 else "prefill"
            telemetry.record_moe_picks(held, zero, absent, touched,
                                       len(layers), phase=phase)
            # the same counts as a host event of a running profiler
            # trace, so that a traced slice carries its own rounds' picks
            with jax.profiler.TraceAnnotation(
                    f"{PICKS_MARK}{phase}:{held}:{zero}:{absent}:{touched}"
                    f":{len(layers)}"):
                pass
        return picked


def longcat_flash_tiny(**kwargs):
    """Test-sized config that keeps the published ratios' kinds: two
    double layers, 8 routed + 4 zero experts, top-3, 2 of the 8 held."""
    cfg = dict(vocab_size=128, num_layers=2, units=32, ffn_hidden_size=64,
               expert_ffn_hidden_size=16, num_heads=4, q_lora_rank=16,
               kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8, n_routed_experts=8, zero_expert_num=4,
               moe_topk=3, routed_scaling_factor=6.0, first_held=0,
               held_experts=2, rope_theta=1e7)
    cfg.update(kwargs)
    return LongcatFlashModel(**cfg)
