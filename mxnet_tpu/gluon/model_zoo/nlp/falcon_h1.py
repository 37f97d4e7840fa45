"""Falcon-H1 (``tiiuae/Falcon-H1-34B-Instruct``, ``model_type:
falcon_h1``): a decoder of PARALLEL HYBRID blocks. Every layer runs a
Mamba-2 / SSD mixer and grouped-query attention SIDE BY SIDE on the same
normed input and adds both to the residual stream, then a SwiGLU MLP;
muP multipliers scale the embedding, each mixer's input and output, the
keys, the five segments of the mixer's in-projection, the MLP's gate and
output, and the logits (the layer equations are written out in
``benchmarks/references/falcon_h1.py``, the plain reference the tests
hold this file to).

So every layer owns BOTH kinds of cache: its own pages of keys and
values (``serving/kvcache.py::make_latent_arena``: a token's heads side by
side in one row, read in place by the GQA paged kernel) and, in the stream's state slot (``StateSlots``; the engine
declares ``state_slots``), a matrix scan state of ``heads x d_state x
head_dim`` float32 values (4 MB at the published widths) and the
convolution's last ``d_conv - 1`` inputs. A decode round moves every live
stream's scan state once in and once out, in place on the slot array
(``pallas_kernels/ssd_state_update.py`` through
``ops/ssm.py::ssd_slot_update``).

**Precision of the served forward.** Matrix products take their operands
in the weights' dtype (bfloat16 as served) and hand float32 on; norms,
the convolution, activations, the recurrence with its state and tail,
softmax statistics and the residual stream are float32; the K/V pages
hold the weights' dtype.

The decode engine prefills a prompt of any length a chunk at a time
(``chunked_prefill``): a chunk at an offset carries scan state and tail
from the slot, writes its keys and values into the layer's pages and
attends through them to everything before it.

Device work is named with ``jax.named_scope``: the mixer's ``ssd.proj``
(its in / out products) and ``ssd.scan`` (convolution, state update,
gated norm) from ``ops/ssm.py::mamba2_forward``, ``h1.proj`` (the q k v o
products), ``h1.attn``, ``h1.mlp`` and ``h1.head``, in programs named
``falcon_h1_<prefill|decode>_layer`` and ``falcon_h1_head``.
"""
from __future__ import annotations

import math

import numpy as _np

from ....serving.engine import PagedDecodeEngine, greedy_pick
from ...block import HybridBlock
from ... import nn
from .glm_moe_dsa import _scatter_rows
from .llama import RMSNorm
from .longcat_flash import _dense, _embed, _named

__all__ = ["FalconH1Mamba2", "FalconH1Attention", "FalconH1MLP",
           "FalconH1Layer", "FalconH1Model", "FalconH1DecodeEngine",
           "falcon_h1_tiny"]


class FalconH1Mamba2(HybridBlock):
    """A Mamba-2 / SSD mixer over whole sequences (no cache).
    ``multipliers``: the muP factors of the in-projection's ``z | x | B |
    C | dt`` segments."""

    def __init__(self, units, d_ssm, n_heads, d_state, n_groups, d_conv,
                 chunk, eps, multipliers, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        from .... import initializer as init

        self._attrs = dict(n_groups=int(n_groups), d_state=int(d_state),
                           chunk=int(chunk), eps=float(eps),
                           multipliers=tuple(float(m) for m in multipliers))
        width = d_ssm + 2 * n_groups * d_state
        get = self.params.get
        with self.name_scope():
            self.in_weight = get("in_weight", init="xavier",
                                 shape=(d_ssm + width + n_heads, units))
            self.conv_weight = get("conv_weight", init="xavier",
                                   shape=(width, d_conv))
            self.conv_bias = get("conv_bias", init="zeros", shape=(width,))
            self.dt_bias = get("dt_bias", shape=(n_heads,),
                               init=init.Constant(math.log(math.expm1(0.01))))
            self.a_log = get("a_log", shape=(n_heads,), init="zeros")
            self.d = get("d", init="ones", shape=(n_heads,))
            self.norm_weight = get("norm_weight", init="ones",
                                   shape=(d_ssm,))
            self.out_weight = get("out_weight", init="xavier",
                                  shape=(units, d_ssm))

    def hybrid_forward(self, F, x, in_weight, conv_weight, conv_bias,
                       dt_bias, a_log, d, norm_weight, out_weight):
        return F._contrib_mamba2_mixer(
            x, in_weight, conv_weight, conv_bias, dt_bias, a_log, d,
            norm_weight, out_weight, **self._attrs)


class FalconH1Attention(HybridBlock):
    """Grouped-query attention over whole sequences (no cache): rotary
    over the whole head (half-split pairs), keys times
    ``key_multiplier``, no bias, no q/k norm."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope_theta,
                 key_multiplier, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._h, self._kv, self._d = num_heads, num_kv_heads, head_dim
        self._theta, self._key_mult = float(rope_theta), float(key_multiplier)
        with self.name_scope():
            self.q_proj = _dense(num_heads * head_dim, "q_")
            self.k_proj = _dense(num_kv_heads * head_dim, "k_")
            self.v_proj = _dense(num_kv_heads * head_dim, "v_")
            self.out_proj = _dense(units, "out_")

    def hybrid_forward(self, F, x):
        b, l = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape((b, l, self._h, self._d))
        k = (self.k_proj(x) * self._key_mult).reshape(
            (b, l, self._kv, self._d))
        v = self.v_proj(x).reshape((b, l, self._kv, self._d))
        q = F._contrib_rope(q, theta=self._theta).transpose((0, 2, 1, 3))
        k = F._contrib_rope(k, theta=self._theta).transpose((0, 2, 1, 3))
        v = v.transpose((0, 2, 1, 3))
        rep = self._h // self._kv
        out = F._contrib_sdp_attention(
            q, F.repeat(k, repeats=rep, axis=1),
            F.repeat(v, repeats=rep, axis=1), causal=True)
        return self.out_proj(out.transpose((0, 2, 1, 3)).reshape(
            (b, l, self._h * self._d)))


class FalconH1MLP(HybridBlock):
    """SwiGLU, gate and up in one matmul; ``multipliers``: the gate's
    and the output's muP factors."""

    def __init__(self, units, hidden_size, multipliers, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._gate, self._out = (float(m) for m in multipliers)
        with self.name_scope():
            self.gate_up = _dense(2 * hidden_size, "gateup_")
            self.down = _dense(units, "down_")

    def hybrid_forward(self, F, x):
        gate, up = F.split(self.gate_up(x), num_outputs=2, axis=-1)
        return self.down(
            F.Activation(gate * self._gate, act_type="silu") * up) * self._out


class FalconH1Layer(HybridBlock):
    """``x + m_ssm Mixer(a_ssm u) + m_attn Attn(a_attn u)`` with ``u =
    RMSNorm(x)``, then ``x + MLP(RMSNorm(x))``."""

    def __init__(self, cfg, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        c = cfg
        self._mults = (c["ssm_in_multiplier"], c["ssm_out_multiplier"],
                       c["attention_in_multiplier"],
                       c["attention_out_multiplier"])
        with self.name_scope():
            self.norm1 = RMSNorm(c["units"], c["eps"], prefix="norm1_")
            self.mixer = FalconH1Mamba2(
                c["units"], c["d_ssm"], c["ssm_heads"], c["d_state"],
                c["n_groups"], c["d_conv"], c["chunk"], c["eps"],
                c["ssm_multipliers"], prefix="mixer_")
            self.attention = FalconH1Attention(
                c["units"], c["num_heads"], c["num_kv_heads"], c["head_dim"],
                c["rope_theta"], c["key_multiplier"], prefix="attn_")
            self.norm2 = RMSNorm(c["units"], c["eps"], prefix="norm2_")
            self.mlp = FalconH1MLP(c["units"], c["ffn_hidden_size"],
                                   c["mlp_multipliers"], prefix="mlp_")

    def hybrid_forward(self, F, x):
        ssm_in, ssm_out, attn_in, attn_out = self._mults
        u = self.norm1(x)
        x = (x + self.mixer(u * ssm_in) * ssm_out
             + self.attention(u * attn_in) * attn_out)
        return x + self.mlp(self.norm2(x))


class FalconH1Model(HybridBlock):
    """Defaults are the published sizes of Falcon-H1-34B-Instruct,
    nothing cut."""

    def __init__(self, vocab_size=261120, num_layers=72, units=5120,
                 ffn_hidden_size=21504, num_heads=20, num_kv_heads=4,
                 head_dim=128, d_ssm=4096, ssm_heads=32, d_state=256,
                 n_groups=2, d_conv=4, chunk=128, rope_theta=1e11, eps=1e-5,
                 embedding_multiplier=5.656854249492381,
                 lm_head_multiplier=0.0078125, ssm_in_multiplier=0.25,
                 ssm_out_multiplier=0.08838834764831845,
                 attention_in_multiplier=1.0,
                 attention_out_multiplier=0.0375,
                 key_multiplier=0.011048543456039804,
                 ssm_multipliers=(0.3535533905932738, 0.25,
                                  0.1767766952966369, 0.5,
                                  0.3535533905932738),
                 mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if d_ssm % ssm_heads or ssm_heads % n_groups \
                or num_heads % num_kv_heads:
            raise ValueError(
                "d_ssm must divide into ssm_heads, ssm_heads into n_groups "
                "and num_heads into num_kv_heads")
        # what the pure cache-aware forward needs beside the weights
        self._decode_cfg = {
            "vocab_size": int(vocab_size), "num_layers": int(num_layers),
            "units": int(units), "ffn_hidden_size": int(ffn_hidden_size),
            "num_heads": int(num_heads), "num_kv_heads": int(num_kv_heads),
            "head_dim": int(head_dim), "d_ssm": int(d_ssm),
            "ssm_heads": int(ssm_heads), "d_state": int(d_state),
            "n_groups": int(n_groups), "d_conv": int(d_conv),
            "chunk": int(chunk), "rope_theta": float(rope_theta),
            "eps": float(eps),
            "embedding_multiplier": float(embedding_multiplier),
            "lm_head_multiplier": float(lm_head_multiplier),
            "ssm_in_multiplier": float(ssm_in_multiplier),
            "ssm_out_multiplier": float(ssm_out_multiplier),
            "attention_in_multiplier": float(attention_in_multiplier),
            "attention_out_multiplier": float(attention_out_multiplier),
            "key_multiplier": float(key_multiplier),
            "ssm_multipliers": tuple(float(m) for m in ssm_multipliers),
            "mlp_multipliers": tuple(float(m) for m in mlp_multipliers),
        }
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = []
            for i in range(num_layers):
                blk = FalconH1Layer(self._decode_cfg, prefix=f"layer{i}_")
                self.blocks.append(blk)
                self.register_child(blk, f"layer{i}")
            self.norm = RMSNorm(units, eps, prefix="norm_")
            # the head is untied (tie_word_embeddings false)
            self.lm_head = _dense(vocab_size, "lm_head_")

    def hybrid_forward(self, F, tokens):
        c = self._decode_cfg
        x = self.embed(tokens) * c["embedding_multiplier"]
        for blk in self.blocks:
            x = blk(x)
        return self.lm_head(self.norm(x)) * c["lm_head_multiplier"]

    def decode_engine(self, pool) -> "FalconH1DecodeEngine":
        """The seam ``serving.Server`` asks for ``submit_generate``: a K
        and a V page arena a layer over ``pool`` and the pool's state
        slots, on the device and in the dtype of the parameters."""
        return FalconH1DecodeEngine.build(self, pool)


# ---------------------------------------------------------------------------
# serving: the cache-aware pure forward and its engine
# ---------------------------------------------------------------------------

def _embed_rows(embed_w, tokens, *, multiplier):
    """The residual stream starts, and stays, in float32."""
    import jax.numpy as jnp

    return _embed(embed_w, tokens).astype(jnp.float32) * multiplier


def _norm(x, gain, eps):
    """RMSNorm of the float32 stream, float32: a consumer scales it by
    its muP factor and rounds it to the weights' dtype (:func:`_mm`)."""
    from ....ops.attention import rms_norm

    return rms_norm(x, gain, eps=eps)


def _mm(x, w):
    """Operands in the weights' dtype, the product handed on in float32:
    one rounding a matrix product, on its input."""
    import jax.numpy as jnp

    return jnp.matmul(x.astype(w.dtype), w.T,
                      preferred_element_type=jnp.float32)


def _cache_attend(q, k_arena, v_arena, positions, page_table, lengths, kv):
    """Causal attention of a chunk's queries ``q`` (B, L, H, d) at
    ``positions`` over everything the rows' pages hold up to each query
    (the chunk's own keys and values are already in them): the pages
    gathered through the page table, scores and softmax in float32,
    probabilities in the cache's dtype. (B, L, H, d) float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    b, l, h, d = q.shape
    # a row is padded to whole lane tiles where kv * d is not one
    k = jnp.take(k_arena, page_table, axis=0)[..., :kv * d].reshape(
        b, -1, kv, d)
    v = jnp.take(v_arena, page_table, axis=0)[..., :kv * d].reshape(
        b, -1, kv, d)
    q = q.reshape(b, l, kv, h // kv, d).astype(k.dtype)
    s = jnp.einsum("blgrd,btgd->bgrlt", q, k,
                   preferred_element_type=f32) / math.sqrt(d)
    key_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    seen = ((key_pos[None, None, :] <= positions[:, :, None])
            & (key_pos[None, None, :] < lengths[:, None, None]))
    prob = jax.nn.softmax(jnp.where(seen[:, None, None], s, f32(-1e30)),
                          axis=-1)
    out = jnp.einsum("bgrlt,btgd->blgrd", prob.astype(v.dtype), v,
                     preferred_element_type=f32)
    return out.reshape(b, l, h, d)


def _attention(u, p, k_arena, v_arena, positions, page_table, lengths, cfg):
    """What a layer's attention adds (before ``attention_out_multiplier``)
    and the layer's two arenas (``(pages, page, kv_heads * head_dim)``: a
    token's heads side by side in one row) with the dispatch's keys and
    values written: a real position into its page, a padded one into
    the scratch page."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import paged_attention, rope_at

    b, l, _ = u.shape
    h, kv, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    ps = cfg["page_size"]
    with jax.named_scope("h1.proj"):
        q = _mm(u, p["q"]).reshape(b, l, h, d)
        k = (_mm(u, p["k"]) * cfg["key_multiplier"]).reshape(b, l, kv, d)
        v = _mm(u, p["v"]).reshape(b, l, kv, d)
    with jax.named_scope("h1.attn"):
        q = rope_at(q, positions, theta=cfg["rope_theta"])
        k = rope_at(k, positions, theta=cfg["rope_theta"])
        real = (positions >= 0) & (positions < lengths[:, None])
        page_of = jnp.clip(positions // ps, 0, page_table.shape[1] - 1)
        page = jnp.where(
            real, jnp.take_along_axis(page_table, page_of, axis=1),
            0).reshape(-1)                              # padding -> scratch
        offset = jnp.mod(positions, ps).reshape(-1)
        dtype = k_arena.dtype
        k_arena = _scatter_rows(
            k_arena, k.reshape(b * l, kv * d).astype(dtype), page, offset)
        v_arena = _scatter_rows(
            v_arena, v.reshape(b * l, kv * d).astype(dtype), page, offset)
        if l == 1:
            # a token's heads side by side in one row: the kernel's own
            # view of an arena, (slots, kv * d), with no relayout
            att = paged_attention(
                q.astype(dtype).transpose(0, 2, 1, 3),
                k_arena[..., :kv * d].reshape(-1, kv, d),
                v_arena[..., :kv * d].reshape(-1, kv, d),
                page_table, lengths, q_positions=positions,
                page_size=ps).transpose(0, 2, 1, 3)
        else:
            att = _cache_attend(q, k_arena, v_arena, positions, page_table,
                                lengths, kv)
    with jax.named_scope("h1.proj"):
        return _mm(att.reshape(b, l, h * d), p["o"]), k_arena, v_arena


def _mixer(u, p, tails, states, positions, lengths, slots, cfg):
    """What a layer's Mamba-2 mixer adds (before ``ssm_out_multiplier``)
    from the rows' slots, and both slot arrays advanced to each row's last
    real token. A padded position is an identity step; a padding row
    (slot 0) changes scratch only; a row at position 0 starts a stream,
    whatever its slot holds. One token a stream updates the scan states
    in place on the slot array; a chunk gathers its rows' states, runs
    the chunk form and scatters them back."""
    import jax.numpy as jnp

    from ....ops.ssm import (conv_tail, mamba2_forward, ssd_chunk_scan,
                             ssd_slot_update)

    l = u.shape[1]
    real = (positions >= 0) & (positions < lengths[:, None])
    fresh = positions[:, 0] == 0
    tail = jnp.where(fresh[:, None, None], 0, tails[slots])

    def scan(x, dt, a, b, c, d, slot_states):
        if l > 1:
            state = jnp.where(fresh[:, None, None, None], 0,
                              slot_states[slots])
            y, state = ssd_chunk_scan(x, dt, a, b, c, d, state,
                                      chunk=min(cfg["chunk"], l))
            return y, slot_states.at[slots].set(state)
        y, slot_states = ssd_slot_update(
            slot_states, slots, fresh, x[:, 0], dt[:, 0], a, b[:, 0],
            c[:, 0], d)
        return y[:, None], slot_states

    out, ext, states = mamba2_forward(
        u, p, tail, states, real, scan=scan, n_groups=cfg["n_groups"],
        d_state=cfg["d_state"], eps=cfg["eps"])
    tail = conv_tail(ext, jnp.sum(real, axis=1, dtype=jnp.int32),
                     cfg["d_conv"])
    return out, tails.at[slots].set(tail.astype(tails.dtype)), states


def _layer_forward(x, p, k_arena, v_arena, tails, states, positions,
                   page_table, lengths, slots, *, cfg):
    """One parallel hybrid block, cache-aware and pure: ``x`` (B, L, U)
    float32 at ``positions``; the layer's K and V arenas, its convolution
    tails and its scan states in and out."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    u = _norm(x, p["ln1"], cfg["eps"])
    ssm, tails, states = _mixer(
        u * f32(cfg["ssm_in_multiplier"]), p, tails, states, positions,
        lengths, slots, cfg)
    att, k_arena, v_arena = _attention(
        u * f32(cfg["attention_in_multiplier"]), p, k_arena, v_arena,
        positions, page_table, lengths, cfg)
    x = (x + f32(cfg["ssm_out_multiplier"]) * ssm
         + f32(cfg["attention_out_multiplier"]) * att)
    with jax.named_scope("h1.mlp"):
        gate_mult, out_mult = cfg["mlp_multipliers"]
        gate, up = jnp.split(_mm(_norm(x, p["ln2"], cfg["eps"]),
                                 p["gate_up"]), 2, axis=-1)
        x = x + f32(out_mult) * _mm(
            jax.nn.silu(f32(gate_mult) * gate) * up, p["down"])
    return x, k_arena, v_arena, tails, states


def _head(x, norm_w, head_w, positions, lengths, *, eps, multiplier):
    """The greedy token id and the float32 logits of each row's last
    REAL token (prefill: ``lengths - 1``; one token a row: row 0)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("h1.head"):
        last = jnp.clip(lengths - 1 - positions[:, 0], 0, x.shape[1] - 1)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = _mm(_norm(x_last, norm_w, eps), head_w) \
            * jnp.float32(multiplier)
        return greedy_pick(logits), logits


class FalconH1DecodeEngine(PagedDecodeEngine):
    """The decode engine over one :class:`FalconH1Model`: every layer's
    pages AND every layer's slot state on one page table and one slot a
    stream.

    ``arenas``: per layer a key arena and a value arena (``arenas[2 *
    li]``, ``arenas[2 * li + 1]``; each ``(pages, page, kv_heads *
    head_dim)``, a token's heads side by side in one lane-dense row, as
    the paged GQA kernel reads them: an array allocated ``(slots,
    kv_heads, head_dim)`` is tiled by (kv_heads, 128) and the kernel's
    ``(slots, kv_heads * head_dim)`` view of it is a copy of the whole
    arena a layer a round; one array a layer, so that a layer's program
    reads and scatters its own in place). ``slot_arrays``: per layer a
    convolution tail ``(slots, d_conv - 1, d_ssm + 2 groups x d_state)``
    and a scan state ``(slots, heads, d_state, head_dim)``, both float32
    (the state transposed, the head's channels in the lanes:
    ``ops/ssm.py``); defrag never touches them.

    A forward is the embedding lookup, ONE layer program run once per
    layer (every layer is the same parallel block, so a signature
    compiles one layer whatever the depth) and, where a row of the
    dispatch ends its prompt or decodes, the head: three programs a
    signature."""

    family = "falcon_h1"
    chunked_prefill = True
    state_slots = True

    def _extract(self, model, w):
        from ....ops.ssm import mamba2_mup

        c = self.cfg
        mup = mamba2_mup(c["ssm_multipliers"], c["d_ssm"],
                         c["n_groups"] * c["d_state"], c["ssm_heads"])

        def layer(blk):
            m, a = blk.mixer, blk.attention
            return {
                "ln1": w(blk.norm1.weight), "ln2": w(blk.norm2.weight),
                "in": w(m.in_weight), "mup": mup,
                "conv_w": w(m.conv_weight), "conv_b": w(m.conv_bias),
                "dt_b": w(m.dt_bias), "a_log": w(m.a_log), "d": w(m.d),
                "norm": w(m.norm_weight), "out": w(m.out_weight),
                "q": w(a.q_proj.weight), "k": w(a.k_proj.weight),
                "v": w(a.v_proj.weight), "o": w(a.out_proj.weight),
                "gate_up": w(blk.mlp.gate_up.weight),
                "down": w(blk.mlp.down.weight)}

        return (w(model.embed.weight),
                tuple(layer(blk) for blk in model.blocks),
                w(model.norm.weight), w(model.lm_head.weight))

    def _make_arenas(self, pool):
        import jax
        import jax.numpy as jnp

        from ....serving.kvcache import make_latent_arena

        cfg = self.cfg
        cfg["page_size"] = pool.page_size
        s = pool.state_slots.n_slots
        width = cfg["d_ssm"] + 2 * cfg["n_groups"] * cfg["d_state"]
        heads = cfg["ssm_heads"]

        def zeros(shape):
            return jax.device_put(
                jnp.zeros(shape, jnp.float32, device=self._device),
                self._device)

        n = cfg["num_layers"]
        self.slot_arrays = {
            "tails": [zeros((s, cfg["d_conv"] - 1, width))
                      for _ in range(n)],
            "states": [zeros((s, heads, cfg["d_state"],
                              cfg["d_ssm"] // heads)) for _ in range(n)],
        }
        return list(make_latent_arena(
            2 * n, pool, cfg["num_kv_heads"] * cfg["head_dim"], self.dtype,
            device=self._device))

    def _run(self, b, l, w_pages, tokens, positions, page_table, lengths,
             slots, final):
        import jax

        phase = "decode" if l == 1 else "prefill"
        sig = (b, l, w_pages)
        embed_w, layers, norm_w, head_w = self._params
        cfg, st = self.cfg, self.slot_arrays
        reads = (lengths > positions[:, 0]) & (final | (l == 1))
        tokens, positions, page_table, lengths, slots = jax.device_put(
            (tokens, positions, page_table, lengths, slots), self._device)
        x = self._fn("embed", *sig, lambda: (_named(
            _embed_rows, "falcon_h1_embed",
            multiplier=cfg["embedding_multiplier"]), ()))(embed_w, tokens)
        layer = self._fn("layer", *sig, lambda: (_named(
            _layer_forward, f"falcon_h1_{phase}_layer", cfg=cfg),
            (2, 3, 4, 5)))
        for li, lp in enumerate(layers):
            (x, self.arenas[2 * li], self.arenas[2 * li + 1],
             st["tails"][li], st["states"][li]) = layer(
                x, lp, self.arenas[2 * li], self.arenas[2 * li + 1],
                st["tails"][li], st["states"][li], positions, page_table,
                lengths, slots)
        if not reads.any():
            # no row ends its prompt here: nobody reads the ids
            return _np.zeros((b,), _np.int32), None
        return self._fn("head", *sig, lambda: (_named(
            _head, "falcon_h1_head", eps=cfg["eps"],
            multiplier=cfg["lm_head_multiplier"]), ()))(
                x, norm_w, head_w, positions, lengths)


def falcon_h1_tiny(**kwargs):
    """Test-sized config of the same kinds: two parallel hybrid blocks,
    4 / 2 attention heads of 8, 4 scan heads of 8 in 2 groups over a
    state of 16, the published multipliers."""
    cfg = dict(vocab_size=128, num_layers=2, units=32, ffn_hidden_size=64,
               num_heads=4, num_kv_heads=2, head_dim=8, d_ssm=32,
               ssm_heads=4, d_state=16, n_groups=2, d_conv=4, chunk=8,
               rope_theta=1e4)
    cfg.update(kwargs)
    return FalconH1Model(**cfg)
