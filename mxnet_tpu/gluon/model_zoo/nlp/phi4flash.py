"""Phi-4-mini-flash-reasoning (``microsoft/Phi-4-mini-flash-reasoning``,
``model_type: phi4flash``; the SambaY architecture, arXiv:2507.06607): a
decoder-decoder hybrid whose layers keep FOUR kinds of state (the layer
equations are written out in ``benchmarks/references/phi4flash.py``, the
plain reference the tests hold this file to).

* **Self-decoder** (the first half of the layers): Mamba layers (a
  causal depthwise convolution and a selective scan; per stream the
  convolution's last inputs and a float32 state) alternate with
  sliding-window differential attention (per stream a ring of the last
  ``sliding_window`` keys and values).
* **The middle**: one more Mamba layer that also publishes its scan's
  output as the **memory**, then ONE full-attention layer whose keys and
  values are the only paged cache of the model.
* **Cross-decoder** (the rest): gated memory units, which gate the
  memory of the same token, alternate with cross-attention layers that
  have query weights only and read the full-attention layer's pages.

Every layer is ``x + mixer(LN(x))`` then ``x + SwiGLU(LN(x))``; no
positional encoding anywhere; the head is tied to the embedding.

**Precision of the served forward.** Matrix products take their operands
in the weights' dtype (bfloat16 as served) and hand float32 on; what
lies between two products (LayerNorm, convolution, activations, gates,
the scan, softmax statistics, the differential combination) and the
residual stream are float32; the K/V pages and the rings hold the
weights' dtype. 64 sublayers that each rounded four or five values to
bfloat16 ended 2.4% of the logits' spread away from the float32
reference; so they end at half of that (``PERF.md``, PR 35).

The decode engine keeps the first two kinds of state in **state slots**
beside the page pool (``serving/kvcache.py::StateSlots``; the engine
declares ``state_slots``) and the one K/V cache in two page arenas, a
token's key heads side by side in one row and its value heads in
another. It prefills a prompt of any length a chunk at a time
(``chunked_prefill``), carrying scan state, convolution tail and ring
from chunk to chunk, and runs the full-attention layer's attention, the
cross-decoder and the head on a prompt's LAST token only (the rows the
slot seam marks ``final``): a chunk that does not end a prompt stops
after the self-decoder and the middle layers' K/V, which is what makes
the architecture's prefill linear.

Device work is named with ``jax.named_scope``: ``ssm.proj``,
``ssm.scan``, ``swa.attend``, ``yoco.kv``, ``yoco.attend``, ``gmu``,
``mlp`` and ``lm_head``, in programs named
``phi4flash_<prefill|decode>_<self|mid>`` (all rows) and
``phi4flash_decode_<full|cross>`` / ``phi4flash_head`` (one row a
stream, in both phases). With telemetry on the engine counts, per
dispatch, the shared cache's live tokens times the layers that read
them (``telemetry.record_shared_kv_read``) and the rows a prefill ran
through each decoder (``telemetry.record_prefill_rows``).
"""
from __future__ import annotations

import math

import numpy as _np

from ....serving.engine import PagedDecodeEngine, greedy_pick
from ...block import HybridBlock
from ... import nn
from .glm_moe_dsa import _scatter_rows
from .longcat_flash import LongcatFFN, _embed, _named

__all__ = ["Phi4FlashMamba", "Phi4FlashAttention", "Phi4FlashCrossAttention",
           "Phi4FlashGMU", "Phi4FlashLayer", "Phi4FlashModel",
           "Phi4FlashDecodeEngine", "phi4flash_tiny", "lambda_init"]


def lambda_init(layer: int) -> float:
    """The differential attention's ``lam0`` of the layer at index
    ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _inv_softplus(v: float) -> float:
    return math.log(math.expm1(v))


class Phi4FlashMamba(HybridBlock):
    """A Mamba mixer over whole sequences (no cache); returns its output
    and the scan's output before the gate (the layer that publishes the
    memory hands the second on)."""

    def __init__(self, units, d_inner, d_state, d_conv, dt_rank,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        from .... import initializer as init

        get = self.params.get
        with self.name_scope():
            self.in_weight = get("in_weight", init="xavier",
                                 shape=(2 * d_inner, units))
            self.conv_weight = get("conv_weight", init="xavier",
                                   shape=(d_inner, d_conv))
            self.conv_bias = get("conv_bias", init="zeros",
                                 shape=(d_inner,))
            self.x_weight = get("x_weight", init="xavier",
                                shape=(dt_rank + 2 * d_state, d_inner))
            self.dt_weight = get("dt_weight", init="xavier",
                                 shape=(d_inner, dt_rank))
            self.dt_bias = get("dt_bias", shape=(d_inner,),
                               init=init.Constant(_inv_softplus(0.01)))
            self.a_log = get("a_log", shape=(d_inner, d_state),
                             init=init.Constant(_np.log(_np.arange(
                                 1, d_state + 1, dtype=_np.float32))))
            self.d = get("d", init="ones", shape=(d_inner,))
            self.out_weight = get("out_weight", init="xavier",
                                  shape=(units, d_inner))

    def hybrid_forward(self, F, x, in_weight, conv_weight, conv_bias,
                       x_weight, dt_weight, dt_bias, a_log, d, out_weight):
        return F._contrib_mamba_mixer(x, in_weight, conv_weight, conv_bias,
                                      x_weight, dt_weight, dt_bias, a_log,
                                      d, out_weight)


class _DiffHeads(HybridBlock):
    """What the two attention kinds share: the lambda vectors, the
    sub-norm's gain, the out-projection and the combination."""

    def __init__(self, units, num_heads, num_kv_heads, layer, eps, prefix,
                 params):
        super().__init__(prefix=prefix, params=params)
        self._units, self._h, self._kv = units, num_heads, num_kv_heads
        self._d = units // num_heads
        self._lam0, self._eps = lambda_init(layer), eps
        get = self.params.get
        with self.name_scope():
            for name in ("lq1", "lk1", "lq2", "lk2"):
                setattr(self, name, get(name, shape=(self._d,),
                                        init="normal"))
            self.subln = get("subln", init="ones", shape=(2 * self._d,))
            self.out_weight = get("out_weight", init="xavier",
                                  shape=(units, units))
            self.out_bias = get("out_bias", init="zeros", shape=(units,))

    def _attend(self, F, q, k, v, window, p):
        b, l = q.shape[0], q.shape[1]
        paired = F._contrib_diff_attention(
            q.reshape((b, l, self._h, self._d)),
            k.reshape((b, l, self._kv, self._d)),
            v.reshape((b, l, self._kv, self._d)),
            window=window, scale=1.0 / math.sqrt(self._d))
        att = F._contrib_diff_attention_combine(
            paired, p["lq1"], p["lk1"], p["lq2"], p["lk2"], p["subln"],
            lambda_init=self._lam0, eps=self._eps)
        return F.FullyConnected(att, p["out_weight"], p["out_bias"],
                                num_hidden=self._units, flatten=False)


class Phi4FlashAttention(_DiffHeads):
    """Differential attention over whole sequences (no cache), windowed
    (``window`` > 0) or full; returns its output, keys and values (the
    full-attention layer's are what the cross-attention layers read)."""

    def __init__(self, units, num_heads, num_kv_heads, layer, window=0,
                 eps=1e-5, prefix=None, params=None):
        super().__init__(units, num_heads, num_kv_heads, layer, eps, prefix,
                         params)
        self._window = int(window)
        self._n_qkv = units + 2 * num_kv_heads * self._d
        with self.name_scope():
            self.qkv_weight = self.params.get(
                "qkv_weight", init="xavier", shape=(self._n_qkv, units))
            self.qkv_bias = self.params.get("qkv_bias", init="zeros",
                                            shape=(self._n_qkv,))

    def hybrid_forward(self, F, x, qkv_weight, qkv_bias, **p):
        qkv = F.FullyConnected(x, qkv_weight, qkv_bias,
                               num_hidden=self._n_qkv, flatten=False)
        u, kv = self._units, self._kv * self._d
        q = F.slice_axis(qkv, axis=-1, begin=0, end=u)
        k = F.slice_axis(qkv, axis=-1, begin=u, end=u + kv)
        v = F.slice_axis(qkv, axis=-1, begin=u + kv, end=u + 2 * kv)
        return self._attend(F, q, k, v, self._window, p), k, v


class Phi4FlashCrossAttention(_DiffHeads):
    """Differential attention with query weights only, over the keys and
    values another layer made."""

    def __init__(self, units, num_heads, num_kv_heads, layer, eps=1e-5,
                 prefix=None, params=None):
        super().__init__(units, num_heads, num_kv_heads, layer, eps, prefix,
                         params)
        with self.name_scope():
            self.q_weight = self.params.get("q_weight", init="xavier",
                                            shape=(units, units))
            self.q_bias = self.params.get("q_bias", init="zeros",
                                          shape=(units,))

    def hybrid_forward(self, F, x, k, v, q_weight, q_bias, **p):
        q = F.FullyConnected(x, q_weight, q_bias, num_hidden=self._units,
                             flatten=False)
        return self._attend(F, q, k, v, 0, p)


class Phi4FlashGMU(HybridBlock):
    """Gated memory unit: ``W_2 (m * silu(W_1 h))``."""

    def __init__(self, units, d_inner, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.in_weight = self.params.get(
                "in_weight", init="xavier", shape=(d_inner, units))
            self.out_weight = self.params.get(
                "out_weight", init="xavier", shape=(units, d_inner))

    def hybrid_forward(self, F, x, memory, in_weight, out_weight):
        return F._contrib_gated_memory_unit(x, memory, in_weight,
                                            out_weight)


class Phi4FlashLayer(HybridBlock):
    """``x + mixer(LN(x))``, ``x + SwiGLU(LN(x))``; ``kind`` is
    ``mamba``, ``window``, ``full``, ``gmu`` or ``cross``. Called with
    (x, memory, k, v) and returns the same four: a memory-publishing
    Mamba layer and the full-attention layer replace theirs."""

    def __init__(self, kind, layer, units, ffn_hidden_size, mixer, eps=1e-5,
                 publishes=False, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.kind, self.publishes = kind, publishes
        with self.name_scope():
            self.norm1 = nn.LayerNorm(epsilon=eps, in_channels=units,
                                      prefix="norm1_")
            self.mixer = mixer(prefix="mixer_")
            self.norm2 = nn.LayerNorm(epsilon=eps, in_channels=units,
                                      prefix="norm2_")
            self.mlp = LongcatFFN(units, ffn_hidden_size, prefix="mlp_")

    def hybrid_forward(self, F, x, memory, k, v):
        h = self.norm1(x)
        if self.kind == "mamba":
            out, y = self.mixer(h)
            memory = y if self.publishes else memory
        elif self.kind in ("window", "full"):
            out, k_own, v_own = self.mixer(h)
            if self.kind == "full":
                k, v = k_own, v_own
        elif self.kind == "gmu":
            out = self.mixer(h, memory)
        else:
            out = self.mixer(h, k, v)
        x = x + out
        return x + self.mlp(self.norm2(x)), memory, k, v


def layer_kinds(num_layers: int) -> list:
    """The kind of each layer: Mamba / windowed attention alternate
    through layer ``n/2``, layer ``n/2 + 1`` is the full attention, then
    gated memory units and cross-attention alternate."""
    half = num_layers // 2
    return [("mamba" if i % 2 == 0 else "window") if i <= half else
            "full" if i == half + 1 else
            ("gmu" if i % 2 == 0 else "cross") for i in range(num_layers)]


class Phi4FlashModel(HybridBlock):
    """Defaults are the published sizes, nothing cut."""

    def __init__(self, vocab_size=200064, num_layers=32, units=2560,
                 ffn_hidden_size=10240, num_heads=40, num_kv_heads=20,
                 sliding_window=512, d_state=16, d_conv=4, expand=2,
                 dt_rank=None, eps=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if num_layers % 4 or num_layers < 4:
            raise ValueError("num_layers must be a multiple of 4: Mamba "
                             "and attention layers alternate in both "
                             f"halves, got {num_layers}")
        d_inner = expand * units
        dt_rank = int(dt_rank) if dt_rank else units // 16
        head_dim = units // num_heads
        # what the pure cache-aware forward needs beside the weights
        self._decode_cfg = {
            "vocab_size": int(vocab_size), "num_layers": int(num_layers),
            "units": int(units), "num_heads": int(num_heads),
            "num_kv_heads": int(num_kv_heads), "head_dim": int(head_dim),
            "window": int(sliding_window), "d_inner": int(d_inner),
            "d_state": int(d_state), "d_conv": int(d_conv),
            "dt_rank": int(dt_rank), "eps": float(eps),
        }

        def mixer(kind, i):
            if kind == "mamba":
                return lambda prefix: Phi4FlashMamba(
                    units, d_inner, d_state, d_conv, dt_rank, prefix=prefix)
            if kind in ("window", "full"):
                return lambda prefix: Phi4FlashAttention(
                    units, num_heads, num_kv_heads, i,
                    sliding_window if kind == "window" else 0, eps,
                    prefix=prefix)
            if kind == "gmu":
                return lambda prefix: Phi4FlashGMU(units, d_inner,
                                                   prefix=prefix)
            return lambda prefix: Phi4FlashCrossAttention(
                units, num_heads, num_kv_heads, i, eps, prefix=prefix)

        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = []
            for i, kind in enumerate(layer_kinds(num_layers)):
                blk = Phi4FlashLayer(
                    kind, i, units, ffn_hidden_size, mixer(kind, i), eps,
                    publishes=i == num_layers // 2, prefix=f"layer{i}_")
                self.blocks.append(blk)
                self.register_child(blk, f"layer{i}")
            self.norm = nn.LayerNorm(epsilon=eps, in_channels=units,
                                     prefix="norm_")

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        memory = k = v = None
        for blk in self.blocks:
            x, memory, k, v = blk(x, memory, k, v)
        # the head is the embedding (tie_word_embeddings)
        return F.FullyConnected(
            self.norm(x), self.embed.weight.data(x.context), None,
            no_bias=True, num_hidden=self._decode_cfg["vocab_size"],
            flatten=False)

    def decode_engine(self, pool) -> "Phi4FlashDecodeEngine":
        """The seam ``serving.Server`` asks for ``submit_generate``: ONE
        paged K/V cache over ``pool`` and the pool's state slots, on the
        device and in the dtype of the parameters."""
        return Phi4FlashDecodeEngine.build(self, pool)


# ---------------------------------------------------------------------------
# serving: the cache-aware pure forward and its engine
# ---------------------------------------------------------------------------

def _embed_rows(embed_w, tokens):
    """The residual stream starts, and stays, in float32: 64 sublayers
    add a fifth of its size each, and rounded to bfloat16 after every one
    it would lose 2^-9 of its own size each time, three times what the
    sublayers' bfloat16 outputs lose. Every matrix product still takes
    bfloat16 rows (:func:`_ln` hands them on in the weights' dtype)."""
    import jax.numpy as jnp

    return _embed(embed_w, tokens).astype(jnp.float32)


def _ln(x, p, which, eps):
    from ....ops.nn import layer_norm

    gain = p[which + "_g"]
    return layer_norm(x, gain, p[which + "_b"], eps=eps).astype(gain.dtype)


def _mlp(x, p, eps):
    """``x + SwiGLU(LN(x))``; as every sublayer here, its matrix products
    take operands in the weights' dtype and hand on float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    with jax.named_scope("mlp"):
        gate, up = jnp.split(jnp.matmul(
            _ln(x, p, "ln2", eps), p["gate_up"].T,
            preferred_element_type=f32), 2, axis=-1)
        act = (jax.nn.silu(gate) * up).astype(p["down"].dtype)
        return x + jnp.matmul(act, p["down"].T, preferred_element_type=f32)


def _rows_real(positions, lengths):
    """Which positions of a dispatch are real, how many a row has, and
    which rows start a stream (their carried state counts as zeros)."""
    import jax.numpy as jnp

    real = (positions >= 0) & (positions < lengths[:, None])
    return real, jnp.sum(real, axis=1, dtype=jnp.int32), positions[:, 0] == 0


def _mamba_layer(x, p, tails, states, positions, lengths, slots, cfg):
    """A Mamba layer from the rows' slots: the layer's output, the
    scan's output (the memory, where the layer publishes it) and both
    slot arrays advanced to each row's last real token. A padded position
    is an identity step; a padding row (slot 0) changes scratch only."""
    import jax.numpy as jnp

    from ....ops.ssm import conv_tail, mamba_forward

    real, n_real, fresh = _rows_real(positions, lengths)
    tail = jnp.where(fresh[:, None, None], 0, tails[slots])
    state = jnp.where(fresh[:, None, None], 0, states[slots])
    out, y, ext, state = mamba_forward(
        _ln(x, p, "ln1", cfg["eps"]), p, tail, state, real)
    tail = conv_tail(ext, n_real, cfg["d_conv"])
    return (_mlp(x + out, p, cfg["eps"]), y,
            tails.at[slots].set(tail.astype(tails.dtype)),
            states.at[slots].set(state))


def _split_qkv(h, p, cfg):
    hq, hkv, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    qkv = h @ p["qkv"].T + p["qkv_b"]
    return (qkv[..., :hq * d], qkv[..., hq * d:(hq + hkv) * d],
            qkv[..., (hq + hkv) * d:])


def _combine(paired, p, cfg):
    from ....ops.diff_attention import diff_attention_combine

    att = diff_attention_combine(
        paired, p["lq1"], p["lk1"], p["lq2"], p["lk2"], p["subln"],
        lambda_init=p["lam0"], eps=cfg["eps"])
    import jax.numpy as jnp

    return jnp.matmul(att.astype(p["o"].dtype), p["o"].T,
                      preferred_element_type=jnp.float32) + p["o_b"]


def _ring_write(ring, rows, ring_rows, positions, lengths, slots):
    """``ring`` (S, W, width) with the dispatch's real ``rows`` (B, L,
    width) in: token ``t`` at index ``t % W`` of its stream's slot, the
    last W of them. ``ring_rows`` (B, W, width): the rows' rings before
    (None where L == 1: one row is scattered in place)."""
    import jax.numpy as jnp

    w = ring.shape[1]
    real, n_real, _ = _rows_real(positions, lengths)
    if rows.shape[1] == 1:
        slot = jnp.where(real[:, 0], slots, 0)      # padding -> scratch
        return ring.at[slot, jnp.mod(positions[:, 0], w)].set(rows[:, 0])
    first = positions[:, 0]
    last = (first + n_real - 1)[:, None]
    # the newest position at or before the last real one, per ring index
    pos = last - jnp.mod(last - jnp.arange(w, dtype=jnp.int32)[None], w)
    new = jnp.take_along_axis(
        rows, jnp.clip(pos - first[:, None], 0, rows.shape[1] - 1)[..., None],
        axis=1)
    new = jnp.where((pos >= first[:, None])[..., None], new, ring_rows)
    return ring.at[slots].set(new)


def _window_attend(x, p, ring_k, ring_v, positions, lengths, slots, cfg):
    """What a sliding-window layer's attention adds to ``x``, from the
    rows' rings, and the rings advanced. One token a stream (L == 1)
    writes its key and value into the ring and reads the ring as ``W /
    page`` pages through the shared-cache read (a ring's rows are live
    from index 0 and attention does not care about their order); a chunk
    attends to ring + chunk in blocks of W queries and then keeps its
    last W tokens."""
    import jax
    import jax.numpy as jnp

    from ....ops.diff_attention import (diff_paged_attention,
                                        ring_window_attention)

    b, l, _ = x.shape
    hq, hkv, d, w = (cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                     cfg["window"])
    scale = 1.0 / math.sqrt(d)
    q, k, v = _split_qkv(_ln(x, p, "ln1", cfg["eps"]), p, cfg)
    with jax.named_scope("swa.attend"):
        ps = cfg["page_size"]
        if l == 1 and w % ps == 0:
            ring_k = _ring_write(ring_k, k, None, positions, lengths, slots)
            ring_v = _ring_write(ring_v, v, None, positions, lengths, slots)
            real = _rows_real(positions, lengths)[0][:, 0]
            table = (slots[:, None] * (w // ps)
                     + jnp.arange(w // ps, dtype=jnp.int32)[None])
            live = jnp.where(real, jnp.minimum(positions[:, 0] + 1, w), 0)
            paired = diff_paged_attention(
                q.reshape(b, hq, d), ring_k.reshape(-1, ps, hkv * d),
                ring_v.reshape(-1, ps, hkv * d), table, live,
                n_kv_heads=hkv, scale=scale)[:, None]
        else:
            rows_k, rows_v = ring_k[slots], ring_v[slots]
            paired = ring_window_attention(
                q.reshape(b, l, hq, d), k.reshape(b, l, hkv, d),
                v.reshape(b, l, hkv, d), rows_k, rows_v, positions, lengths,
                window=w, scale=scale)
            ring_k = _ring_write(ring_k, k, rows_k, positions, lengths, slots)
            ring_v = _ring_write(ring_v, v, rows_v, positions, lengths, slots)
        return _combine(paired, p, cfg), ring_k, ring_v


def _window_layer(x, p, ring_k, ring_v, positions, lengths, slots, cfg):
    att, ring_k, ring_v = _window_attend(x, p, ring_k, ring_v, positions,
                                         lengths, slots, cfg)
    return _mlp(x + att, p, cfg["eps"]), ring_k, ring_v


def _self_pair(x, pm, pw, tails, states, ring_k, ring_v, positions, lengths,
               slots, *, cfg):
    """A Mamba layer and the window layer after it: one pair of the
    self-decoder, over every row of the dispatch."""
    x, _, tails, states = _mamba_layer(x, pm, tails, states, positions,
                                       lengths, slots, cfg)
    x, ring_k, ring_v = _window_layer(x, pw, ring_k, ring_v, positions,
                                      lengths, slots, cfg)
    return x, tails, states, ring_k, ring_v


def _middle(x, pm, pf, tails, states, k_arena, v_arena, positions,
            page_table, lengths, slots, *, cfg):
    """The memory-publishing Mamba layer over every row, the
    full-attention layer's keys and values of every row into the pages,
    and each row's LAST real token picked out: its residual stream and
    its memory, (B, 1, .) each, are all the layers above need."""
    import jax
    import jax.numpy as jnp

    b, l, _ = x.shape
    x, y, tails, states = _mamba_layer(x, pm, tails, states, positions,
                                       lengths, slots, cfg)
    with jax.named_scope("yoco.kv"):
        _, k, v = _split_qkv(_ln(x, pf, "ln1", cfg["eps"]), pf, cfg)
        ps = k_arena.shape[1]
        real = _rows_real(positions, lengths)[0]
        page_of = jnp.clip(positions // ps, 0, page_table.shape[1] - 1)
        page = jnp.where(
            real, jnp.take_along_axis(page_table, page_of, axis=1),
            0).reshape(-1)                              # padding -> scratch
        offset = jnp.mod(positions, ps).reshape(-1)
        k_arena = _scatter_rows(k_arena, k.reshape(b * l, -1), page, offset)
        v_arena = _scatter_rows(v_arena, v.reshape(b * l, -1), page, offset)
    last = jnp.clip(lengths - 1 - positions[:, 0], 0, l - 1)[:, None, None]
    return (jnp.take_along_axis(x, last, axis=1),
            jnp.take_along_axis(y, last, axis=1), tails, states, k_arena,
            v_arena)


def _shared_attend(q, p, k_arena, v_arena, page_table, lengths, cfg):
    """What the differential attention of one row a stream adds: queries
    ``q`` (B, 1, Hq * d) over the stream's ``lengths`` live tokens of the
    shared cache, (B, 1, U)."""
    import jax

    from ....ops.diff_attention import diff_paged_attention

    with jax.named_scope("yoco.attend"):
        paired = diff_paged_attention(
            q.reshape(q.shape[0], cfg["num_heads"], cfg["head_dim"]),
            k_arena, v_arena, page_table, lengths,
            n_kv_heads=cfg["num_kv_heads"],
            scale=1.0 / math.sqrt(cfg["head_dim"]))
        return _combine(paired[:, None], p, cfg)


def _full_last(x, pf, k_arena, v_arena, page_table, lengths, *, cfg):
    """The full-attention layer on each row's last token, its keys and
    values already in the pages."""
    q, _, _ = _split_qkv(_ln(x, pf, "ln1", cfg["eps"]), pf, cfg)
    x = x + _shared_attend(q, pf, k_arena, v_arena, page_table, lengths,
                           cfg)
    return _mlp(x, pf, cfg["eps"])


def _cross_pair(x, memory, pg, pc, k_arena, v_arena, page_table, lengths, *,
                cfg):
    """A gated memory unit layer and the cross-attention layer after it:
    one pair of the cross-decoder, on each row's last token."""
    import jax

    from ....ops.ssm import gated_memory_unit

    with jax.named_scope("gmu"):
        x = x + gated_memory_unit(_ln(x, pg, "ln1", cfg["eps"]), memory,
                                  pg["gmu_in"], pg["gmu_out"])
    x = _mlp(x, pg, cfg["eps"])
    h = _ln(x, pc, "ln1", cfg["eps"])
    x = x + _shared_attend(h @ pc["q"].T + pc["q_b"], pc, k_arena, v_arena,
                           page_table, lengths, cfg)
    return _mlp(x, pc, cfg["eps"])


def _tied_head(x, norm_g, norm_b, embed_w, *, eps):
    """The greedy token id and the float32 logits of (B, 1, U) rows; the
    head is the embedding."""
    import jax
    import jax.numpy as jnp

    from ....ops.nn import layer_norm

    with jax.named_scope("lm_head"):
        logits = jnp.einsum(
            "bu,vu->bv", layer_norm(x[:, 0], norm_g, norm_b,
                                    eps=eps).astype(embed_w.dtype),
            embed_w, preferred_element_type=jnp.float32)
        return greedy_pick(logits), logits


class Phi4FlashDecodeEngine(PagedDecodeEngine):
    """The decode engine over one :class:`Phi4FlashModel`: four kinds of
    cache on one page table and one slot a stream.

    ``arenas``: the full-attention layer's key arena and value arena
    (``(pages, page, kv_heads * head_dim)`` each: the ONLY paged cache;
    the cross-attention layers read them and own none). ``slot_arrays``:
    per Mamba layer a convolution tail ``(slots, d_conv - 1, d_inner)``
    and a scan state ``(slots, d_state, d_inner)``, both float32, per window
    layer a key ring and a value ring ``(slots, window, kv_heads *
    head_dim)``; defrag never touches them.

    A forward is the embedding lookup, ONE self-decoder pair program run
    once per Mamba + window pair, the middle program, and, where a row
    of the dispatch is final, the full-attention program, ONE
    cross-decoder pair program run once per pair, and the head: six
    programs a signature whatever the depth. The last three take one row
    a stream in both phases, so a prefill signature shares them with the
    decode signature of its batch bucket."""

    family = "phi4flash"
    chunked_prefill = True
    state_slots = True

    def _extract(self, model, w):
        import jax.numpy as jnp

        def common(blk):
            return {"ln1_g": w(blk.norm1.gamma), "ln1_b": w(blk.norm1.beta),
                    "ln2_g": w(blk.norm2.gamma), "ln2_b": w(blk.norm2.beta),
                    "gate_up": w(blk.mlp.gate_up.weight),
                    "down": w(blk.mlp.down.weight)}

        def heads(m):
            return {"lq1": w(m.lq1), "lk1": w(m.lk1), "lq2": w(m.lq2),
                    "lk2": w(m.lk2), "subln": w(m.subln),
                    "o": w(m.out_weight), "o_b": w(m.out_bias),
                    "lam0": jnp.float32(m._lam0)}

        def layer(blk):
            m = blk.mixer
            if blk.kind == "mamba":
                mixer = {"in": w(m.in_weight), "conv_w": w(m.conv_weight),
                         "conv_b": w(m.conv_bias), "x": w(m.x_weight),
                         "dt_w": w(m.dt_weight), "dt_b": w(m.dt_bias),
                         "a_log": w(m.a_log), "d": w(m.d),
                         "out": w(m.out_weight)}
            elif blk.kind in ("window", "full"):
                mixer = dict(heads(m), qkv=w(m.qkv_weight),
                             qkv_b=w(m.qkv_bias))
            elif blk.kind == "gmu":
                mixer = {"gmu_in": w(m.in_weight),
                         "gmu_out": w(m.out_weight)}
            else:
                mixer = dict(heads(m), q=w(m.q_weight), q_b=w(m.q_bias))
            return dict(common(blk), **mixer)

        return (w(model.embed.weight),
                tuple(layer(blk) for blk in model.blocks),
                w(model.norm.gamma), w(model.norm.beta))

    def _make_arenas(self, pool):
        import jax
        import jax.numpy as jnp

        from ....serving.kvcache import make_latent_arena

        cfg = self.cfg
        # a ring is read as window / page_size pages (_window_layer)
        cfg["page_size"] = pool.page_size
        n_pairs = cfg["num_layers"] // 4
        s = pool.state_slots.n_slots
        width = cfg["num_kv_heads"] * cfg["head_dim"]

        def zeros(shape, dtype):
            return jax.device_put(
                jnp.zeros(shape, dtype, device=self._device), self._device)

        self.slot_arrays = {
            "tails": [zeros((s, cfg["d_conv"] - 1, cfg["d_inner"]),
                            jnp.float32) for _ in range(n_pairs + 1)],
            "states": [zeros((s, cfg["d_state"], cfg["d_inner"]),
                             jnp.float32) for _ in range(n_pairs + 1)],
            "ring_k": [zeros((s, cfg["window"], width), self.dtype)
                       for _ in range(n_pairs)],
            "ring_v": [zeros((s, cfg["window"], width), self.dtype)
                       for _ in range(n_pairs)],
        }
        return make_latent_arena(2, pool, width, self.dtype,
                                 device=self._device)

    def _run(self, b, l, w_pages, tokens, positions, page_table, lengths,
             slots, final):
        import jax

        from .... import telemetry

        phase = "decode" if l == 1 else "prefill"
        sig, last_sig = (b, l, w_pages), (b, 1, w_pages)
        embed_w, layers, norm_g, norm_b = self._params
        cfg, st = self.cfg, self.slot_arrays
        n_pairs = cfg["num_layers"] // 4
        real_rows = _np.asarray(lengths) > _np.asarray(positions)[:, 0]
        reads = real_rows & (final | (l == 1))
        if telemetry._state.enabled:
            if reads.any():
                # the full layer and n_pairs - 1 cross-attention layers
                telemetry.record_shared_kv_read(
                    int(_np.asarray(lengths)[reads].sum()) * n_pairs, phase)
            if l > 1:
                telemetry.record_prefill_rows(
                    int(_np.clip(lengths - positions[:, 0], 0, l).sum()),
                    int(reads.sum()))
        tokens, positions, page_table, lengths, slots = jax.device_put(
            (tokens, positions, page_table, lengths, slots), self._device)
        x = self._fn("embed", *sig, lambda: (_embed_rows, ()))(embed_w,
                                                               tokens)
        pair = self._fn("self", *sig, lambda: (_named(
            _self_pair, f"phi4flash_{phase}_self", cfg=cfg), (3, 4, 5, 6)))
        for i in range(n_pairs):
            (x, st["tails"][i], st["states"][i], st["ring_k"][i],
             st["ring_v"][i]) = pair(
                x, layers[2 * i], layers[2 * i + 1], st["tails"][i],
                st["states"][i], st["ring_k"][i], st["ring_v"][i],
                positions, lengths, slots)
        mid = 2 * n_pairs
        middle = self._fn("mid", *sig, lambda: (_named(
            _middle, f"phi4flash_{phase}_mid", cfg=cfg), (3, 4, 5, 6)))
        (x, memory, st["tails"][n_pairs], st["states"][n_pairs],
         self.arenas[0], self.arenas[1]) = middle(
            x, layers[mid], layers[mid + 1], st["tails"][n_pairs],
            st["states"][n_pairs], self.arenas[0], self.arenas[1],
            positions, page_table, lengths, slots)
        if not reads.any():
            # no row ends a prompt here: the layers above are skipped
            # wholly, and nobody reads the ids
            return _np.zeros((b,), _np.int32), None
        x = self._fn("full", *last_sig, lambda: (_named(
            _full_last, "phi4flash_decode_full", cfg=cfg), ()))(
                x, layers[mid + 1], self.arenas[0], self.arenas[1],
                page_table, lengths)
        cross = self._fn("cross", *last_sig, lambda: (_named(
            _cross_pair, "phi4flash_decode_cross", cfg=cfg), ()))
        for i in range(mid + 2, cfg["num_layers"], 2):
            x = cross(x, memory, layers[i], layers[i + 1], self.arenas[0],
                      self.arenas[1], page_table, lengths)
        return self._fn("head", *last_sig, lambda: (_named(
            _tied_head, "phi4flash_head", eps=cfg["eps"]), ()))(
                x, norm_g, norm_b, embed_w)


def phi4flash_tiny(**kwargs):
    """Test-sized config of the same kinds: 8 layers (two Mamba + window
    pairs, the middle pair, two GMU + cross pairs), a window of 8 tokens
    (shorter than the tests' sequences), 8 / 4 heads of 8."""
    cfg = dict(vocab_size=128, num_layers=8, units=64, ffn_hidden_size=128,
               num_heads=8, num_kv_heads=4, sliding_window=8, d_state=4,
               d_conv=4, expand=2, dt_rank=8)
    cfg.update(kwargs)
    return Phi4FlashModel(**cfg)
