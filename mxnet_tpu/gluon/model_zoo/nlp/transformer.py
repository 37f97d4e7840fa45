"""Transformer encoder/decoder — the GluonNLP NMT capability.

Reference capability: GluonNLP's `transformer_en_de_512` (scripts/nmt) built
on MXNet's fused attention kernels (src/operator/contrib/transformer.cc).
TPU-native re-design: pre/post-LN cells over the fused
`_contrib_sdp_attention` op, sinusoidal positions computed in-graph (no
host-side tables), everything shaped (batch, seq, units) so the `dp`/`sp`
mesh axes shard dims 0/1 directly.
"""
from __future__ import annotations

import math

import numpy as _np

from ...block import HybridBlock
from ... import nn
from .attention import MultiHeadAttention

__all__ = ["PositionwiseFFN", "TransformerEncoderCell",
           "TransformerDecoderCell", "TransformerEncoder",
           "TransformerDecoder", "Transformer", "get_transformer",
           "transformer_sharding_rules"]


class PositionwiseFFN(HybridBlock):
    """reference capability: gluonnlp PositionwiseFFN (ffn1-act-ffn2)."""

    def __init__(self, units, hidden_size, dropout=0.0, activation="relu",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.ffn1 = nn.Dense(hidden_size, flatten=False,
                                 activation=activation, prefix="ffn1_")
            self.ffn2 = nn.Dense(units, flatten=False, prefix="ffn2_")
            self.dropout = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        out = self.ffn2(self.ffn1(x))
        if self.dropout is not None:
            out = self.dropout(out)
        return out


class TransformerEncoderCell(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 pre_norm=False, activation="relu", attn_dropout=0.0,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._pre_norm = pre_norm
        self._drop_rate = float(dropout)
        with self.name_scope():
            self.attention = MultiHeadAttention(units, num_heads,
                                                dropout=dropout,
                                                attn_dropout=attn_dropout,
                                                prefix="attn_")
            self.ffn = PositionwiseFFN(units, hidden_size, dropout=dropout,
                                       activation=activation, prefix="ffn_")
            self.ln1 = nn.LayerNorm(prefix="ln1_")
            self.ln2 = nn.LayerNorm(prefix="ln2_")
            self.dropout = nn.Dropout(dropout) if dropout else None

    def _fused_add_norm(self, F, h, residual, ln, dropout=0.0):
        """``LN(dropout(h) + residual)`` through the fused op (one
        Pallas VMEM pass when gated; eager composition otherwise). The
        LayerNorm child keeps owning gamma/beta — parameter names and
        checkpoints are unchanged — but its forward is bypassed, so a
        deferred shape is settled here first."""
        if ln.gamma._data is None:
            ln._infer_param_shapes(h)
        ctx = h.context
        return F._contrib_fused_layer_norm(
            h, ln.gamma.data(ctx), ln.beta.data(ctx), residual,
            eps=ln._epsilon, dropout=dropout)

    def hybrid_forward(self, F, x, mask=None):
        from ....pallas_kernels.fused_layers import fused_layers_enabled

        if self._pre_norm:
            h = self.attention(self.ln1(x), None, mask) if mask is not None \
                else self.attention(self.ln1(x))
            x = x + (self.dropout(h) if self.dropout else h)
            h = self.ffn(self.ln2(x))
            return x + h
        h = self.attention(x, None, mask) if mask is not None \
            else self.attention(x)
        if fused_layers_enabled():
            # post-LN add+norm pairs collapse into the fused op — the
            # PERF_HISTORY.md residue buckets this PR targets (epilogue re-reads,
            # dropout mask traffic, the LN sweep) in one kernel
            x = self._fused_add_norm(F, h, x, self.ln1,
                                     dropout=self._drop_rate)
            h = self.ffn(x)
            return self._fused_add_norm(F, h, x, self.ln2)
        x = self.ln1(x + (self.dropout(h) if self.dropout else h))
        h = self.ffn(x)
        return self.ln2(x + h)


class TransformerDecoderCell(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.self_attention = MultiHeadAttention(
                units, num_heads, dropout=dropout, causal=True,
                prefix="selfattn_")
            self.cross_attention = MultiHeadAttention(
                units, num_heads, dropout=dropout, cross=True,
                prefix="crossattn_")
            self.ffn = PositionwiseFFN(units, hidden_size, dropout=dropout,
                                       prefix="ffn_")
            self.ln1 = nn.LayerNorm(prefix="ln1_")
            self.ln2 = nn.LayerNorm(prefix="ln2_")
            self.ln3 = nn.LayerNorm(prefix="ln3_")

    def hybrid_forward(self, F, x, memory, mem_mask=None):
        h = self.self_attention(x)
        x = self.ln1(x + h)
        h = self.cross_attention(x, memory, mem_mask) if mem_mask is not None \
            else self.cross_attention(x, memory)
        x = self.ln2(x + h)
        return self.ln3(x + self.ffn(x))


def _sinusoid_table(length, units):
    pos = _np.arange(length)[:, None]
    dim = _np.arange(units)[None, :]
    angle = pos / _np.power(10000, 2 * (dim // 2) / units)
    table = _np.where(dim % 2 == 0, _np.sin(angle), _np.cos(angle))
    return table.astype("float32")


class _PositionalEncoding(HybridBlock):
    """Sinusoidal position table added to embeddings (a Constant param so it
    rides inside the compiled graph; reference capability: gluonnlp
    position_weight)."""

    def __init__(self, max_length, units, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        with self.name_scope():
            self.pos_weight = self.params.get_constant(
                "pos_weight", _sinusoid_table(max_length, units))

    def hybrid_forward(self, F, x, pos_weight):
        l = x.shape[1]
        return x * math.sqrt(self._units) + \
            pos_weight[:l].reshape((1, l, self._units))


class TransformerEncoder(HybridBlock):
    def __init__(self, num_layers=6, units=512, hidden_size=2048,
                 num_heads=8, dropout=0.1, max_length=512,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.pos = _PositionalEncoding(max_length, units, prefix="pos_")
            self.dropout = nn.Dropout(dropout) if dropout else None
            self.cells = nn.HybridSequential(prefix="")
            for i in range(num_layers):
                self.cells.add(TransformerEncoderCell(
                    units, hidden_size, num_heads, dropout=dropout,
                    prefix=f"layer{i}_"))

    def hybrid_forward(self, F, x, mask=None):
        x = self.pos(x)
        if self.dropout is not None:
            x = self.dropout(x)
        for cell in self.cells._children.values():
            x = cell(x, mask) if mask is not None else cell(x)
        return x


class TransformerDecoder(HybridBlock):
    def __init__(self, num_layers=6, units=512, hidden_size=2048,
                 num_heads=8, dropout=0.1, max_length=512,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.pos = _PositionalEncoding(max_length, units, prefix="pos_")
            self.dropout = nn.Dropout(dropout) if dropout else None
            self.cells = []
            for i in range(num_layers):
                cell = TransformerDecoderCell(units, hidden_size, num_heads,
                                              dropout=dropout,
                                              prefix=f"layer{i}_")
                self.cells.append(cell)
                self.register_child(cell, f"layer{i}")

    def hybrid_forward(self, F, x, memory, mem_mask=None):
        x = self.pos(x)
        if self.dropout is not None:
            x = self.dropout(x)
        for cell in self.cells:
            x = cell(x, memory, mem_mask)
        return x


class Transformer(HybridBlock):
    """Full NMT transformer (capability parity: gluonnlp
    transformer_en_de_512). Shared source/target embedding and tied output
    projection (tie_weights)."""

    def __init__(self, src_vocab=32768, tgt_vocab=None, num_layers=6,
                 units=512, hidden_size=2048, num_heads=8, dropout=0.1,
                 max_length=512, shared_embed=True, tie_weights=True,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        tgt_vocab = tgt_vocab or src_vocab
        self._units = units
        with self.name_scope():
            self.src_embed = nn.Embedding(src_vocab, units, prefix="src_embed_")
            if shared_embed and tgt_vocab == src_vocab:
                self.tgt_embed = self.src_embed
            else:
                self.tgt_embed = nn.Embedding(tgt_vocab, units,
                                              prefix="tgt_embed_")
                self.register_child(self.tgt_embed, "tgt_embed")
            self.encoder = TransformerEncoder(
                num_layers, units, hidden_size, num_heads, dropout,
                max_length, prefix="enc_")
            self.decoder = TransformerDecoder(
                num_layers, units, hidden_size, num_heads, dropout,
                max_length, prefix="dec_")
            if tie_weights:
                self.proj = nn.Dense(tgt_vocab, flatten=False, use_bias=False,
                                     params=self.tgt_embed.params,
                                     prefix="tgt_embed_")
            else:
                self.proj = nn.Dense(tgt_vocab, flatten=False, use_bias=False,
                                     prefix="proj_")

    def hybrid_forward(self, F, src_tokens, tgt_tokens, src_mask=None):
        memory = self.encoder(self.src_embed(src_tokens), src_mask)
        dec = self.decoder(self.tgt_embed(tgt_tokens), memory, src_mask)
        return self.proj(dec)


def transformer_sharding_rules(tp_axis="tp"):
    """Megatron-style tensor-parallel layout for transformer blocks.

    Column-parallel QKV/FFN-in (shard output features = weight dim 0 in the
    (out, in) MXNet convention), row-parallel out-proj/FFN-out (shard input
    features = dim 1); embeddings sharded on vocab. GSPMD inserts the
    all-reduces after the row-parallel matmuls.
    """
    from ....parallel import ShardingRules
    from jax.sharding import PartitionSpec as P

    return ShardingRules([
        (r"(qkv|q|kv)_weight$", P(tp_axis, None)),
        (r"(qkv|q|kv)_bias$", P(tp_axis)),
        (r"ffn1_weight$", P(tp_axis, None)),
        (r"ffn1_bias$", P(tp_axis)),
        (r"out_weight$", P(None, tp_axis)),
        (r"ffn2_weight$", P(None, tp_axis)),
        (r"embed_weight$", P(tp_axis, None)),
    ])


def get_transformer(**kwargs):
    return Transformer(**kwargs)
