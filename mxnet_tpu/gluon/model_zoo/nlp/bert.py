"""BERT — the GluonNLP pretraining/finetune capability.

Reference capability: gluonnlp `bert_12_768_12` / `bert_24_1024_16`
(BERTModel + BERTEncoder over MXNet fused attention,
src/operator/contrib/transformer.cc). TPU-native re-design: post-LN encoder
cells over `_contrib_sdp_attention` (f32 softmax, Pallas flash path),
learned position embeddings added in-graph, bf16-friendly throughout. The
masked-LM decoder ties the word embedding, and the pooler/NSP heads match
the reference model surface so finetune scripts port 1:1.
"""
from __future__ import annotations

from ...block import HybridBlock
from ... import nn
from .transformer import TransformerEncoderCell

__all__ = ["BERTEncoder", "BERTModel", "BERTForPretrainFused",
           "bert_12_768_12", "bert_24_1024_16",
           "bert_sharding_rules"]


class BERTEncoder(HybridBlock):
    """Stack of post-LN transformer cells with GELU FFN."""

    def __init__(self, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, dropout=0.1, attn_dropout=0.0,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.cells = nn.HybridSequential(prefix="")
            for i in range(num_layers):
                # BERT FFN uses GELU (reference: gluonnlp BERTEncoder);
                # attn_dropout = dropout ON the attention probabilities
                # (gluonnlp BERTEncoder attention_dropout), generated
                # inside the flash kernels
                self.cells.add(TransformerEncoderCell(
                    units, hidden_size, num_heads, dropout=dropout,
                    activation="gelu", attn_dropout=attn_dropout,
                    prefix=f"layer{i}_"))

    def hybrid_forward(self, F, x, mask=None):
        for cell in self.cells._children.values():
            x = cell(x, mask) if mask is not None else cell(x)
        return x


class BERTModel(HybridBlock):
    """word + token-type + position embeddings -> encoder -> heads.

    Outputs (matching the reference surface):
      sequence_output (B, L, U); pooled_output (B, U);
      and when ``use_decoder`` the masked-LM logits (B, L, vocab).
    """

    def __init__(self, vocab_size=30522, token_type_vocab_size=2,
                 max_length=512, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, dropout=0.1, attn_dropout=0.0,
                 use_pooler=True,
                 use_classifier=True, use_decoder=True,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._use_pooler = use_pooler
        self._use_classifier = use_classifier
        self._use_decoder = use_decoder
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units,
                                           prefix="word_embed_")
            self.token_type_embed = nn.Embedding(token_type_vocab_size, units,
                                                 prefix="token_type_embed_")
            self.position_embed = nn.Embedding(max_length, units,
                                               prefix="position_embed_")
            self.embed_ln = nn.LayerNorm(prefix="embed_ln_")
            self.embed_dropout = nn.Dropout(dropout) if dropout else None
            self.encoder = BERTEncoder(num_layers, units, hidden_size,
                                       num_heads, dropout,
                                       attn_dropout=attn_dropout,
                                       prefix="enc_")
            if use_pooler:
                self.pooler = nn.Dense(units, flatten=False, activation="tanh",
                                       prefix="pooler_")
            if use_classifier:
                self.classifier = nn.Dense(2, flatten=False,
                                           prefix="classifier_")
            if use_decoder:
                # masked-LM head: transform + tied-embedding output matmul
                self.decoder_transform = nn.Dense(
                    units, flatten=False, activation="gelu",
                    prefix="decoder_transform_")
                self.decoder_ln = nn.LayerNorm(prefix="decoder_ln_")
                self.decoder = nn.Dense(
                    vocab_size, flatten=False,
                    params=self.word_embed.params, prefix="word_embed_")

    def hybrid_forward(self, F, token_ids, token_types=None, valid_mask=None):
        l = token_ids.shape[1]
        positions = F.arange(0, l, dtype="float32", ctx=token_ids.context)
        x = self.word_embed(token_ids)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = x + self.position_embed(positions).reshape((1, l, self._units))
        x = self.embed_ln(x)
        if self.embed_dropout is not None:
            x = self.embed_dropout(x)
        attn_mask = None
        if valid_mask is not None:
            # (B, L) 1/0 -> (B, 1, 1, L): every query may attend valid keys
            attn_mask = valid_mask.reshape(
                (valid_mask.shape[0], 1, 1, valid_mask.shape[1]))
        seq = self.encoder(x, attn_mask)
        outs = [seq]
        pooled = None
        if self._use_pooler:
            pooled = self.pooler(seq[:, 0:1, :].reshape((-1, self._units)))
            outs.append(pooled)
        if self._use_classifier and pooled is not None:
            outs.append(self.classifier(pooled))
        if self._use_decoder:
            h = self.decoder_ln(self.decoder_transform(seq))
            outs.append(self.decoder(h))
        return tuple(outs) if len(outs) > 1 else outs[0]


def bert_sharding_rules(tp_axis="tp"):
    """Megatron TP layout for BERT (same rule shapes as the transformer)."""
    from .transformer import transformer_sharding_rules

    return transformer_sharding_rules(tp_axis)


def bert_12_768_12(**kwargs):
    """BERT-base (reference capability: gluonnlp bert_12_768_12)."""
    cfg = dict(num_layers=12, units=768, hidden_size=3072, num_heads=12)
    cfg.update(kwargs)
    return BERTModel(**cfg)


def bert_24_1024_16(**kwargs):
    """BERT-large (reference capability: gluonnlp bert_24_1024_16)."""
    cfg = dict(num_layers=24, units=1024, hidden_size=4096, num_heads=16)
    cfg.update(kwargs)
    return BERTModel(**cfg)


class BERTForPretrainFused(HybridBlock):
    """BERT masked-LM pretraining with the FUSED projection+CE head.

    Identical parameters and math to ``BERTModel(use_decoder=True)`` + a
    sparse softmax CE over the (B, L, vocab) logits — but the logits are
    never materialized: ``_contrib_softmax_ce_head`` scans vocab chunks
    with an online logsumexp (the SoftmaxOutput lineage taken one step
    further; see ops/fused_loss.py). On BERT-base the logits tensor and
    its relayout copies were ~6 GB of HBM traffic per step (PERF_HISTORY.md
    round 3).

    ``forward(token_ids, mlm_labels) -> (B, L)`` per-position loss; use
    with ``TrainStep(net, loss_fn=mean, loss_only=True)`` passing the
    labels as a second DATA input.

    Parameter-name note: the head lives at THIS block's scope
    (``decoder_transform_*`` / ``decoder_bias``), while
    ``BERTModel(use_decoder=True)`` scopes its head inside the backbone
    — checkpoints move between the two pretraining paths via name-mapped
    ``load_parameters``, not byte-identical files.
    """

    def __init__(self, vocab_size=30522, token_type_vocab_size=2,
                 max_length=512, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, dropout=0.1, attn_dropout=0.0, chunk=5120,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._chunk = chunk
        with self.name_scope():
            self.bert = BERTModel(
                vocab_size=vocab_size,
                token_type_vocab_size=token_type_vocab_size,
                max_length=max_length, num_layers=num_layers, units=units,
                hidden_size=hidden_size, num_heads=num_heads,
                dropout=dropout, attn_dropout=attn_dropout,
                use_pooler=False, use_classifier=False,
                use_decoder=False, prefix="bert_")
            self.decoder_transform = nn.Dense(
                units, flatten=False, activation="gelu",
                prefix="decoder_transform_")
            self.decoder_ln = nn.LayerNorm(prefix="decoder_ln_")
            # output projection stays TIED to the word embedding; its bias
            # is this block's own parameter (reference decoder layout)
            self.vocab_bias = self.params.get(
                "decoder_bias", shape=(vocab_size,), init="zeros")

    def hybrid_forward(self, F, token_ids, mlm_labels, vocab_bias):
        seq = self.bert(token_ids)
        h = self.decoder_ln(self.decoder_transform(seq))
        # the tied projection weight is the backbone's embedding table;
        # under a TrainStep trace p.data() resolves to the traced value,
        # so gradients flow to the shared parameter from BOTH uses
        w = self.bert.word_embed.weight.data(token_ids.context)
        return F._contrib_softmax_ce_head(h, w, vocab_bias, mlm_labels,
                                          chunk=self._chunk)
