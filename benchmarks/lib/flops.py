"""Operations per token, from shapes. Matmul = 2 x M x N x K; attention is
counted; recomputation, elementwise work and embedding lookups are not."""
from __future__ import annotations


def bert_forward_flops_per_token(cfg: dict, seq: int) -> int:
    """Forward pass of BERT pretraining (masked-LM head over every
    position, projection tied to the word embedding), per token of a
    sequence of ``seq`` tokens attending bidirectionally."""
    u = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    v = cfg["vocab_size"]
    layer = (2 * u * 3 * u          # fused QKV projection
             + 2 * u * u            # attention output projection
             + 2 * 2 * u * f        # the two FFN matmuls
             + 2 * 2 * seq * u)     # QK^T and PV over seq keys, all heads
    head = 2 * u * u + 2 * u * v    # transform + vocabulary projection
    return cfg["num_hidden_layers"] * layer + head


def bert_train_flops_per_token(cfg: dict, seq: int) -> int:
    """Forward + backward: the backward pass of a matmul is two matmuls of
    the forward's size."""
    return 3 * bert_forward_flops_per_token(cfg, seq)


def decoder_forward_flops_per_token(cfg: dict, context: int) -> int:
    """Forward pass of a Llama-family decoder for ONE token that attends
    to ``context`` keys (its own included), logits included."""
    u = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or u // h
    layer = (2 * u * h * d              # q projection
             + 2 * u * 2 * kv * d       # fused k, v projection
             + 2 * h * d * u            # attention output projection
             + 2 * u * 2 * f            # gate and up
             + 2 * f * u                # down
             + 2 * 2 * context * h * d)  # QK^T and PV
    return cfg["num_hidden_layers"] * layer + 2 * u * cfg["vocab_size"]
