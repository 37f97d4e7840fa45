"""BERT masked-LM pretraining under ``parallel.TrainStep``, built from a
config file the way ``chip_smoke.py`` / ``bench_bert.py`` build it."""
from __future__ import annotations

import numpy as np

KIND = "train"


def build(config: dict, traffic: dict, seed: int, devices) -> dict:
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.model_zoo.nlp import bert

    a = config["assumed"]
    chips = len(devices)
    mx.random.seed(seed)
    net = bert.BERTForPretrainFused(
        vocab_size=config["vocab_size"],
        token_type_vocab_size=config["type_vocab_size"],
        max_length=config["max_position_embeddings"],
        num_layers=config["num_hidden_layers"],
        units=config["hidden_size"],
        hidden_size=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        dropout=config["hidden_dropout_prob"],
        attn_dropout=config["attention_probs_dropout_prob"],
        chunk=a["ce_chunk"])
    ctx = mx.tpu(0)
    net.initialize(ctx=ctx)
    net.cast(config["dtype"])
    mesh = par.make_mesh({"dp": chips}, devices=list(devices))
    step = par.TrainStep(
        net, lambda outs, *rest: outs, a["optimizer"], mesh=mesh,
        loss_only=True,
        optimizer_params={"learning_rate": a["learning_rate"],
                          "multi_precision": a["multi_precision"]})
    return {"net": net, "step": step, "ctx": ctx, "seed": seed}


def make_pool(built: dict, config: dict, traffic: dict, seed: int) -> list:
    """``pool`` seeded batches of (masked tokens, original tokens): 15% of
    the positions are replaced by the mask id, the label is the original
    token at EVERY position (the fused head scores every position)."""
    import mxnet_tpu as mx

    a = config["assumed"]
    rs = np.random.RandomState(seed)
    shape = (traffic["batch"], traffic["seq"])
    pool = []
    for _ in range(traffic["pool"]):
        labels = rs.randint(1, config["vocab_size"], shape).astype(np.int32)
        masked = rs.random_sample(shape) < a["mask_rate"]
        tokens = np.where(masked, a["mask_token_id"], labels).astype(np.int32)
        pool.append((mx.nd.array(tokens, ctx=built["ctx"]),
                     mx.nd.array(labels, ctx=built["ctx"])))
    return pool


def eval_loss(built: dict, tokens: np.ndarray, labels: np.ndarray) -> float:
    """The system's eval-mode loss (dropout off) on the given rows, through
    the net's own forward. Settles deferred parameters on first use."""
    import mxnet_tpu as mx

    # deferred parameters draw their values at the first forward
    mx.random.seed(built["seed"])
    ctx = built["ctx"]
    per_pos = built["net"](mx.nd.array(tokens, ctx=ctx),
                           mx.nd.array(labels, ctx=ctx))
    return float(per_pos.asnumpy().astype(np.float64).mean())


def export_weights(built: dict) -> dict:
    """The net's weights under the reference's names, as device arrays in
    the dtype they are held in."""
    net = built["net"]

    def w(p):
        return p.data().data

    bert = net.bert
    layers = []
    for cell in bert.encoder.cells._children.values():
        att, ffn = cell.attention, cell.ffn
        layers.append({
            "qkv_w": w(att.qkv_proj.weight), "qkv_b": w(att.qkv_proj.bias),
            "out_w": w(att.out_proj.weight), "out_b": w(att.out_proj.bias),
            "ffn1_w": w(ffn.ffn1.weight), "ffn1_b": w(ffn.ffn1.bias),
            "ffn2_w": w(ffn.ffn2.weight), "ffn2_b": w(ffn.ffn2.bias),
            "ln1_g": w(cell.ln1.gamma), "ln1_b": w(cell.ln1.beta),
            "ln2_g": w(cell.ln2.gamma), "ln2_b": w(cell.ln2.beta)})
    return {
        "word_embed": w(bert.word_embed.weight),
        "pos_embed": w(bert.position_embed.weight),
        "embed_ln_g": w(bert.embed_ln.gamma),
        "embed_ln_b": w(bert.embed_ln.beta),
        "layers": layers,
        "head_w": w(net.decoder_transform.weight),
        "head_b": w(net.decoder_transform.bias),
        "head_ln_g": w(net.decoder_ln.gamma),
        "head_ln_b": w(net.decoder_ln.beta),
        "vocab_bias": w(net.vocab_bias)}


def flops_per_token(config: dict, traffic: dict) -> int:
    from benchmarks.lib import flops

    return flops.bert_train_flops_per_token(config, traffic["seq"])
