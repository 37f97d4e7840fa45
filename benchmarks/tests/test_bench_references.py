"""System against the plain references, at tiny widths on the CPU."""
import json
import os

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name, **over):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    return cfg


def test_bert_eval_loss_matches_reference():
    import jax

    from benchmarks.builders import bert_pretrain as builder
    from benchmarks.references import bert_pretrain as reference

    # float32 end to end: any difference is the arithmetic, not rounding
    cfg = _config("tiny_bert", dtype="float32")
    traffic = {"batch": 2, "seq": 32, "pool": 1}
    built = builder.build(cfg, traffic, 3, jax.devices()[:1])
    tokens, labels = (a.asnumpy() for a in
                      builder.make_pool(built, cfg, traffic, 3)[0])
    sys_loss = builder.eval_loss(built, tokens, labels)
    ref_loss = reference.loss(builder.export_weights(built), cfg,
                              tokens, labels)
    assert np.isfinite(ref_loss)
    assert sys_loss == pytest.approx(ref_loss, rel=2e-5)
    # the reference is a function of the labels: a shifted label moves it
    other = reference.loss(builder.export_weights(built), cfg, tokens,
                           (labels + 1) % cfg["vocab_size"])
    assert abs(other - ref_loss) > 1e-3


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 0.05)])
def test_decoder_prefill_and_decode_match_reference(dtype, tol):
    """Prefill, then decoding through the paged cache, against the
    reference's full forward: logits, not tokens."""
    import jax  # noqa: F401
    from mxnet_tpu.serving.kvcache import PagePool

    from benchmarks.builders import llama_family_decoder as builder
    from benchmarks.references import llama_family_decoder as reference

    cfg = _config("tiny_decoder", dtype=dtype)
    net, _ctx = builder.build_net(cfg, 5)
    weights = builder.export_weights({"net": net})
    pool = PagePool(9, 16)
    engine = net.decode_engine(pool)
    rs = np.random.RandomState(0)
    prompt = rs.randint(1, cfg["vocab_size"], (21,)).astype(np.int32)
    owner = object()
    pages = pool.alloc(owner, 32)
    table = np.zeros((1, 8), np.int32)
    table[0, :len(pages)] = pages
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :21] = prompt
    got = [engine.prefill(tokens, np.array([21], np.int32), table)[0]]
    seq = list(prompt)
    for _ in range(4):
        seq.append(int(np.argmax(got[-1])))
        got.append(engine.decode_step(
            np.array([seq[-1]], np.int32),
            np.array([len(seq)], np.int32), table)[0])
    ref = np.asarray(reference.logits_at(
        weights, cfg, np.asarray(seq, np.int32),
        np.arange(20, 20 + len(got))))
    got = np.asarray(got, np.float32)
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= tol * scale
    # seeded weights: the same seed gives the same weights
    again = builder.export_weights({"net": builder.build_net(cfg, 5)[0]})
    assert (np.asarray(again["lm_head"], np.float32)
            == np.asarray(weights["lm_head"], np.float32)).all()
