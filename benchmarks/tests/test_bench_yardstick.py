"""The yardstick's arithmetic: FLOPs from shapes against hand counts, the
peaks table, percentiles with failed requests, seeded traffic."""
import math

import numpy as np
import pytest

from benchmarks.lib import arrivals, flops, peaks, stats


def test_bert_base_flops_by_hand():
    cfg = {"hidden_size": 768, "intermediate_size": 3072,
           "num_hidden_layers": 12, "vocab_size": 30522}
    # per layer and token: QKV 2*768*2304, out 2*768*768, FFN 2*2*768*3072,
    # attention 2*2*512*768
    layer = 3538944 + 1179648 + 9437184 + 1572864
    head = 2 * 768 * 768 + 2 * 768 * 30522
    assert layer == 15728640 and head == 48061440
    assert flops.bert_forward_flops_per_token(cfg, 512) == 12 * layer + head
    assert flops.bert_train_flops_per_token(cfg, 512) == 710415360


def test_mistral_flops_by_hand():
    cfg = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_hidden_layers": 32, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32768}
    # q 2*4096*4096, kv 2*4096*2048, out 2*4096*4096, gate+up
    # 2*4096*28672, down 2*14336*4096, attention at 1000 keys 4*1000*4096
    layer = (33554432 + 16777216 + 33554432 + 234881024 + 117440512
             + 16384000)
    assert flops.decoder_forward_flops_per_token(cfg, 1000) == \
        32 * layer + 2 * 4096 * 32768
    # with no context: 2 x the 7.25 B parameters less the embedding table
    # (a lookup, no operations)
    assert flops.decoder_forward_flops_per_token(cfg, 0) == pytest.approx(
        2 * (7.248e9 - 32768 * 4096), rel=0.001)


def test_peaks_exact_kind_only():
    v5e = peaks.load("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.load("TPU v5")            # no substring matching
    with pytest.raises(peaks.UnknownDevice):
        peaks.load("cpu")


def test_percentile_matches_numpy_and_carries_inf():
    xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0, 25, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], 50) is None
    # one failed request in twenty pushes the p95 to +inf's neighbour
    assert stats.percentile([1.0] * 19 + [math.inf], 95) == math.inf
    assert stats.percentile([1.0] * 99 + [math.inf], 50) == 1.0
    assert stats.spread([10.0, 10.0, 12.0, 8.0]) == pytest.approx(0.1)


def test_open_loop_schedule_is_seeded():
    mix = {"rate_rps": 20.0,
           "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                          "min": 32, "max": 2048},
           "output_len": {"dist": "uniform", "min": 16, "max": 64}}
    a = arrivals.open_loop_schedule(7, mix, 1000, 30.0)
    b = arrivals.open_loop_schedule(7, mix, 1000, 30.0)
    c = arrivals.open_loop_schedule(8, mix, 1000, 30.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert [r.due_s for r in a] != [r.due_s for r in c]
    # the amount of work is the traffic file's, not the seed's
    assert len(a) == len(c) == 600
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in c]
    assert all(0 <= r.due_s < 30.0 for r in a)
    lens = [r.prompt.size for r in a]
    assert min(lens) >= 32 and max(lens) <= 2048
    assert 180 < np.median(lens) < 360
    assert all(16 <= r.max_new <= 64 for r in a)
    assert all(r.prompt.min() >= 1 for r in a)      # 0 is the pad id


def test_quantile_lengths():
    spec = {"dist": "lognormal", "median": 256, "sigma": 1.0,
            "min": 32, "max": 2048}
    q = arrivals.quantile_lengths(spec, 1000)
    assert (np.diff(q) >= 0).all() and q[0] == 32 and q[-1] == 2048
    assert abs(int(np.median(q)) - 256) <= 1
    # P(z > ln(2048/256)) = 1.9% of the prompts are clipped to the maximum
    assert 15 <= (q == 2048).sum() <= 22
    u = arrivals.quantile_lengths({"dist": "uniform", "min": 16, "max": 64},
                                  490)
    assert u.min() == 16 and u.max() == 64
    assert set(np.bincount(u)[16:]) == {10}
    rs = np.random.RandomState(0)
    blocks = arrivals.draw_lengths(rs, spec, 48, block=16)
    assert all(sorted(blocks[i:i + 16]) == list(
        arrivals.quantile_lengths(spec, 16)) for i in (0, 16, 32))


def test_closed_loop_schedule():
    mix = {"clients": 3,
           "prompt_len": {"dist": "fixed", "value": 10},
           "output_len": {"dist": "fixed", "value": 4}}
    clients = arrivals.closed_loop_schedule(1, mix, 100, 5)
    assert [len(c) for c in clients] == [5, 5, 5]
    assert sorted(r.index for c in clients for r in c) == list(range(15))
    assert {r.client for r in clients[2]} == {2}
