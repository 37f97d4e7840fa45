"""Phi-4-mini-flash-reasoning (``phi4flash``, SambaY): Mamba and
sliding-window layers whose state lives in per-stream state slots, ONE
full-attention K/V cache in pages that seven cross-attention layers read,
gated memory units, differential attention: the zoo model, its decode
engine, the ops, the slot allocator and the server's slot seam, held to
the plain reference in ``benchmarks/references/phi4flash.py`` on seeded
weights (float32, tiny widths that keep every kind: 8 layers, a window of
8 tokens, 8 / 4 heads of 8, a scan state of 4)."""
import hashlib
import json
import os
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.nlp import (glm_moe_dsa_tiny, llama_tiny,
                                           longcat_flash_tiny, phi4flash)
from mxnet_tpu.ops import diff_attention as da
from mxnet_tpu.ops.ssm import gated_memory_unit, selective_scan
from mxnet_tpu.serving.engine import PagedDecodeEngine
from mxnet_tpu.serving.kvcache import (CacheFull, PagePool, Preempted,
                                       StateSlots)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.builders import phi4flash as builder  # noqa: E402
from benchmarks.references import phi4flash as ref  # noqa: E402

pytestmark = pytest.mark.serving

TOL = 2e-5      # float32 on both sides; logits are O(10)
WINDOW = 8


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny_phi4flash.json")) as f:
        return json.load(f)


_BUILT = {}


def tiny(seed=3):
    """(net, the reference's weights, config) of the benchmark's tiny
    configuration, seeded as the builder seeds the real one."""
    if seed not in _BUILT:
        config = _config()
        net, _ = builder.build_net(config, seed, ctx=mx.cpu())
        _BUILT[seed] = (net, builder.export_weights({"net": net}), config)
    return _BUILT[seed]


def _tokens(seed, *shape):
    return np.random.RandomState(seed).randint(1, 256, shape).astype(np.int32)


def _pool(pages=41, page=8, slots=5):
    return PagePool(pages, page, n_state_slots=slots)


def _admit(pool, owners, n_tokens):
    table = np.stack([pool.page_table(o, pool.pages_for(n_tokens))
                      for o in owners if pool.alloc(o, n_tokens)])
    return table, np.asarray([pool.state_slots.alloc(o) for o in owners],
                             np.int32)


def _prefill(engine, toks, lens, table, slots, chunk):
    """``toks`` (B, L) prefilled ``chunk`` tokens a dispatch, rows of
    ``lens`` real tokens; the logits of each row's final chunk."""
    b = toks.shape[0]
    logits = [None] * b
    for off in range(0, int(lens.max()), chunk):
        n = np.clip(lens - off, 0, chunk)
        part = np.zeros((b, chunk), np.int32)
        for i in range(b):
            part[i, :n[i]] = toks[i, off:off + n[i]]
        final = (n > 0) & (off + chunk >= lens)
        engine.prefill(part, np.minimum(lens, off + chunk), table,
                       np.full((b,), off, np.int32) if off else None,
                       slots, final)
        for i in np.nonzero(final)[0]:
            logits[i] = engine.last_logits()[i]
    return logits


# -- ops ------------------------------------------------------------------------

def _scan_inputs(b=2, l=13, d=16, n=4, seed=0):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)  # noqa: E731
    return (f(b, l, d), jnp.abs(f(b, l, d)) * 0.1, -jnp.abs(f(n, d)),
            f(b, l, n), f(b, l, n), f(d), f(b, n, d))


@pytest.mark.parametrize("block", [1, 4, 16])
def test_scan_chunk_form_is_the_step_form_token_by_token(block):
    u, dt, a, b_, c, d, s0 = _scan_inputs()
    y, s = selective_scan(u, dt, a, b_, c, d, s0, block=block)
    state, steps = s0, []
    for t in range(u.shape[1]):
        y_t, state = selective_scan(u[:, t:t + 1], dt[:, t:t + 1], a,
                                    b_[:, t:t + 1], c[:, t:t + 1], d, state)
        steps.append(y_t)
    assert np.abs(np.asarray(y - jnp.concatenate(steps, 1))).max() < 1e-5
    assert np.abs(np.asarray(s - state)).max() < 1e-5


def test_scan_is_the_written_recurrence():
    u, dt, a, b_, c, d, s0 = (np.asarray(x, np.float64)
                              for x in _scan_inputs(b=1, l=6))
    s, want = s0[0].copy(), []
    for t in range(6):
        s = np.exp(dt[0, t][None] * a) * s \
            + (dt[0, t] * u[0, t])[None] * b_[0, t][:, None]
        want.append((s * c[0, t][:, None]).sum(0) + d * u[0, t])
    y, s_out = selective_scan(*_scan_inputs(b=1, l=6))
    assert np.abs(np.asarray(y[0]) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(s_out[0]) - s).max() < 1e-5


def test_scan_zero_step_is_an_identity_step():
    u, dt, a, b_, c, d, s0 = _scan_inputs()
    _, s_short = selective_scan(u[:, :9], dt[:, :9], a, b_[:, :9], c[:, :9],
                                d, s0)
    _, s_padded = selective_scan(u, dt.at[:, 9:].set(0.0), a, b_, c, d, s0)
    assert np.abs(np.asarray(s_short - s_padded)).max() < 1e-6
    # and bit for bit where every step is one
    _, s_same = selective_scan(u, jnp.zeros_like(dt), a, b_, c, d, s0)
    assert np.array_equal(np.asarray(s_same), np.asarray(s0))


def test_gated_memory_unit_against_a_direct_computation():
    rs = np.random.RandomState(1)
    h, m = rs.randn(3, 5, 8), rs.randn(3, 5, 12)
    w1, w2 = rs.randn(12, 8), rs.randn(8, 12)
    g = h @ w1.T
    want = (m * g / (1 + np.exp(-g))) @ w2.T
    out = gated_memory_unit(*(jnp.asarray(x, jnp.float32)
                              for x in (h, m, w1, w2)))
    assert np.abs(np.asarray(out) - want).max() < 1e-4


def test_differential_combine_against_a_direct_computation():
    rs = np.random.RandomState(2)
    paired = rs.randn(3, 2, 2, 2, 16)               # (rows, P, g, 2, 2d)
    lq1, lk1, lq2, lk2 = rs.randn(4, 8) * 0.3
    gain, lam0 = rs.rand(16) + 0.5, 0.37
    lam = np.exp(lq1 @ lk1) - np.exp(lq2 @ lk2) + lam0
    diff = paired[..., 0, :] - lam * paired[..., 1, :]
    want = (1 - lam0) * diff / np.sqrt((diff ** 2).mean(-1, keepdims=True)
                                       + 1e-5) * gain
    out = da.diff_attention_combine(
        *(jnp.asarray(x, jnp.float32)
          for x in (paired, lq1, lk1, lq2, lk2, gain)), lambda_init=lam0)
    assert out.shape == (3, 2 * 2 * 16)
    assert np.abs(np.asarray(out) - want.reshape(3, -1)).max() < 1e-5
    assert phi4flash.lambda_init(17) == pytest.approx(
        0.8 - 0.6 * np.exp(-0.3 * 17))


def _dense_paired(q, k, v, window):
    """Masked dense paired attention by explicit head indices."""
    l, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    out = np.zeros((l, hkv // 2, g, 2, 2 * d))
    pos = np.arange(l)
    seen = (pos[None] <= pos[:, None]) & (pos[None] > pos[:, None] - window)
    for i in range(hq // 2):
        p = i // g
        for j in range(2):
            s = q[:, 2 * i + j] @ k[:, 2 * p + j].T / np.sqrt(d)
            s = np.where(seen, s, -np.inf)
            prob = np.exp(s - s.max(-1, keepdims=True))
            prob /= prob.sum(-1, keepdims=True)
            out[:, p, i - g * p, j] = prob @ v[:, 2 * p:2 * p + 2].reshape(
                l, 2 * d)
    return out


@pytest.mark.parametrize("chunk", [1, 5, 8, 16])
def test_ring_attention_is_masked_dense_attention_across_the_wrap(chunk):
    """A sequence of 40 tokens through a ring of 8 a dispatch of ``chunk``
    tokens at a time (one token: a decode step; 16: blocks of the window)
    against one masked dense product."""
    rs = np.random.RandomState(3)
    l, hq, hkv, d, w = 40 if chunk != 16 else 48, 8, 4, 8, WINDOW
    q, k, v = (rs.randn(l, h, d) for h in (hq, hkv, hkv))
    want = _dense_paired(q, k, v, w)
    ring_k = ring_v = jnp.zeros((1, w, hkv * d), jnp.float32)
    got = []
    for off in range(0, l, chunk):
        n = min(chunk, l - off)
        pad = lambda x: jnp.asarray(np.pad(  # noqa: E731
            x[off:off + n], ((0, chunk - n), (0, 0), (0, 0))),
            jnp.float32)[None]
        pos = off + jnp.arange(chunk, dtype=jnp.int32)[None]
        lens = jnp.array([off + n], jnp.int32)
        out = da.ring_window_attention(pad(q), pad(k), pad(v), ring_k,
                                       ring_v, pos, lens, window=w,
                                       scale=d ** -0.5)
        got.append(np.asarray(out[0, :n]))
        slot = jnp.zeros((1,), jnp.int32)
        ring_k, ring_v = (phi4flash._ring_write(
            r, pad(x).reshape(1, chunk, -1), r, pos, lens, slot)
            for r, x in ((ring_k, k), (ring_v, v)))
    assert np.abs(np.concatenate(got) - want).max() < 1e-5
    # the ring holds the last ``w`` tokens, token t at index t % w
    for t in range(l - w, l):
        assert np.allclose(np.asarray(ring_k[0, t % w]), k[t].reshape(-1),
                           atol=1e-6)


def test_paged_read_is_the_dense_read_and_the_kernel_is_its_reference():
    rs = np.random.RandomState(4)
    b, hq, hkv, d, ps, pages = 3, 8, 8, 16, 8, 20     # rows of 128 lanes
    k_arena, v_arena = (jnp.asarray(rs.randn(pages, ps, hkv * d),
                                    jnp.float32) for _ in range(2))
    table = jnp.asarray(rs.permutation(np.arange(1, pages))[:b * 5]
                        .reshape(b, 5), jnp.int32)
    lengths = jnp.array([37, 0, 9], jnp.int32)
    q = jnp.asarray(rs.randn(b, hq, d), jnp.float32)
    out = da.diff_paged_attention(q, k_arena, v_arena, table, lengths,
                                  n_kv_heads=hkv, scale=0.25)
    assert out.shape == (b, hkv // 2, 1, 2, 2 * d)
    for row, n in ((0, 37), (2, 9)):
        rows = np.asarray(table[row])[np.arange(n) // ps], np.arange(n) % ps
        k = np.asarray(k_arena)[rows].reshape(n, hkv, d)
        v = np.asarray(v_arena)[rows].reshape(n, hkv, d)
        qs = np.zeros((n, hq, d))
        qs[-1] = np.asarray(q[row])
        want = _dense_paired(qs, k, v, n)[-1]
        assert np.abs(np.asarray(out[row]) - want).max() < 1e-5
    assert not np.asarray(out[1]).any()             # a padding row
    from mxnet_tpu.pallas_kernels.paged_attention import (
        diff_paged_decode_kernel, diff_paged_shape_supported)

    wide = da.spread_queries(q, hkv)
    assert diff_paged_shape_supported(wide, k_arena, v_arena)
    kernel = diff_paged_decode_kernel(wide, k_arena, v_arena, table, lengths,
                                      scale=0.25, interpret=True)
    oracle = da._diff_paged_reference(wide, k_arena, v_arena, table, lengths,
                                      0.25)
    assert np.abs(np.asarray(kernel - oracle)).max() < 1e-5
    assert not diff_paged_shape_supported(wide[:, :, :64], k_arena, v_arena)


# -- model, engine, reference -----------------------------------------------------

def test_gluon_forward_is_the_references():
    net, weights, config = tiny()
    toks = _tokens(0, 2, 29)
    out = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    want = np.asarray(ref.logits_at(weights, config, toks[1],
                                    np.arange(29)))
    assert np.abs(out[1] - want).max() < TOL
    assert phi4flash.layer_kinds(8) == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu",
        "cross"]
    assert ref.kinds(8) == ["mamba", "window", "mamba", "window", "publish",
                            "full", "gmu", "cross"]


def test_engine_chunks_and_decode_match_the_reference():
    """Two streams of different depth and a padding row, prompts longer
    than the window (8) and than a chunk (16), prefilled in chunks and
    decoded through slots and pages: every final chunk's and every decode
    step's logits are the reference's."""
    net, weights, config = tiny()
    pool = _pool()
    engine = net.decode_engine(pool)
    toks = _tokens(1, 2, 48)
    lens = np.array([37, 21], np.int32)
    table, slots = _admit(pool, ["a", "b"], 48)
    table = np.concatenate([table, np.zeros((2, table.shape[1]), np.int32)])
    slots = np.concatenate([slots, np.zeros((2,), np.int32)])   # padding
    padded = np.concatenate([toks, np.zeros((2, 48), np.int32)])
    lens4 = np.concatenate([lens, np.zeros((2,), np.int32)])
    got = _prefill(engine, padded, lens4, table, slots, 16)
    want = [np.asarray(ref.logits_at(weights, config, toks[i],
                                     np.arange(48))) for i in range(2)]
    for i in range(2):
        assert np.abs(got[i] - want[i][lens[i] - 1]).max() < TOL
    for _ in range(6):
        lens4[:2] += 1
        step = np.zeros((4,), np.int32)
        step[:2] = [toks[i, lens4[i] - 1] for i in range(2)]
        engine.decode_step(step, lens4, table, slots)
        for i in range(2):
            assert np.abs(engine.last_logits()[i]
                          - want[i][lens4[i] - 1]).max() < TOL


def test_chunked_prefill_equals_one_shot_prefill():
    net, _, _ = tiny()
    engine = net.decode_engine(_pool())
    toks = _tokens(2, 2, 40)
    whole = engine.forward_full(toks)
    assert np.abs(engine.forward_full(toks, chunk=16) - whole).max() < TOL
    assert np.abs(engine.forward_full(toks, chunk=8) - whole).max() < TOL
    # scratch pages and scratch slots are freed
    assert engine.pool.stats()["used"] == 0
    assert engine.pool.state_slots.stats()["used"] == 0


def _slot_state(engine, slot):
    return {k: [np.asarray(a[slot]) for a in v]
            for k, v in engine.slot_arrays.items()}


def test_a_padded_tail_leaves_state_tail_and_ring_at_the_last_real_token():
    net, _, _ = tiny()
    toks = _tokens(3, 1, 32)
    states = []
    for bucket in (16, 32):             # 13 real tokens + 3 or 19 padded
        pool = _pool()
        engine = net.decode_engine(pool)
        table, slots = _admit(pool, ["a"], 32)
        part = np.zeros((1, bucket), np.int32)
        part[0, :13] = toks[0, :13]
        engine.prefill(part, np.array([13], np.int32), table, None, slots)
        states.append(_slot_state(engine, slots[0]))
    for key in states[0]:
        for a, b in zip(states[0][key], states[1][key]):
            assert np.abs(a - b).max() < 1e-6, key
    # and they are what 13 one-token steps leave
    pool = _pool()
    engine = net.decode_engine(pool)
    table, slots = _admit(pool, ["a"], 32)
    for t in range(13):
        engine.decode_step(toks[:, t], np.array([t + 1], np.int32), table,
                           slots)
    by_steps = _slot_state(engine, slots[0])
    for key in by_steps:
        for a, b in zip(states[0][key], by_steps[key]):
            assert np.abs(a - b).max() < 1e-5, key


def test_padding_rows_and_short_rows_leave_other_slots_bit_equal():
    net, _, _ = tiny()
    pool = _pool(slots=6)
    engine = net.decode_engine(pool)
    toks = _tokens(4, 3, 16)
    table, slots = _admit(pool, ["a", "b", "c"], 24)
    engine.prefill(toks[:1], np.array([16], np.int32), table[:1], None,
                   slots[:1])
    before = _slot_state(engine, slots[0])
    # a dispatch of b (short of its bucket), c and two padding rows
    rows = np.concatenate([toks[1:], np.zeros((2, 16), np.int32)])
    lens = np.array([9, 16, 0, 0], np.int32)
    tbl = np.concatenate([table[1:], np.zeros((2, table.shape[1]),
                                              np.int32)])
    sl = np.concatenate([slots[1:], np.zeros((2,), np.int32)])
    nxt = engine.prefill(rows, lens, tbl, None, sl)
    engine.decode_step(nxt, lens + (lens > 0), tbl, sl)
    after = _slot_state(engine, slots[0])
    for key in before:
        for a, b in zip(before[key], after[key]):
            assert np.array_equal(a, b), key
    free = [s for s in range(1, 6) if s not in slots]
    for s in free:                      # never handed out: still zeros
        assert not any(a.any() for v in _slot_state(engine, s).values()
                       for a in v)


def test_cross_layers_read_the_full_layers_pages_and_own_none():
    net, _, _ = tiny()
    pool = _pool(pages=41, page=8, slots=5)
    engine = net.decode_engine(pool)
    cfg = engine.cfg
    # ONE key arena and ONE value arena, whatever the depth
    assert len(engine.arenas) == 2
    width = cfg["num_kv_heads"] * cfg["head_dim"]
    for a in engine.arenas:
        assert a.shape == (41, 8, -(-width // 128) * 128)
    n_pairs = cfg["num_layers"] // 4
    st = engine.slot_arrays
    assert [len(st[k]) for k in ("tails", "states", "ring_k", "ring_v")] \
        == [n_pairs + 1, n_pairs + 1, n_pairs, n_pairs]
    assert st["tails"][0].shape == (5, cfg["d_conv"] - 1, cfg["d_inner"])
    assert st["states"][0].shape == (5, cfg["d_state"], cfg["d_inner"])
    assert st["states"][0].dtype == jnp.float32
    assert st["ring_k"][0].shape == (5, WINDOW, width)
    # the cross layers have query weights only
    kinds = phi4flash.layer_kinds(cfg["num_layers"])
    for kind, lp in zip(kinds, engine._params[1]):
        assert ("qkv" in lp) == (kind in ("window", "full"))
        assert ("q" in lp) == (kind == "cross")
    # a token of the whole model costs one key row and one value row
    per_token = sum(a.shape[2] for a in engine.arenas) * 4
    assert per_token == 2 * 128 * 4
    # at the published sizes: 5,120 B a token, 24.2 MB a slot
    assert 2 * 20 * 64 * 2 == 5120
    assert 8 * 512 * 5120 + 9 * (5120 * 16 * 4 + 3 * 5120 * 2) == 24197120


def test_prefill_skip_gives_the_same_logits_and_fewer_cross_rows():
    net, _, _ = tiny()
    toks = _tokens(5, 1, 40)
    rows = {}
    telemetry.enable()
    try:
        for skip in (True, False):
            telemetry.reset()
            pool = _pool()
            engine = net.decode_engine(pool)
            table, slots = _admit(pool, ["a"], 40)
            for off in range(0, 40, 8):
                engine.prefill(toks[:, off:off + 8],
                               np.array([off + 8], np.int32), table,
                               np.array([off], np.int32) if off else None,
                               slots, np.array([off + 8 == 40 or not skip]))
            fam = telemetry.snapshot()["metrics"]["mxnet_prefill_rows_total"]
            rows[skip] = ({s["labels"]["part"]: s["value"]
                           for s in fam["samples"]}, engine.last_logits())
            reads = telemetry.snapshot()["metrics"][
                "mxnet_shared_kv_tokens_read_total"]["samples"]
            # live tokens x the reading layers (the full one + 1 cross)
            want = (40 if skip else 8 + 16 + 24 + 32 + 40) * 2
            assert [s["value"] for s in reads] == [want]
    finally:
        telemetry.disable()
        telemetry.reset()
    assert rows[True][0] == {"self": 40.0, "cross": 1.0}
    assert rows[False][0] == {"self": 40.0, "cross": 5.0}
    assert np.abs(rows[True][1] - rows[False][1]).max() < 1e-6


def test_an_engine_without_slots_is_refused_and_named():
    net, _, _ = tiny()
    with pytest.raises(MXNetError, match="n_state_slots"):
        net.decode_engine(PagePool(9, 8))
    engine = net.decode_engine(_pool())
    with pytest.raises(ValueError, match="slots"):
        engine.decode_step(np.array([1]), np.array([1]), np.zeros((1, 2)))
    assert (engine.state_slots, engine.chunked_prefill) == (True, True)
    assert not PagedDecodeEngine.state_slots


# -- the slot allocator ---------------------------------------------------------------

def test_slot_allocator_alloc_free_full_scratch():
    slots = StateSlots(4)
    got = [slots.alloc(o) for o in "abc"]
    assert sorted(got) == [1, 2, 3]                 # 0 is the scratch slot
    assert slots.stats() == {"free": 0, "used": 3, "reserved": 1,
                             "n_slots": 4}
    with pytest.raises(CacheFull, match="state slots full"):
        slots.alloc("d")
    with pytest.raises(MXNetError, match="already holds"):
        slots.alloc("a")
    assert slots.free("b") == got[1] and slots.free("b") is None
    assert slots.alloc("d") == got[1]
    with pytest.raises(MXNetError, match="scratch"):
        StateSlots(1)
    assert PagePool(4, 8).state_slots is None
    assert PagePool(4, 8, n_state_slots=3).state_slots.n_slots == 3


def test_defrag_leaves_slots_alone():
    pool = _pool(pages=9, page=8, slots=4)
    held = {}
    for o in "abc":
        pool.alloc(o, 16)
        held[o] = pool.state_slots.alloc(o)
    pool.free("b")
    pool.state_slots.free("b")
    assert pool.defrag()                            # pages of c move down
    # a and c still hold what they held: freeing gives those slots back
    assert {o: pool.state_slots.free(o) for o in "ac"} == \
        {o: held[o] for o in "ac"}
    net, _, _ = tiny()
    engine = net.decode_engine(_pool())
    before = _slot_state(engine, 1)
    engine.apply_defrag([(3, 1)])
    assert all(np.array_equal(a, b) for k in before
               for a, b in zip(before[k], _slot_state(engine, 1)[k]))


def test_slot_telemetry():
    telemetry.enable()
    try:
        telemetry.reset()
        slots = StateSlots(4)
        slots.alloc("a"), slots.alloc("b")
        slots.free("a")
        m = telemetry.snapshot()["metrics"]
        value = lambda n: m[n]["samples"][0]["value"]  # noqa: E731
        assert value("mxnet_state_slots_in_use") == 1.0
        assert value("mxnet_state_slot_allocs_total") == 2.0
    finally:
        telemetry.disable()
        telemetry.reset()


# -- the server's slot seam -------------------------------------------------------------

def _server(net, **kw):
    kw = dict(dict(batch_buckets=(1, 4), len_buckets=(8, 16), page_size=8,
                   decode_pages=41, max_generate_tokens=72, dtype="int32",
                   slo_ms=60000.0, defrag_threshold=None,
                   max_prefill_tokens=32), **kw)
    return serving.Server(net, **kw).start()


def _reference_tokens(weights, config, prompt, out):
    seq = np.concatenate([prompt, out])
    logits = np.asarray(ref.logits_at(
        weights, config, seq, np.arange(prompt.size - 1, seq.size - 1)))
    return logits.argmax(-1)


def test_server_serves_multi_chunk_prompts_beside_decoding_streams():
    net, weights, config = tiny()
    srv = _server(net)
    try:
        assert srv.stats()["state_slots"] == {
            "free": 4, "used": 0, "reserved": 1, "n_slots": 5}
        prompts = [_tokens(10 + i, n) for i, n in enumerate((40, 9, 53, 21))]
        # the long ones arrive while the short ones decode
        handles = [srv.submit_generate(p, 12) for p in prompts]
        outs = [h.result(timeout=120.0) for h in handles]
        for p, out in zip(prompts, outs):
            assert out.tolist() == _reference_tokens(weights, config, p,
                                                     out).tolist()
        st = srv.stats()
        assert st["errors"] == 0
        assert st["state_slots"]["used"] == 0 and st["kvcache"]["used"] == 0
        # 40, 53 and 21 tokens against a largest bucket of 16: chunks
        assert st["batches"] > len(prompts)
    finally:
        srv.stop(timeout=30.0)


def test_a_stream_in_a_just_freed_slot_sees_none_of_its_state():
    """Two slots for real streams (bucket 2 + scratch would be 3): the
    third request waits for a slot, gets a used one, and still generates
    what the reference does."""
    net, weights, config = tiny()
    srv = _server(net, batch_buckets=(1, 2))
    try:
        assert srv.stats()["state_slots"]["n_slots"] == 3
        prompts = [_tokens(20 + i, n) for i, n in enumerate((30, 25, 19, 33))]
        handles = [srv.submit_generate(p, 8) for p in prompts]
        for p, h in zip(prompts, handles):
            out = h.result(timeout=120.0)
            assert out.tolist() == _reference_tokens(weights, config, p,
                                                     out).tolist()
        engine = srv._tenants["default"].engine
        assert engine.pool.state_slots.stats()["used"] == 0
    finally:
        srv.stop(timeout=30.0)


def test_slot_is_freed_on_error_and_on_preemption():
    net, _, _ = tiny()
    srv = _server(net, batch_buckets=(1, 2), decode_pages=13)
    try:
        engine = srv._tenants["default"].engine
        slots = engine.pool.state_slots
        # an error inside a dispatch finalizes the stream: slot and pages
        boom = RuntimeError("boom")
        sound = engine.decode_step

        def failing(*a, **kw):
            raise boom

        h = srv.submit_generate(_tokens(30, 12), 6)
        h.next_token(0, timeout=60.0)
        engine.decode_step = failing
        with pytest.raises(RuntimeError, match="boom"):
            h.result(timeout=60.0)
        engine.decode_step = sound
        assert slots.stats()["used"] == 0 and engine.pool.stats()["used"] == 0
        # preemption: a low-priority stream that fills the pool gives
        # its pages AND its slot to a higher-priority arrival
        gate = threading.Event()
        low = srv.submit_generate(_tokens(31, 30), 40, priority=0,
                                  on_token=lambda i, t: gate.set())
        assert gate.wait(60.0)
        high = srv.submit_generate(_tokens(32, 30), 4, priority=5)
        assert len(high.result(timeout=120.0)) == 4
        with pytest.raises(Preempted):
            low.result(timeout=60.0)
        assert slots.stats()["used"] == 0 and engine.pool.stats()["used"] == 0
        assert srv.stats()["preemptions"] == 1
    finally:
        srv.stop(timeout=30.0)


# -- the engines that keep no slots trace what they traced ---------------------------------

ENGINE_JAXPR_SHA = {
    "llama_tiny":       # PR 48's: PR 44's decode step, the fresh prefill
        "9510c2c850ea359d36ab4af1228e2c277423e98c527c006b32154ab8fa11c455",
    "longcat_flash_tiny":
        "3e923ccb954c4cc2c859231265686746ca29064df50b5040b3d89072cfd3c32b",
    "glm_moe_dsa_tiny":
        "ab7eee2ca74f843be775128107f7be43b9a1bce46974217dfd39e8d6d83556da",
}


@pytest.mark.parametrize("make", [llama_tiny, longcat_flash_tiny,
                                  glm_moe_dsa_tiny],
                         ids=lambda f: f.__name__)
def test_engines_without_slots_trace_to_the_same_programs(make, monkeypatch):
    """The slot seam does not enter an engine that does not ask for it:
    every program of a prefill and a decode step of the tiny Llama,
    LongCat and GLM engines has the jaxpr PR 34's commit traces, byte for
    byte (the hashes were taken on that commit's tree; the tiny Llama's
    on PR 48's: its decode step is the text PR 44 gave it, with a key
    and a value page array a layer, its prefill from position 0 the
    ``fresh`` form)."""
    texts = {}

    def recording(self, part, b, l, w_pages, build):
        fn, _ = build()

        def call(*args):
            texts[(self.family, part, b, l)] = str(
                jax.make_jaxpr(fn)(*args))
            return jax.jit(fn)(*args)
        return call

    monkeypatch.setattr(PagedDecodeEngine, "_fn", recording)
    mx.random.seed(0)
    net = make()
    net.initialize()
    pool = PagePool(9, 8)
    engine = net.decode_engine(pool)
    toks = np.arange(1, 17, dtype=np.int32).reshape(2, 8)
    table = np.stack([pool.alloc("a", 12), pool.alloc("b", 12)])
    lens = np.array([8, 5], np.int32)
    nxt = engine.prefill(toks, lens, table)
    engine.decode_step(nxt, lens + 1, table)
    joined = "\n".join(f"{k}\n{v}" for k, v in
                       sorted(texts.items(), key=lambda kv: str(kv[0])))
    assert hashlib.sha256(joined.encode()).hexdigest() == \
        ENGINE_JAXPR_SHA[make.__name__]
