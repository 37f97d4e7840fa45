"""LongCat-Flash: latent attention, a shortcut-connected expert layer of
which a chip holds a share, zero-compute experts — the zoo model, its
decode engine over a latent paged cache, the dropless routed-expert op
and ``Server(max_prefill_tokens=)``, held to the plain reference in
``benchmarks/references/longcat_flash.py`` on seeded weights (float32,
tiny widths that keep every ratio's kind: two double layers, 8 routed +
4 zero experts, top-3, 2 of the 8 held)."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.gluon.model_zoo.nlp import longcat_flash_tiny
from mxnet_tpu.ops.contrib import moe_routed_experts
from mxnet_tpu.serving.kvcache import (PagePool, apply_defrag,
                                       make_latent_arena)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.references import longcat_flash as ref  # noqa: E402

pytestmark = pytest.mark.serving

# the reference's view of longcat_flash_tiny()
CONFIG = {"num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
          "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
          "rope_theta": 1e7, "rms_norm_eps": 1e-5, "router_outputs": 12,
          "zero_expert_num": 4, "moe_topk": 3, "routed_scaling_factor": 6.0}
TOL = 5e-6      # float32 on both sides; logits are O(1)

_NETS = {}


def tiny_net(seed=0, **kw):
    """One seeded tiny model per (seed, kwargs): Xavier matrices, random
    norm gains, the router drawn wide enough that picks differ in weight,
    a small selection bias."""
    key = (seed, tuple(sorted(kw.items())))
    if key not in _NETS:
        mx.random.seed(seed)
        net = longcat_flash_tiny(**kw)
        net.initialize(mx.init.Xavier(magnitude=2.0))
        rs = np.random.RandomState(seed)
        for name, p in net.collect_params().items():
            if name.endswith("router_bias"):
                v = rs.uniform(-0.01, 0.01, p.shape)
            elif name.endswith("router_weight"):
                v = rs.randn(*p.shape) * 2.0 / np.sqrt(p.shape[1])
            elif len(p.shape) == 1:
                v = rs.uniform(0.5, 1.5, p.shape)       # norm gains
            else:
                continue
            p.set_data(mx.nd.array(v.astype("float32")))
        _NETS[key] = net
    return _NETS[key]


def weights_of(engine):
    embed, layers, norm, head = engine._params
    return {"embed": embed, "layers": layers, "norm": norm, "lm_head": head}


def ref_logits(engine, seq):
    return np.asarray(ref.logits_at(weights_of(engine), CONFIG, seq,
                                    np.arange(len(seq))))


# -- (a) the zoo model's forward against the reference ----------------------

@pytest.mark.parametrize("seed,length", [(0, 5), (1, 12), (2, 17)])
def test_forward_matches_reference(seed, length):
    net = tiny_net(seed)
    engine = net.decode_engine(PagePool(8, 4))
    tokens = np.random.RandomState(seed).randint(1, 128, (2, length))
    out = net(mx.nd.array(tokens.astype("int32"), dtype="int32")).asnumpy()
    for b in range(2):
        np.testing.assert_allclose(out[b], ref_logits(engine, tokens[b]),
                                   atol=TOL, rtol=0)


# -- (b) prefill, then decode through the latent paged cache ----------------

@pytest.mark.parametrize("case", ["exact_batch", "padded_batch",
                                  "scattered_pages"])
def test_prefill_then_decode_matches_reference(case):
    net = tiny_net(0)
    pool = PagePool(64, 4)
    engine = net.decode_engine(pool)
    rs = np.random.RandomState(3)
    prompts = [7, 5] if case != "padded_batch" else [6]
    n_new, width, cap = 8, 8, 2
    seqs = [rs.randint(1, 128, (p + n_new,)) for p in prompts]
    table = np.zeros((cap, width), np.int32)
    owners = [object() for _ in prompts]
    for i, (o, p) in enumerate(zip(owners, prompts)):
        if case == "scattered_pages" and i == 0:
            # take every other page: hold the ones between, then free them
            pages = []
            holes = []
            for _ in range(pool.pages_for(p + n_new)):
                pages.extend(pool.alloc(object(), 1))
                hole = object()
                holes.append(hole)
                pool.alloc(hole, 1)
            for h in holes:
                pool.free(h)
            assert np.any(np.diff(pages) != 1)
        else:
            pages = pool.alloc(o, p + n_new)
        table[i, :len(pages)] = pages
    lengths = np.zeros((cap,), np.int32)
    tokens = np.zeros((cap, 8), np.int32)           # len bucket 8
    for i, (s, p) in enumerate(zip(seqs, prompts)):
        tokens[i, :p] = s[:p]
        lengths[i] = p
    want = [ref_logits(engine, s) for s in seqs]
    ids = engine.prefill(tokens, lengths, table)
    got = engine.last_logits()
    assert got.dtype == np.float32
    assert ids.dtype == np.int32 and ids.shape == (cap,)

    def picks():        # (h) per expert layer, padding rows route nowhere
        return [int(np.asarray(c)[:3].sum()) for c in engine.last_counts]

    assert picks() == 2 * [3 * sum(prompts)]
    for i, p in enumerate(prompts):
        np.testing.assert_allclose(got[i], want[i][p - 1], atol=TOL, rtol=0)
    for step in range(n_new - 1):
        nxt = np.zeros((cap,), np.int32)
        for i, (s, p) in enumerate(zip(seqs, prompts)):
            nxt[i] = s[p + step]
            lengths[i] = p + step + 1
        engine.decode_step(nxt, lengths, table)
        got = engine.last_logits()
        assert picks() == 2 * [3 * len(prompts)]
        for i, p in enumerate(prompts):
            np.testing.assert_allclose(got[i], want[i][p + step],
                                       atol=TOL, rtol=0)


def test_defrag_moves_latent_pages():
    pool = PagePool(8, 2)
    arenas = make_latent_arena(2, pool, 12)
    assert all(a.shape == (8, 2, 128) for a in arenas)  # pages; lanes padded
    a = arenas[0].at[5].set(
        jnp.arange(256, dtype=jnp.float32).reshape(2, 128))
    moved = apply_defrag(a, [(5, 1)])
    np.testing.assert_array_equal(np.asarray(moved[1]), np.asarray(a[5]))


# -- (c)-(e), (h): the routed-expert op -------------------------------------

def _moe_inputs(n, seed=0, units=16, hidden=8, routed=8, zero=4):
    rs = np.random.RandomState(seed)
    f = lambda *s, scale=1.0: jnp.asarray(rs.randn(*s) * scale, jnp.float32)
    return {"x": f(n, units), "router": f(routed + zero, units, scale=0.7),
            "router_bias": f(routed + zero, scale=0.01),
            "gate_up": f(routed, units, 2 * hidden, scale=0.3),
            "down": f(routed, hidden, units, scale=0.3)}


def _consts(first_held, routed=8, top_k=3):
    return {"n_routed": routed, "top_k": top_k, "moe_scale": 6.0,
            "first_held": first_held}


def _run_op(w, first, held, routed=8, zero=4, top_k=3, **kw):
    return moe_routed_experts(
        w["x"], w["router"], w["router_bias"],
        w["gate_up"][first:first + held], w["down"][first:first + held],
        first_held=first, n_routed=routed, n_zero=zero, top_k=top_k,
        scale=6.0, **kw)


def _ref_moe(w, first, held):
    m = {"router": w["router"], "router_bias": w["router_bias"],
         "gate_up": w["gate_up"][first:first + held],
         "down": w["down"][first:first + held]}
    return np.asarray(ref.moe(w["x"], m, _consts(first)))


@pytest.mark.parametrize("n_tokens", [6, 40])
def test_shares_sum_to_the_uncut_layer(n_tokens):
    """The share test: the partial results of the four shares (experts
    0-1, 2-3, 4-5, 6-7), with the zero-expert part that every chip
    computes alike counted once, add up to the uncut layer."""
    w = _moe_inputs(n_tokens, seed=n_tokens)
    zero_part = _ref_moe(w, 0, 0)
    uncut = _ref_moe(w, 0, 8)
    total = np.zeros_like(uncut)
    picks = np.zeros((3,), np.int64)
    for first in (0, 2, 4, 6):
        out, counts = _run_op(w, first, 2)
        np.testing.assert_allclose(np.asarray(out), _ref_moe(w, first, 2),
                                   atol=TOL, rtol=0)
        total += np.asarray(out) - zero_part
        picks += np.asarray(counts[:3])
        # (h) held + zero + absent picks = top_k x tokens, on every share
        assert int(np.sum(np.asarray(counts[:3]))) == 3 * n_tokens
    np.testing.assert_allclose(total + zero_part, uncut, atol=4 * TOL,
                               rtol=0)
    # every routed pick is held by exactly one share
    assert picks[0] + int(counts[1]) == 3 * n_tokens


@pytest.mark.parametrize("n_valid", [0, 3, 10])
def test_counts_leave_out_padding_tokens(n_valid):
    w = _moe_inputs(10, seed=5)
    valid = jnp.arange(10) < n_valid
    out, counts = _run_op(w, 2, 2, valid=valid)
    assert int(jnp.sum(counts[:3])) == 3 * n_valid
    want = _ref_moe(w, 2, 2)
    np.testing.assert_allclose(np.asarray(out)[:n_valid], want[:n_valid],
                               atol=TOL, rtol=0)
    assert not np.asarray(out)[n_valid:].any()      # routed nowhere


def test_all_zero_picks_cost_no_expert_rows():
    """(d) a token whose picks are all zero-compute experts: no row of
    the grouped matmul, and the result is scale * sum(p) * h."""
    w = _moe_inputs(7, seed=1)
    bias = np.zeros((12,), np.float32)
    bias[8:] = 1.0                                  # 4 zero experts, top-3
    w["router_bias"] = jnp.asarray(bias)
    out, counts = _run_op(w, 0, 2)
    assert [int(c) for c in counts] == [0, 21, 0, 0]
    p = np.asarray(jax.nn.softmax(w["x"] @ w["router"].T, axis=-1))
    top3 = np.sort(p[:, 8:], axis=1)[:, -3:].sum(axis=1)
    np.testing.assert_allclose(np.asarray(out),
                               6.0 * top3[:, None] * np.asarray(w["x"]),
                               atol=TOL, rtol=0)


@pytest.mark.parametrize("rows_per_pass", [0, 16, 5])
def test_no_token_dropped_when_all_pick_one_expert(rows_per_pass):
    """(e) every token picks held expert 3: 40 pairs for one expert, in
    one pass or in several, none dropped."""
    w = _moe_inputs(40, seed=2)
    bias = np.zeros((12,), np.float32)
    bias[3] = 1.0
    w["router_bias"] = jnp.asarray(bias)
    out, counts = _run_op(w, 2, 2, rows_per_pass=rows_per_pass)
    assert int(counts[0]) >= 40 and int(counts[3]) >= 1
    np.testing.assert_allclose(np.asarray(out), _ref_moe(w, 2, 2),
                               atol=4 * TOL, rtol=0)


def test_every_pick_held_takes_top_k_passes():
    """All top-3 picks of all tokens go to held experts (3 of 4 held):
    three times the tokens in rows, still dropless."""
    w = _moe_inputs(9, seed=4, routed=4, zero=2)
    bias = np.zeros((6,), np.float32)
    bias[:3] = 1.0
    w["router_bias"] = jnp.asarray(bias)
    out, counts = _run_op(w, 0, 4, routed=4, zero=2, rows_per_pass=9)
    assert [int(c) for c in counts[:3]] == [27, 0, 0]
    m = {k: w[k] for k in ("router", "router_bias", "gate_up", "down")}
    want = np.asarray(ref.moe(w["x"], m, _consts(0, routed=4)))
    np.testing.assert_allclose(np.asarray(out), want, atol=4 * TOL, rtol=0)


# -- (f) the scheduler's bound on one prefill dispatch ----------------------

def _server(net, **kw):
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("dtype", "int32")
    kw.setdefault("warmup", False)
    kw.setdefault("slo_ms", 60000.0)
    kw.setdefault("decode_pages", 96)
    kw.setdefault("page_size", 4)
    kw.setdefault("len_buckets", (8, 16))
    kw.setdefault("defrag_threshold", None)
    return serving.Server(net, **kw)


def _burst(srv, prompts, n_new=3):
    """Submit ``prompts`` from the scheduler thread (inside the token
    callback of a first request), so that they are all pending in ONE
    tick; returns the prefill signatures the engine then saw, the first
    token of each prefill row in dispatch order, and the results."""
    engine = srv._tenants["default"].engine
    seen = []
    inner = engine.prefill

    def spy(tokens, lengths, table):
        seen.append((tokens.shape, [int(t[0]) for t, n in
                                    zip(tokens, lengths) if n]))
        return inner(tokens, lengths, table)

    handles = []

    def on_first(i, _tok):
        if i == 0:
            engine.prefill = spy
            handles.extend(srv.submit_generate(p, n_new) for p in prompts)

    srv.submit_generate(np.array([1, 2, 3], np.int32), 2,
                        on_token=on_first).result(timeout=120)
    results = [h.result(timeout=120) for h in handles]
    engine.prefill = inner
    return seen, results


@pytest.mark.parametrize("bound,want_shapes", [
    (None, [(4, 8), (2, 8)]),       # today: one batch per tick, 4 then 2
    (16, [(2, 8), (2, 8), (2, 8)]),  # bucket(3) x 8 = 32 > 16: pairs
    (8, [(1, 8)] * 6),
])
def test_max_prefill_tokens_splits_a_burst_in_order(bound, want_shapes):
    net = tiny_net(0)
    prompts = [np.array([10 + i, 3, 4, 5, 6], np.int32) for i in range(6)]
    srv = _server(net, max_prefill_tokens=bound).start()
    try:
        before = srv.stats()["batches"]
        seen, results = _burst(srv, prompts)
        assert [s for s, _ in seen] == want_shapes
        assert [t for _, firsts in seen for t in firsts] == \
            [10 + i for i in range(6)]              # arrival order
        assert srv.stats()["batches"] - before == len(want_shapes) + 1
        if bound is not None:
            assert all(s[0] * s[1] <= bound for s, _ in seen)
        # the split changes no token: the same greedy completions
        engine = srv._tenants["default"].engine
        for p, out in zip(prompts, results):
            seq = np.concatenate([p, out])
            logits = ref_logits(engine, seq)
            picked = logits[np.arange(len(p) - 1, len(seq) - 1), out]
            np.testing.assert_allclose(
                picked, logits[len(p) - 1:len(seq) - 1].max(axis=1),
                atol=TOL, rtol=0)
    finally:
        srv.stop()


def test_max_prefill_tokens_must_be_positive():
    with pytest.raises(mx.base.MXNetError):
        _server(tiny_net(0), max_prefill_tokens=0)


# -- (g), (h): steady state and counters through the Server -----------------

@pytest.mark.retrace
def test_zero_steady_state_retraces():
    net = tiny_net(0)
    srv = _server(net).start()
    was = telemetry.enabled()
    telemetry.reset()
    try:
        srv.submit_generate(np.array([3, 1, 4, 1, 5], np.int32),
                            4).result(timeout=120)           # warm
        telemetry.enable()
        srv.submit_generate(np.array([2, 7, 1, 8, 2, 8, 1], np.int32),
                            6).result(timeout=120)
        snap = telemetry.snapshot()["metrics"]
        lookups = {tuple(s["labels"].values()): s["value"]
                   for s in snap["mxnet_jit_cache_total"]["samples"]}
        assert lookups.get(("serving_decode", "hit"), 0) > 0
        assert ("serving_decode", "miss") not in lookups
        # (h) one prefill of 7 tokens and 5 decode steps of one stream,
        # two expert layers each: picks = top_k x tokens x layers
        picks = {tuple(s["labels"].values()): s["value"]
                 for s in snap["mxnet_moe_picks_total"]["samples"]}
        for phase, tokens in (("prefill", 7), ("decode", 5)):
            assert sum(v for (to, ph), v in picks.items()
                       if ph == phase) == 3 * tokens * 2
        calls = {s["labels"]["phase"]: s["value"] for s in
                 snap["mxnet_moe_layer_calls_total"]["samples"]}
        assert calls == {"prefill": 2, "decode": 10}      # layers x calls
        touched = snap["mxnet_moe_held_experts_touched"]["samples"][0]
        assert touched["count"] == 5
    finally:
        srv.stop()
        telemetry.reset()
        if not was:
            telemetry.disable()


def test_engines_of_both_families_share_one_pool_and_site():
    """A Llama-family tenant and a LongCat tenant on one server: one
    PagePool, one compile-cache site, two sorts of arena row."""
    fixtures = os.path.join(os.path.dirname(__file__), "fixtures")
    if fixtures not in sys.path:
        sys.path.insert(0, fixtures)
    import worker_factory

    srv = _server(worker_factory.tiny_llama(seed=7))
    srv.register_model("longcat", tiny_net(0))
    srv.start()
    try:
        a = srv.submit_generate(np.array([3, 1, 4], np.int32), 3)
        b = srv.submit_generate(np.array([3, 1, 4], np.int32), 3,
                                model="longcat")
        assert a.result(timeout=120).shape == (3,)
        assert b.result(timeout=120).shape == (3,)
        engines = {n: t.engine for n, t in srv._tenants.items()}
        assert engines["default"].pool is engines["longcat"].pool
        assert len(engines["longcat"].arenas) == 4
    finally:
        srv.stop()
