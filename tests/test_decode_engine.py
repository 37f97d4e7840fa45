"""The contract every served decoder keeps, whatever its cache holds:
``serving.engine.PagedDecodeEngine`` through both of its subclasses
(``LlamaDecodeEngine``: one whole-stack program over a key and a value
page array a layer, and a second form of it for a prefill that starts
at position 0; ``LongcatFlashDecodeEngine``: a layer program per double
layer over latent page arenas), and the seam ``Server`` holds a model
to.
"""
import functools
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import HybridBlock
from mxnet_tpu.gluon.model_zoo.nlp.llama import llama_tiny
from mxnet_tpu.gluon.model_zoo.nlp.longcat_flash import longcat_flash_tiny
from mxnet_tpu.serving.engine import PagedDecodeEngine
from mxnet_tpu.serving.kvcache import PagePool

pytestmark = pytest.mark.serving

# float32 on both sides and logits of O(1); a decode step attends through
# the cache (LongCat: in the latent space) where the oracle's one prefill
# attends over the prompt's own keys, so the sums associate differently
TOL = 2e-5
MODELS = {"llama_tiny": llama_tiny, "longcat_flash_tiny": longcat_flash_tiny}
_NETS = {}


def engine_of(name):
    if name not in _NETS:
        mx.random.seed(11)
        net = MODELS[name]()
        net.initialize()
        _NETS[name] = net
    engine = _NETS[name].decode_engine(PagePool(48, 4))
    assert isinstance(engine, PagedDecodeEngine)
    return engine


def start(engine, seqs, prompt, width):
    """Prefill ``seqs[:, :prompt]`` (one len bucket of 8) on pages the pool
    hands out; returns the owners, the page table and the logits."""
    pool = engine.pool
    owners = [object() for _ in seqs]
    table = np.zeros((len(seqs), width), np.int32)
    for i, o in enumerate(owners):
        pages = pool.alloc(o, seqs.shape[1])
        table[i, :len(pages)] = pages
    tokens = np.zeros((len(seqs), 8), np.int32)
    tokens[:, :prompt] = seqs[:, :prompt]
    engine.prefill(tokens, np.full(len(seqs), prompt, np.int32), table)
    return owners, table, engine.last_logits()


def step(engine, seqs, n, table):
    """The logits of the decode step that has ``seqs[:, :n]`` behind it."""
    engine.decode_step(seqs[:, n - 1], np.full(len(seqs), n, np.int32), table)
    return engine.last_logits()


@pytest.mark.parametrize("name", sorted(MODELS))
def test_prefill_then_decode_matches_the_no_cache_oracle(name):
    engine = engine_of(name)
    prompt, n_new = 5, 6
    seqs = np.random.RandomState(0).randint(1, 100, (2, prompt + n_new))
    _, table, got = start(engine, seqs, prompt, width=4)
    np.testing.assert_allclose(got, engine.forward_full(seqs[:, :prompt]),
                               atol=TOL, rtol=0)
    for n in range(prompt + 1, prompt + n_new + 1):
        np.testing.assert_allclose(step(engine, seqs, n, table),
                                   engine.forward_full(seqs[:, :n]),
                                   atol=TOL, rtol=0)
    assert engine.pool.stats()["used"] == 2 * 3     # the oracle freed its own


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_replayed_defrag_leaves_the_next_logits_unchanged(name):
    seqs = np.random.RandomState(1).randint(1, 100, (2, 9))

    def run(defrag):
        engine = engine_of(name)
        pool = engine.pool
        hole = object()
        pool.alloc(hole, 8)                 # two pages below the streams'
        owners, table, _ = start(engine, seqs, 7, width=3)
        step(engine, seqs, 8, table)
        if defrag:
            pool.free(hole)
            moves = pool.defrag()
            assert moves
            engine.apply_defrag(moves)
            for i, o in enumerate(owners):
                table[i] = pool.page_table(o, width=3)
        return step(engine, seqs, 9, table)

    np.testing.assert_array_equal(run(defrag=True), run(defrag=False))


def test_llama_keeps_a_key_and_a_value_page_array_a_layer():
    """``llama_tiny``'s rows (2 heads x 16) are padded to one lane tile,
    the padding stays zero through prefill, decode and a defrag between
    two decode steps, and the step after it reads what the pages held."""
    engine = engine_of("llama_tiny")
    pool, layers = engine.pool, engine.cfg["num_layers"]
    width = engine.cfg["num_kv_heads"] * engine.cfg["head_dim"]
    assert len(engine.arenas) == 2 * layers
    assert all(a.shape == (pool.n_pages, pool.page_size, 128)
               for a in engine.arenas)
    seqs = np.random.RandomState(4).randint(1, 100, (2, 9))
    hole = object()
    pool.alloc(hole, 8)                     # two pages below the streams'
    owners, table, _ = start(engine, seqs, 7, width=3)
    step(engine, seqs, 8, table)
    before = [np.asarray(a) for a in engine.arenas]
    pool.free(hole)
    moves = pool.defrag()
    assert moves
    engine.apply_defrag(moves)
    for i, o in enumerate(owners):
        table[i] = pool.page_table(o, width=3)
    assert len(engine.arenas) == 2 * layers
    for a, was in zip(engine.arenas, before):
        a = np.asarray(a)
        assert a.shape == was.shape and not a[..., width:].any()
        for src, dst in moves:              # the rows followed their page
            np.testing.assert_array_equal(a[dst], was[src])
        assert any(was[src, :, :width].any() for src, _ in moves)
    np.testing.assert_allclose(step(engine, seqs, 9, table),
                               engine.forward_full(seqs), atol=TOL, rtol=0)


def counted(run, family, *labels):
    """The counter ``family`` by its ``labels`` over ``run()``."""
    was = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        run()
        return {tuple(s["labels"][k] for k in labels): s["value"]
                for s in telemetry.snapshot()["metrics"][family]["samples"]}
    finally:
        telemetry.reset()
        if not was:
            telemetry.disable()


def jit_lookups(run):
    """``mxnet_jit_cache_total`` by (cache, result) over ``run()``."""
    return counted(run, "mxnet_jit_cache_total", "cache", "result")


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_second_forward_of_a_signature_compiles_nothing(name):
    engine = engine_of(name)
    seqs = np.random.RandomState(2).randint(1, 100, (2, 8))
    _, table, _ = start(engine, seqs, 6, width=2)
    step(engine, seqs, 7, table)

    def again():
        start(engine, seqs, 6, width=2)
        step(engine, seqs, 8, table)

    lookups = jit_lookups(again)
    assert lookups.get(("serving_decode", "hit"), 0) > 0
    assert ("serving_decode", "miss") not in lookups


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_ids_are_the_host_argmax_of_the_accessors_logits(name):
    """Row by row, the padding row of the batch bucket included."""
    engine = engine_of(name)
    seqs = np.random.RandomState(3).randint(1, 100, (2, 9))
    table = np.zeros((3, 3), np.int32)              # bucket 3: one padding row
    for i in range(2):
        table[i] = engine.pool.alloc(object(), 9)
    tokens = np.zeros((3, 8), np.int32)
    tokens[:2, :6] = seqs[:, :6]
    ids = engine.prefill(tokens, np.array([6, 6, 0], np.int32), table)
    assert ids.dtype == np.int32 and ids.shape == (3,)
    np.testing.assert_array_equal(ids, np.argmax(engine.last_logits(), -1))
    for n in (7, 8, 9):
        ids = engine.decode_step(np.append(seqs[:, n - 1], 0),
                                 np.array([n, n, 0], np.int32), table)
        assert ids.dtype == np.int32 and ids.shape == (3,)
        logits = engine.last_logits()
        assert logits.shape == (3, engine.cfg["vocab_size"])
        np.testing.assert_array_equal(ids, np.argmax(logits, -1))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_tie_goes_to_the_lower_index(name):
    """Two head rows made equal give two equal logits, exactly; made the
    largest of their row, the lower index is picked, as ``np.argmax``
    picks it."""
    engine = engine_of(name)
    seqs = np.random.RandomState(4).randint(1, 100, (2, 7))
    _, table, _ = start(engine, seqs, 6, width=2)
    logits = step(engine, seqs, 7, table)
    best = int(np.argmax(logits[0]))
    lo, hi = 17, 90
    assert logits[0, best] > 0 and best not in (lo, hi)
    *rest, head_w = engine._params
    # the head row of the first stream's pick, doubled, twice: the same
    # step again (it rewrites the same cache slot) has both above the rest
    twice = 2 * head_w[best]
    engine._params = (*rest, head_w.at[lo].set(twice).at[hi].set(twice))
    try:
        ids = engine.decode_step(seqs[:, 6], np.full(2, 7, np.int32), table)
        tied = engine.last_logits()
    finally:
        engine._params = (*rest, head_w)
    assert tied[0, lo] == tied[0, hi] == tied[0].max()
    assert ids[0] == lo
    np.testing.assert_array_equal(ids, np.argmax(tied, -1))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_served_tokens_are_the_host_argmax_path(name):
    """3 requests x 8 tokens through ``Server.submit_generate`` against
    the path before the pick moved to the device: prefill, then decode
    steps, each next token ``np.argmax`` of the accessor's logits on the
    host."""
    engine = engine_of(name)
    prompts = [np.random.RandomState(5 + i).randint(1, 100, (4 + i,))
               .astype(np.int32) for i in range(3)]
    srv = serving.Server(_NETS[name], batch_buckets=(1, 2, 4),
                         shape_buckets=[(8,)],
                         dtype="int32", warmup=False, slo_ms=60000.0,
                         decode_pages=48, page_size=4, len_buckets=(8,))
    srv.start()
    try:
        handles = [srv.submit_generate(p, 8) for p in prompts]
        served = [h.result(timeout=120) for h in handles]
    finally:
        srv.stop()
    for p, got in zip(prompts, served):
        table = np.zeros((1, 4), np.int32)
        pages = engine.pool.alloc(object(), len(p) + 8)
        table[0, :len(pages)] = pages
        tokens = np.zeros((1, 8), np.int32)
        tokens[0, :len(p)] = p
        engine.prefill(tokens, np.array([len(p)], np.int32), table)
        want = [int(np.argmax(engine.last_logits()[0]))]
        while len(want) < 8:
            engine.decode_step(np.array(want[-1:], np.int32),
                               np.array([len(p) + len(want)], np.int32),
                               table)
            want.append(int(np.argmax(engine.last_logits()[0])))
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 0.05)])
def test_llama_logits_match_the_benchmarks_reference(dtype, tol):
    """``benchmarks/tests/test_bench_references.py``'s decoder case,
    which reads what ``prefill`` and ``decode_step`` return as logits,
    through the accessor: prefill, then four greedy steps through the
    paged cache, against ONE forward of the benchmark's plain float32
    reference over the finished sequence."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.builders import llama_family_decoder as builder
    from benchmarks.references import llama_family_decoder as reference

    with open(os.path.join(root, "benchmarks", "configs",
                           "tiny_decoder.json")) as f:
        cfg = dict(json.load(f), dtype=dtype)
    net, _ctx = builder.build_net(cfg, 5)
    weights = builder.export_weights({"net": net})
    pool = PagePool(9, 16)
    engine = net.decode_engine(pool)
    prompt = np.random.RandomState(0).randint(
        1, cfg["vocab_size"], (21,)).astype(np.int32)
    table = np.zeros((1, 8), np.int32)
    pages = pool.alloc(object(), 32)
    table[0, :len(pages)] = pages
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :21] = prompt
    nxt = engine.prefill(tokens, np.array([21], np.int32), table)
    got = [engine.last_logits()[0]]
    seq = list(prompt)
    for _ in range(4):
        seq.append(int(nxt[0]))
        nxt = engine.decode_step(nxt, np.array([len(seq)], np.int32), table)
        got.append(engine.last_logits()[0])
    assert got[0].dtype == np.dtype(dtype)      # the head's own dtype
    ref = np.asarray(reference.logits_at(
        weights, cfg, np.asarray(seq, np.int32),
        np.arange(20, 20 + len(got))))
    got = np.asarray(got, np.float32)
    assert np.abs(got - ref).max() <= tol * np.abs(ref).max()
    np.testing.assert_array_equal(seq[21:], np.argmax(got[:-1], -1))


@pytest.mark.parametrize("name,programs", [("llama_tiny", 1),
                                           ("longcat_flash_tiny", 3)])
def test_a_new_signature_compiles_as_many_programs_as_before(name, programs):
    """The pick is made in the forward's last program: a signature is
    still one program for Llama, and embedding, layer and head for
    LongCat."""
    engine = engine_of(name)
    lookups = jit_lookups(lambda: engine.decode_step(
        np.zeros(5, np.int32), np.zeros(5, np.int32),
        np.zeros((5, 7), np.int32)))                # a shape seen nowhere else
    assert lookups == {("serving_decode", "miss"): programs}


def _prefill_inputs(engine, lengths, bucket, width):
    """A (rows, bucket) prefill from position 0 of random prompts of
    ``lengths`` (0: a whole padding row, on no page) on the pool's pages."""
    rows = len(lengths)
    tokens = np.zeros((rows, bucket), np.int32)
    table = np.zeros((rows, width), np.int32)
    rs = np.random.RandomState(7)
    for i, n in enumerate(lengths):
        tokens[i, :n] = rs.randint(1, 100, (n,))
        if n:
            pages = engine.pool.alloc(object(), n)
            table[i, :len(pages)] = pages
    positions = np.broadcast_to(np.arange(bucket, dtype=np.int32),
                                (rows, bucket))
    return tokens, positions, table, np.asarray(lengths, np.int32)


@pytest.mark.parametrize("lengths", [(8, 8), (5, 8, 3), (6, 0, 2)],
                         ids=["whole", "padded_tails", "padding_row"])
@pytest.mark.parametrize("kv_heads", [4, 1], ids=["mha", "gqa4"])
def test_llama_fresh_and_gather_programs_agree(kv_heads, lengths):
    """The two forms of the Llama program over one prefill from position
    0: the layers that attend over their own fresh keys and values
    against the layers that gather them back through the page table.
    The real rows' greedy ids are equal and their logits within the
    file's tolerance; the pages hold the same rows (the first layer's to
    the bit: both forms scatter before they attend)."""
    import jax

    from mxnet_tpu.gluon.model_zoo.nlp.llama import _paged_forward

    mx.random.seed(13)
    net = llama_tiny(num_heads=4, num_kv_heads=kv_heads)
    net.initialize()
    engine = net.decode_engine(PagePool(16, 4))
    inputs = _prefill_inputs(engine, lengths, bucket=8, width=3)

    def run(fresh):
        ids, logits, *arenas = jax.jit(functools.partial(
            _paged_forward, cfg=engine.cfg, page_size=engine.page_size,
            fresh=fresh))(engine._params, *inputs, *engine.arenas)
        # page 0 is the scratch page: padding rows land there, unordered
        return (np.asarray(ids), np.asarray(logits),
                [np.asarray(a)[1:] for a in arenas])

    real = np.asarray(lengths) > 0
    ids, logits, arenas = run(fresh=True)
    want_ids, want_logits, want_arenas = run(fresh=False)
    np.testing.assert_array_equal(ids[real], want_ids[real])
    np.testing.assert_allclose(logits[real], want_logits[real], atol=TOL,
                               rtol=0)
    assert np.isfinite(logits).all()
    assert any(a.any() for a in arenas)
    for li, (a, want) in enumerate(zip(arenas, want_arenas)):
        if li < 2:
            np.testing.assert_array_equal(a, want)
        np.testing.assert_allclose(a, want, atol=TOL, rtol=0)


def test_llama_takes_the_fresh_program_for_a_prefill_from_zero_only(
        monkeypatch):
    """The engine reads its program off the positions: ``prefill`` (every
    row ``arange(l)``) takes the ``fresh`` part of the cache key, a
    forward of several positions at an offset and every decode step the
    parent's (no part), all three agree with the no-cache oracle, and
    ``mxnet_serving_prefill_dispatch_total{path}`` counts the first two."""
    engine = engine_of("llama_tiny")
    seqs = np.random.RandomState(6).randint(1, 100, (2, 9))
    parts = []
    plain = engine._fn
    monkeypatch.setattr(engine, "_fn", lambda part, *a: (
        parts.append(part), plain(part, *a))[1])
    table = np.zeros((2, 3), np.int32)
    for i in range(2):
        table[i] = engine.pool.alloc(object(), 9)
    logits = []

    def three_forwards():
        engine.prefill(seqs[:, :4], np.full(2, 4, np.int32), table)
        logits.append(engine.last_logits())
        engine.forward(seqs[:, 4:8], 4 + np.arange(4)[None].repeat(2, 0),
                       table, np.full(2, 8, np.int32))
        logits.append(engine.last_logits())
        engine.decode_step(seqs[:, 8], np.full(2, 9, np.int32), table)
        logits.append(engine.last_logits())

    paths = counted(three_forwards, "mxnet_serving_prefill_dispatch_total",
                    "path")
    assert parts == ["fresh", None, None]
    assert paths == {("fresh",): 1, ("gather",): 1}
    for got, n in zip(logits, (4, 8, 9)):
        np.testing.assert_allclose(got, engine.forward_full(seqs[:, :n]),
                                   atol=TOL, rtol=0)


class _NoSeam(HybridBlock):
    def hybrid_forward(self, F, x):
        return x


class _ForeignEngine(_NoSeam):
    def decode_engine(self, pool):
        return object()


@pytest.mark.parametrize("block,text", [
    (_NoSeam, "the model has no decode_engine() seam"),
    (_ForeignEngine, "returned object, which is not a "
                     "serving.engine.PagedDecodeEngine"),
], ids=["no_seam", "foreign_engine"])
def test_the_server_refuses_what_is_not_a_paged_decode_engine(block, text):
    net = block()
    net.initialize()
    srv = serving.Server(net, batch_buckets=(1,), shape_buckets=[(4,)],
                         dtype="int32", warmup=False, decode_pages=8,
                         page_size=4, len_buckets=(4,))
    try:
        with pytest.raises(MXNetError, match=re.escape(text)):
            srv.start()
    finally:
        assert not srv.is_running
