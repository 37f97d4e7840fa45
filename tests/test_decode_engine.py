"""The contract every served decoder keeps, whatever its cache holds:
``serving.engine.PagedDecodeEngine`` through both of its subclasses
(``LlamaDecodeEngine``: one whole-stack program over K/V slot arenas;
``LongcatFlashDecodeEngine``: a layer program per double layer over latent
page arenas), and the seam ``Server`` holds a model to.
"""
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
    logits = engine.prefill(tokens, np.full(len(seqs), prompt, np.int32),
                            table)
    return owners, table, logits


def step(engine, seqs, n, table):
    """The decode step that has ``seqs[:, :n]`` behind it."""
    return engine.decode_step(seqs[:, n - 1], np.full(len(seqs), n, np.int32),
                              table)


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


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_second_forward_of_a_signature_compiles_nothing(name):
    engine = engine_of(name)
    seqs = np.random.RandomState(2).randint(1, 100, (2, 8))
    _, table, _ = start(engine, seqs, 6, width=2)
    step(engine, seqs, 7, table)
    was = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        start(engine, seqs, 6, width=2)
        step(engine, seqs, 8, table)
        lookups = {(s["labels"]["cache"], s["labels"]["result"]): s["value"]
                   for s in telemetry.snapshot()["metrics"]
                   ["mxnet_jit_cache_total"]["samples"]}
    finally:
        telemetry.reset()
        if not was:
            telemetry.disable()
    assert lookups.get(("serving_decode", "hit"), 0) > 0
    assert ("serving_decode", "miss") not in lookups


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
