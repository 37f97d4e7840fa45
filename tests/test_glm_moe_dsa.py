"""GLM-5 (``glm_moe_dsa``): latent attention over a learned selection of
the cached tokens, sigmoid-routed experts of which a chip holds a share,
a shared expert: the zoo model, its decode engine over a latent cache and
an index-key cache on one page table, the three sparse-attention ops, the
router's new statics and the server's chunked prefill, held to the plain
reference in ``benchmarks/references/glm_moe_dsa.py`` on seeded weights
(float32, tiny widths that keep every kind: one dense and two expert
layers, 8 router outputs of which 2 are held, top-2, a shared expert, 2
index heads that pick 12 cached tokens a query)."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.nlp import (glm_moe_dsa_tiny,
                                           longcat_flash_tiny)
from mxnet_tpu.ops.attention import (_one_hot_take, _selected_slots,
                                     dsa_index_scores, dsa_select,
                                     mla_sparse_attend)
from mxnet_tpu.ops.contrib import moe_routed_experts
from mxnet_tpu.serving.kvcache import PagePool, Preempted

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.builders import glm_moe_dsa as builder  # noqa: E402
from benchmarks.references import glm_moe_dsa as ref  # noqa: E402

pytestmark = pytest.mark.serving

TOL = 1e-5      # float32 on both sides; logits are O(1)
TOP_K = 12


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny_glm_dsa.json")) as f:
        return json.load(f)


_BUILT = {}


def tiny(seed=3, **config_kw):
    """(net, the reference's weights, config) of the benchmark's tiny
    configuration, seeded as the builder seeds the real one."""
    key = (seed, tuple(sorted(config_kw.items())))
    if key not in _BUILT:
        config = dict(_config(), **config_kw)
        net, _ = builder.build_net(config, seed, ctx=mx.cpu())
        _BUILT[key] = (net, builder.export_weights({"net": net}), config)
    return _BUILT[key]


def _tokens(seed, *shape):
    return np.random.RandomState(seed).randint(1, 256, shape).astype(np.int32)


# -- the ops ------------------------------------------------------------------

@pytest.mark.parametrize("top_k,density", [(17, 0.8), (17, 0.05), (1, 0.5),
                                           (64, 1.0)])
def test_select_is_the_exact_top_k(top_k, density):
    rs = np.random.RandomState(top_k)
    scores = rs.randn(3, 5, 200).astype(np.float32)
    valid = rs.rand(3, 5, 200) < density
    got = np.asarray(dsa_select(jnp.asarray(scores), jnp.asarray(valid),
                                top_k=top_k))
    masked = np.where(valid, scores, -np.inf)
    want = np.zeros_like(valid)
    np.put_along_axis(want, np.argsort(-masked, axis=-1,
                                       kind="stable")[..., :top_k], True, -1)
    assert (got == (want & valid)).all()
    assert (got.sum(-1) == np.minimum(valid.sum(-1), top_k)).all()


@pytest.mark.parametrize("live", [(3, 200), (700, 90), (1024, 1024)])
def test_select_searches_only_the_live_prefix(live):
    """A table of 1,024 slots is searched a quarter at a time: the mask
    of the whole-table search, whatever prefix held the live slots."""
    rs = np.random.RandomState(sum(live))
    scores = jnp.asarray(rs.randn(2, 6, 1024).astype(np.float32))
    lengths = jnp.asarray(live, jnp.int32)
    valid = jnp.broadcast_to(
        jnp.arange(1024)[None, None] < lengths[:, None, None], scores.shape)
    whole = dsa_select(scores, valid, top_k=17)
    assert (np.asarray(dsa_select(scores, valid, lengths, top_k=17))
            == np.asarray(whole)).all()
    assert int(whole.sum()) == 6 * sum(min(n, 17) for n in live)


def test_select_ranks_equal_scores_by_position():
    """Exact zeros (every index head's product negative), -0.0 among
    them: the lower position first, as ``lax.top_k`` orders equal values
    (the reference makes -0.0 an equal of 0.0 first, as the op does)."""
    scores = np.array([[0.0, 3.0, -0.0, 0.0, -1.0, 0.0, 2.0, 0.0]],
                      np.float32)
    valid = np.array([[True, True, True, False, True, True, True, True]])
    got = np.asarray(dsa_select(jnp.asarray(scores), jnp.asarray(valid),
                                top_k=4))
    assert got.tolist() == [[True, True, True, False, False, False, True,
                             False]]
    _, idx = jax.lax.top_k(jnp.where(valid, np.where(scores == 0, 0.0,
                                                     scores), -jnp.inf), 4)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 1, 2, 6]


def test_index_scores_match_the_equation():
    rs = np.random.RandomState(0)
    q = rs.randn(2, 70, 3, 8).astype(np.float32)     # 70: not a block multiple
    w = rs.randn(2, 70, 3).astype(np.float32)
    k = rs.randn(2, 40, 8).astype(np.float32)
    want = np.einsum("bljt,blj->blt",
                     np.maximum(np.einsum("bljd,btd->bljt", q, k), 0.0), w)
    got = np.asarray(dsa_index_scores(*map(jnp.asarray, (q, w, k))))
    assert np.abs(got - want).max() < 1e-4


def _slot_masks(t, top_k, rs):
    """Rows of a (B, T) selection that a count over blocks of 128 slots
    could get wrong, by name."""
    k = min(top_k, t)
    rows = {"nothing": np.zeros(t, bool)}
    few = np.zeros(t, bool)
    few[rs.permutation(t)[:max(k // 3, 1)]] = True
    rows["fewer than top_k"] = few
    full = np.zeros(t, bool)
    full[rs.permutation(t - 1)[:k - 1]] = True
    full[t - 1] = True
    rows["exactly top_k, the last slot among them"] = full
    tail = np.zeros(t, bool)
    tail[t - k:] = True
    rows["the last top_k slots"] = tail
    one = np.zeros(t, bool)
    start = 128 * ((t - 1) // 128 // 2)
    one[start + 3:min(start + 3 + min(k, 100), t)] = True
    rows["all in one block"] = one
    edges = np.zeros(t, bool)
    for edge in range(128, t, 128)[:max(k // 4, 1)]:
        edges[edge - 2:edge + 2] = True
    edges[0] = True
    rows["straddling block edges"] = edges
    rows["more than top_k"] = rs.rand(t) < min(1.0, 3.0 * k / t)
    rows["every slot"] = np.ones(t, bool)
    return rows


@pytest.mark.parametrize("t,top_k", [(80, 12), (80, 128), (1000, 64),
                                     (4096, 2048), (35328, 2048),
                                     (35328, 100)])
def test_selected_slots_are_the_first_top_k_nonzeros_in_order(t, top_k):
    """The decode step's list of selected slots against
    ``np.flatnonzero(row)[:top_k]``: the same slots in the same order,
    the same count, and a valid slot at every place past the count."""
    rows = _slot_masks(t, top_k, np.random.RandomState(t + top_k))
    slot, n_sel = jax.jit(_selected_slots, static_argnums=1)(
        jnp.asarray(np.stack(list(rows.values()))), top_k)
    slot, n_sel = np.asarray(slot), np.asarray(n_sel)
    assert slot.shape == (len(rows), top_k) and slot.dtype == np.int32
    for i, (name, row) in enumerate(rows.items()):
        want = np.flatnonzero(row)[:top_k]
        assert n_sel[i] == len(want), name
        assert (slot[i, :len(want)] == want).all(), name
        assert ((slot[i] >= 0) & (slot[i] < t)).all(), name


def test_the_slot_list_is_counted_not_scattered():
    """No scatter, gather or sort in the lowered list at the cell's
    sizes: on the TPU each is serial where the count's compares and two
    products are not (PERF.md section 6, PR 34)."""
    text = jax.jit(_selected_slots, static_argnums=1).lower(
        jax.ShapeDtypeStruct((8, 35328), jnp.bool_), 2048).as_text()
    for op in ("scatter", "gather", "sort"):
        assert f"stablehlo.{op}" not in text, op


@pytest.mark.parametrize("n,k", [(1, 5), (10, 12), (2208, 2048)])
def test_one_hot_take_is_take_along_axis(n, k):
    """Page ids as large as the cell's arena has pages, read through the
    one-hot product: exact."""
    rs = np.random.RandomState(n)
    table = jnp.asarray(rs.randint(0, 17665, (3, n)), jnp.int32)
    table = table.at[0, 0].set(2 ** 24 - 1).at[2, n - 1].set(17664)
    index = jnp.asarray(rs.randint(0, n, (3, k)), jnp.int32)
    index = index.at[0, 0].set(0).at[2, k - 1].set(n - 1)
    got = jax.jit(_one_hot_take)(table, index)
    assert got.dtype == jnp.int32
    assert (np.asarray(got) ==
            np.asarray(jnp.take_along_axis(table, index, axis=1))).all()


@pytest.mark.parametrize("length", [5, 41, 80])
def test_sparse_attend_gathers_what_the_masked_form_reads(length):
    """A decode step gathers the selected rows; a chunk reads every row
    under the selection's mask: the same attention."""
    rs = np.random.RandomState(length)
    b, h, nope, rope, v, r, ps, w = 3, 4, 16, 8, 16, 16, 8, 10
    arena = jnp.asarray(rs.randn(64, ps, 128).astype(np.float32))
    kvb = jnp.asarray(rs.randn(h * (nope + v), r).astype(np.float32))
    table = jnp.asarray(rs.permutation(63)[:b * w].reshape(b, w) + 1,
                        jnp.int32)
    q = jnp.asarray(rs.randn(b, 1, h, nope + rope).astype(np.float32))
    scores = jnp.asarray(rs.randn(b, 1, w * ps).astype(np.float32))
    valid = jnp.broadcast_to(jnp.arange(w * ps)[None, None] < length,
                             scores.shape)
    sel = dsa_select(scores, valid, top_k=TOP_K)
    kw = dict(nope_dim=nope, v_dim=v, scale=0.2, top_k=TOP_K)
    one = mla_sparse_attend(q, arena, table, sel, kvb, **kw)
    two = mla_sparse_attend(jnp.concatenate([q, q], 1), arena, table,
                            jnp.concatenate([sel, sel], 1), kvb, **kw)
    assert one.shape == (b, 1, h * v)
    assert float(jnp.abs(one[:, 0] - two[:, 0]).max()) < 1e-5
    assert float(jnp.abs(two[:, 0] - two[:, 1]).max()) == 0.0


@pytest.mark.parametrize("live", [None, (300, 37, 0)])
def test_a_chunk_walks_the_cache_in_key_blocks_as_far_as_it_is_live(
        monkeypatch, live):
    """Three key blocks of 128 slots and query blocks of 8: the scores
    and the attention of the one-block pass, with the blocks past a
    stream's length skipped."""
    from mxnet_tpu.ops import attention as A

    rs = np.random.RandomState(1)
    b, l, h, nope, rope, v, r, ps, w, j, d = 3, 16, 4, 16, 8, 16, 16, 8, 48, 2, 16
    t = w * ps                                              # 384 slots
    arena = jnp.asarray(rs.randn(160, ps, 128).astype(np.float32))
    kvb = jnp.asarray(rs.randn(h * (nope + v), r).astype(np.float32))
    table = jnp.asarray(rs.permutation(159)[:b * w].reshape(b, w) + 1,
                        jnp.int32)
    q = jnp.asarray(rs.randn(b, l, h, nope + rope).astype(np.float32))
    q_i = jnp.asarray(rs.randn(b, l, j, d).astype(np.float32))
    w_i = jnp.asarray(rs.randn(b, l, j).astype(np.float32))
    k_i = jnp.asarray(rs.randn(b, t, d).astype(np.float32))
    lengths = jnp.asarray(live if live else (t, t, t), jnp.int32)
    valid = jnp.broadcast_to(
        jnp.arange(t)[None, None] < lengths[:, None, None], (b, l, t))
    kw = dict(nope_dim=nope, v_dim=v, scale=0.2, top_k=TOP_K)
    whole_scores = dsa_index_scores(q_i, w_i, k_i)
    sel = dsa_select(whole_scores, valid, top_k=TOP_K)
    whole = mla_sparse_attend(q, arena, table, sel, kvb, **kw)
    monkeypatch.setattr(A, "_DSA_KEY_BLOCK", 128)
    monkeypatch.setattr(A, "_DSA_QUERY_BLOCK", 8)
    assert A._key_block(t) == 128 and A._key_block(35328) == 128
    args = () if live is None else (lengths,)
    scores = dsa_index_scores(q_i, w_i, k_i, *args)
    assert float(jnp.abs(jnp.where(valid, scores - whole_scores,
                                   0.0)).max()) < 1e-4
    if live is not None:        # a skipped block scores 0, and is masked
        assert float(jnp.abs(scores[1, :, 128:]).max()) == 0.0
        assert float(jnp.abs(scores[0, :, 256:]).max()) > 0.0
    got = mla_sparse_attend(q, arena, table, sel, kvb, *args, **kw)
    assert float(jnp.abs(got - whole).max()) < 1e-5
    if live is not None:        # nothing live: nothing selected: zeros
        assert float(jnp.abs(got[2]).max()) == 0.0


def test_the_cells_table_is_walked_in_blocks_of_1536():
    from mxnet_tpu.ops.attention import _key_block

    assert _key_block(35328) == 1536 and 35328 // 1536 == 23
    assert _key_block(96) == 96 and _key_block(2048) == 2048


@pytest.mark.parametrize("name", ["dsa_index_scores", "dsa_select",
                                  "mla_sparse_attend", "dsa_mla_attention"])
def test_ops_are_registered_and_listed(name):
    from mxnet_tpu.ops.registry import get_op

    assert get_op("_contrib_" + name) is get_op(name)
    with open(os.path.join(ROOT, "OPS_MANIFEST.tsv")) as f:
        rows = dict(line.rstrip("\n").split("\t") for line in f
                    if "\t" in line)
    assert rows[name] == rows["_contrib_" + name] == "_contrib_" + name


# -- the router ---------------------------------------------------------------

@pytest.mark.parametrize("n_valid", [16, 11])
def test_sigmoid_renormalised_routing_matches_a_direct_computation(n_valid):
    rs = np.random.RandomState(n_valid)
    n, u, e, hid, held, first, k, scale = 16, 32, 8, 16, 3, 2, 2, 2.5
    x = rs.randn(n, u).astype(np.float32)
    router = rs.randn(e, u).astype(np.float32)
    bias = rs.uniform(-0.1, 0.1, e).astype(np.float32)
    gate_up = rs.randn(held, u, 2 * hid).astype(np.float32) / 6
    down = rs.randn(held, hid, u).astype(np.float32) / 4
    valid = np.arange(n) < n_valid
    out, counts = moe_routed_experts(
        *map(jnp.asarray, (x, router, bias, gate_up, down, valid)),
        first_held=first, n_routed=e, top_k=k, scale=scale, score="sigmoid",
        renormalize=True)
    s = 1.0 / (1.0 + np.exp(-(x @ router.T)))
    idx = np.argsort(-(s + bias), axis=-1)[:, :k]
    want = np.zeros_like(x)
    held_picks = 0
    for t in range(n_valid):
        total = s[t, idx[t]].sum()
        for j in idx[t]:
            if first <= j < first + held:
                held_picks += 1
                g = x[t] @ gate_up[j - first]
                act = g[:hid] / (1 + np.exp(-g[:hid])) * g[hid:]
                want[t] += scale * s[t, j] / total * (act @ down[j - first])
    assert np.abs(np.asarray(out) - want).max() < 1e-4
    assert np.asarray(counts).tolist()[:3] == [
        held_picks, 0, n_valid * k - held_picks]


def test_unknown_score_is_refused():
    z = jnp.zeros
    with pytest.raises(ValueError, match="softmax.*sigmoid"):
        moe_routed_experts(z((4, 8)), z((4, 8)), z((4,)), z((2, 8, 8)),
                           z((2, 4, 8)), n_routed=4, score="tanh")


def test_longcat_router_traces_to_the_same_program():
    """The new statics at their defaults leave LongCat's call as it was:
    the jaxpr of its tiny expert layer, byte for byte (the hash is of the
    text PR 32's commit traces, taken before this file existed)."""
    import functools

    fn = functools.partial(moe_routed_experts, first_held=0, n_routed=8,
                           n_zero=4, top_k=3, scale=6.0)
    s, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    args = (s((16, 32), bf), s((12, 32), bf), s((12,), bf),
            s((2, 32, 32), bf), s((2, 16, 32), bf), s((16,), jnp.bool_))
    text = str(jax.make_jaxpr(fn)(*args))
    explicit = str(jax.make_jaxpr(functools.partial(
        fn, score="softmax", renormalize=False))(*args))
    assert text == explicit
    assert hashlib.sha256(text.encode()).hexdigest() == LONGCAT_JAXPR_SHA


LONGCAT_JAXPR_SHA = \
    "61c29810be1daa168c2660c4a8789ca2edb40495bf1518f72db63d74532f73d2"


# -- model, engine, reference ---------------------------------------------------

def test_gluon_forward_is_the_engines():
    net, weights, config = tiny()
    toks = _tokens(0, 2, 24)
    out = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    engine = net.decode_engine(PagePool(33, 4))
    assert np.abs(engine.forward_full(toks) - out[:, -1]).max() < TOL
    want = np.asarray(ref.logits_at(weights, config, toks[1],
                                    np.arange(24)))
    assert np.abs(out[1] - want).max() < TOL


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_equals_one_shot_prefill(chunk):
    net, _, _ = tiny()
    engine = net.decode_engine(PagePool(33, 8))
    toks = _tokens(1, 2, 40)
    whole = engine.forward_full(toks)
    parts = engine.forward_full(toks, chunk=chunk)
    assert np.abs(whole - parts).max() < TOL
    # a non-chunking engine says so
    mx.random.seed(0)
    other = longcat_flash_tiny()
    other.initialize(mx.init.Xavier())
    longcat = other.decode_engine(PagePool(33, 8))
    assert not longcat.chunked_prefill and engine.chunked_prefill
    with pytest.raises(NotImplementedError, match="offset"):
        longcat.forward_full(toks[:, :32], chunk=16)


@pytest.mark.parametrize("prompt,chunk,rows", [(40, 16, 1), (53, 16, 4),
                                               (30, 8, 4)])
def test_chunked_prefill_then_decode_matches_reference(prompt, chunk, rows):
    """A prompt longer than ``index_topk`` prefilled in chunks smaller
    than it, then decoded through both caches in a batch whose other rows
    are padding: every step's logits against ONE reference forward."""
    net, weights, config = tiny()
    n_new, ps = 6, 8
    pool = PagePool(64, ps)
    engine = net.decode_engine(pool)
    seq = _tokens(prompt, prompt + n_new)
    want = np.asarray(ref.logits_at(weights, config, seq,
                                    np.arange(prompt - 1, prompt + n_new - 1)))
    width = pool.pages_for(prompt + n_new) + 2
    table = np.zeros((rows, width), np.int32)
    pages = pool.alloc("s", prompt + n_new)
    table[0, :len(pages)] = pages
    for off in range(0, prompt, chunk):
        n = min(chunk, prompt - off)
        part = np.zeros((1, chunk), np.int32)
        part[0, :n] = seq[off:off + n]
        engine.prefill(part, np.array([off + n], np.int32), table[:1],
                       np.array([off], np.int32))
    assert np.abs(engine.last_logits()[0] - want[0]).max() < TOL
    for i in range(1, n_new):
        tok = np.zeros((rows,), np.int32)
        lens = np.zeros((rows,), np.int32)
        tok[0], lens[0] = seq[prompt + i - 1], prompt + i
        engine.decode_step(tok, lens, table)
        assert np.abs(engine.last_logits()[0] - want[i]).max() < TOL, i


def test_selected_sets_equal_the_references():
    """Per layer, on the reference's own layer input: what the program's
    index scores and exact top-k select over the cache, chunk by chunk."""
    from mxnet_tpu.gluon.model_zoo.nlp import glm_moe_dsa as model

    net, weights, config = tiny()
    prompt, chunk, ps = 56, 16, 8
    pool = PagePool(16, ps)
    engine = net.decode_engine(pool)
    seq = _tokens(7, prompt)
    table = np.zeros((1, 8), np.int32)
    table[0, :7] = pool.alloc("s", prompt)
    _, layers, _, _ = engine._params
    for io, lp, li in zip(ref.layer_io(weights, config, seq), layers,
                          range(3)):
        want = np.asarray(io["selected"])[:prompt, :prompt]
        assert (want.sum(-1) == np.minimum(np.arange(prompt) + 1,
                                           TOP_K)).all()
        arena, iarena = engine.arenas[2 * li], engine.arenas[2 * li + 1]
        for off in range(0, prompt, chunk):
            n = min(chunk, prompt - off)
            x = jnp.zeros((1, chunk, 64)).at[0, :n].set(
                io["x"][off:off + n])
            pos = (off + np.arange(chunk, dtype=np.int32))[None]
            _, arena, iarena, scores, valid, _ = model._index_and_cache(
                x, lp, arena, iarena, pos, table,
                np.array([off + n], np.int32), engine.cfg)
            got = np.asarray(dsa_select(scores, valid, top_k=TOP_K))
            assert (got[0, :n, :prompt] == want[off:off + n]).all(), (li, off)
            assert not got[0, :n, prompt:].any() and not got[0, n:].any()


def test_shares_sum_to_the_uncut_layer():
    """What all four shares' held experts give, plus the shared expert
    counted ONCE, is the uncut reference's whole FFN half."""
    net, weights, config = tiny()
    rs = np.random.RandomState(5)
    u, hid, outs, per = 64, 32, 8, 2
    lw = dict(weights["layers"][1])
    bound = np.sqrt(6.0 / (u + 2 * hid))
    gate_up = rs.uniform(-bound, bound, (outs, u, 2 * hid)).astype(np.float32)
    down = rs.uniform(-bound, bound, (outs, hid, u)).astype(np.float32)
    lw["moe"] = dict(lw["moe"], gate_up=jnp.asarray(gate_up),
                     down=jnp.asarray(down))
    a = jnp.asarray(rs.randn(24, u).astype(np.float32))
    consts = dict(ref.constants(dict(config, first_held_expert=0)))
    whole = np.asarray(ref.ffn(a, lw, consts))
    h = ref._rms(a, lw["post_norm"], consts["eps"])
    total = np.asarray(ref._swiglu(h, lw["shared_gate_up"],
                                   lw["shared_down"]))
    picks = 0
    for first in range(0, outs, per):
        part, counts = moe_routed_experts(
            h, lw["moe"]["router"], lw["moe"]["router_bias"],
            jnp.asarray(gate_up[first:first + per]),
            jnp.asarray(down[first:first + per]), first_held=first,
            n_routed=outs, top_k=config["num_experts_per_tok"],
            scale=config["routed_scaling_factor"], score="sigmoid",
            renormalize=True)
        # the reference told the same share agrees with the op
        share = dict(consts, first_held=first)
        m = dict(lw["moe"], gate_up=lw["moe"]["gate_up"][first:first + per],
                 down=lw["moe"]["down"][first:first + per])
        assert np.abs(np.asarray(part)
                      - np.asarray(ref.routed(h, m, share))).max() < TOL
        total = total + np.asarray(part)
        picks += int(counts[0])
    assert picks == 24 * config["num_experts_per_tok"]   # every pick, once
    assert np.abs(total - whole).max() < 1e-4


# -- the cache ------------------------------------------------------------------

def test_defrag_moves_both_arenas():
    """A stream's latent rows AND index keys follow its pages: decoding
    after a defrag gives what it gives without one."""
    net, _, _ = tiny()
    ps, prompt = 4, 21
    toks = _tokens(2, prompt + 1)

    def run(defrag):
        pool = PagePool(24, ps)
        engine = net.decode_engine(pool)
        assert [a.shape[-1] for a in engine.arenas[:2]] == [128, 128]
        assert len(engine.arenas) == 6
        pool.alloc("hole", 3 * ps)
        pages = pool.alloc("s", prompt + 1)
        pool.free("hole")
        table = np.zeros((1, 8), np.int32)
        table[0, :len(pages)] = pages
        part = np.zeros((1, 32), np.int32)
        part[0, :prompt] = toks[:prompt]
        engine.prefill(part, np.array([prompt], np.int32), table)
        if defrag:
            moves = pool.defrag()
            assert moves
            engine.apply_defrag(moves)
            table[0, :len(pages)] = pool.owned("s")
            assert table[0, 0] == 1
        engine.decode_step(toks[prompt:], np.array([prompt + 1], np.int32),
                           table)
        return engine.last_logits()

    assert np.abs(run(True) - run(False)).max() < TOL


# -- the server -----------------------------------------------------------------

def _server(net, **kw):
    args = dict(batch_buckets=(1, 4), dtype="int32", slo_ms=60000.0,
                decode_pages=65, page_size=8, len_buckets=(8, 16),
                max_generate_tokens=96, max_prefill_tokens=16,
                defrag_threshold=None, warmup=False)
    args.update(kw)
    return serving.Server(net, **args)


def _greedy(net, prompt, n_new, ps=8):
    """The unchunked engine's greedy continuation of ``prompt``."""
    pool = PagePool(32, ps)
    engine = net.decode_engine(pool)
    table = np.zeros((1, 16), np.int32)
    pages = pool.alloc("s", prompt.size + n_new)
    table[0, :len(pages)] = pages
    nxt = engine.prefill(prompt[None], np.array([prompt.size], np.int32),
                         table)
    out = []
    for _ in range(n_new):
        out.append(int(nxt[0]))
        nxt = engine.decode_step(nxt, np.array([prompt.size + len(out)],
                                               np.int32), table)
    return out


def test_server_prefills_a_long_prompt_in_chunks_beside_a_decoding_stream():
    net, _, _ = tiny()
    short, long_ = _tokens(11, 12), _tokens(12, 61)
    want_short, want_long = _greedy(net, short, 40), _greedy(net, long_, 6)
    telemetry.enable()
    try:
        with _server(net) as srv:
            order = []
            a = srv.submit_generate(
                short, 40, on_token=lambda i, t: order.append(("a", i)))
            a.next_token(0, timeout=60)
            b = srv.submit_generate(
                long_, 6, on_token=lambda i, t: order.append(("b", i)))
            got_long = b.result(timeout=120).tolist()
            got_short = a.result(timeout=120).tolist()
            stats = srv.stats()
        chunks = telemetry.snapshot()["metrics"][
            "mxnet_prefill_chunks_total"]["samples"][0]["value"]
    finally:
        telemetry.disable()
    assert got_long == want_long and got_short == want_short
    # 61 tokens = 3 chunks of 16 and a tail of 13: four prefill dispatches
    # beside the short prompt's one
    assert stats["batches"] == 5 and chunks == 4
    # the short stream kept answering while the long prompt was prefilled:
    # a token after each chunk, none of them held back to its end
    first_b = order.index(("b", 0))
    last_a_before = max(i for who, i in order[:first_b] if who == "a")
    assert last_a_before >= 3


def test_server_refuses_a_long_prompt_for_an_engine_that_cannot_chunk():
    mx.random.seed(0)
    net = longcat_flash_tiny()
    net.initialize(mx.init.Xavier())
    with _server(net) as srv:
        with pytest.raises(MXNetError, match="no len bucket fits.*chunk"):
            srv.submit_generate(_tokens(0, 17) % 128, 2)
        assert srv.submit_generate(_tokens(0, 16) % 128, 2).result(
            timeout=60).size == 2


def _spied_prefills(srv, on_first=None):
    """The (tokens shape, lengths[0], offsets[0]) of every prefill
    dispatch of ``srv``'s engine, in order; ``on_first()`` runs inside the
    first one (on the scheduler thread)."""
    shapes = []
    engine = srv._tenants["default"].engine
    inner = engine.prefill

    def spy(tokens, lengths, table, offsets=None):
        shapes.append((tokens.shape, int(lengths[0]),
                       None if offsets is None else int(offsets[0])))
        if on_first is not None and len(shapes) == 1:
            on_first()
        return inner(tokens, lengths, table, offsets)

    engine.prefill = spy
    return shapes


@pytest.mark.parametrize("arrivals", [(40, 20, 5), (20, 40, 5)])
def test_one_chunk_a_tick_and_new_admissions_after_the_chunks_in_flight(
        arrivals):
    """Two long prompts and a short one arrive together: the bound lets
    one chunk through a tick, an admitted prompt's chunks come before the
    next admission, and of the long prompts that wait together the
    LONGEST is admitted first, whichever arrived first: the dispatches
    (and so what every stream waits behind) do not depend on the order
    of arrival."""
    net, _, _ = tiny()
    with _server(net) as srv:
        shapes = _spied_prefills(srv)
        with srv._cond:        # admitted in ONE tick
            hs = [srv.submit_generate(_tokens(n, n), 2) for n in arrivals]
        for h in hs:
            h.result(timeout=120)
    assert shapes == [((1, 16), 16, None), ((1, 16), 32, 16),
                      ((1, 8), 40, 32), ((1, 16), 16, None),
                      ((1, 8), 20, 16), ((1, 8), 5, None)]


def test_a_later_longer_prompt_does_not_pass_over_a_waiting_wave():
    """Longest first holds among the long prompts that were waiting
    TOGETHER: one that arrives while their wave is being admitted waits
    for the wave's end, however long it is, so none of them starves."""
    net, _, _ = tiny()
    late = []
    with _server(net) as srv:
        shapes = _spied_prefills(srv, on_first=lambda: late.append(
            srv.submit_generate(_tokens(3, 60), 2)))
        with srv._cond:
            hs = [srv.submit_generate(_tokens(n, n), 2) for n in (20, 30)]
        for h in hs + late:
            h.result(timeout=120)
    firsts = [n for _, n, off in shapes if off is None]
    assert firsts == [16, 16, 16]
    # the prompts' last chunks, in the order they were admitted
    assert [n for _, n, off in shapes if n in (20, 30, 60)] == [30, 20, 60]


def test_preempting_a_half_prefilled_stream_frees_it_whole():
    net, _, _ = tiny()
    with _server(net, decode_pages=13) as srv:      # 96 tokens of cache
        pool_free = srv.stats()["kvcache"]["free"]
        low = srv.submit_generate(_tokens(4, 60), 20, priority=0)
        # wait until it holds its pages and is part-way through
        import time
        deadline = time.time() + 60
        while (not any(g.prefilled < g.prompt.size
                       for g in list(srv._gen_active))
               and time.time() < deadline):
            time.sleep(0.001)
        high = srv.submit_generate(_tokens(5, 30), 4, priority=5)
        assert high.result(timeout=120).size == 4
        with pytest.raises(Preempted):
            low.result(timeout=120)
        assert not srv._gen_active
        assert srv.stats()["kvcache"]["free"] == pool_free
        assert srv.stats()["preemptions"] == 1


def test_zero_steady_state_retraces_with_chunks():
    """After one long and a few short requests, any mix of them compiles
    nothing: offsets and lengths are arrays, not signatures."""
    net, _, _ = tiny(seed=4)
    srv = _server(net).start()
    was = telemetry.enabled()
    telemetry.reset()
    try:
        # both length buckets, a chunk at an offset, and streams that
        # decode side by side (the 4 bucket) as well as alone
        for h in [srv.submit_generate(_tokens(n, n), 8)
                  for n in (45, 7, 12)]:
            h.result(timeout=120)
        srv.submit_generate(_tokens(5, 5), 3).result(timeout=120)
        telemetry.enable()
        for h in [srv.submit_generate(_tokens(n, n), 3)
                  for n in (70, 33, 6, 50, 12)]:
            h.result(timeout=120)
        lookups = {tuple(s["labels"].values()): s["value"]
                   for s in telemetry.snapshot()["metrics"][
                       "mxnet_jit_cache_total"]["samples"]}
        assert lookups.get(("serving_decode", "hit"), 0) > 0
        assert ("serving_decode", "miss") not in lookups
    finally:
        srv.stop()
        telemetry.reset()
        if not was:
            telemetry.disable()


def test_engine_counts_scored_and_selected_keys():
    net, _, _ = tiny()
    pool = PagePool(16, 8)
    engine = net.decode_engine(pool)
    telemetry.enable()
    try:
        def total(name, phase):
            fam = telemetry.snapshot()["metrics"].get(name, {"samples": []})
            return sum(s["value"] for s in fam["samples"]
                       if s["labels"]["phase"] == phase)

        before = [total("mxnet_dsa_keys_scored_total", "prefill"),
                  total("mxnet_dsa_keys_selected_total", "prefill")]
        engine.forward_full(_tokens(0, 1, 20))
        scored = total("mxnet_dsa_keys_scored_total", "prefill") - before[0]
        picked = total("mxnet_dsa_keys_selected_total", "prefill") - before[1]
    finally:
        telemetry.disable()
    # 3 layers; query t sees t + 1 keys and selects min(t + 1, 12)
    assert scored == 3 * sum(range(1, 21))
    assert picked == 3 * sum(min(t + 1, TOP_K) for t in range(20))
    keys, picks = engine.last_counts[0]
    assert np.asarray(picks).tolist() == [0, 0, 0, 0]       # a dense layer
