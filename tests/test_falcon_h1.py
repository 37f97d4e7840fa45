"""Falcon-H1 (parallel hybrid blocks: Mamba-2 / SSD beside GQA attention
in every layer) on the CPU at the tiny preset, float32, seeded weights:
the library model, its decode engine through pages AND state slots, the
SSD ops and the slot-update kernel (interpret mode), each held to
``benchmarks/references/falcon_h1.py`` or to the step recurrence."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import serving, telemetry  # noqa: E402
from mxnet_tpu.gluon.model_zoo.nlp import (falcon_h1_tiny, get_model,  # noqa: E402
                                           glm_moe_dsa_tiny, llama_tiny,
                                           longcat_flash_tiny,
                                           phi4flash_tiny)
from mxnet_tpu.gluon.model_zoo.nlp import falcon_h1 as model  # noqa: E402
from mxnet_tpu.ops.ssm import (ssd_chunk_scan, ssd_slot_update,  # noqa: E402
                               ssd_step)
from mxnet_tpu.pallas_kernels.ssd_state_update import (  # noqa: E402
    ssd_state_update_kernel, ssd_update_shape_supported)
from mxnet_tpu.serving.engine import PagedDecodeEngine  # noqa: E402
from mxnet_tpu.serving.kvcache import PagePool  # noqa: E402

# float32 on the CPU: the library, the engine and the reference differ by
# the order of float32 sums alone (readings 5e-7 .. 2e-6 on logits of
# spread 0.6); ten times that
TOL = 2e-5


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny_falcon_h1.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration's net with the builder's seeded weights (the
    published multipliers, Mamba-2's initialisation), and the same
    weights under the reference's names."""
    from benchmarks.builders import falcon_h1 as builder

    config = _config()
    net, _ = builder.build_net(config, 11, ctx=mx.cpu(0))
    return net, config, builder.export_weights({"net": net})


def _ref_logits(tiny, tokens, rows):
    from benchmarks.references import falcon_h1 as reference

    _, config, weights = tiny
    return np.asarray(reference.logits_at(weights, config, tokens,
                                          np.asarray(rows)))


def _tokens(seed, *shape):
    return np.random.RandomState(seed).randint(1, 256, shape).astype(
        np.int32)


# -- the model ------------------------------------------------------------------

def test_library_model_matches_the_reference_logits(tiny):
    net = tiny[0]
    tokens = _tokens(0, 2, 29)
    out = net(mx.nd.array(tokens, dtype="int32")).asnumpy()
    for row in range(2):
        ref = _ref_logits(tiny, tokens[row], np.arange(29))
        assert np.abs(out[row] - ref).max() < TOL
        # the layers decide the token, not the last token alone
        assert len(set(ref.argmax(axis=1))) >= 15


def test_model_zoo_exports_the_model():
    assert get_model("falcon_h1_tiny").__class__.__name__ == "FalconH1Model"
    net = falcon_h1_tiny(num_layers=1)
    assert len(net.blocks) == 1 and net._decode_cfg["d_state"] == 16
    with pytest.raises(ValueError, match="divide"):
        falcon_h1_tiny(ssm_heads=3)


@pytest.mark.parametrize("name", ["ssd_step", "ssd_chunk_scan",
                                  "mamba2_mixer"])
def test_ops_are_registered_and_listed(name):
    from mxnet_tpu.ops.registry import get_op

    assert get_op("_contrib_" + name) is get_op(name)
    with open(os.path.join(ROOT, "OPS_MANIFEST.tsv")) as f:
        rows = dict(line.rstrip("\n").split("\t") for line in f
                    if "\t" in line)
    assert rows[name] == rows["_contrib_" + name] == "_contrib_" + name


# -- the recurrence ---------------------------------------------------------------

def _scan_inputs(seed, b, l, h=4, p=8, g=2, n=16):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rs.uniform(np.log(1e-3), np.log(0.1),
                                       (b, l, h))), jnp.float32)
    a = -jnp.asarray(rs.uniform(1.0, 16.0, h), jnp.float32)
    return (f(b, l, h, p), dt, a, f(b, l, g, n), f(b, l, g, n),
            jnp.asarray(rs.uniform(0.5, 1.5, h), jnp.float32),
            f(b, h, n, p))


def _by_steps(x, dt, a, b, c, d, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssd_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], d, state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("length,chunk,zero_state", [
    (300, 128, False),      # the published chunk, a ragged tail of 44
    (128, 128, True),       # one whole chunk from zeros
    (21, 8, False),         # three chunks, a tail of 5
    (5, 8, False)])         # shorter than a chunk
def test_chunk_scan_matches_the_step_recurrence(length, chunk, zero_state):
    """The chunk form (matrix products inside a chunk, one hand-over
    between chunks) against the definition, token by token, from a
    NON-ZERO start state: float32 sums in another order, 1e-5 of outputs
    of size ~10."""
    x, dt, a, b, c, d, s0 = _scan_inputs(length, 2, length)
    if zero_state:
        s0 = jnp.zeros_like(s0)
    y, s = ssd_chunk_scan(x, dt, a, b, c, d, s0, chunk=chunk)
    y_ref, s_ref = _by_steps(x, dt, a, b, c, d, s0)
    assert y.shape == x.shape and s.shape == s0.shape
    assert float(jnp.abs(y - y_ref).max()) < 1e-4
    assert float(jnp.abs(s - s_ref).max()) < 1e-5


def test_padded_positions_are_identity_steps():
    x, dt, a, b, c, d, s0 = _scan_inputs(3, 2, 20)
    _, s_short = ssd_chunk_scan(x[:, :13], dt[:, :13], a, b[:, :13],
                                c[:, :13], d, s0, chunk=8)
    y_pad, s_pad = ssd_chunk_scan(x, dt.at[:, 13:].set(0.0), a, b, c, d, s0,
                                  chunk=8)
    assert float(jnp.abs(s_pad - s_short).max()) < 1e-6
    _, s_same = ssd_chunk_scan(x, jnp.zeros_like(dt), a, b, c, d, s0,
                               chunk=8)
    assert float(jnp.abs(s_same - s0).max()) == 0.0
    y1, s1 = ssd_step(x[:, 0], jnp.zeros_like(dt[:, 0]), a, b[:, 0],
                      c[:, 0], d, s0)
    assert float(jnp.abs(s1 - s0).max()) == 0.0 and y1.shape == (2, 4, 8)


# -- the kernel ---------------------------------------------------------------------

def _update_inputs(seed, slots, s=7, h=4, p=8, g=2, n=16):
    rs = np.random.RandomState(seed)
    b = len(slots)
    f = lambda *sh: jnp.asarray(rs.randn(*sh), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rs.uniform(np.log(1e-3), np.log(0.1), (b, h))),
                     jnp.float32)
    a = -jnp.asarray(rs.uniform(1.0, 16.0, h), jnp.float32)
    return (f(s, h, n, p), jnp.asarray(slots, jnp.int32), f(b, h, p), dt, a,
            f(b, g, n), f(b, g, n))


def test_pallas_update_matches_ssd_step_in_interpret_mode():
    """The kernel against ``ssd_step`` over ``states[slots]``: a live
    row's slot holds the step's state and its ``y`` is the step's; slot 0
    alone takes the padding rows; no other slot changes."""
    live = [3, 5, 1]
    states, slots, x, dt, a, b, c = _update_inputs(0, [3, 0, 5, 0, 1])
    decay = jnp.exp(dt * a)
    new, y = ssd_state_update_kernel(states, slots, dt[..., None] * x,
                                     decay, b, c, interpret=True)
    y_ref, s_ref = ssd_step(x, dt, a, b, c, jnp.zeros((4,)), states[slots])
    rows = [0, 2, 4]
    assert float(jnp.abs(y[jnp.asarray(rows)]
                         - y_ref[jnp.asarray(rows)]).max()) < 1e-5
    for row, slot in zip(rows, live):
        assert float(jnp.abs(new[slot] - s_ref[row]).max()) < 1e-6
    for slot in (2, 4, 6):                  # nobody's: untouched
        assert float(jnp.abs(new[slot] - states[slot]).max()) == 0.0
    assert float(jnp.abs(new[0] - states[0]).max()) > 0.0   # scratch


def test_pallas_update_drops_a_dirty_slot_where_the_decay_is_zero():
    states, slots, x, dt, a, b, c = _update_inputs(1, [2, 4])
    states = states.at[2].set(jnp.inf)      # whatever the slot held
    decay = jnp.exp(dt * a).at[0].set(0.0)
    new, y = ssd_state_update_kernel(states, slots, dt[..., None] * x,
                                     decay, b, c, interpret=True)
    fresh = jnp.zeros_like(states[slots]).at[1].set(states[4])
    y_ref, s_ref = ssd_step(x, dt, a, b, c, jnp.zeros((4,)), fresh)
    assert bool(jnp.isfinite(new[2]).all())
    assert float(jnp.abs(new[2] - s_ref[0]).max()) < 1e-6
    assert float(jnp.abs(y - y_ref).max()) < 1e-5


def test_slot_update_takes_the_xla_path_off_the_chip():
    """Off a TPU the gate says no, and the dispatcher is ``ssd_step``
    over the gathered rows, scattered back."""
    states, slots, x, dt, a, b, c = _update_inputs(2, [3, 1])
    d = jnp.ones((4,))
    fresh = jnp.asarray([False, True])
    y, new = ssd_slot_update(states, slots, fresh, x, dt, a, b, c, d)
    start = states[slots].at[1].set(0.0)
    y_ref, s_ref = ssd_step(x, dt, a, b, c, d, start)
    assert float(jnp.abs(y - y_ref).max()) == 0.0
    assert float(jnp.abs(new[slots] - s_ref).max()) == 0.0


def test_kernel_shape_gate():
    f32 = jnp.float32
    big = jax.ShapeDtypeStruct((129, 32, 256, 128), f32)
    x = jax.ShapeDtypeStruct((128, 32, 128), f32)
    b = jax.ShapeDtypeStruct((128, 2, 256), f32)
    assert ssd_update_shape_supported(big, x, b)
    assert not ssd_update_shape_supported(
        jax.ShapeDtypeStruct(big.shape, jnp.bfloat16), x, b)
    # the tiny preset's heads are narrower than a lane tile
    assert not ssd_update_shape_supported(
        jax.ShapeDtypeStruct((5, 4, 16, 16), f32),
        jax.ShapeDtypeStruct((2, 4, 16), f32),
        jax.ShapeDtypeStruct((2, 2, 16), f32))


# -- the engine: pages and slots ----------------------------------------------------

def _engine(net, n_pages=41, page=8, n_slots=6):
    pool = PagePool(n_pages, page, n_state_slots=n_slots)
    return net.decode_engine(pool), pool


@pytest.mark.parametrize("chunk", [None, 16, 8])
def test_prefill_then_decode_matches_the_references_full_forward(tiny,
                                                                 chunk):
    """Two streams of different lengths beside two PADDING rows of the
    batch bucket: prefill (whole, or in chunks over a chunk boundary with
    a padded last chunk), then decode steps through pages and slots, each
    step's logits against one full float32 forward of the reference."""
    net = tiny[0]
    engine, pool = _engine(net)
    lens = [21, 13]
    seqs = [_tokens(5 + i, n + 6) for i, n in enumerate(lens)]
    cap = 4
    table = np.zeros((cap, pool.pages_for(40)), np.int32)
    slots = np.zeros((cap,), np.int32)
    for i in range(2):
        table[i] = pool.alloc(i, 40)
        slots[i] = pool.state_slots.alloc(i)
    step = chunk or 24
    logits = None
    for off in range(0, max(lens), step):
        part = np.zeros((cap, step), np.int32)
        upto = np.zeros((cap,), np.int32)
        for i, n in enumerate(lens):
            take = max(0, min(step, n - off))
            part[i, :take] = seqs[i][off:off + take]
            upto[i] = min(n, off + step) if take else 0
        final = np.asarray([0 < n - off <= step for n in lens] + [0, 0],
                           bool)
        # a row whose prompt is already in sits this chunk out as padding
        rows_slots = np.where(upto > 0, slots, 0)
        engine.prefill(part, upto, table * (upto > 0)[:, None],
                       np.full((cap,), off, np.int32) if off else None,
                       rows_slots, final)
        got = engine.last_logits()
        for i, n in enumerate(lens):
            if final[i]:
                ref = _ref_logits(tiny, seqs[i][:n], [n - 1])[0]
                assert np.abs(got[i] - ref).max() < TOL, (i, off)
    for t in range(6):
        tokens = np.zeros((cap,), np.int32)
        upto = np.zeros((cap,), np.int32)
        for i, n in enumerate(lens):
            tokens[i], upto[i] = seqs[i][n + t], n + t + 1
        engine.decode_step(tokens, upto, table, slots)
        logits = engine.last_logits()
        for i, n in enumerate(lens):
            ref = _ref_logits(tiny, seqs[i][:n + t + 1], [n + t])[0]
            assert np.abs(logits[i] - ref).max() < TOL, (i, t)


def test_a_dirty_slot_starts_a_stream_from_zeros(tiny):
    net = tiny[0]
    engine, pool = _engine(net)
    first, second = _tokens(40, 1, 19), _tokens(41, 1, 23)
    engine.forward_full(first)               # dirties slot 1 and its pages
    for arrays in engine.slot_arrays.values():
        assert float(jnp.abs(arrays[0][1]).max()) > 0.0
    got = engine.forward_full(second, chunk=8)
    ref = _ref_logits(tiny, second[0], [22])[0]
    assert np.abs(got[0] - ref).max() < TOL
    assert pool.state_slots.stats()["used"] == 0


def test_engine_declares_both_kinds_of_cache(tiny):
    net = tiny[0]
    engine, pool = _engine(net)
    cfg = engine.cfg
    assert engine.state_slots and engine.chunked_prefill
    assert len(engine.arenas) == 2 * cfg["num_layers"]
    # (pages, page, kv_heads * head_dim): a token's heads in one row
    assert engine.arenas[0].shape == (pool.n_pages, 8, 128)
    states = engine.slot_arrays["states"]
    assert len(states) == cfg["num_layers"]
    # the state transposed: (slots, heads, d_state, head_dim), float32
    assert states[0].shape == (6, 4, 16, 16) and states[0].dtype == jnp.float32
    assert engine.slot_arrays["tails"][0].shape == (6, 3, 64 + 2 * 2 * 16)
    with pytest.raises(mx.base.MXNetError, match="state slots"):
        net.decode_engine(PagePool(9, 8))


def test_defrag_moves_every_layers_pages_and_no_slot(tiny):
    net = tiny[0]
    engine, pool = _engine(net)
    a, b = _tokens(50, 17), _tokens(51, 14)
    owners = ["a", "b"]
    table = np.zeros((2, pool.pages_for(24)), np.int32)
    slots = np.zeros((2,), np.int32)
    pool.alloc("hole", 16)                   # pages 1-2, freed below
    for i, o in enumerate(owners):
        table[i] = pool.alloc(o, 24)
        slots[i] = pool.state_slots.alloc(o)
    toks = np.zeros((2, 24), np.int32)
    toks[0, :17], toks[1, :14] = a, b
    nxt = engine.prefill(toks, np.array([17, 14], np.int32), table,
                         None, slots)
    pool.free("hole")
    moves = pool.defrag()
    assert moves
    engine.apply_defrag(moves)
    table = np.stack([pool.page_table(o, table.shape[1]) for o in owners])
    engine.decode_step(nxt, np.array([18, 15], np.int32), table, slots)
    got = engine.last_logits()
    for i, seq in enumerate((a, b)):
        ref = _ref_logits(tiny, np.append(seq, nxt[i]), [seq.size])[0]
        assert np.abs(got[i] - ref).max() < TOL


# -- through the server ---------------------------------------------------------------

def test_server_turns_slots_over_and_answers_as_the_model_does(tiny):
    """More requests than slots, prompts longer than the largest length
    bucket (chunks) beside decoding streams: every answer is the greedy
    continuation of the library model's own full forward, every slot and
    page is free at the end, and every admission counted."""
    net = tiny[0]
    telemetry.enable()
    try:
        telemetry.reset()
        srv = serving.Server(
            net, batch_buckets=(1, 2), dtype="int32", ctx=mx.cpu(0),
            slo_ms=60000.0, decode_pages=19, page_size=8,
            len_buckets=(8, 16), max_generate_tokens=48,
            max_prefill_tokens=32, name="h1").start()
        try:
            prompts = [_tokens(60 + i, n) for i, n in
                       enumerate((9, 30, 16, 5, 23))]
            handles = [srv.submit_generate(p, 5) for p in prompts]
            outs = [np.asarray(h.result(timeout=300.0)) for h in handles]
            engine = srv._tenants["default"].engine
            assert engine.pool.state_slots.n_slots == 3
            assert engine.pool.state_slots.stats()["used"] == 0
            assert engine.pool.stats()["used"] == 0
        finally:
            srv.stop(timeout=60.0)
        allocs = telemetry.snapshot()["metrics"][
            "mxnet_state_slot_allocs_total"]["samples"][0]["value"]
        assert allocs == len(prompts)
    finally:
        telemetry.disable()
    for prompt, out in zip(prompts, outs):
        seq = np.concatenate([prompt, out])
        ref = _ref_logits(tiny, seq[:-1],
                          np.arange(prompt.size - 1, seq.size - 1))
        # float32 and no near ties at this size: the tokens themselves
        assert (ref.argmax(axis=1) == out).all()


# -- the engines this PR did not touch trace what they traced ----------------------------

ENGINE_JAXPR_SHA = {
    "llama_tiny":       # PR 48's: PR 44's decode step, the fresh prefill
        "9510c2c850ea359d36ab4af1228e2c277423e98c527c006b32154ab8fa11c455",
    "longcat_flash_tiny":
        "3e923ccb954c4cc2c859231265686746ca29064df50b5040b3d89072cfd3c32b",
    "glm_moe_dsa_tiny":
        "ab7eee2ca74f843be775128107f7be43b9a1bce46974217dfd39e8d6d83556da",
    "phi4flash_tiny":
        "67e923c476fe1eaece7b9aa47803134ec1f19678b387b9db7a4aded8c6f7187c",
}


@pytest.mark.parametrize("make", [llama_tiny, longcat_flash_tiny,
                                  glm_moe_dsa_tiny, phi4flash_tiny],
                         ids=lambda f: f.__name__)
def test_the_other_engines_trace_to_the_parents_programs(make, monkeypatch):
    """``ops/ssm.py``, ``ops/attention.py``, ``serving/`` and the paged
    kernel are shared: every program of a prefill and a decode step of
    the tiny Llama (Mistral's engine), LongCat, GLM and Phi engines has
    the jaxpr the parent commit (PR 39) traces, byte for byte (the hashes
    were taken on that commit's tree; the tiny Llama's on PR 48's: its
    decode step is the text PR 44 gave it, with a key and a value page
    array a layer, its prefill from position 0 the ``fresh`` form)."""
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
    pool = PagePool(9, 8, n_state_slots=3)
    engine = net.decode_engine(pool)
    toks = np.arange(1, 17, dtype=np.int32).reshape(2, 8)
    table = np.stack([pool.alloc("a", 12), pool.alloc("b", 12)])
    lens = np.array([8, 5], np.int32)
    seam = {}
    if engine.state_slots:
        seam = {"slots": np.array([pool.state_slots.alloc("a"),
                                   pool.state_slots.alloc("b")], np.int32)}
    nxt = engine.prefill(toks, lens, table, **seam)
    engine.decode_step(nxt, lens + 1, table, **seam)
    joined = "\n".join(f"{k}\n{v}" for k, v in
                       sorted(texts.items(), key=lambda kv: str(kv[0])))
    assert hashlib.sha256(joined.encode()).hexdigest() == \
        ENGINE_JAXPR_SHA[make.__name__]


def test_state_tail_and_stream_are_float32_whatever_the_weights(tiny):
    """The precision the configuration states (``assumed.precision``): a
    float32 scan state, convolution tail and residual stream under
    bfloat16 weights. The benchmark's ``correct`` cannot tell a bfloat16
    state or stream from the sound program (0.10 / 0.14 against 0.10 of
    its limit on the chip, ``assumed.what_correct_cannot_see``), so the
    dtypes are pinned here: on the slot arrays as allocated, and on what
    a prefill's and a decode round's layer program hand back."""
    net = falcon_h1_tiny()
    net.cast("bfloat16")
    net.initialize()
    pool = PagePool(9, 8, n_state_slots=3)
    engine = net.decode_engine(pool)
    assert jnp.dtype(engine.dtype) == jnp.bfloat16
    cfg, lp = engine.cfg, engine._params[1][0]
    table = np.stack([pool.alloc("a", 12), pool.alloc("b", 12)])
    slots = np.array([pool.state_slots.alloc("a"),
                      pool.state_slots.alloc("b")], np.int32)
    st = engine.slot_arrays
    for length in (8, 1):
        out = jax.eval_shape(
            lambda *a: model._layer_forward(*a, cfg=cfg),
            jnp.zeros((2, length, cfg["units"]), jnp.float32), lp,
            engine.arenas[0], engine.arenas[1], st["tails"][0],
            st["states"][0], jnp.zeros((2, length), jnp.int32),
            jnp.asarray(table), jnp.full((2,), length, jnp.int32),
            jnp.asarray(slots))
        x, k_arena, _, tails, states = out
        assert (x.dtype, tails.dtype, states.dtype) == (jnp.float32,) * 3
        assert k_arena.dtype == jnp.bfloat16
        assert states.shape == st["states"][0].shape
    toks = np.arange(1, 17, dtype=np.int32).reshape(2, 8)
    lens = np.array([8, 5], np.int32)
    nxt = engine.prefill(toks, lens, table, slots=slots)
    engine.decode_step(nxt, lens + 1, table, slots=slots)
    for name in ("tails", "states"):
        assert {a.dtype for a in st[name]} == {jnp.dtype(jnp.float32)}
