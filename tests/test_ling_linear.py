"""Ling-3.0-flash's language model (Kimi Delta Attention layers beside one
latent-attention layer, group-limited sigmoid experts) on the CPU at the
tiny preset, float32, seeded weights: the library model, its decode
engine through the ONE latent layer's pages AND state slots, the
delta-rule ops in their three forms and the slot-update kernel (interpret
mode), each held to ``benchmarks/references/ling_linear.py`` (the
recurrence token by token) or to the step form."""
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
from mxnet_tpu.gluon.model_zoo.nlp import (get_model,  # noqa: E402
                                           ling_linear_tiny)
from mxnet_tpu.gluon.model_zoo.nlp import ling_linear as model  # noqa: E402
from mxnet_tpu.ops import linear_attention as la  # noqa: E402
from mxnet_tpu.pallas_kernels.kda_state_update import (  # noqa: E402
    kda_state_update_kernel, kda_update_shape_supported)
from mxnet_tpu.serving.kvcache import PagePool  # noqa: E402

# float32 on the CPU: the library, the engine and the reference differ by
# the order of float32 sums alone (readings 2e-7 .. 2e-6 on logits of
# spread 0.5); ten times that
TOL = 2e-5


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny_ling_linear.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration's net with the builder's seeded weights (a
    decay that spans channels of thousands of tokens and of two), and the
    same weights under the reference's names."""
    from benchmarks.builders import ling_linear as builder

    config = _config()
    net, _ = builder.build_net(config, 11, ctx=mx.cpu(0))
    return net, config, builder.export_weights({"net": net})


def _ref_logits(tiny, tokens, rows, **controls):
    from benchmarks.references import ling_linear as reference

    _, config, weights = tiny
    return np.asarray(reference.logits_at(weights, config, tokens,
                                          np.asarray(rows), **controls))


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
    assert get_model("ling_linear_tiny").__class__.__name__ == \
        "LingLinearModel"
    net = ling_linear_tiny(layer_kinds=("kda", "mla"))
    assert [b.kind for b in net.blocks] == ["kda", "mla"]
    assert [b.is_moe for b in net.blocks] == [False, True]
    # the published pattern: MLA where (i + 1) % 6 == 0; the defaults
    # hold published layers 1-7
    assert model.LingLinearModel(layer_kinds=())._decode_cfg["held"] == 64
    with pytest.raises(ValueError, match="'kda' or 'mla'"):
        ling_linear_tiny(layer_kinds=("gqa",))


def test_mla_block_without_a_query_bottleneck_is_a_variant_not_a_copy():
    """``q_lora_rank`` None: one query projection, no scale factor to
    divide by; the block is LongCat's class with its hooks."""
    from mxnet_tpu.gluon.model_zoo.nlp import LingMLA, LongcatMLA

    kw = dict(num_heads=2, kv_lora_rank=8, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8)
    plain = LongcatMLA(16, q_lora_rank=None, **kw)
    assert plain._s_q == 1.0 and hasattr(plain, "q_proj") \
        and not hasattr(plain, "q_a")
    bottled = LongcatMLA(16, q_lora_rank=4, **kw)
    assert bottled._s_q == 2.0 and not hasattr(bottled, "q_proj")
    assert issubclass(LingMLA, LongcatMLA)
    assert "hybrid_forward" not in vars(LingMLA)
    gated = LingMLA(16, interleaved=False, **kw)
    gated.initialize()
    out = gated(mx.nd.array(np.random.RandomState(0).randn(1, 5, 16)))
    assert out.shape == (1, 5, 16) and np.isfinite(out.asnumpy()).all()


@pytest.mark.parametrize("name", ["kda_step", "kda_chunk_scan",
                                  "kda_mixer"])
def test_ops_are_registered_and_listed(name):
    from mxnet_tpu.ops.registry import get_op

    assert get_op("_contrib_" + name) is get_op(name)
    with open(os.path.join(ROOT, "OPS_MANIFEST.tsv")) as f:
        rows = dict(line.rstrip("\n").split("\t") for line in f
                    if "\t" in line)
    assert rows[name] == rows["_contrib_" + name] == "_contrib_" + name


def test_the_convolution_helper_is_the_one_both_families_call():
    """``ops/ssm.py::causal_conv`` / ``conv_tail``: Mamba's, Mamba-2's
    and KDA's convolution and tail are one function each."""
    import inspect

    from mxnet_tpu.gluon.model_zoo.nlp import falcon_h1, phi4flash
    from mxnet_tpu.ops import ssm

    for fn in (ssm.mamba_forward, ssm.mamba2_forward, la.kda_forward):
        assert "causal_conv(" in inspect.getsource(fn)
    for fn in (falcon_h1._mixer, phi4flash._mamba_layer, model._kda_mix):
        assert "conv_tail(" in inspect.getsource(fn)
    rs = np.random.RandomState(0)
    tail = jnp.asarray(rs.randn(2, 3, 5), jnp.float32)
    x = jnp.asarray(rs.randn(2, 7, 5), jnp.float32)
    w = jnp.asarray(rs.randn(5, 4), jnp.float32)
    conv, ext = ssm.causal_conv(tail, x, w)
    full = np.concatenate([tail, x], axis=1)
    want = sum(full[:, j:j + 7] * np.asarray(w)[:, j] for j in range(4))
    assert np.abs(np.asarray(conv) - want).max() < 1e-6
    # the tail that ends at the last real token: row 0 has 7, row 1 has 4
    kept = np.asarray(ssm.conv_tail(ext, jnp.asarray([7, 4]), 4))
    assert (kept[0] == full[0, 7:10]).all() and \
        (kept[1] == full[1, 4:7]).all()


# -- the recurrence ---------------------------------------------------------------

def _scan_inputs(seed, b, l, h=3, d=16):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)  # noqa: E731
    q = la.l2_normalize(f(b, l, h, d)) * d ** -0.5
    k = la.l2_normalize(f(b, l, h, d))
    # log-decays from -5 (a channel that forgets at once) to ~0
    g = -5.0 * jax.nn.sigmoid(f(b, l, h, d) * 3.0 - 3.0)
    beta = jax.nn.sigmoid(f(b, l, h))
    return q, k, f(b, l, h, d), g, beta, f(b, h, d, d)


def _by_steps(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = la.kda_step(q[:, t], k[:, t], v[:, t], g[:, t],
                               beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("length,chunk", [(64, 16), (150, 64), (37, 16),
                                          (5, 64)])
@pytest.mark.parametrize("carried", [False, True])
def test_chunk_form_is_the_token_recurrence(length, chunk, carried):
    """Chunk lengths that do and do not divide the prompt, from a zero
    and from a carried state."""
    q, k, v, g, beta, state = _scan_inputs(length, 2, length)
    if not carried:
        state = jnp.zeros_like(state)
    want_o, want_s = _by_steps(q, k, v, g, beta, state)
    got_o, got_s = la.kda_chunk_scan(q, k, v, g, beta, state, chunk=chunk)
    assert np.abs(np.asarray(got_o - want_o)).max() < 1e-5
    assert np.abs(np.asarray(got_s - want_s)).max() < 1e-5


def test_step_is_the_equation_written_out():
    """``S = (I - beta k k^T) Diag(alpha) S + beta k v^T``, ``o = S^T
    q``, in NumPy."""
    q, k, v, g, beta, state = (np.asarray(x) for x in _scan_inputs(3, 1, 1))
    got_o, got_s = la.kda_step(*(jnp.asarray(x[:, 0]) for x in
                                 (q, k, v, g, beta)), jnp.asarray(state))
    for h in range(q.shape[2]):
        kk, vv = k[0, 0, h][:, None], v[0, 0, h][:, None]
        s = ((np.eye(16) - beta[0, 0, h] * kk @ kk.T)
             @ np.diag(np.exp(g[0, 0, h])) @ state[0, h]
             + beta[0, 0, h] * kk @ vv.T)
        assert np.abs(np.asarray(got_s[0, h]) - s).max() < 1e-5
        assert np.abs(np.asarray(got_o[0, h]) - s.T @ q[0, 0, h]).max() \
            < 1e-5


def test_a_padded_position_is_an_identity_step():
    q, k, v, g, beta, state = _scan_inputs(5, 2, 24)
    real = jnp.arange(24)[None] < jnp.asarray([24, 9])[:, None]
    g = jnp.where(real[..., None, None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    _, s_all = la.kda_chunk_scan(q, k, v, g, beta, state, chunk=16)
    _, s_cut = la.kda_chunk_scan(q[1:, :9], k[1:, :9], v[1:, :9], g[1:, :9],
                                 beta[1:, :9], state[1:], chunk=16)
    assert np.abs(np.asarray(s_all[1] - s_cut[0])).max() < 1e-6
    # kda_gates writes them so
    a = jnp.zeros((2, 3, 4 * 8))
    gg, bb = la.kda_gates(a, jnp.zeros((2, 3, 4)), jnp.zeros((4,)),
                          jnp.full((32,), -1.0), jnp.asarray(
                              [[True, True, False]] * 2))
    assert float(jnp.abs(gg[:, 2]).max()) == 0.0 == float(bb[:, 2].max())
    assert float(gg[:, 0].max()) < 0 < float(bb[:, 0].min())


def test_safe_gate_is_bounded_and_the_other_form_is_not():
    a = jnp.full((1, 8), 30.0)
    real = jnp.ones((1,), bool)
    safe, _ = la.kda_gates(a, jnp.zeros((1, 1)), jnp.zeros((1,)),
                           jnp.zeros((8,)), real, lower_bound=-5.0)
    wild, _ = la.kda_gates(a, jnp.zeros((1, 1)), jnp.zeros((1,)),
                           jnp.zeros((8,)), real, safe=False)
    assert -5.0 <= float(safe.min()) <= float(safe.max()) <= 0.0
    assert float(wild.min()) < -29.0


# -- the kernel -------------------------------------------------------------------

def _kernel_inputs(seed, n_slots, rows, h=8, d=128):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)  # noqa: E731
    q = la.l2_normalize(f(rows, h, d)) * d ** -0.5
    k = la.l2_normalize(f(rows, h, d))
    g = -5.0 * jax.nn.sigmoid(f(rows, h, d) * 3.0 - 3.0)
    return (f(n_slots, h, d, d), q, k, f(rows, h, d), g,
            jax.nn.sigmoid(f(rows, h)))


def test_kernel_updates_scattered_slots_in_place():
    """Interpret mode against the step form over the gathered rows:
    scattered slots, padding rows on slot 0, a row that starts a stream
    in a dirty slot; slots no row names keep their content."""
    states, q, k, v, g, beta = _kernel_inputs(0, 7, 5)
    slots = jnp.asarray([3, 0, 5, 1, 0], jnp.int32)
    fresh = jnp.asarray([False, False, True, False, False])
    alpha = jnp.where(fresh[:, None, None], 0.0, jnp.exp(g))
    o, new = kda_state_update_kernel(states, slots, q, k, v, alpha, beta,
                                     interpret=True)
    want_o, want_s = la.kda_step(
        q, k, v, g, beta,
        jnp.where(fresh[:, None, None, None], 0.0, states[slots]))
    live = np.asarray([0, 2, 3])
    assert np.abs(np.asarray(o - want_o))[live].max() < 1e-5
    assert np.abs(np.asarray(new[slots[live]] - want_s[live])).max() < 1e-5
    for untouched in (2, 4, 6):
        assert (np.asarray(new[untouched])
                == np.asarray(states[untouched])).all()


def test_slot_update_routes_by_platform_and_shapes_alone(monkeypatch):
    states, q, k, v, g, beta = _kernel_inputs(1, 4, 2)
    assert kda_update_shape_supported(states, q)
    assert not kda_update_shape_supported(states.astype(jnp.bfloat16), q)
    assert not kda_update_shape_supported(states[:, :, :, :64], q)
    assert not kda_update_shape_supported(states[:, :4], q[:, :4])
    slots = jnp.asarray([2, 0], jnp.int32)
    fresh = jnp.asarray([False, False])
    # the CPU: the gathered step, no kernel
    text = str(jax.make_jaxpr(la.kda_slot_update)(
        states, slots, fresh, q, k, v, g, beta))
    assert "pallas_call" not in text
    o, new = la.kda_slot_update(states, slots, fresh, q, k, v, g, beta)
    want_o, want_s = la.kda_step(q, k, v, g, beta, states[slots])
    assert np.abs(np.asarray(o - want_o)).max() < 1e-6
    assert np.abs(np.asarray(new[2] - want_s[0])).max() < 1e-6
    # a TPU trace: the kernel, whatever MXNET_PALLAS_FUSED says
    from mxnet_tpu.base import execution_platform

    monkeypatch.delenv("MXNET_PALLAS_FUSED", raising=False)
    with execution_platform("tpu"):
        # a function of its own: a trace is cached by the function traced
        text = str(jax.make_jaxpr(lambda *a: la.kda_slot_update(*a))(
            states, slots, fresh, q, k, v, g, beta))
    assert "pallas_call" in text and "kda_state_update" in text


# -- the engine -----------------------------------------------------------------

def _engine(net, pages=33, page=8, slots=5):
    pool = PagePool(pages, page, n_state_slots=slots)
    return net.decode_engine(pool), pool


def test_engine_owns_one_latent_layer_and_a_slot_state_a_kda_layer(tiny):
    engine, pool = _engine(tiny[0])
    assert engine.state_slots and engine.chunked_prefill
    # ONE MLA layer of four: one arena, padded to whole lane tiles
    assert [a.shape for a in engine.arenas] == [(33, 8, 128)]
    st = engine.slot_arrays
    assert [s.shape for s in st["states"]] == [(5, 4, 16, 16)] * 3
    assert [s.shape for s in st["tails"]] == [(5, 3, 3 * 64)] * 3
    assert all(s.dtype == jnp.float32 for s in st["states"] + st["tails"])
    assert engine.state_bytes_per_stream == 4 * 3 * (4 * 256 + 3 * 192)


def test_full_prefill_matches_the_reference(tiny):
    engine, _ = _engine(tiny[0])
    tokens = _tokens(1, 1, 37)
    got = engine.forward_full(tokens)
    assert np.abs(got[0] - _ref_logits(tiny, tokens[0], [36])[0]).max() < TOL


def test_one_chunk_and_three_leave_the_same_state_in_the_slot(tiny):
    """A prompt of one chunk and the same prompt in three: the logits and
    the slot's state and tails agree."""
    net = tiny[0]
    tokens = _tokens(2, 1, 40)
    got = {}
    for chunk in (40, 16):
        engine, pool = _engine(net)
        table = np.asarray(pool.alloc("a", 48))[None]
        slot = np.asarray([pool.state_slots.alloc("a")], np.int32)
        for off in range(0, 40, chunk):
            n = min(chunk, 40 - off)
            part = np.zeros((1, chunk), np.int32)
            part[:, :n] = tokens[:, off:off + n]
            engine.prefill(part, np.asarray([off + n], np.int32), table,
                           np.asarray([off], np.int32) if off else None,
                           slot, np.asarray([off + n == 40]))
        got[chunk] = (engine.last_logits()[0],
                      [np.asarray(s[slot[0]])
                       for s in engine.slot_arrays["states"]],
                      [np.asarray(s[slot[0]])
                       for s in engine.slot_arrays["tails"]])
    assert np.abs(got[40][0] - got[16][0]).max() < TOL
    for one, three in zip(got[40][1] + got[40][2], got[16][1] + got[16][2]):
        assert np.abs(one - three).max() < 1e-5
    assert max(np.abs(s).max() for s in got[40][1]) > 1e-2


def test_prefill_then_decode_matches_the_reference(tiny):
    """Two streams of different depth in a bucket of three (a padding row
    on slot 0), one prefilled in chunks: every decode step's logits
    against the reference's full forward."""
    engine, pool = _engine(tiny[0])
    prompts = [_tokens(3, 21), _tokens(4, 12)]
    new = [_tokens(5, 6), _tokens(6, 6)]
    table = np.zeros((3, 4), np.int32)
    slots = np.zeros((3,), np.int32)
    for i, p in enumerate(prompts):
        table[i] = pool.alloc(i, 32)
        slots[i] = pool.state_slots.alloc(i)
    for i, p in enumerate(prompts):
        for off in range(0, p.size, 16):
            n = min(16, p.size - off)
            part = np.zeros((1, 16), np.int32)
            part[0, :n] = p[off:off + n]
            engine.prefill(part, np.asarray([off + n], np.int32),
                           table[i:i + 1],
                           np.asarray([off], np.int32) if off else None,
                           slots[i:i + 1], np.asarray([off + n == p.size]))
    lengths = np.asarray([21, 12, 0], np.int32)
    for t in range(6):
        toks = np.asarray([new[0][t], new[1][t], 0], np.int32)
        lengths = lengths + np.asarray([1, 1, 0], np.int32)
        engine.decode_step(toks, lengths, table, slots)
        logits = engine.last_logits()
        for i, p in enumerate(prompts):
            seq = np.concatenate([p, new[i][:t + 1]])
            ref = _ref_logits(tiny, seq, [seq.size - 1])[0]
            assert np.abs(logits[i] - ref).max() < TOL, (t, i)


def test_a_stream_starts_from_zeros_in_a_dirty_slot(tiny):
    engine, pool = _engine(tiny[0])
    tokens = _tokens(7, 1, 19)
    clean = engine.forward_full(tokens)
    st = engine.slot_arrays
    st["states"] = [s + 3.0 for s in st["states"]]
    st["tails"] = [s - 2.0 for s in st["tails"]]
    assert np.abs(engine.forward_full(tokens) - clean).max() < 1e-6


def test_served_through_the_server_matches_the_reference(tiny):
    """``Server.submit_generate``: prompts of one to three chunks beside
    decoding streams, slots and pages handed out together; the greedy
    tokens are the reference's (float32, no near ties at this size)."""
    net = tiny[0]
    srv = serving.Server(net, batch_buckets=(1, 4), dtype="int32",
                         ctx=mx.cpu(0), slo_ms=60000.0, decode_pages=25,
                         page_size=8, len_buckets=(8, 16),
                         max_generate_tokens=56, max_prefill_tokens=32,
                         name="ling-test").start()
    telemetry.enable()
    try:
        telemetry.reset()
        prompts = [_tokens(10 + i, n) for i, n in enumerate((37, 9, 20))]
        handles = [srv.submit_generate(p, 8) for p in prompts]
        outs = [np.asarray(h.result(timeout=300.0)) for h in handles]
        snap = telemetry.snapshot()["metrics"]
        assert snap["mxnet_state_slot_allocs_total"]["samples"][0][
            "value"] == 3
        # three streams x 3 KDA layers' state and tails at the most
        assert snap["mxnet_state_bytes_live_peak"]["samples"][0][
            "value"] == 3 * 4 * 3 * (4 * 256 + 3 * 192)
        held = [s["value"] for s in snap["mxnet_moe_picks_total"]["samples"]
                if s["labels"]["to"] == "held"]
        assert sum(held) > 0
    finally:
        telemetry.disable()
        srv.stop(timeout=60.0)
    for prompt, out in zip(prompts, outs):
        seq = np.concatenate([prompt, out])
        ref = _ref_logits(tiny, seq[:-1],
                          np.arange(prompt.size - 1, seq.size - 1))
        assert (ref.argmax(axis=1) == out).all()


def test_picks_are_read_a_forward_late(tiny, monkeypatch):
    """With telemetry on the engine records the picks of the forward
    BEFORE the one it has just dispatched: the first forward records
    nothing, the second the first's."""
    engine, pool = _engine(tiny[0])
    seen = []
    monkeypatch.setattr(telemetry, "record_moe_picks",
                        lambda *a, **kw: seen.append((a, kw)))
    telemetry.enable()
    try:
        tokens = _tokens(8, 1, 16)
        table = np.asarray(pool.alloc("a", 24))[None]
        slot = np.asarray([pool.state_slots.alloc("a")], np.int32)
        engine.prefill(tokens, np.asarray([16], np.int32), table,
                       slots=slot)
        assert seen == []
        engine.decode_step(tokens[:, 0], np.asarray([17], np.int32), table,
                           slot)
    finally:
        telemetry.disable()
    assert len(seen) == 1 and seen[0][1] == {"phase": "prefill"}
    held, zero, absent, _, layers = seen[0][0]
    # 16 tokens x 2 picks in each of 3 expert layers
    assert held + absent == 16 * 2 * 3 and zero == 0 and layers == 3


# -- the share ----------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: four chips hold 4 of 16 experts each; the
    routed parts all four shares give + the shared expert, which every
    chip computes alike, ONCE, add up to what the uncut reference gives
    for the whole expert layer."""
    from benchmarks.references import ling_linear as reference
    from benchmarks.references.dots_vlm import _swiglu, routed

    _, config, weights = tiny
    u, e = config["hidden_size"], config["moe_intermediate_size"]
    rs = np.random.RandomState(0)
    gate_up = jnp.asarray(rs.randn(16, u, 2 * e) * 0.2, jnp.float32)
    down = jnp.asarray(rs.randn(16, e, u) * 0.2, jnp.float32)
    lw = weights["layers"][1]
    h = jnp.asarray(rs.randn(24, u), jnp.float32)
    c = dict(reference.constants(config))
    whole_moe = dict(lw["moe"], gate_up=gate_up, down=down)
    with jax.default_matmul_precision("highest"):
        shared = _swiglu(h, lw["shared_gate_up"], lw["shared_down"])
        whole = routed(h, whole_moe, dict(c, first_held=0)) + shared
        parts = sum(routed(h, dict(whole_moe,
                                   gate_up=gate_up[4 * i:4 * i + 4],
                                   down=down[4 * i:4 * i + 4]),
                           dict(c, first_held=4 * i)) for i in range(4))
    assert np.abs(np.asarray(parts + shared - whole)).max() < 1e-5
    assert np.abs(np.asarray(parts)).max() > 0.1
    # and the program's share is the reference's share
    from mxnet_tpu.ops.contrib import moe_routed_experts

    cfg = tiny[0]._decode_cfg
    for i in (0, 2):
        got, _ = moe_routed_experts(
            h, lw["moe"]["router"], lw["moe"]["router_bias"],
            gate_up[4 * i:4 * i + 4], down[4 * i:4 * i + 4],
            first_held=4 * i, n_routed=cfg["n_routed"], top_k=cfg["top_k"],
            scale=cfg["moe_scale"], score="sigmoid", renormalize=True,
            n_group=cfg["n_group"], topk_group=cfg["topk_group"])
        with jax.default_matmul_precision("highest"):
            want = routed(h, dict(whole_moe,
                                  gate_up=gate_up[4 * i:4 * i + 4],
                                  down=down[4 * i:4 * i + 4]),
                          dict(c, first_held=4 * i))
        assert np.abs(np.asarray(got - want)).max() < 1e-4


# -- precision ------------------------------------------------------------------------

def test_state_tail_and_stream_are_float32_whatever_the_weights(tiny):
    """A bfloat16 net keeps its slot arrays and its residual stream in
    float32; only the latent pages take the weights' dtype."""
    net = ling_linear_tiny()
    net.cast("bfloat16")
    net.initialize()
    engine, pool = _engine(net)
    assert engine.arenas[0].dtype == jnp.bfloat16
    st = engine.slot_arrays
    assert all(s.dtype == jnp.float32 for s in st["states"] + st["tails"])
    engine.forward_full(_tokens(9, 1, 12))
    assert all(s.dtype == jnp.float32 for s in st["states"] + st["tails"])
    assert engine.last_logits().dtype == np.float32


def test_a_bfloat16_state_fails_where_the_float32_state_holds(tiny):
    """The float32-state pin. Over a few hundred tokens a state rounded
    to bfloat16 after every token (``lax.reduce_precision``) drifts from
    the recurrence by more than a hundred times what the float32 chunk
    form does: the slow channels (a decay of 0.999 a token) keep the sum
    of every rounding."""
    q, k, v, g, beta, state = _scan_inputs(11, 1, 384, h=2, d=16)
    g = g * 0.002                                   # slow channels
    _, want_s = _by_steps(q, k, v, g, beta, state)

    def rounded(state):
        for t in range(q.shape[1]):
            _, state = la.kda_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                   beta[:, t], state)
            state = jax.lax.reduce_precision(state, 8, 7)
        return None, state

    _, low_s = rounded(state)
    _, got_s = la.kda_chunk_scan(q, k, v, g, beta, state)
    sound = float(jnp.abs(got_s - want_s).max())
    low = float(jnp.abs(low_s - want_s).max())
    assert sound < 1e-4 and low > 100 * sound and low > 5e-3
    # and the reference says the same of whole logits
    tokens = _tokens(12, 200)
    ref = _ref_logits(tiny, tokens, [199])
    ref_low = _ref_logits(tiny, tokens, [199], state=(8, 7))
    assert np.abs(ref - ref_low).max() > 100 * TOL
