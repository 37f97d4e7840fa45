"""SDAR-MoE (a Qwen3-MoE-shaped decoder, every routed expert held, that
generates by diffusion over blocks of 4 positions) on the CPU at the tiny
preset, float32, seeded weights: the library model, the pick op, the
block mask and the folded-query kernel call (interpret mode), the decode
engine's block step and the server's block rounds, each held to
``benchmarks/references/sdar_moe.py``."""
import hashlib
import json
import os
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import serving, telemetry  # noqa: E402
from mxnet_tpu.gluon.model_zoo.nlp import (dots_vlm_tiny,  # noqa: E402
                                           falcon_h1_tiny, get_model,
                                           glm_moe_dsa_tiny, llama_tiny,
                                           longcat_flash_tiny,
                                           phi4flash_tiny, sdar_moe_tiny)
from mxnet_tpu.gluon.model_zoo.nlp import sdar_moe as model  # noqa: E402
from mxnet_tpu.ops import attention as attn_ops  # noqa: E402
from mxnet_tpu.ops.diffusion import block_denoise_pick  # noqa: E402
from mxnet_tpu.pallas_kernels.paged_attention import (  # noqa: E402
    paged_attention_kernel, paged_shape_supported)
from mxnet_tpu.serving.engine import PagedDecodeEngine  # noqa: E402
from mxnet_tpu.serving.kvcache import PagePool  # noqa: E402

from benchmarks.references import sdar_moe as reference  # noqa: E402

# float32 on the CPU: the library, the engine and the reference differ by
# the order of float32 sums alone (readings 1e-6 .. 4e-6 on logits of
# spread 2); ten times that
TOL = 4e-5
MASK = 255


def _config(**over):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny_sdar_moe.json")) as f:
        return dict(json.load(f), **over)


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration's net with the builder's seeded weights,
    and the same weights under the reference's names."""
    from benchmarks.builders import sdar_moe as builder

    config = _config()
    net, _ = builder.build_net(config, 11, ctx=mx.cpu(0))
    return net, config, builder.export_weights({"net": net})


@pytest.fixture(scope="module")
def sure(tiny):
    """The same net with its head scaled until a denoising step is SURE
    of some positions (c > 0.9): the threshold's path."""
    from benchmarks.builders import sdar_moe as builder

    config = _config()
    net, _ = builder.build_net(config, 11, ctx=mx.cpu(0))
    head = net.lm_head.weight
    head.set_data(head.data() * 40.0)
    return net, config, builder.export_weights({"net": net})


def _tokens(seed, *shape):
    return np.random.RandomState(seed).randint(1, MASK, shape).astype(
        np.int32)


# -- the model ------------------------------------------------------------------

def test_library_model_matches_the_reference_logits(tiny):
    net, config, weights = tiny
    tokens = _tokens(0, 2, 23)
    tokens[:, 17:] = MASK               # masked positions are inputs too
    out = net(mx.nd.array(tokens, dtype="int32")).asnumpy()
    for row in range(2):
        ref = np.asarray(reference.forward(weights, config, tokens[row],
                                           np.arange(23)))
        assert np.abs(out[row] - ref).max() < TOL


def test_model_zoo_exports_the_model():
    assert get_model("sdar_moe_tiny").__class__.__name__ == "SdarMoeModel"
    net = sdar_moe_tiny(num_layers=1)
    assert len(net.blocks) == 1 and net._decode_cfg["block_length"] == 4
    assert model.transfer_schedule(4, 4) == (1, 1, 1, 1)
    assert model.transfer_schedule(8, 3) == (3, 3, 2)
    with pytest.raises(ValueError, match="mask_token_id"):
        sdar_moe_tiny(mask_token_id=128)
    with pytest.raises(ValueError, match="denoising_steps"):
        sdar_moe_tiny(denoising_steps=5)


def test_the_pick_is_registered_and_listed():
    from mxnet_tpu.ops.registry import get_op

    name = "block_denoise_pick"
    assert get_op("_contrib_" + name) is get_op(name)
    with open(os.path.join(ROOT, "OPS_MANIFEST.tsv")) as f:
        rows = dict(line.rstrip("\n").split("\t") for line in f
                    if "\t" in line)
    assert rows[name] == rows["_contrib_" + name] == "_contrib_" + name


def test_the_reference_sees_blocks_both_ways_and_no_later_block(tiny):
    """Position i's logits move with a LATER position of its own block
    and with nothing of a later block."""
    _, config, weights = tiny
    tokens = _tokens(1, 12)
    base = np.asarray(reference.forward(weights, config, tokens, [4, 5]))
    later_in_block, later_block = tokens.copy(), tokens.copy()
    later_in_block[7] += 1
    later_block[8] += 1
    assert np.abs(np.asarray(reference.forward(
        weights, config, later_in_block, [4, 5])) - base).max() > 1e-3
    assert np.array_equal(np.asarray(reference.forward(
        weights, config, later_block, [4, 5])), base)


# -- the pick ---------------------------------------------------------------------

def _logits_with(conf_rows, winners, vocab=32):
    """(B, 4, vocab) logits whose position (b, i) has its largest logit
    at ``winners[b][i]`` with softmax probability ``conf_rows[b][i]``."""
    out = np.zeros((len(conf_rows), 4, vocab), np.float32)
    for b, row in enumerate(conf_rows):
        for i, c in enumerate(row):
            # c = e^x / (e^x + vocab - 2): the mask id's column is out
            out[b, i, winners[b][i]] = np.log(c * (vocab - 2) / (1.0 - c))
    return out


@pytest.mark.parametrize("quota", [1, 2])
def test_pick_unmasks_the_surest_masked_positions(quota):
    m = 31
    conf = [[0.5, 0.3, 0.8, 0.6], [0.2, 0.2, 0.2, 0.1],
            [0.3, 0.95, 0.4, 0.92], [0.1, 0.2, 0.3, 0.4]]
    winners = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12], [13, 14, 15, 16]]
    state = np.array([[m, m, 20, m],        # position 2 is known already
                      [m, m, m, m],         # a tie: the lower position
                      [m, m, m, m],         # two above the threshold
                      [20, 21, 22, 23]],    # a commit: nothing masked
                     np.int32)
    new = np.asarray(block_denoise_pick(
        jnp.asarray(_logits_with(conf, winners)), jnp.asarray(state),
        jnp.full((4,), quota, jnp.int32), mask_id=m, threshold=0.9))
    want = {1: [[m, m, 20, 4], [5, m, m, m], [m, 10, m, 12]],
            2: [[1, m, 20, 4], [5, 6, m, m], [m, 10, m, 12]]}[quota]
    assert new[:3].tolist() == want
    assert new[3].tolist() == [20, 21, 22, 23]
    # the reference's rule, position by position
    config = {"mask_token_id": m, "confidence_threshold": 0.9,
              "block_length": 4, "denoising_steps": 4 // quota}
    for b in range(4):
        ref = reference.pick(config, state[b], winners[b],
                             np.log(conf[b]), 0)
        assert ref.tolist() == new[b].tolist()


def test_pick_never_takes_the_mask_id_and_a_zero_quota_changes_nothing():
    m = 7
    logits = np.zeros((2, 4, 16), np.float32)
    logits[:, :, m] = 9.0               # the mask id scores highest
    logits[:, :, 3] = 1.0
    state = np.full((2, 4), m, np.int32)
    new = np.asarray(block_denoise_pick(
        jnp.asarray(logits), jnp.asarray(state),
        jnp.asarray([1, 0], jnp.int32), mask_id=m, threshold=0.9))
    assert new.tolist() == [[3, m, m, m], [m, m, m, m]]


# -- the block mask and the folded-query kernel call -------------------------------------

def _paged_case(seed, b=3, h=8, kv=2, d=128, ps=8, pages=12, bk=4,
                lengths=(24, 12, 0), dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    table = np.zeros((b, 4), np.int32)
    table[0], table[1, :2] = [3, 7, 1, 9], [5, 2]
    k = jnp.asarray(rs.randn(pages * ps, kv, d), dtype)
    v = jnp.asarray(rs.randn(pages * ps, kv, d), dtype)
    q = jnp.asarray(rs.randn(b, h, bk, d), dtype)
    return q, k, v, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


def test_block_one_is_the_causal_mask_exactly():
    q, k, v, table, lengths = _paged_case(2)
    pos = jnp.asarray([[20, 21, 22, 23], [8, 9, 10, 11], [0, 0, 0, 0]],
                      jnp.int32)
    args = (q, k, v, table, lengths, pos, 8, 0.1)
    causal = attn_ops._paged_reference(*args)
    assert np.array_equal(np.asarray(attn_ops._paged_reference(*args, 1)),
                          np.asarray(causal))
    # the same jaxpr: nothing of the block mask is traced at block 1
    assert str(jax.make_jaxpr(lambda *a: attn_ops._paged_reference(
        *a, pos, 8, 0.1, 1))(q, k, v, table, lengths)) == str(
            jax.make_jaxpr(lambda *a: attn_ops._paged_reference(
                *a, pos, 8, 0.1))(q, k, v, table, lengths))
    blocked = attn_ops._paged_reference(*args, 4)
    # inside a block the earlier positions now see the later ones
    assert np.abs(np.asarray(blocked - causal))[0, :, :3].max() > 1e-3
    assert np.allclose(np.asarray(blocked)[0, :, 3],
                       np.asarray(causal)[0, :, 3], atol=1e-6)


def test_folded_queries_through_the_kernel_match_the_block_mask():
    """A block's 4 queries as 4 more heads of their kv group over ONE
    walk of the live pages (interpret mode) against the gather under the
    block mask; a row of length 0 emits zeros."""
    q, k, v, table, lengths = _paged_case(3)
    b, h, bk, d = q.shape
    folded = q.reshape(b, h * bk, 1, d)
    assert paged_shape_supported(folded, k, 8)
    out = paged_attention_kernel(folded, k, v, table, lengths, page_size=8,
                                 scale=0.1, interpret=True).reshape(q.shape)
    pos = lengths[:, None] - bk + jnp.arange(bk)[None, :]
    ref = attn_ops._paged_reference(q, k, v, table, lengths, pos, 8, 0.1, 4)
    assert np.abs(np.asarray(out - ref))[:2].max() < 2e-5
    assert not np.asarray(out)[2].any()


def test_the_op_folds_only_a_rows_trailing_block(monkeypatch):
    """By shapes alone: ``Lq == block`` goes to the kernel (where the
    gate passes), folded; every other ``Lq`` takes the gather."""
    calls = []

    def kernel(q, *a, **kw):
        calls.append(q.shape)
        return jnp.zeros_like(q)

    from mxnet_tpu.pallas_kernels import paged_attention as pk

    monkeypatch.setattr(pk, "paged_supported", lambda *a: True)
    monkeypatch.setattr(pk, "paged_attention_kernel", kernel)
    q, k, v, table, lengths = _paged_case(4)
    out = attn_ops.paged_attention(q, k, v, table, lengths, page_size=8,
                                   block=4)
    assert calls == [(3, 32, 1, 128)] and out.shape == q.shape
    attn_ops.paged_attention(jnp.concatenate([q, q], axis=2), k, v, table,
                             lengths, page_size=8, block=4)
    assert len(calls) == 1              # a prefill: the gather


# -- the engine ---------------------------------------------------------------------

def _engine(net, pages=40, page_size=8):
    pool = PagePool(pages, page_size)
    return net.decode_engine(pool), pool


def _tool(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", name + ".py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def _serve_by_hand(engine, pool, config, prompt, max_new, commit=True,
                   logits=None):
    """The published loop over the engine's own calls, one stream
    (``tools/sdar_chip_check.py::serve_by_hand``): the answer, the step
    each token was unmasked at and the forwards run. ``logits``: a list
    that gets (state before, logits) of every forward."""
    outs, steps, forwards = _tool("sdar_chip_check").serve_by_hand(
        engine, pool, [prompt], max_new, commit=commit,
        seen=None if logits is None else [logits])
    return outs[0], steps[0], forwards[0]


@pytest.mark.parametrize("p_len,max_new", [(16, 8), (13, 7), (18, 5),
                                           (23, 10), (3, 6), (1, 4)])
def test_prefill_then_block_steps_match_the_references_loop(tiny, p_len,
                                                            max_new):
    """Prompt lengths and budgets of every remainder mod 4, and a prompt
    shorter than a block (no prefill at all): the tokens, the step each
    was unmasked at and every step's logits."""
    net, config, weights = tiny
    engine, pool = _engine(net)
    prompt = _tokens(20 + p_len, p_len)
    seen = []
    out, steps, forwards = _serve_by_hand(engine, pool, config, prompt,
                                          max_new, logits=seen)
    ref_out, ref_steps = reference.generate(weights, config, prompt,
                                            max_new, pad_to=36)
    assert out.tolist() == ref_out.tolist()
    assert steps.tolist() == ref_steps.tolist()
    # the static schedule: a forward a generated position, one a block
    blocks = -(-(p_len % 4 + max_new) // 4)
    assert forwards == 4 * blocks - p_len % 4 + blocks
    # every forward's logits against one full forward of the reference
    seq = np.concatenate([prompt, ref_out])
    base = 4 * (p_len // 4)
    worst, at = 0.0, 0
    for state, got in seen:
        if at + 4 > seq.size:
            break                       # a last block cut by the budget
        ctx = np.concatenate([seq[:base + at], state])
        ref = np.asarray(reference.forward(
            weights, config, ctx, np.arange(ctx.size - 4, ctx.size)))
        worst = max(worst, np.abs(got - ref).max())
        if not (state == MASK).any():
            at += 4                     # that was the block's commit
    assert worst < TOL


def test_two_streams_at_different_steps_share_a_round(tiny):
    """One (2, 4) dispatch: row 0 at its block's step 2, row 1 at its
    commit, beside a padding row's worth of nothing; each as alone."""
    net, config, weights = tiny
    engine, pool = _engine(net)
    a, b = _tokens(30, 8), _tokens(31, 12)
    tables = np.stack([pool.alloc("a", 16), pool.alloc("b", 16)])
    engine.prefill(np.stack([np.pad(a, (0, 4)), b]),
                   np.array([8, 12], np.int32), tables)
    blocks = np.array([[5, MASK, 9, MASK], [1, 2, 3, 4]], np.int32)
    new = engine.decode_block(blocks, np.array([12, 16], np.int32), tables,
                              np.array([1, 0], np.int32))
    both = engine.last_logits()
    assert new[1].tolist() == [1, 2, 3, 4]
    assert (new[0] != MASK).sum() == 3 and new[0, 0] == 5 and new[0, 2] == 9
    for row, seq in ((0, np.concatenate([a, blocks[0]])),
                     (1, np.concatenate([b, blocks[1]]))):
        ref = np.asarray(reference.forward(
            weights, config, seq, np.arange(seq.size - 4, seq.size)))
        assert np.abs(both[row] - ref).max() < TOL


def test_a_skipped_commit_changes_the_next_blocks_logits(tiny):
    """The cache has to hold the keys and values of a block's FINAL
    tokens: left at the state of its last denoising step (the last
    position still the mask token), the next block's logits are off by
    thousands of tolerances, and the reference's loop with the same
    fault planted agrees with the faulty engine."""
    net, config, weights = tiny
    prompt = _tokens(40, 8)
    sound, faulty = [], []
    engine, pool = _engine(net)
    _serve_by_hand(engine, pool, config, prompt, 8, logits=sound)
    out, steps, forwards = _serve_by_hand(engine, pool, config, prompt, 8,
                                          commit=False, logits=faulty)
    assert forwards == 8                # no commit forward ran
    # the second block's first step: sound[5] (after 4 steps + a commit)
    gap = np.abs(sound[5][1] - faulty[4][1]).max()
    assert gap > 1000 * TOL
    ref_out, _ = reference.generate(weights, config, prompt, 8, pad_to=36,
                                    commit=False)
    assert out.tolist() == ref_out.tolist()


def test_the_threshold_unmasks_several_positions_a_step(sure):
    """A head scaled until c > 0.9: blocks take 2-4 forwards, fewer than
    the static schedule's 5, by the same rule in engine and reference."""
    net, config, weights = sure
    engine, pool = _engine(net)
    prompt = _tokens(50, 10)
    out, steps, forwards = _serve_by_hand(engine, pool, config, prompt, 18)
    ref_out, ref_steps = reference.generate(weights, config, prompt, 18,
                                            pad_to=36)
    assert out.tolist() == ref_out.tolist()
    assert steps.tolist() == ref_steps.tolist()
    assert forwards < 5 * 5 - 2         # the static schedule's count
    per_block = np.bincount(np.arange(2, 20) // 4, minlength=5)
    assert np.all(steps.reshape(-1) < 4)
    assert any(np.sum(steps[i:i + 4] == 0) > 1
               for i in range(2, 18, 4)), (steps, per_block)


def test_engine_declares_its_block_and_the_others_one(tiny):
    engine, pool = _engine(tiny[0])
    assert engine.block_length == 4 and engine.mask_id == MASK
    assert engine.transfer == (1, 1, 1, 1)
    assert len(engine.arenas) == 2 * 3 and not engine.state_slots
    assert engine.arenas[0].shape == (40, 8, 128)
    net = llama_tiny()
    net.initialize()
    assert net.decode_engine(PagePool(9, 8)).block_length == 1


# -- the server ---------------------------------------------------------------------

def _serve(net, requests, **kw):
    """``requests`` [(prompt, max_new)] through one started server:
    answers, unmask steps, pushes in arrival order, and the server."""
    kw = dict(dict(batch_buckets=(1, 4), dtype="int32", ctx=mx.cpu(0),
                   slo_ms=60000.0, decode_pages=41, page_size=8,
                   len_buckets=(8, 16, 32), max_generate_tokens=64,
                   name="sdar"), **kw)
    srv = serving.Server(net, **kw).start()
    pushed = [[] for _ in requests]
    try:
        handles = [srv.submit_generate(
            p, n, on_token=lambda i, t, k=k: pushed[k].append((i, t)))
            for k, (p, n) in enumerate(requests)]
        outs = [np.asarray(h.result(timeout=300.0)) for h in handles]
        steps = [h.unmask_steps() for h in handles]
        used = srv._tenants["default"].engine.pool.stats()["used"]
    finally:
        srv.stop(timeout=60.0)
    return outs, steps, pushed, used


def test_server_answers_as_the_references_loop_does(tiny):
    """Streams that join and leave at any round, at different steps of
    their blocks, prompts and budgets of every remainder mod 4 and one
    shorter than a block: each answer is the reference loop's, token for
    token and step for step, pushed in order, the budget exactly, every
    page free at the end; the counters count forwards and tokens."""
    net, config, weights = tiny
    requests = [(_tokens(60 + i, p), n) for i, (p, n) in enumerate(
        ((9, 7), (16, 4), (2, 9), (30, 6), (13, 1), (7, 12)))]
    telemetry.enable()
    try:
        telemetry.reset()
        outs, steps, pushed, used = _serve(net, requests)
        snap = telemetry.snapshot()["metrics"]
    finally:
        telemetry.disable()
    assert used == 0
    for (prompt, n), out, st, push in zip(requests, outs, steps, pushed):
        ref_out, ref_steps = reference.generate(weights, config, prompt, n,
                                                pad_to=48)
        assert out.tolist() == ref_out.tolist()
        assert st == ref_steps.tolist()
        assert push == list(enumerate(out.tolist()))
    forwards = {s["labels"]["kind"]: s["value"] for s in
                snap["mxnet_diffusion_block_forwards_total"]["samples"]}
    unmasked = snap["mxnet_diffusion_tokens_unmasked_total"]["samples"][0][
        "value"]
    # a denoising step a token (the static schedule); a request ends with
    # its budget's last token, so its last block is never committed
    assert forwards["denoise"] == unmasked
    blocks = [-(-(p.size % 4 + n) // 4) for p, n in requests]
    assert forwards["commit"] == sum(blocks) - len(requests)
    assert unmasked >= sum(n for _, n in requests)
    held = sum(s["value"] for s in snap["mxnet_moe_picks_total"]["samples"]
               if s["labels"]["to"] == "held")
    assert held > 0


def test_a_stream_is_preempted_whole_inside_a_block(tiny):
    """A higher-priority arrival reclaims the pages of a stream that is
    inside a block: the victim resolves typed, what it streamed is a
    clean prefix of its answer, the arrival is served."""
    from mxnet_tpu.serving.kvcache import Preempted

    net, config, weights = tiny
    srv = serving.Server(net, batch_buckets=(1, 2), dtype="int32",
                         ctx=mx.cpu(0), slo_ms=60000.0, decode_pages=7,
                         page_size=8, len_buckets=(8, 16),
                         max_generate_tokens=40, name="sdar-p").start()
    try:
        low_prompt = _tokens(70, 8)
        started = threading.Event()
        low = srv.submit_generate(low_prompt, 30, priority=0,
                                  on_token=lambda i, t: started.set())
        assert started.wait(120.0)
        high = srv.submit_generate(_tokens(71, 8), 6, priority=5)
        out = np.asarray(high.result(timeout=300.0))
        with pytest.raises(Preempted):
            low.result(timeout=300.0)
        got = low.tokens()
    finally:
        srv.stop(timeout=60.0)
    assert out.size == 6
    ref_out, _ = reference.generate(weights, config, low_prompt, 30,
                                    pad_to=48)
    assert 0 < len(got) < 30 and got == ref_out[:len(got)].tolist()


def test_server_refuses_what_the_last_block_cannot_hold(tiny):
    from mxnet_tpu.serving.kvcache import CacheFull

    net = tiny[0]
    srv = serving.Server(net, batch_buckets=(1,), dtype="int32",
                         ctx=mx.cpu(0), slo_ms=60000.0, decode_pages=9,
                         page_size=8, len_buckets=(8, 16),
                         max_generate_tokens=24, name="sdar-r").start()
    try:
        # 13 + 10 = 23 tokens, but the last block ends at 12 + 12 = 24
        assert np.asarray(srv.submit_generate(
            _tokens(80, 13), 10).result(timeout=300.0)).size == 10
        # 14 + 10 = 24 tokens end at 12 + 12; 13 + 12 reaches 12 + 16
        with pytest.raises(CacheFull):
            srv.submit_generate(_tokens(81, 13), 12)
    finally:
        srv.stop(timeout=60.0)


# -- the engines this PR did not touch trace what they traced ----------------------------

ENGINE_JAXPR_SHA = {
    "llama_tiny":
        "9510c2c850ea359d36ab4af1228e2c277423e98c527c006b32154ab8fa11c455",
    "longcat_flash_tiny":
        "3e923ccb954c4cc2c859231265686746ca29064df50b5040b3d89072cfd3c32b",
    "glm_moe_dsa_tiny":
        "ab7eee2ca74f843be775128107f7be43b9a1bce46974217dfd39e8d6d83556da",
    "phi4flash_tiny":
        "67e923c476fe1eaece7b9aa47803134ec1f19678b387b9db7a4aded8c6f7187c",
    "falcon_h1_tiny":
        "e0bba90b8414ec284cd882950f1b51e041aa2b74d382a24ced0c24a41faff887",
    # PR 49's: its two PREFILL layer programs pick a chunk's attention by
    # the positions (`dots_vlm.py::_attention`); the six other programs of
    # the two forwards are the parent's, the decode step's pinned below
    "dots_vlm_tiny":
        "a78e9649eec56403b7e2bbdbc612db65b5cf6faae92af54be0149e2d53431c18",
}
# the dots engine's four decode-step programs alone, as PR 48's tree
# traces them
DOTS_DECODE_JAXPR_SHA = \
    "9aaea37712553e84cfb698d6235cbe08324c6caf5bab835fed2d3b3940928d41"


@pytest.mark.parametrize("make", [llama_tiny, longcat_flash_tiny,
                                  glm_moe_dsa_tiny, phi4flash_tiny,
                                  falcon_h1_tiny, dots_vlm_tiny],
                         ids=lambda f: f.__name__)
def test_the_other_engines_trace_to_the_parents_programs(make, monkeypatch):
    """``ops/attention.py::paged_attention``, ``serving/engine.py`` and
    ``serving/server.py`` are shared: every program of a prefill and a
    decode step of the tiny Llama (Mistral's engine), LongCat, GLM, Phi,
    Falcon-H1 and dots engines has the jaxpr the parent commit (PR 46)
    traces, byte for byte (the first four hashes are
    ``tests/test_falcon_h1.py``'s, the tiny Llama's PR 48's among them;
    Falcon-H1's was taken on that commit's tree, dots' on PR 49's, whose
    decode programs are held to PR 48's tree apart)."""
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
    def digest(keep):
        return hashlib.sha256("\n".join(
            f"{k}\n{v}" for k, v in sorted(
                texts.items(), key=lambda kv: str(kv[0]))
            if keep(k)).encode()).hexdigest()

    assert digest(lambda k: True) == ENGINE_JAXPR_SHA[make.__name__]
    if make is dots_vlm_tiny:
        assert digest(lambda k: k[3] == 1) == DOTS_DECODE_JAXPR_SHA
