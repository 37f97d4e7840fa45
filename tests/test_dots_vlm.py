"""dots.vlm1 (a NaViT vision tower in front of DeepSeek-V3 layers) against
its plain reference ``benchmarks/references/dots_vlm.py`` at a tiny size
on the CPU, seeded weights, float32: the tower, the language model whole
and through the cache, the router's group limit, YaRN, the key-length
bound of the flash forward, and the sum of the 16 shares."""
import json
import math
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(**over):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny_dots_vlm.json")) as f:
        return dict(json.load(f), **over)


@pytest.fixture(scope="module")
def built():
    from benchmarks.builders import dots_vlm as builder

    config = _config()
    net, ctx = builder.build_net(config, 7, ctx=mx.cpu(0))
    weights = builder.export_weights({"net": net})
    return config, net, weights


def _image(seed, rows, cols):
    rs = np.random.RandomState(seed)
    return rs.standard_normal((rows * cols, 588)).astype(np.float32), \
        (rows, cols)


def _prompt(config, images, before, after, seed=0):
    rs = np.random.RandomState(seed)
    holder = config["image_token_id"]
    parts = [rs.randint(1, holder, (before,))]
    for _, (r, c) in images:
        parts.append(np.full((r * c // 4,), holder))
    parts.append(rs.randint(1, holder, (after,)))
    return np.concatenate(parts).astype(np.int32)


def _engine(net, n_pages=65, page_size=8, buckets=(128, 256), rows=160):
    engine = net.decode_engine(serving.PagePool(n_pages, page_size))
    engine.vision.configure(buckets, rows)
    return engine


def test_patch_order_puts_a_merge_group_side_by_side():
    from benchmarks.references import dots_vlm as ref
    from mxnet_tpu.gluon.model_zoo.vision.navit import (patch_positions,
                                                        patchify)

    pos = patch_positions(4, 6)
    assert pos.shape == (24, 2) and np.array_equal(pos, ref.patch_positions(
        4, 6))
    assert pos[:4].tolist() == [[0, 0], [0, 1], [1, 0], [1, 1]]
    assert pos[4:8].tolist() == [[0, 2], [0, 3], [1, 2], [1, 3]]
    image = np.arange(56 * 84 * 3, dtype=np.float32).reshape(56, 84, 3)
    patches, grid = patchify(image)
    assert grid == (4, 6) and patches.shape == (24, 588)
    assert np.array_equal(patches[5].reshape(14, 14, 3),
                          image[0:14, 42:56])           # patch (0, 3)
    with pytest.raises(ValueError):
        patchify(np.zeros((56, 70, 3)))


@pytest.mark.parametrize("grid", [(4, 6), (8, 2)])
def test_tower_alone_against_the_reference(built, grid):
    from benchmarks.references import dots_vlm as ref

    config, net, weights = built
    patches, grid = _image(1, *grid)
    want = np.asarray(ref.vision_encode(weights["vision"], config, patches,
                                        grid))
    got = net.vision(patches, grid).asnumpy()
    assert got.shape == want.shape == (grid[0] * grid[1] // 4, 64)
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def test_a_padded_bucket_equals_the_unpadded_image(built):
    config, net, _ = built
    engine = _engine(net)
    patches, grid = _image(2, 6, 6)                  # 36 patches -> 128
    buf, bucket = engine.vision.encode(patches, grid,
                                       engine.vision.new_buffer(), 5)
    assert bucket == 128
    want = net.vision(patches, grid).asnumpy()
    np.testing.assert_allclose(np.asarray(buf)[0, 5:14], want, atol=1e-5)


def test_two_images_of_one_request_do_not_see_each_other(built):
    from benchmarks.references import dots_vlm as ref

    config, net, weights = built
    engine = _engine(net)
    a, b = _image(3, 4, 6), _image(4, 6, 2)
    buf = engine.vision.new_buffer()
    buf, _ = engine.vision.encode(*a, buf, 0)
    buf, _ = engine.vision.encode(*b, buf, 6)
    got = np.asarray(buf)[0, :9]
    want = np.concatenate([np.asarray(ref.vision_encode(
        weights["vision"], config, *im)) for im in (a, b)])
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())
    # one sequence of both images' patches is another answer
    both = np.concatenate([a[0], b[0]])
    mixed = np.asarray(ref.vision_encode(weights["vision"], config,
                                         both, (6, 6)))
    assert np.abs(mixed[:6] - want[:6]).max() > 1e-2 * np.abs(want).max()


def test_language_model_whole_sequence_against_the_reference(built):
    from benchmarks.references import dots_vlm as ref

    config, net, weights = built
    tokens = np.random.RandomState(5).randint(1, 255, (40,)).astype(np.int32)
    want = np.asarray(ref.logits_at(weights, config, tokens,
                                    np.arange(40)))
    got = net(mx.nd.array(tokens[None], dtype="int32")).asnumpy()[0]
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("chunk", [None, 16])
def test_prefill_and_decode_through_the_cache_with_images(built, chunk):
    """Prefill (whole, or in chunks that cut through an image's rows) then
    decode through the cache, against ONE reference forward over images,
    prompt and generated ids: logits."""
    from benchmarks.references import dots_vlm as ref

    config, net, weights = built
    engine = _engine(net)
    images = [_image(6, 4, 6), _image(7, 6, 2)]
    prompt = _prompt(config, images, 5, 9)
    p, new = prompt.size, 4
    buf = engine.vision.new_buffer()
    row0 = 0
    for patches, grid in images:
        buf, _ = engine.vision.encode(patches, grid, buf, row0)
        row0 += grid[0] * grid[1] // 4
    at = np.flatnonzero(prompt == config["image_token_id"])
    assert at.size == row0 == 9
    pages = engine.pool.alloc("s", p + new)
    table = np.zeros((1, engine.pool.pages_for(p + new)), np.int32)
    table[0, :len(pages)] = pages
    step = chunk or 32
    logits = []
    for off in range(0, p, step):
        n = min(step, p - off)
        part = np.zeros((1, step), np.int32)
        part[0, :n] = prompt[off:off + n]
        rows = np.full((1, step), -1, np.int32)
        first = np.searchsorted(at, off)
        here = at[first:np.searchsorted(at, off + n)]
        rows[0, here - off] = first + np.arange(here.size)
        ids = engine.prefill(part, np.array([off + n], np.int32), table,
                             np.array([off], np.int32) if off else None,
                             embeds=buf, embed_rows=rows)
    logits.append(engine.last_logits()[0])
    out = [int(ids[0])]
    for i in range(1, new):
        ids = engine.decode_step(np.array([out[-1]], np.int32),
                                 np.array([p + i], np.int32), table)
        logits.append(engine.last_logits()[0])
        out.append(int(ids[0]))
    engine.pool.free("s")
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])
    want = np.asarray(ref.logits_at(weights, config, seq,
                                    np.arange(p - 1, p - 1 + new), images))
    np.testing.assert_allclose(np.stack(logits), want,
                               atol=3e-4 * np.abs(want).max())
    # the rows of y matter: ids alone give other logits
    ids_only = np.asarray(ref.logits_at(weights, config, seq, [p - 1]))
    assert np.abs(ids_only - want[:1]).max() > 1e-2 * np.abs(want).max()


def _counted(run, family, *labels):
    """The counter ``family`` by its ``labels`` over ``run()``."""
    from mxnet_tpu import telemetry

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


@pytest.mark.parametrize("lengths", [(32,), (21,), (27, 0)],
                         ids=["whole", "padded_tail", "padding_row"])
def test_fresh_and_walk_forms_of_a_chunk_from_zero_agree(built, monkeypatch,
                                                         lengths):
    """A prefill whose rows all start at position 0 attends over its own
    fresh latents (the true branch of ``_attention``'s ``lax.cond``); the
    same input through the walk over the cache (the branch forced, in an
    engine keyed apart) picks the same ids from logits within the file's
    tolerance, both within it of the reference, and leaves the same
    pages; so does the decode step after it, which reads those pages."""
    import jax

    from benchmarks.references import dots_vlm as ref

    config, net, weights = built
    b, l = len(lengths), 32
    rs = np.random.RandomState(8)
    tokens = rs.randint(2, 255, (b, l)).astype(np.int32)
    n = np.asarray(lengths, np.int32)

    def run(engine):
        table = np.zeros((b, engine.pool.pages_for(l + 1)), np.int32)
        for i in range(b):
            if n[i]:
                pages = engine.pool.alloc(("row", i), l + 1)
                table[i, :len(pages)] = pages
        ids = [engine.prefill(tokens, n, table)]
        logits = [engine.last_logits()]
        ids.append(engine.decode_step(ids[0], n + (n > 0), table))
        logits.append(engine.last_logits())
        return ids, logits, [np.asarray(a) for a in engine.arenas]

    fresh = run(_engine(net))
    walking = _engine(net)
    walking._ident = walking._ident + ("walk",)      # programs of its own
    monkeypatch.setattr(jax.lax, "cond", lambda pred, t, f, *ops: f(*ops))
    walk = run(walking)
    monkeypatch.undo()
    live = n > 0
    for step in range(2):
        want = np.stack([np.asarray(ref.logits_at(
            weights, config,
            np.append(tokens[i, :n[i]], fresh[0][0][i])[:n[i] + step],
            [n[i] - 1 + step]))[0] for i in np.flatnonzero(live)])
        tol = 3e-4 * np.abs(want).max()
        assert np.array_equal(fresh[0][step][live], walk[0][step][live])
        np.testing.assert_allclose(fresh[1][step][live], walk[1][step][live],
                                   atol=tol)
        np.testing.assert_allclose(fresh[1][step][live], want, atol=tol)
    assert np.array_equal(fresh[2][0][1:], walk[2][0][1:])   # to the bit
    for a, c in zip(fresh[2], walk[2]):
        np.testing.assert_allclose(a[1:], c[1:], atol=1e-5)
    # page 0 is the scratch page padding is written to: a padding query
    # attends to what lies before it in the fresh form and to nothing in
    # the walk, so the two engines did run the two forms
    assert np.array_equal(fresh[2][1][0], walk[2][1][0]) == (lengths == (32,))


def test_the_engine_counts_a_chunk_from_zero_as_fresh(built):
    """``mxnet_serving_prefill_dispatch_total{path}``: a prefill from
    position 0 counts ``fresh``, a chunk at an offset ``gather``, a decode
    step neither; the three forwards' logits are the reference's."""
    from benchmarks.references import dots_vlm as ref

    config, net, weights = built
    engine = _engine(net)
    seq = np.random.RandomState(9).randint(2, 255, (41,)).astype(np.int32)
    pages = engine.pool.alloc("s", 41)
    table = np.zeros((1, engine.pool.pages_for(41)), np.int32)
    table[0, :len(pages)] = pages
    logits = []

    def three_forwards():
        engine.prefill(seq[None, :16], np.array([16], np.int32), table)
        logits.append(engine.last_logits()[0])
        part = np.zeros((1, 32), np.int32)
        part[0, :24] = seq[16:40]
        engine.prefill(part, np.array([40], np.int32), table,
                       np.array([16], np.int32))
        logits.append(engine.last_logits()[0])
        engine.decode_step(seq[40:41], np.array([41], np.int32), table)
        logits.append(engine.last_logits()[0])

    paths = _counted(three_forwards, "mxnet_serving_prefill_dispatch_total",
                     "path")
    engine.pool.free("s")
    assert paths == {("fresh",): 1, ("gather",): 1}
    want = np.asarray(ref.logits_at(weights, config, seq, [15, 39, 40]))
    np.testing.assert_allclose(np.stack(logits), want,
                               atol=3e-4 * np.abs(want).max())


def test_causal_flash_forward_at_the_latent_head_widths():
    """The Pallas causal flash forward (interpret mode) as
    ``mla_fresh_attention`` calls it at dots.vlm1's head widths: scores
    over nope + rope = 192, the values zero-padded 128 -> 192 and the
    output cut back, the scale the caller's and not the padded width's;
    1,024 positions are two 512-blocks a side, the streaming body."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import _sdpa_reference
    from mxnet_tpu.pallas_kernels.flash_attention import flash_attention

    b, h, l, qk, dv = 1, 2, 1024, 192, 128
    kq, kk, kv = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(kq, (b, h, l, qk), jnp.float32)
    k = jax.random.normal(kk, (b, h, l, qk), jnp.float32)
    v = jax.random.normal(kv, (b, h, l, dv), jnp.float32)
    scale = 1.37 * qk ** -0.5
    out = flash_attention(q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, qk - dv),)),
                          scale=scale, causal=True, interpret=True)
    assert float(jnp.abs(out[..., dv:]).max()) == 0.0
    want = _sdpa_reference(q, k, v, None, scale, True)
    np.testing.assert_allclose(np.asarray(out[..., :dv]), np.asarray(want),
                               atol=2e-5)


def test_an_engine_without_the_seam_refuses_embeddings():
    from mxnet_tpu.gluon.model_zoo.nlp import glm_moe_dsa_tiny

    net = glm_moe_dsa_tiny()
    net.initialize(ctx=mx.cpu(0))
    engine = net.decode_engine(serving.PagePool(9, 8))
    assert engine.vision is None and not engine.takes_embeds
    with pytest.raises(NotImplementedError):
        engine.prefill(np.ones((1, 8), np.int32), np.array([8], np.int32),
                       np.ones((1, 1), np.int32),
                       embeds=np.zeros((1, 4, 32), np.float32),
                       embed_rows=np.full((1, 8), -1, np.int32))


def _numpy_group_pick(choice, n_group, topk_group, top_k):
    """The group-limited pick written out: per token, the groups' scores
    (sum of the 2 largest), the best groups, top-k among their experts."""
    picks = []
    for row in choice:
        groups = row.reshape(n_group, -1)
        score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        keep = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.full_like(row, -np.inf).reshape(n_group, -1)
        masked[keep] = groups[keep]
        picks.append(np.sort(np.argsort(-masked.reshape(-1),
                                        kind="stable")[:top_k]))
    return np.stack(picks)


@pytest.mark.parametrize("n_group,topk_group", [(8, 4), (4, 1), (1, 1)])
def test_group_limited_pick_against_numpy(n_group, topk_group):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.contrib import moe_routed_experts

    rs = np.random.RandomState(11)
    n, u, e, k = 24, 16, 32, 4
    x = rs.standard_normal((n, u)).astype(np.float32)
    router = rs.standard_normal((e, u)).astype(np.float32) * 0.5
    bias = rs.uniform(-0.05, 0.05, (e,)).astype(np.float32)
    # every expert held, down = identity-free probe: the experts' output
    # is not read; the picks are, through the counts of a layer that
    # holds ONE expert at a time
    gate_up = rs.standard_normal((1, u, 8)).astype(np.float32)
    down = rs.standard_normal((1, 4, u)).astype(np.float32)
    choice = 1 / (1 + np.exp(-(x @ router.T))) + bias
    want = _numpy_group_pick(choice, n_group, topk_group, k)
    got = np.zeros((e,), np.int64)
    for first in range(e):
        _, counts = moe_routed_experts(
            jnp.asarray(x), jnp.asarray(router), jnp.asarray(bias),
            jnp.asarray(gate_up), jnp.asarray(down), first_held=first,
            n_routed=e, top_k=k, score="sigmoid", renormalize=True,
            n_group=n_group, topk_group=topk_group)
        got[first] = int(counts[0])
    assert np.array_equal(got, np.bincount(want.reshape(-1), minlength=e))
    if n_group > 1 and topk_group < n_group:
        free = _numpy_group_pick(choice, 1, 1, k)
        assert not np.array_equal(free, want)   # the limit changes picks
    del jax


def test_yarn_table_against_the_formula():
    from benchmarks.references import dots_vlm as ref
    from mxnet_tpu.ops.attention import yarn_inv_freq, yarn_mscale

    d, theta, factor, fast, slow, orig = 64, 10000.0, 40.0, 32.0, 1.0, 4096.0
    got = np.asarray(yarn_inv_freq(d, theta, factor, fast, slow, orig))

    def dim_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(
            theta))

    low, high = math.floor(dim_of(fast)), math.ceil(dim_of(slow))
    assert (low, high) == (10, 23)
    want = []
    for i in range(d // 2):
        plain = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(plain * (1 - ramp) + plain / factor * ramp)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(ref.yarn_inv_freq(d, theta, factor, fast, slow,
                                                 orig), want, rtol=1e-5)
    assert got[0] == 1.0 and abs(got[-1] * factor * theta ** (62 / 64) - 1) \
        < 1e-5
    assert abs(yarn_mscale(40.0) - (0.1 * math.log(40.0) + 1)) < 1e-12
    assert yarn_mscale(1.0) == 1.0


def test_rope_at_without_yarn_is_the_table_it_was():
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import rope, rope_at

    x = jnp.asarray(np.random.RandomState(0).standard_normal(
        (2, 6, 3, 8)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
    assert np.array_equal(np.asarray(rope_at(x, pos, theta=1e4)),
                          np.asarray(rope(x, theta=1e4)))
    yarn = (4.0, 4.0, 1.0, 32.0)
    assert np.array_equal(np.asarray(rope_at(x, pos, theta=1e4, yarn=yarn)),
                          np.asarray(rope(x, theta=1e4, yarn=yarn)))
    assert not np.array_equal(np.asarray(rope_at(x, pos, theta=1e4,
                                                 yarn=yarn)),
                              np.asarray(rope_at(x, pos, theta=1e4)))


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """At a tiny size the routed parts of all the chips that share a
    layer, with the shared expert counted once, sum to what the uncut
    reference gives for the whole expert layer's FFN half."""
    import jax
    import jax.numpy as jnp

    from benchmarks.builders import dots_vlm as builder
    from benchmarks.references import dots_vlm as ref
    from mxnet_tpu.ops.contrib import moe_routed_experts

    whole = _config(n_routed_experts=16)            # holds all 16 outputs
    key = jax.random.key(3)
    lw = builder.make_layer(whole, True, key)
    c = dict(ref.constants(whole))
    a = jnp.asarray(np.random.RandomState(1).standard_normal(
        (12, whole["hidden_size"])).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.ffn(a, lw, c))
        h = ref._rms(a, lw["post_norm"], c["eps"])
        total = np.asarray(ref._swiglu(h, lw["shared_gate_up"],
                                       lw["shared_down"]))  # counted once
        m = lw["moe"]
        for chip in range(4):                       # 4 chips x 4 experts
            part, counts = moe_routed_experts(
                h, m["router"], m["router_bias"],
                m["gate_up"][4 * chip:4 * chip + 4],
                m["down"][4 * chip:4 * chip + 4], first_held=4 * chip,
                n_routed=16, top_k=c["top_k"], scale=c["moe_scale"],
                score="sigmoid", renormalize=True, n_group=c["n_group"],
                topk_group=c["topk_group"])
            total = total + np.asarray(part)
            # and the reference's own share is that chip's part
            share = dict(c, first_held=4 * chip)
            np.testing.assert_allclose(
                np.asarray(part), np.asarray(ref.routed(h, {
                    **m, "gate_up": m["gate_up"][4 * chip:4 * chip + 4],
                    "down": m["down"][4 * chip:4 * chip + 4]}, share)),
                atol=1e-5)
    np.testing.assert_allclose(total, want, atol=1e-5)


@pytest.mark.parametrize("lengths", [(700, 1024), (100, 300), (1, 513)])
def test_flash_forward_bounded_by_a_key_length(lengths):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import _sdpa_reference
    from mxnet_tpu.pallas_kernels.flash_attention import flash_attention

    b, h, l, d = 2, 2, 1024, 128
    q, k, v = (jax.random.normal(x, (b, h, l, d), jnp.float32)
               for x in jax.random.split(jax.random.key(0), 3))
    n = jnp.asarray(lengths, jnp.int32)
    out = flash_attention(q, k, v, interpret=True, kv_len=n)
    live = jnp.arange(l)[None, :] < n[:, None]
    ref = _sdpa_reference(q, k, v, live[:, None, None, :], d ** -0.5, False)
    for row, nb in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(out[row, :, :nb]),
                                   np.asarray(ref[row, :, :nb]), atol=2e-6)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, interpret=True, kv_len=n, causal=True)


def test_flash_forward_skips_query_blocks_past_the_length():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.pallas_kernels.flash_attention import flash_attention

    q, k, v = (jax.random.normal(x, (1, 1, 2048, 128), jnp.float32)
               for x in jax.random.split(jax.random.key(1), 3))
    out = flash_attention(q, k, v, interpret=True,
                          kv_len=jnp.asarray([900], jnp.int32))
    assert float(jnp.abs(out[0, 0, 1024:]).max()) == 0.0
    assert float(jnp.abs(out[0, 0, :900]).min()) > 0.0


def test_sdp_attention_takes_the_bound_off_the_kernel_too():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import sdp_attention

    q, k, v = (jax.random.normal(x, (2, 2, 24, 8), jnp.float32)
               for x in jax.random.split(jax.random.key(2), 3))
    n = jnp.asarray([24, 10], jnp.int32)
    out = sdp_attention(None, q, k, v, None, n)
    cut = sdp_attention(None, q[1:, :, :10], k[1:, :, :10], v[1:, :, :10])
    np.testing.assert_allclose(np.asarray(out[1, :, :10]),
                               np.asarray(cut[0]), atol=1e-5)
    with pytest.raises(ValueError):
        sdp_attention(None, q, k, v, None, n, causal=True)
