"""Falcon-H1 on the chip at the benchmark's widths against its float32
reference (``benchmarks/references/falcon_h1.py``), through the cell's own
pool sizes: what the harness's ``correct`` (greedy tokens within 2^-5 of
the largest logit) cannot see is compared here as numbers.

1. ``logits``: a prompt prefilled in CHUNKS at an offset (scan state and
   tail carried through the slot, keys and values written into every
   layer's pages) in a batch bucket with padding rows, then decode steps
   in the widest bucket with padding rows: the engine's logits against
   one full float32 forward, as the largest difference over the
   reference logits' spread (``LIMIT``), once sound and once each with a
   convolution tail not carried and with a bfloat16 residual stream,
   which have to read worse (the first by far).
2. ``ssd_mixer``: one layer's Mamba-2 mixer ALONE on the reference's own
   layer input, through the chunk form and the slots, against the
   reference's token-by-token recurrence; the same with the scan state
   kept in bfloat16 between dispatches (a READING: what ``correct``
   cannot see).
3. ``attention``: the same layer's attention alone, through the pages.
4. ``kernel``: the in-place Pallas state update against the XLA path on
   the same slot array (largest difference, untouched slots unchanged)
   and its time over the bytes of the states read once and written once.

    chiprun -- python3 tools/falcon_h1_chip_check.py
    JAX_PLATFORMS=cpu python3 tools/falcon_h1_chip_check.py \
        --config tiny_falcon_h1 --prompt 37 --new 4 --chunk 16 \
        --pages 33 --page-size 8 --slots 5 --width 8

Exit code 0 only if every comparison is inside its limit and every
control outside. Prints one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the largest logit difference over the reference logits' spread
# (standard deviation): bfloat16 operands through six layers read ~0.02
LIMIT = 0.06
# a single mixer's output: largest difference over its spread
MIXER_LIMIT = 0.05


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", kind, name + ".json")) as f:
        return json.load(f)


def run_logits(net, weights, config, reference, args, tokens, np):
    """Chunked prefill + decode through one engine; the worst relative
    logit difference and the worst greedy gap in the harness's
    tolerances."""
    from mxnet_tpu.serving.kvcache import PagePool

    pool = PagePool(args.pages, args.page_size, n_state_slots=args.slots)
    engine = net.decode_engine(pool)
    p, new, chunk = args.prompt, args.new, args.chunk
    cap = args.width
    owner = object()
    table = np.zeros((cap, pool.pages_for(p + new)), np.int32)
    table[1] = pool.alloc(owner, p + new)        # row 0 is a padding row
    slots = np.zeros((cap,), np.int32)
    slots[1] = pool.state_slots.alloc(owner)
    got = []
    for off in range(0, p, chunk):
        n = min(chunk, p - off)
        # prefill in a bucket of two rows: the padding row and the prompt
        part = np.zeros((2, chunk), np.int32)
        part[1, :n] = tokens[off:off + n]
        engine.prefill(part, np.asarray([0, off + n], np.int32), table[:2],
                       np.full((2,), off, np.int32) if off else None,
                       slots[:2], np.asarray([False, off + n == p]))
    got.append(engine.last_logits()[1])
    for t in range(new - 1):
        step = np.zeros((cap,), np.int32)
        step[1] = tokens[p + t]
        upto = np.zeros((cap,), np.int32)
        upto[1] = p + t + 1
        engine.decode_step(step, upto, table, slots)
        got.append(engine.last_logits()[1])
    got = np.asarray(got, np.float32)
    ref = np.asarray(reference.logits_at(
        weights, config, tokens[:p + new - 1],
        np.arange(p - 1, p + new - 1)), np.float32)
    rel = float(np.abs(got - ref).max() / ref.std())
    picks = got.argmax(axis=1)
    tol = np.abs(ref).max(axis=1) * 2.0 ** -5
    gap = float(((ref.max(axis=1) - ref[np.arange(len(picks)), picks])
                 / tol).max())
    del engine
    return {"rel": rel, "gap_in_tolerances": gap,
            "spread": float(ref.std())}


def run_mixers(net, weights, config, reference, args, tokens, np):
    """Layer 1's two mixers alone on the reference's own layer input."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp import falcon_h1 as model
    from mxnet_tpu.serving.kvcache import PagePool

    li = 1
    p = args.prompt
    io = reference.layer_io(weights, config, tokens[:p])[li]
    x = jnp.asarray(io["x"][:p])[None]
    pool = PagePool(args.pages, args.page_size, n_state_slots=args.slots)
    engine = net.decode_engine(pool)
    cfg, lp = engine.cfg, engine._params[1][li]
    f32 = jnp.float32
    positions = jnp.arange(p, dtype=jnp.int32)[None]
    lengths = jnp.asarray([p], jnp.int32)
    slots = jnp.asarray([1], jnp.int32)
    table = jnp.asarray([pool.alloc("m", p)], jnp.int32)
    out = {}

    def ssm(low):
        def fn(x, lp, tails, states):
            u = model._norm(x, lp["ln1"], cfg["eps"]) \
                * f32(cfg["ssm_in_multiplier"])
            half = p // 2          # two dispatches: the state is carried
            o1, tails, states = model._mixer(
                u[:, :half], lp, tails, states, positions[:, :half],
                jnp.asarray([half], jnp.int32), slots, cfg)
            if low:
                states = states.astype(jnp.bfloat16).astype(f32)
            o2, tails, states = model._mixer(
                u[:, half:], lp, tails, states, positions[:, half:],
                lengths, slots, cfg)
            return jnp.concatenate([o1, o2], axis=1)[0]
        with execution_platform(engine._device.platform):
            return jax.jit(fn)(x, lp, engine.slot_arrays["tails"][li],
                               engine.slot_arrays["states"][li])

    ref = np.asarray(reference.mixer_io(weights, config, io["x"], li,
                                        "ssm"))[:p]
    for name, low in (("ssd_mixer", False), ("ssd_mixer_bf16_state", True)):
        got = np.asarray(ssm(low), np.float32)
        out[name] = float(np.abs(got - ref).max() / ref.std())

    def attn(x, lp, k_arena, v_arena):
        u = model._norm(x, lp["ln1"], cfg["eps"]) \
            * f32(cfg["attention_in_multiplier"])
        return model._attention(u, lp, k_arena, v_arena, positions, table,
                                lengths, cfg)[0][0]

    with execution_platform(engine._device.platform):
        got = np.asarray(jax.jit(attn)(x, lp, engine.arenas[2 * li],
                                       engine.arenas[2 * li + 1]),
                         np.float32)
    ref = np.asarray(reference.mixer_io(weights, config, io["x"], li,
                                        "attention"))[:p]
    out["attention"] = float(np.abs(got - ref).max() / ref.std())
    return out


def run_kernel(config, args, np):
    """The slot update as the decode step routes it (on the chip: the
    Pallas kernel) against ``ssd_step`` over the gathered rows on one
    slot array, and its time."""
    import jax
    import jax.numpy as jnp

    from benchmarks.kernels import ssd_state_update as k
    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.ops.ssm import ssd_slot_update, ssd_step

    h, n, g = (config["mamba_n_heads"], config["mamba_d_state"],
               config["mamba_n_groups"])
    pdim = config["mamba_d_ssm"] // h
    s, b = args.slots, args.width
    platform = jax.devices()[0].platform
    rs = np.random.RandomState(3)
    f = lambda *sh: jnp.asarray(rs.randn(*sh), jnp.float32)  # noqa: E731
    slots = np.zeros((b,), np.int32)
    live = min(b, s - 1) - 1                 # one padding row at least
    slots[:live] = rs.permutation(np.arange(1, s))[:live]
    args_ = (jnp.asarray(slots), jnp.asarray(slots == -1), f(b, h, pdim),
             jnp.asarray(np.exp(rs.uniform(-7, -2.3, (b, h))), jnp.float32),
             -jnp.asarray(rs.uniform(1, 16, h), jnp.float32), f(b, g, n),
             f(b, g, n), jnp.ones((h,), jnp.float32))
    states = f(s, h, n, pdim)
    with execution_platform(platform):
        fn = jax.jit(lambda st, *a: ssd_slot_update(st, *a),
                     donate_argnums=(0,))
        _, new = fn(states + 0.0, *args_)
        jax.block_until_ready(new)
        t0 = time.perf_counter()
        for _ in range(20):
            _, new = fn(new, *args_)
        jax.block_until_ready(new)
        seconds = (time.perf_counter() - t0) / 20
        y, new = (np.asarray(v) for v in fn(states + 0.0, *args_))
    # no row is fresh: each starts from what its slot holds
    slot_ids, _, *step = args_
    y_ref, s_ref = (np.asarray(v) for v in jax.jit(ssd_step)(
        *step, states[slot_ids]))
    touched = np.unique(slots)
    idle = np.setdiff1d(np.arange(s), touched)
    rows = slots > 0
    shapes = k.shapes(config, {}, 1)
    nbytes = k.bytes_moved(shapes, b)
    return {"y_diff": float(np.abs(y[rows] - y_ref[rows]).max()),
            "state_diff": float(np.abs(
                new[slots[rows]] - s_ref[rows]).max()),
            "untouched_diff": float(np.abs(
                new[idle] - np.asarray(states)[idle]).max())
            if idle.size else 0.0,
            "call_ms": seconds * 1e3,
            "gb_per_s": nbytes / seconds / 1e9, "rows": int(b)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="falcon_h1_34b_l6")
    ap.add_argument("--seed", type=int, default=2147483711)
    ap.add_argument("--prompt", type=int, default=300)
    ap.add_argument("--new", type=int, default=24)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--pages", type=int, default=8193)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--slots", type=int, default=129)
    ap.add_argument("--width", type=int, default=128,
                    help="rows of the decode dispatches (one is live)")
    args = ap.parse_args()
    config = _load("configs", args.config)
    for key, value in config.get("env", {}).items():
        os.environ[key] = str(value)

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from benchmarks.builders import falcon_h1 as builder
    from benchmarks.references import falcon_h1 as reference
    from mxnet_tpu import telemetry

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import falcon_h1_correct_controls as controls

    t0 = time.perf_counter()

    def log(msg):
        print(f"[check +{time.perf_counter() - t0:6.1f}s] {msg}", flush=True)

    telemetry.enable()
    on_cpu = jax.devices()[0].platform == "cpu"
    net, _ = builder.build_net(config, args.seed,
                               ctx=mx.cpu() if on_cpu else None)
    weights = builder.export_weights({"net": net})
    log("weights made")
    rs = np.random.RandomState(args.seed % (2 ** 31))
    tokens = rs.randint(1, config["vocab_size"],
                        (args.prompt + args.new,)).astype(np.int32)
    out = {"device": str(jax.devices()[0].device_kind)}
    for fault in (None, "tail", "residual_bf16"):
        with controls.planted(fault, net):
            out["logits_" + (fault or "sound")] = run_logits(
                net, weights, config, reference, args, tokens, np)
        log(f"logits {fault or 'sound'}: {out['logits_' + (fault or 'sound')]}")
    out.update(run_mixers(net, weights, config, reference, args, tokens, np))
    log(f"mixers: { {k: out[k] for k in ('ssd_mixer', 'ssd_mixer_bf16_state', 'attention')} }")
    out["kernel"] = run_kernel(config, args, np)
    log(f"kernel: {out['kernel']}")
    out["pallas_dispatch"] = {
        s["labels"]["kernel"]: s["value"] for s in telemetry.snapshot()[
            "metrics"].get("mxnet_pallas_dispatch_total",
                           {"samples": []})["samples"]}
    sound = out["logits_sound"]
    ok = (sound["rel"] < LIMIT and sound["gap_in_tolerances"] <= 1.0
          and out["logits_tail"]["rel"] > LIMIT
          and out["logits_residual_bf16"]["rel"] > sound["rel"]
          and out["ssd_mixer"] < MIXER_LIMIT
          and out["attention"] < MIXER_LIMIT
          and out["kernel"]["y_diff"] < 1e-3
          and out["kernel"]["state_diff"] < 1e-4
          and out["kernel"]["untouched_diff"] == 0.0)
    print(json.dumps(dict(out, limit=LIMIT, mixer_limit=MIXER_LIMIT,
                          ok=bool(ok))))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
