"""Ling-3.0-flash's language model on the chip at the benchmark's widths
against its float32 reference (``benchmarks/references/ling_linear.py``):
numbers beside their limits, and planted faults that have to be refused.

1. ``kernel``: the in-place delta-rule state update
   (``pallas_kernels/kda_state_update.py``) against
   ``ops/linear_attention.py::kda_step`` over the gathered rows, on a
   round's shapes: 256 rows in scattered slots of a 257-slot array,
   padding rows on slot 0, a row that starts a stream; and its GB/s.
2. ``mixer``: ONE KDA layer's mixer through the engine's own function
   (``_kda_mix``: a prompt in chunks of 2,048 through the chunk form, the
   state and the convolution tail carried in the slot, then decode steps
   through the kernel) against the reference's token-by-token recurrence
   on the same layer input, as the largest difference over the
   reference output's largest value; the slot's state after ONE chunk
   against three; and the per-token decay the seeded weights give.
3. ``experts``: the routed expert layer alone
   (``ops/contrib.py::moe_routed_experts``, 64 held of 512, the pick
   inside 4 of 8 groups) against the reference's routed sum, and with
   the group limit dropped, which has to read worse by far.
4. ``controls``: what the benchmark's ``correct`` sees. The engine behind
   ``serving.Server`` answers two requests (one prompt of two chunks)
   and ``benchmarks/lib/serve_loop.py::check_outputs``, the comparison
   that decides ``correct`` in the cell, with its own limit, judges
   them: once as the program is (ok), once with each fault planted (NOT
   ok): ``no_decay`` (``alpha = 1``), ``no_delta``
   (``S' + beta k v^T``: the correction dropped), ``unsafe_gate`` (the
   unbounded gate the config turns off), ``chunk_from_zero`` (a chunk at
   an offset started from zeros, not from the slot),
   ``lower_precision`` (the nearest precision below the configuration's:
   an e4m3 residual stream and a bfloat16 state). READINGS, not controls
   (``correct`` cannot refuse them, the file's
   ``what_correct_cannot_see`` says what holds them instead):
   ``state_bf16`` (the state rounded to bfloat16 after every token,
   ``lax.reduce_precision``: 0.68 of the limit over 192 tokens where the
   sound program reads 0.05) and ``no_group_limit`` (0.15).

    chiprun -- python3 tools/ling_chip_check.py
    JAX_PLATFORMS=cpu python3 tools/ling_chip_check.py \\
        --config tiny_ling_linear --prompts 37,12 --new 6 --chunk 16 \\
        --page-size 8 --streams 4 --slots 7 --rows 48

Exit code 0 only if every comparison is inside its limit and every
control outside. Prints one JSON line.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the kernel against its oracle, float32 both: the largest difference
# over the largest value (the sums run in another order)
KERNEL_LIMIT = 1e-5
# a mixer's or the expert layer's output, largest difference over the
# reference's largest value: bfloat16 operands into every product
# (Falcon-H1's mixer alone read 0.004-0.02 under the same limit)
PART_LIMIT = 2.0 ** -4
CONTROLS = ("no_decay", "no_delta", "unsafe_gate", "chunk_from_zero",
            "lower_precision")
READINGS = ("state_bf16", "no_group_limit")


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", kind, name + ".json")) as f:
        return json.load(f)


def _rel(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


# -- 1. the kernel ------------------------------------------------------------------------------

def check_kernel(config, n_slots, n_rows, seed, on_cpu):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import linear_attention as la
    from mxnet_tpu.pallas_kernels.kda_state_update import (
        kda_state_update_kernel)

    h, d = config["num_attention_heads"], config["head_dim"]
    rs = np.random.RandomState(seed % (2 ** 31))
    f32 = jnp.float32
    states = jnp.asarray(rs.randn(n_slots, h, d, d), f32)
    live = n_rows - 3                       # three padding rows
    slots = np.zeros((n_rows,), np.int32)
    slots[:live] = rs.permutation(np.arange(1, n_slots))[:live]
    fresh = np.zeros((n_rows,), bool)
    fresh[1] = True
    q = la.l2_normalize(jnp.asarray(rs.randn(n_rows, h, d), f32)) * d ** -0.5
    k = la.l2_normalize(jnp.asarray(rs.randn(n_rows, h, d), f32))
    v = jnp.asarray(rs.randn(n_rows, h, d), f32)
    g = -5.0 * jax.nn.sigmoid(jnp.asarray(rs.randn(n_rows, h, d) * 3 - 3,
                                          f32))
    beta = jax.nn.sigmoid(jnp.asarray(rs.randn(n_rows, h), f32))
    alpha = jnp.where(fresh[:, None, None], 0.0, jnp.exp(g))
    want_o, want_s = la.kda_step(
        q, k, v, g, beta,
        jnp.where(fresh[:, None, None, None], 0.0, states[slots]))
    kernel = jax.jit(lambda s, *a: kda_state_update_kernel(
        s, *a, interpret=on_cpu), donate_argnums=() if on_cpu else (0,))
    untouched = np.setdiff1d(np.arange(1, n_slots), slots)
    before = np.asarray(states[untouched[:4]])
    got_o, got_s = kernel(states + 0.0, jnp.asarray(slots), q, k, v, alpha,
                          beta)
    out = {"output": _rel(got_o[:live], want_o[:live]),
           "state": _rel(got_s[slots[:live]], want_s[:live]),
           "untouched_slots_changed": float(np.abs(
               np.asarray(got_s[untouched[:4]]) - before).max()),
           "limit": KERNEL_LIMIT}
    if not on_cpu:
        s = got_s
        for _ in range(3):
            _, s = kernel(s, jnp.asarray(slots), q, k, v, alpha, beta)
        jax.block_until_ready(s)
        t = time.perf_counter()
        for _ in range(10):
            _, s = kernel(s, jnp.asarray(slots), q, k, v, alpha, beta)
        jax.block_until_ready(s)
        dt = (time.perf_counter() - t) / 10
        out["ms_per_call"] = dt * 1e3
        out["state_gb_s"] = 2 * n_rows * h * d * d * 4 / dt / 1e9
    out["ok"] = bool(out["output"] <= KERNEL_LIMIT
                     and out["state"] <= KERNEL_LIMIT
                     and out["untouched_slots_changed"] == 0.0)
    return out


# -- 2. one mixer through chunks, the slot and the kernel -----------------------------------------

def check_mixer(config, weights, net, seed, prompt_len, n_new, chunk,
                n_slots, li=1):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.references import ling_linear as reference
    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp import ling_linear as model

    cfg = dict(net._decode_cfg)
    h, d, k = cfg["num_heads"], cfg["head_dim"], cfg["conv_kernel"]
    u = config["hidden_size"]
    rs = np.random.RandomState(seed % (2 ** 31))
    total = prompt_len + n_new
    x = jnp.asarray(rs.randn(total, u), jnp.float32)
    lw = weights["layers"][li]
    want = np.asarray(reference.mixer_io(weights, config, x, li))
    platform = jax.devices()[0].platform
    mix = jax.jit(lambda *a: model._kda_mix(*a, cfg)[:3])
    slot = 2

    def through(chunks, slot_arrays, chunk=chunk):
        tails, states = slot_arrays
        outs, pos = [], 0
        for n in chunks:
            width = 1 if n == 1 else chunk
            xs = jnp.zeros((2, width, u), jnp.float32).at[0, :n].set(
                x[pos:pos + n])
            positions = np.stack([pos + np.arange(width),
                                  np.full((width,), -1)]).astype(np.int32)
            with execution_platform(platform):
                out, tails, states = mix(
                    xs, lw, tails, states, jnp.asarray(positions),
                    jnp.asarray([pos + n, 0], jnp.int32),
                    jnp.asarray([slot, 0], jnp.int32))
            outs.append(np.asarray(out[0, :n]))
            pos += n
        return np.concatenate(outs), tails, states

    def fresh_slots():
        # dirty slots: a stream starts from zeros whatever they hold
        return (jnp.ones((n_slots, k - 1, 3 * h * d), jnp.float32),
                jnp.ones((n_slots, h, d, d), jnp.float32))

    sizes = [chunk] * (prompt_len // chunk)
    if prompt_len % chunk:
        sizes.append(prompt_len % chunk)
    got, _, _ = through(sizes + [1] * n_new, fresh_slots())
    out = {"prefill": _rel(got[:prompt_len], want[:prompt_len]),
           "decode": _rel(got[prompt_len:], want[prompt_len:total]),
           "chunks": len(sizes), "limit": PART_LIMIT}
    if prompt_len <= 4 * chunk:
        # ONE chunk (a width of its own) against the chunks above
        _, _, states_one = through([prompt_len], fresh_slots(),
                                   chunk=-(-prompt_len // 8) * 8)
        _, _, states_chunks = through(sizes, fresh_slots())
        out["state_one_chunk_vs_many"] = _rel(states_chunks[slot],
                                              states_one[slot])
    # the decay the seeded weights give, a token a channel
    from mxnet_tpu.ops import linear_attention as la

    un = reference._rms(x[:prompt_len], lw["in_norm"],
                        float(config["rms_norm_eps"]))
    g, _ = la.kda_gates(
        un.astype(lw["f"].dtype) @ lw["f"].T, jnp.zeros((prompt_len, h)),
        lw["a_log"], lw["dt_b"], jnp.ones((prompt_len,), bool),
        lower_bound=cfg["kda_lower_bound"], safe=cfg["kda_safe_gate"])
    alpha = np.exp(np.asarray(g, np.float32)).reshape(-1)
    out["alpha_percentiles_1_10_50_90_99"] = [
        float(np.percentile(alpha, p)) for p in (1, 10, 50, 90, 99)]
    out["ok"] = bool(out["prefill"] <= PART_LIMIT
                     and out["decode"] <= PART_LIMIT
                     and out.get("state_one_chunk_vs_many", 0.0) <= 1e-3)
    return out


# -- 3. the expert layer alone ----------------------------------------------------------------------

def check_experts(config, weights, net, seed, n_rows, li=1):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.references import ling_linear as reference
    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.ops.contrib import moe_routed_experts

    cfg = net._decode_cfg
    m = weights["layers"][li]["moe"]
    rs = np.random.RandomState(seed % (2 ** 31))
    hs = jnp.asarray(rs.randn(n_rows, config["hidden_size"]), jnp.float32)
    hs = hs.astype(m["router"].dtype)
    want = np.asarray(reference.expert_layer_io(
        weights, config, hs.astype(jnp.float32), li))

    def run(n_group, topk_group):
        fn = jax.jit(lambda t: moe_routed_experts(
            t, m["router"], m["router_bias"], m["gate_up"], m["down"],
            first_held=cfg["first_held"], n_routed=cfg["n_routed"],
            top_k=cfg["top_k"], scale=cfg["moe_scale"], score="sigmoid",
            renormalize=True, n_group=n_group, topk_group=topk_group))
        with execution_platform(jax.devices()[0].platform):
            out, counts = fn(hs)
        return np.asarray(out, np.float32), [int(c) for c in counts]

    got, counts = run(cfg["n_group"], cfg["topk_group"])
    # rows none of whose picks is held read zero on both sides
    held = np.abs(want).max(axis=1) > 0
    out = {"rows_with_a_held_pick": int(held.sum()), "picks": counts,
           "sound": _rel(got[held], want[held]), "limit": PART_LIMIT}
    loose, _ = run(1, 1)
    out["no_group_limit"] = _rel(loose[held], want[held])
    out["ok"] = bool(out["sound"] <= PART_LIMIT
                     and out["no_group_limit"] > PART_LIMIT)
    return out


# -- 4. what `correct` sees ----------------------------------------------------------------------------

@contextlib.contextmanager
def planted(fault, net):
    """The model's serving functions with ``fault`` in them, for the
    programs traced inside (an engine built inside has program cache
    entries of its own: the fault's name is in its ``cfg``)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.nlp import ling_linear as model
    from mxnet_tpu.ops import linear_attention as la

    sound = (la.kda_chunk_scan, la.kda_slot_update, la.kda_gates,
             model._kda_layer, model._mla_layer)
    chunk_scan, _, gates, kda_layer, mla_layer = sound
    cfg = net._decode_cfg
    kept = {k: cfg[k] for k in ("kda_safe_gate", "n_group", "topk_group")}

    def step(delta, fmt):
        def one(q, k, v, g, beta, s):
            f32 = jnp.float32
            s = jnp.exp(g.astype(f32))[..., None] * s
            seen = jnp.sum(k[..., None] * s, axis=2) if delta else 0.0
            s = s + k[..., None] * (beta[..., None] * (v - seen))[
                :, :, None, :]
            if fmt is not None:
                s = jax.lax.reduce_precision(s, *fmt)
            return jnp.sum(q[..., None] * s, axis=2), s
        return one

    def by_steps(one):
        """Both forms of the recurrence through ``one``, token by
        token."""
        def scan(q, k, v, g, beta, state):
            def body(s, xs):
                o, s = one(*xs, s)
                return s, o
            xs = tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta))
            state, o = jax.lax.scan(body, state.astype(jnp.float32), xs)
            return jnp.swapaxes(o, 0, 1), state

        def slot_update(states, slots, fresh, q, k, v, g, beta):
            s = jnp.where(fresh[:, None, None, None], 0.0, states[slots])
            o, s = one(q, k, v, g, beta, s)
            return o, states.at[slots].set(s)
        return scan, slot_update

    def rounded(layer, fmt):
        def call(*args, **kw):
            x, *rest = layer(*args, **kw)
            return (jax.lax.reduce_precision(x, *fmt), *rest)
        return call

    if fault is not None:
        cfg["planted"] = fault
    if fault in ("state_bf16", "lower_precision"):
        la.kda_chunk_scan, la.kda_slot_update = by_steps(step(True, (8, 7)))
        if fault == "lower_precision":
            model._kda_layer = rounded(kda_layer, (4, 3))
            model._mla_layer = rounded(mla_layer, (4, 3))
    elif fault == "no_delta":
        la.kda_chunk_scan, la.kda_slot_update = by_steps(step(False, None))
    elif fault == "no_decay":
        def no_decay(*args, **kw):
            g, beta = gates(*args, **kw)
            # times zero, not zeros: a constant decay would have the
            # compiler fold the chunk form's exponentials, for minutes
            return g * 0.0, beta
        la.kda_gates = no_decay
    elif fault == "unsafe_gate":
        cfg["kda_safe_gate"] = False
    elif fault == "chunk_from_zero":
        la.kda_chunk_scan = lambda q, k, v, g, beta, state: chunk_scan(
            q, k, v, g, beta, jnp.zeros_like(state))
    elif fault == "no_group_limit":
        cfg["n_group"] = cfg["topk_group"] = 1
    try:
        yield
    finally:
        (la.kda_chunk_scan, la.kda_slot_update, la.kda_gates,
         model._kda_layer, model._mla_layer) = sound
        cfg.pop("planted", None)
        cfg.update(kept)


def judge(config, weights, net, ctx, seed, prompt_lens, n_new, chunk,
          page_size, faults, log):
    """``check_outputs`` on the answers of the engine behind a server of
    ONE prefill signature (two rows x ``chunk``) and one decode bucket of
    two, once per entry of ``faults`` (None: the program as it is)."""
    import numpy as np

    from benchmarks.builders import ling_linear as builder
    from benchmarks.lib import arrivals, serve_loop
    from benchmarks.references import ling_linear as reference

    n = len(prompt_lens)
    longest = max(prompt_lens) + n_new
    traffic = {"server": {
        "batch_buckets": [n], "len_buckets": [chunk],
        "page_size": page_size,
        "decode_pages": n * -(-longest // page_size) + 1,
        "max_generate_tokens": longest, "max_prefill_tokens": None,
        "defrag_threshold": None}}
    rs = np.random.RandomState(seed % (2 ** 31))
    prompts = [rs.randint(1, config["vocab_size"], (k,)).astype(np.int32)
               for k in prompt_lens]
    run = types.SimpleNamespace(seed=seed, config=config,
                                reference=reference)
    got = {}
    for fault in faults:
        with planted(fault, net):
            srv = builder.start_server(net, ctx, traffic)
            gen = serve_loop.Generator(run, srv, traced=False)
            for i, prompt in enumerate(prompts):
                gen.send(serve_loop.Rec(arrivals.Request(
                    i, 0.0, prompt, n_new, i)), time.perf_counter())
            gen.drain(serve_loop.DRAIN_TIMEOUT_S * 4)
            srv.stop(timeout=60.0)
            gen.srv = None
            del srv
            gc.collect()
        check = serve_loop.check_outputs(run, weights, gen.records, n)
        errors = [repr(r.error) for r in gen.records if r.error]
        if errors:
            check = dict(check, ok=False, errors=errors)
        check["distinct_tokens"] = [
            int(np.unique(r.handle.result(timeout=1.0)).size)
            for r in gen.records if r.error is None]
        log(f"{fault or 'sound'}: {check}")
        got[fault or "sound"] = check
    return got


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ling3_flash_ep8_l7")
    ap.add_argument("--seed", type=int, default=2147483723)
    ap.add_argument("--prompts", default="2300,700",
                    help="the judged requests' prompt lengths; the first "
                    "is also the mixer comparison's")
    ap.add_argument("--new", type=int, default=192)
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--streams", type=int, default=256,
                    help="rows of the kernel comparison")
    ap.add_argument("--slots", type=int, default=257)
    ap.add_argument("--rows", type=int, default=2048,
                    help="tokens of the expert comparison")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of kernel, mixer, experts, "
                    "sound and the faults")
    args = ap.parse_args()
    t0 = time.perf_counter()

    def log(msg):
        print(f"[ling +{time.perf_counter() - t0:6.1f}s] {msg}", flush=True)

    only = set(filter(None, args.only.split(",")))

    def wanted(name):
        return not only or name in only

    config = _load("configs", args.config)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["MXNET_XLA_CACHE_DIR"] = os.path.join(
            ROOT, ".cache", "mxnet_tpu_xla")
    os.environ.setdefault("MXNET_XLA_CACHE_MIN_COMPILE_S", "0")
    import jax

    import mxnet_tpu as mx
    from benchmarks.builders import ling_linear as builder

    on_cpu = jax.devices()[0].platform == "cpu"
    out, ok = {}, True
    if wanted("kernel"):
        out["kernel"] = check_kernel(config, args.slots, args.streams,
                                     args.seed, on_cpu)
        log(f"kernel: {out['kernel']}")
        ok &= out["kernel"]["ok"]
    net, ctx = builder.build_net(config, args.seed,
                                 ctx=mx.cpu() if on_cpu else None)
    weights = builder.export_weights({"net": net})
    log("weights made")
    lens = [int(n) for n in args.prompts.split(",")]
    if wanted("mixer"):
        out["mixer"] = check_mixer(config, weights, net, args.seed, lens[0],
                                   min(args.new, 16), args.chunk,
                                   min(args.slots, 5))
        log(f"mixer: {out['mixer']}")
        ok &= out["mixer"]["ok"]
    if wanted("experts"):
        out["experts"] = check_experts(config, weights, net, args.seed,
                                       args.rows)
        log(f"experts: {out['experts']}")
        ok &= out["experts"]["ok"]
    faults = [n for n in (None,) + CONTROLS + READINGS
              if wanted(n or "sound")]
    if faults:
        got = judge(config, weights, net, ctx, args.seed, lens, args.new,
                    args.chunk, args.page_size, faults, log)
        out["controls"] = got
        ok &= got.get("sound", {"ok": True})["ok"]
        ok &= not any(got[n]["ok"] for n in CONTROLS if n in got)
    print(json.dumps(dict(out, prompts=lens, new=args.new,
                          verdict=bool(ok))))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
