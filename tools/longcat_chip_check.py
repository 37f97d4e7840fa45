"""LongCat-Flash on the chip against its plain reference, at the
benchmark's own widths, before any timing is believed.

    chiprun -- python3 tools/longcat_chip_check.py            # the chip
    JAX_PLATFORMS=cpu python3 tools/longcat_chip_check.py \
        --config tiny_longcat --prompt 20 --new 8 --pages 33  # control flow

Three comparisons, all on LOGITS or activations, never on tokens:

1. **End to end**: a seeded prompt prefilled through the decode engine
   and ``--new`` tokens decoded through the latent paged cache; every
   step's logits against ONE full float32 forward of the reference over
   the finished sequence. Limit ``E2E_TOL`` of the largest reference
   logit (bf16 weights and activations through 4 double layers against
   float32 ``highest``).
2. **Per double layer**, the reference fed the program's OWN layer input
   (so differences do not pile up across layers): the layer's output
   within ``LAYER_TOL`` of the largest reference activation on the
   tokens whose picks agree (measured 0.007-0.018 on the chip, PR 28:
   layer 0 is the noisiest because the seeded embedding is tiny and
   the first attention's bf16 error is not diluted by a residual), and
   the router's picks equal except where the reference's 12th and 13th
   ``p + b`` lie closer than ``PICK_EPS`` (3% of the 12th pick's
   ``p``: the program's ``h`` comes through bf16 activations, the
   reference's does not, so those two can swap).
3. **A deliberately wrong program** (the zero-compute experts left out:
   the engine told that all router outputs are routed experts) has to
   FAIL comparison 1, or the limits are too loose to see the mechanism.
4. **The held experts alone.** A held pick reaches about one token in
   four at a weight of ~0.025, so comparisons 1 to 3 (and the harness's
   greedy-token check) pass whether or not the grouped matmul of the
   held experts is right. So, per layer: the expert layer's output ``m``
   from the program's own op (``moe_routed_experts``, on the TPU the
   megablox kernel) against the reference's ``MoE`` on the SAME input
   (the program's ``h_0``), on the tokens with a held pick, as a share of
   the largest value of the reference's HELD part. Limit ``HELD_TOL``,
   between two readings on the chip (PR 28): the sound program
   0.0034-0.0048 over the four layers (``m`` leaves the op in bf16), the
   same op with the held experts' weights rolled by one expert (every
   pair multiplied by its neighbour's weights) 1.15-1.50, which has to
   FAIL it.

Exit code 0 only if 1, 2 and 4 pass and both wrong programs fail. Also prints the names a
profiler trace gives to operations under ``jax.named_scope`` (the
per-layer readers of the benchmark read them) and the Pallas kernels the
op routing took (``mla_paged_decode``: the decode steps of 1 and 3 ran
the paged latent kernel, 2 sites a program; none on the CPU).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

E2E_TOL = 2.0 ** -5       # of max |reference logit|; the harness's own
LAYER_TOL = 2.0 ** -5     # of max |reference activation| of the layer
PICK_EPS = 1e-4           # on p + b; p of the 12th pick is ~3.4e-3
HELD_TOL = 2.0 ** -4      # of max |reference held part|; see 4. above


def scope_probe(out_dir):
    """What a device trace calls operations under named scopes."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import trace_reduce, xplane_scopes

    def probe_fn(x):
        with jax.named_scope("outer.scope"):
            y = x @ x
            with jax.named_scope("inner"):
                y = jnp.tanh(y) @ x
        return y

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f = jax.jit(probe_fn)
    f(x).block_until_ready()
    d = os.path.join(out_dir, "scope_probe")
    jax.profiler.start_trace(d)
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(d)
    chips = xplane_scopes.read_xplane(path) if path else {}
    for c, chip in chips.items():
        print(f"[probe] chip {c}: modules",
              sorted({m.op_name for m in chip["modules"]}))
        print(f"[probe] chip {c}: op names",
              sorted({o.op_name for o in chip["ops"]}))
    if not chips:
        print("[probe] no device plane in the trace (not a TPU)")


def gap_study(args, config, log):
    """How near the harness's own ``correct`` runs to its limit, and what
    the router's seeded scale has to do with it: for each (router std x
    sqrt(hidden), selection-bias range) the worst gap, in the harness's
    tolerances (``serve_loop.LOGIT_TOL`` of the largest reference logit),
    between the reference's top logit and the reference logit of the
    token the program chose, over ``--streams`` streams of ``--new``
    generated tokens. A pick that swaps between the program (bf16
    activations) and the reference (float32) moves a token's expert
    output by ``6 p`` of one expert's part, whatever the precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.serving.kvcache import PagePool

    from benchmarks.builders import longcat_flash as builder
    from benchmarks.lib.serve_loop import LOGIT_TOL
    from benchmarks.references import longcat_flash as reference

    net, ctx = builder.build_net(config, args.seed)
    pool = PagePool(args.pages, 16)
    engine = net.decode_engine(pool)
    dev = ctx.jax_device()
    u, outs = config["hidden_size"], config["router_outputs"]
    b, p, n_new = args.streams, args.prompt, args.new
    bucket = args.len_bucket or 1 << (p - 1).bit_length()
    width = pool.pages_for(bucket + n_new)
    rs = np.random.RandomState(args.seed % (2 ** 31))
    prompts = rs.randint(1, config["vocab_size"], (b, p)).astype(np.int32)
    out = {}
    for std, bias in [tuple(map(float, v.split(":")))
                      for v in args.gap_study.split(",")]:
        keys = jax.random.split(jax.random.key(args.seed, impl="rbg"),
                                2 * len(net.blocks))
        for li, blk in enumerate(net.blocks):
            with jax.default_device(dev):
                w = (jax.random.normal(keys[2 * li], (outs, u), jnp.float32)
                     * (std / u ** 0.5)).astype(jnp.bfloat16)
                bb = jax.random.uniform(keys[2 * li + 1], (outs,),
                                        jnp.float32, -bias,
                                        bias).astype(jnp.bfloat16)
            blk.moe.router_weight.set_data(mx.nd.NDArray(data=w, ctx=ctx))
            blk.moe.router_bias.set_data(mx.nd.NDArray(data=bb, ctx=ctx))
        engine.refresh_params(net)
        weights = builder.export_weights({"net": net})
        owners = [object() for _ in range(b)]
        table = np.zeros((b, width), np.int32)
        for i, o in enumerate(owners):
            pages = pool.alloc(o, p + n_new)
            table[i, :len(pages)] = pages
        tokens = np.zeros((b, bucket), np.int32)
        tokens[:, :p] = prompts
        nxt = engine.prefill(tokens, np.full((b,), p, np.int32), table)
        seqs = [list(r) for r in prompts]
        for step in range(n_new):
            for i in range(b):
                seqs[i].append(int(nxt[i]))
            if step + 1 < n_new:
                nxt = engine.decode_step(
                    nxt, np.full((b,), p + step + 1, np.int32), table)
        for o in owners:
            pool.free(o)
        gaps = []
        for seq in seqs:
            seq = np.asarray(seq, np.int32)
            ref = np.asarray(reference.logits_at(
                weights, config, seq, np.arange(p - 1, p - 1 + n_new)))
            tol = np.abs(ref).max(axis=1) * LOGIT_TOL
            gaps.append((ref.max(axis=1) - ref[np.arange(n_new), seq[p:]])
                        / tol)
        gaps = np.concatenate(gaps)
        top = np.sort(gaps)[::-1][:6]
        out[f"{std}:{bias}"] = {
            "positions": int(gaps.size), "worst": float(top[0]),
            "over_1": int((gaps > 1).sum()), "over_half": int((gaps > .5).sum()),
            "top": [round(float(t), 3) for t in top]}
        log(f"gap study router std {std}/sqrt(hidden), bias +-{bias}: "
            f"{out[f'{std}:{bias}']}")
    print(json.dumps({"gap_study": out}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="longcat_flash_chat_ep32")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--prompt", type=int, default=200)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--pages", type=int, default=18433)
    ap.add_argument("--len-bucket", type=int, default=0)
    ap.add_argument("--gap-study", default="",
                    help="std:bias,std:bias,... see gap_study()")
    ap.add_argument("--streams", type=int, default=8)
    args = ap.parse_args()

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.serving.kvcache import PagePool

    from benchmarks.builders import longcat_flash as builder
    from benchmarks.references import longcat_flash as reference

    t0 = time.perf_counter()

    def log(msg):
        print(f"[check +{time.perf_counter() - t0:6.1f}s] {msg}", flush=True)

    log(f"devices {jax.devices()}")
    # which Pallas kernels the op routing took is visible nowhere else
    from mxnet_tpu import telemetry

    telemetry.enable()
    if args.gap_study:
        return gap_study(args, config, log)
    net, ctx = builder.build_net(config, args.seed)
    weights = builder.export_weights({"net": net})
    log("weights made")
    pool = PagePool(args.pages, 16)
    engine = net.decode_engine(pool)
    rs = np.random.RandomState(args.seed % (2 ** 31))
    vocab = config["vocab_size"]
    p, n_new = args.prompt, args.new
    bucket = args.len_bucket or 1 << (p - 1).bit_length()
    prompt = rs.randint(1, vocab, (p,)).astype(np.int32)
    width = pool.pages_for(bucket + n_new)

    def generate(eng):
        owner = object()
        pages = pool.alloc(owner, p + n_new)
        table = np.zeros((1, width), np.int32)
        table[0, :len(pages)] = pages
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :p] = prompt
        nxt = eng.prefill(tokens, np.array([p], np.int32), table)
        got = [eng.last_logits()[0]]
        seq = list(prompt)
        for _ in range(n_new):
            seq.append(int(nxt[0]))
            nxt = eng.decode_step(nxt, np.array([len(seq)], np.int32), table)
            got.append(eng.last_logits()[0])
        pool.free(owner)
        return np.asarray(seq, np.int32), np.asarray(got, np.float32)

    def against_reference(seq, got):
        ref = np.asarray(reference.logits_at(
            weights, config, seq, np.arange(p - 1, p - 1 + len(got))),
            np.float32)
        worst = float(np.abs(got - ref).max() / np.abs(ref).max())
        return worst, float(np.abs(ref).max())

    # -- 1. end to end ------------------------------------------------------
    seq, got = generate(engine)
    log(f"prefill ({bucket}) + {n_new} decode steps done")
    e2e, ref_max = against_reference(seq, got)
    engine.arenas = ()      # the wrong engine below brings its own
    ok_e2e = e2e <= E2E_TOL
    log(f"1. end to end: worst |logit - ref| = {e2e:.5f} of max |ref| "
        f"{ref_max:.3f} (limit {E2E_TOL:.5f}) -> "
        f"{'pass' if ok_e2e else 'FAIL'}")

    # -- 2. per double layer, on the program's own layer input --------------
    consts = dict(reference.constants(config))
    tokens_nd = mx.nd.array(seq[None, :], dtype="int32", ctx=ctx)
    x = net.embed(tokens_nd)
    ok_layers, swapped_total = True, 0

    @jax.jit
    def ref_layer(x_in, lw):
        """The layer's output, and the reference's router on ITS h_0."""
        p0 = lw["sub"][0]
        a = x_in + reference.mla(
            reference._rms(x_in, p0["in_norm"], consts["eps"]), p0, consts)
        h = reference._rms(a, p0["post_norm"], consts["eps"])
        idx, _, biased = reference.router(h, lw["moe"], consts)
        return reference.double_layer(x_in, lw, consts), idx, biased

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.ops.contrib import moe_routed_experts

    moe_kw = {k: engine.cfg[k] for k in ("first_held", "n_routed", "n_zero",
                                         "top_k")}
    n_held = engine.cfg["held"]

    @jax.jit
    def sys_moe(h, m, gate_up, down):
        return moe_routed_experts(h, m["router"], m["router_bias"], gate_up,
                                  down, scale=engine.cfg["moe_scale"],
                                  **moe_kw)[0].astype(jnp.float32)

    @jax.jit
    def ref_moe(h, m):
        """The reference's expert layer, its held part, and which tokens
        have a held pick."""
        idx, w, _ = reference.router(h, m, consts)
        zero_w = jnp.sum(jnp.where(idx >= consts["n_routed"], w, 0.0), -1)
        out = reference.moe(h, m, consts)
        local = idx - consts["first_held"]
        return (out, out - zero_w[:, None] * h,
                jnp.any((local >= 0) & (local < n_held), axis=-1), idx)

    ok_held, held_ok_err, held_wrong_err = True, [], []
    for li, blk in enumerate(net.blocks):
        lw = weights["layers"][li]
        with jax.default_matmul_precision("highest"):
            want, idx_ref, biased = ref_layer(
                x.data[0].astype(jnp.float32), lw)
        want = np.asarray(want)
        # the program's router on its own (bf16) h_0
        a_sys = x + blk.attns[0](blk.in_norms[0](x))
        h_sys = blk.post_norms[0](a_sys).data[0]
        logits = jnp.einsum("nu,eu->ne", h_sys, lw["moe"]["router"],
                            preferred_element_type=jnp.float32)
        _, idx_sys = jax.lax.top_k(
            jax.nn.softmax(logits, axis=-1)
            + lw["moe"]["router_bias"].astype(jnp.float32),
            consts["top_k"])
        idx_ref, idx_sys = np.asarray(idx_ref), np.asarray(idx_sys)
        top = np.sort(np.asarray(biased), axis=1)[:, ::-1]
        k = consts["top_k"]
        margin = top[:, k - 1] - top[:, k]
        swapped = [t for t in range(len(seq))
                   if set(idx_ref[t]) != set(idx_sys[t])]
        unexplained = [t for t in swapped if margin[t] >= PICK_EPS]
        swapped_total += len(swapped)
        # -- 4. the expert layer alone, right and with rolled experts
        m_w = lw["moe"]
        with jax.default_matmul_precision("highest"):
            m_ref, held_ref, has_held, idx_4 = (
                np.asarray(v) for v in ref_moe(h_sys.astype(jnp.float32),
                                               m_w))
        # same input on both sides: picks differ only at a float32 tie
        has_held = has_held & np.array(
            [set(a) == set(b) for a, b in zip(idx_4, idx_sys)])
        readings = []
        with execution_platform(ctx.jax_device().platform):
            for shift in (0, 1):
                held_w = [jnp.roll(m_w[k], 1, axis=0) if shift else m_w[k]
                          for k in ("gate_up", "down")]
                m_sys = np.asarray(sys_moe(h_sys, m_w, *held_w))
                del held_w
                readings.append(float(
                    np.abs(m_sys - m_ref)[has_held].max()
                    / np.abs(held_ref).max()))
        held_ok_err.append(readings[0])
        held_wrong_err.append(readings[1])
        ok_4 = readings[0] <= HELD_TOL < readings[1]
        ok_held &= ok_4
        log(f"4. layer {li}: expert layer on {int(has_held.sum())} of "
            f"{len(seq)} tokens with a held pick and equal picks: worst "
            f"|m - ref| = "
            f"{readings[0]:.4f} of max |held part| "
            f"{np.abs(held_ref).max():.4f} (limit {HELD_TOL}); held experts "
            f"rolled by one: {readings[1]:.4f} -> "
            f"{'pass, and the wrong one fails' if ok_4 else 'FAIL'}")
        x = blk(x)
        got_l = np.asarray(x.data[0].astype(jnp.float32))
        err = float(np.abs(got_l - want).max() / np.abs(want).max())
        # a token whose 12th pick swapped differs by one expert's part
        rows = np.ones(len(seq), bool)
        rows[swapped] = False
        err_same = float(np.abs(got_l - want)[rows].max()
                         / np.abs(want).max())
        ok = err_same <= LAYER_TOL and not unexplained
        ok_layers &= ok
        log(f"2. layer {li}: output worst {err:.5f} of max |ref| "
            f"(tokens with equal picks {err_same:.5f}, limit "
            f"{LAYER_TOL:.5f}); picks differ on {len(swapped)} of "
            f"{len(seq)} tokens, {len(unexplained)} with a 12th-13th "
            f"margin over {PICK_EPS} -> {'pass' if ok else 'FAIL'}")

    # -- 3. the wrong program has to fail -----------------------------------
    wrong = net.decode_engine(pool)
    wrong.cfg = dict(wrong.cfg, n_zero=0,
                     n_routed=wrong.cfg["n_routed"] + wrong.cfg["n_zero"])
    wrong._ident = ("longcat_flash_no_zero_experts",) + wrong._ident[1:]
    seq_w, got_w = generate(wrong)
    e2e_w, _ = against_reference(seq_w, got_w)
    caught = e2e_w > E2E_TOL
    log(f"3. zero-compute experts left out: worst {e2e_w:.5f} of max |ref| "
        f"(limit {E2E_TOL:.5f}) -> "
        f"{'fails, as it must' if caught else 'PASSES: limits too loose'}")

    scope_probe(os.path.join(ROOT, ".cache", "longcat_check"))
    stats = jax.devices()[0].memory_stats() or {}
    log(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
        f"bytes_limit {stats.get('bytes_limit')}")
    verdict = ok_e2e and ok_layers and caught and ok_held
    from benchmarks.lib import harness

    routed = {labels["kernel"]: n for labels, n in
              harness.program_counters().get("mxnet_pallas_dispatch_total",
                                             ())}
    log(f"Pallas kernels routed (sites per traced program): {routed}")
    print(json.dumps({"end_to_end": e2e, "end_to_end_wrong": e2e_w,
                      "pallas_sites": routed,
                      "swapped_picks": swapped_total,
                      "layers_ok": bool(ok_layers),
                      "held_experts": held_ok_err,
                      "held_experts_rolled": held_wrong_err,
                      "ok": bool(verdict)}))
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
