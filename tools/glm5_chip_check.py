"""GLM-5 on the chip against its plain reference, at the benchmark's own
widths and under the cell's shapes (a 16k+ prompt prefilled in chunks of
2048 at the cell's page-table width), before any timing is believed.

    chiprun -- python3 tools/glm5_chip_check.py                # the chip
    JAX_PLATFORMS=cpu python3 tools/glm5_chip_check.py --config tiny_glm_dsa \
        --prompt 70 --new 6 --chunk 16 --tail 8 --page-size 8 --width 12

Four comparisons, on LOGITS, sets or activations, never on tokens. The
reference makes ONE float32 pass over the finished sequence, a layer at a
time; 2 to 4 are made per layer on the REFERENCE's own layer input (cast
to the served dtype), so differences do not pile up across layers.

1. **End to end**: the prompt prefilled through the decode engine in
   chunks (each attending to the chunks before it through the cache) and
   ``--new`` tokens decoded through the cache; every step's logits
   against the reference's. Limit ``E2E_TOL`` of the largest reference
   logit (the harness's own).
2. **The selected sets**: per layer, the program's index scores and exact
   top-k over the cache against the reference's ``S_t``, over the queries
   past ``index_topk``: the share of the reference's picks the program
   also picked, mean and worst query. bf16 scores swap picks near the
   2048th, so it is not 1; limit ``SET_MEAN`` on the mean, set between
   the sound program's reading and that of the SAME program with the
   indexer's ``W_Iq`` rolled by one head (each head weight then meets
   its neighbour's query), which has to FAIL it.
3. **The sparse attention alone**: ``mla_sparse_attend`` over the cache
   given the REFERENCE's own selection, against the reference's attention
   output (before the output projection), as a share of its largest
   value; limit ``ATT_TOL``. The same op told to read DENSELY (every
   causal token selected) has to FAIL it: with seeded weights attention
   over 2048 picks is nearly uniform, so the logits of comparison 1 and
   the harness's ``correct`` cannot see which tokens were read; this can.
4. **The held experts alone**: ``moe_routed_experts`` (sigmoid scores,
   renormalised weights; on the TPU the megablox kernel) against the
   reference's routed sum on the SAME input, on the tokens with a held
   pick whose 8th and 9th ``s + b`` lie further apart than ``PICK_EPS``,
   as a share of the largest value of the reference's routed part. Limit
   ``HELD_TOL``; the same op with the held experts' weights rolled by one
   expert has to FAIL it.

Exit code 0 only if 1 to 4 pass and the three wrong programs fail. Also
prints what a prefill chunk at each offset and a one-stream decode step
took (host clock around a blocking call), the Pallas kernels the op
routing took, and the device's peak memory.
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
SET_MEAN = 0.9            # mean share of the reference's picks also picked
ATT_TOL = 2.0 ** -6       # of max |reference attention output|
HELD_TOL = 2.0 ** -4      # of max |reference routed part|
PICK_EPS = 1e-4           # on s + b, between the 8th and the 9th


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="glm5_ep16")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--prompt", type=int, default=16640)
    ap.add_argument("--new", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--tail", type=int, default=256,
                    help="the length bucket of a prompt's tail")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--width", type=int, default=2208,
                    help="page-table width (the cell's: 35328 tokens)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=NUMBER",
                    help="override a number of the config file (a study "
                         "of the seeded scales; the cell reads the file)")
    ap.add_argument("--e2e-only", action="store_true",
                    help="comparison 1 alone, with every position's reading")
    args = ap.parse_args()

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    for item in getattr(args, "set"):
        key, value = item.split("=")
        config[key] = float(value)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.nlp import glm_moe_dsa as model
    from mxnet_tpu.ops.attention import dsa_select, mla_sparse_attend
    from mxnet_tpu.ops.contrib import moe_routed_experts
    from mxnet_tpu.serving.kvcache import PagePool

    from benchmarks.builders import glm_moe_dsa as builder
    from benchmarks.references import glm_moe_dsa as reference

    t0 = time.perf_counter()

    def log(msg):
        print(f"[check +{time.perf_counter() - t0:6.1f}s] {msg}", flush=True)

    log(f"devices {jax.devices()}")
    telemetry.enable()      # which Pallas kernels the op routing took
    on_cpu = jax.devices()[0].platform == "cpu"
    net, ctx = builder.build_net(config, args.seed,
                                 ctx=mx.cpu() if on_cpu else None)
    weights = builder.export_weights({"net": net})
    log("weights made")
    ps, width, chunk = args.page_size, args.width, args.chunk
    p, n_new = args.prompt, args.new
    pool = PagePool(width + 1, ps)              # one stream
    engine = net.decode_engine(pool)
    cfg = engine.cfg
    top_k = cfg["index_topk"]
    rs = np.random.RandomState(args.seed % (2 ** 31))
    prompt = rs.randint(1, config["vocab_size"], (p,)).astype(np.int32)
    owner = object()
    pages = pool.alloc(owner, p + n_new)
    table = np.zeros((1, width), np.int32)
    table[0, :len(pages)] = pages

    def chunks():
        """(offset, real tokens, length bucket) of the prompt's chunks."""
        for off in range(0, p, chunk):
            n = min(chunk, p - off)
            yield off, n, chunk if n > args.tail else args.tail

    # -- the program: prefill in chunks, then decode ------------------------
    times = []
    for off, n, bucket in chunks():
        part = np.zeros((1, bucket), np.int32)
        part[0, :n] = prompt[off:off + n]
        t = time.perf_counter()
        nxt = engine.prefill(part, np.array([off + n], np.int32), table,
                             np.array([off], np.int32))
        times.append((off, bucket, time.perf_counter() - t))
    got = [engine.last_logits()[0]]
    seq = list(prompt)
    step_s = []
    for _ in range(n_new):
        seq.append(int(nxt[0]))
        t = time.perf_counter()
        nxt = engine.decode_step(nxt, np.array([len(seq)], np.int32), table)
        step_s.append(time.perf_counter() - t)
        got.append(engine.last_logits()[0])
    seq = np.asarray(seq, np.int32)
    got = np.asarray(got, np.float32)
    log("prefill chunks (offset, bucket, ms; the first of a bucket "
        "compiles): "
        + ", ".join(f"{o}:{b}:{s * 1e3:.0f}" for o, b, s in times))
    log("decode steps of one stream, ms: "
        + ", ".join(f"{s * 1e3:.1f}" for s in step_s))

    if args.e2e_only:
        ref = np.asarray(reference.logits_at(
            weights, config, seq, np.arange(p - 1, p - 1 + len(got))),
            np.float32)
        each = np.abs(got - ref).max(axis=1) / np.abs(ref).max()
        log("1. end to end, per position (the prefill's, then each decode "
            "step's), worst |logit - ref| of max |ref| "
            f"{np.abs(ref).max():.3f}: "
            + ", ".join(f"{e:.4f}" for e in each))
        print(json.dumps({"end_to_end": float(each.max()),
                          "per_position": [float(e) for e in each],
                          "set": getattr(args, "set"),
                          "ok": bool(each.max() <= E2E_TOL)}))
        return 0 if each.max() <= E2E_TOL else 1

    # -- the reference, a layer at a time, and comparisons 2 to 4 -----------
    consts = dict(reference.constants(config))
    run_layer, head = reference._jitted(reference.constants(config), True)
    dtype = jnp.dtype(config["dtype"])
    total = len(seq)
    dev = ctx.jax_device()

    @jax.jit
    def probe(x, lp, arena, iarena, positions, page_table, lengths, ref_sel):
        """One chunk through the program's own attention pieces: its
        selection, and its sparse attention given ANOTHER selection
        (the reference's) and given none (a dense causal read)."""
        q, arena, iarena, scores, valid, _ = model._index_and_cache(
            x, lp, arena, iarena, positions, page_table, lengths, cfg)
        kw = dict(nope_dim=cfg["nope"], v_dim=cfg["v_dim"],
                  scale=cfg["scale"], top_k=top_k)
        mine = dsa_select(scores, valid, top_k=top_k)
        n_ref = ref_sel.shape[-1]
        ref_sel = jnp.pad(ref_sel, ((0, 0), (0, 0),
                                    (0, valid.shape[-1] - n_ref)))
        given = mla_sparse_attend(q, arena, page_table, ref_sel & valid,
                                  lp["kvb"], lengths, **kw)
        dense = mla_sparse_attend(q, arena, page_table, valid, lp["kvb"],
                                  lengths, **kw)
        return arena, iarena, mine[..., :n_ref], given, dense

    moe_kw = dict(first_held=cfg["first_held"], n_routed=cfg["n_routed"],
                  top_k=cfg["top_k"], scale=cfg["moe_scale"],
                  score="sigmoid", renormalize=True)

    @jax.jit
    def sys_moe(h, m, gate_up, down):
        return moe_routed_experts(h, m["router"], m["router_bias"], gate_up,
                                  down, **moe_kw)[0].astype(jnp.float32)

    @jax.jit
    def ref_moe(a, lw):
        """The reference's routed part on the program's dtype of ITS FFN
        input, which tokens have a held pick, and the 8th-9th margin."""
        h = reference._rms(a, lw["post_norm"], consts["eps"]).astype(dtype)
        h32 = h.astype(jnp.float32)
        idx, _, biased = reference.router(h32, lw["moe"], consts)
        local = idx - consts["first_held"]
        held = jnp.any((local >= 0) & (local < lw["moe"]["gate_up"].shape[0]),
                       axis=-1)
        top = jax.lax.top_k(biased, consts["top_k"] + 1)[0]
        return h, reference.routed(h32, lw["moe"], consts), held, \
            top[:, -2] - top[:, -1]

    embed_w, layers, _, _ = engine._params
    readings = {"sets_mean": [], "sets_worst": [], "sets_rolled": [],
                "att": [], "att_dense": [], "held": [], "held_rolled": []}
    ok = {"sets": True, "att": True, "held": True}
    with jax.default_matmul_precision("highest"):
        x = reference._f32(weights["embed"][reference._padded(seq)])
    for li, (lw, lp) in enumerate(zip(weights["layers"], layers)):
        with jax.default_matmul_precision("highest"):
            y, parts = run_layer(x, lw)
        sel_ref = parts["selected"]
        att_ref = np.asarray(parts["att"][:p])
        att_max = float(np.abs(att_ref).max())
        rolled_lp = dict(lp, iq=jnp.roll(
            lp["iq"].reshape(cfg["index_heads"], cfg["index_dim"], -1), 1,
            axis=0).reshape(lp["iq"].shape))
        share = {"sound": [], "rolled": []}
        err_given = err_dense = 0.0
        with execution_platform(dev.platform):
            for name, layer_p in (("sound", lp), ("rolled", rolled_lp)):
                arena, iarena = engine.arenas[2 * li], engine.arenas[2 * li + 1]
                for off, n, bucket in chunks():
                    xs = jnp.zeros((1, bucket, x.shape[1]), dtype).at[
                        0, :n].set(x[off:off + n].astype(dtype))
                    pos = (off + np.arange(bucket, dtype=np.int32))[None]
                    ref_sel = jnp.zeros((1, bucket, total), bool).at[
                        0, :n].set(sel_ref[off:off + n, :total])
                    arena, iarena, mine, given, dense = probe(
                        xs, layer_p, arena, iarena, pos, table,
                        np.array([off + n], np.int32), ref_sel)
                    rows = np.arange(off, off + n) >= top_k   # past top-k
                    if rows.any():
                        both = np.asarray(jnp.sum(mine[0, :n] & ref_sel[0, :n],
                                                  axis=-1))[rows]
                        share[name].append(both / float(top_k))
                    if name == "sound":
                        want = att_ref[off:off + n]
                        err_given = max(err_given, float(np.abs(
                            np.asarray(given[0, :n], np.float32)
                            - want).max()) / att_max)
                        err_dense = max(err_dense, float(np.abs(
                            np.asarray(dense[0, :n], np.float32)
                            - want).max()) / att_max)
                del arena, iarena
        sound = np.concatenate(share["sound"]) if share["sound"] \
            else np.ones(1)
        rolled = np.concatenate(share["rolled"]) if share["rolled"] \
            else np.zeros(1)
        ok_sets = sound.mean() >= SET_MEAN > rolled.mean()
        ok["sets"] &= bool(ok_sets)
        readings["sets_mean"].append(float(sound.mean()))
        readings["sets_worst"].append(float(sound.min()))
        readings["sets_rolled"].append(float(rolled.mean()))
        log(f"2. layer {li}: of the reference's {top_k} picks the program "
            f"also picked {sound.mean():.4f} (mean over {sound.size} queries "
            f"past top-k, worst query {sound.min():.4f}; limit on the mean "
            f"{SET_MEAN}); W_Iq rolled by one head: {rolled.mean():.4f} -> "
            f"{'pass, and the wrong one fails' if ok_sets else 'FAIL'}")
        ok_att = err_given <= ATT_TOL < err_dense
        ok["att"] &= ok_att
        readings["att"].append(err_given)
        readings["att_dense"].append(err_dense)
        log(f"3. layer {li}: sparse attention given the reference's "
            f"selection: worst |att - ref| = {err_given:.5f} of max |ref| "
            f"{att_max:.4f} (limit {ATT_TOL:.5f}); a dense read: "
            f"{err_dense:.5f} -> "
            f"{'pass, and the wrong one fails' if ok_att else 'FAIL'}")
        if "moe" in lw:
            m = lw["moe"]
            with jax.default_matmul_precision("highest"):
                h, routed_ref, held, margin = ref_moe(
                    parts["post_attention"][:total], lw)
            routed_ref = np.asarray(routed_ref)
            rows = np.asarray(held) & (np.asarray(margin) >= PICK_EPS)
            pad = -total % 128                  # the kernel's row tile
            h = jnp.pad(h, ((0, pad), (0, 0)))
            both = []
            with execution_platform(dev.platform):
                for shift in (0, 1):
                    held_w = [jnp.roll(m[k], 1, axis=0) if shift else m[k]
                              for k in ("gate_up", "down")]
                    out = np.asarray(sys_moe(h, m, *held_w))[:total]
                    both.append(float(np.abs(out - routed_ref)[rows].max()
                                      / np.abs(routed_ref).max()))
            ok_held = both[0] <= HELD_TOL < both[1]
            ok["held"] &= ok_held
            readings["held"].append(both[0])
            readings["held_rolled"].append(both[1])
            log(f"4. layer {li}: routed experts on {int(rows.sum())} of "
                f"{total} tokens with a held pick: worst |m - ref| = "
                f"{both[0]:.4f} of max |routed part| "
                f"{np.abs(routed_ref).max():.4f} (limit {HELD_TOL}); held "
                f"experts rolled by one: {both[1]:.4f} -> "
                f"{'pass, and the wrong one fails' if ok_held else 'FAIL'}")
        del parts, sel_ref
        x = y
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(head(x, weights["norm"], weights["lm_head"],
                              jnp.arange(p - 1, p - 1 + len(got))),
                         np.float32)
    each = np.abs(got - ref).max(axis=1) / np.abs(ref).max()
    e2e = float(each.max())
    ok_e2e = e2e <= E2E_TOL
    log("1. per position (the prefill's, then each decode step's): "
        + ", ".join(f"{e:.4f}" for e in each))
    log(f"1. end to end ({len(times)} chunks, {n_new} decode steps): worst "
        f"|logit - ref| = {e2e:.5f} of max |ref| {np.abs(ref).max():.3f} "
        f"(limit {E2E_TOL:.5f}) -> {'pass' if ok_e2e else 'FAIL'}")

    stats = jax.devices()[0].memory_stats() or {}
    log(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
        f"peak_bytes_reserved {stats.get('peak_bytes_reserved')}, "
        f"bytes_limit {stats.get('bytes_limit')}")
    from benchmarks.lib import harness

    routed = {labels["kernel"]: n for labels, n in
              harness.program_counters().get("mxnet_pallas_dispatch_total",
                                             ())}
    log(f"Pallas kernels routed (sites per traced program): {routed}")
    verdict = ok_e2e and all(ok.values())
    print(json.dumps(dict(
        readings, end_to_end=e2e, pallas_sites=routed,
        chunk_ms=[[o, b, round(s * 1e3, 1)] for o, b, s in times],
        decode_step_ms=[round(s * 1e3, 2) for s in step_s],
        ok=bool(verdict))))
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
