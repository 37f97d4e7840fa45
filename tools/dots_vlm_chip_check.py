"""dots.vlm1 on the chip against its plain reference, at the benchmark's
own widths and under the cell's shapes (two page images of different
grids, a prompt prefilled in chunks of 2048 at the cell's page-table
width), before any timing is believed.

    chiprun -- python3 tools/dots_vlm_chip_check.py                # the chip
    JAX_PLATFORMS=cpu python3 tools/dots_vlm_chip_check.py \
        --config tiny_dots_vlm --grids 4x6,6x2 --text 14 --before 5 --new 4 \
        --chunk 16 --page-size 8 --width 12 --buckets 128,256 --tokens 64

Comparisons on LOGITS or activations, never on tokens, each with the
planted faults that have to FAIL it:

1. **The tower alone**: the encode engine's rows of ``y`` for each image
   (padded to its bucket, bounded by its patch count) against the
   reference's, as a share of the largest reference value; limit
   ``TOWER_TOL``. Faults: the 2-D rotary's row and column swapped;
   attention let across the two images (both encoded as ONE sequence).
2. **End to end**: images + prompt prefilled through the decode engine in
   chunks (each handed its rows of ``y`` through the embeddings seam),
   ``--new`` tokens decoded through the cache; every step's logits
   against the reference's ONE forward. Limit ``E2E_TOL`` of the largest
   reference logit (the harness's own). Faults: the image's rows replaced
   by the placeholder id's embedding (ids alone); YaRN's blend dropped
   (plain frequencies, the softmax scale kept). And the nearest
   precision below: the reference itself with its residual stream rounded
   through an 8-bit float (e4m3) after every block has to FAIL the same
   limit.
3. **The held experts alone**: ``moe_routed_experts`` with the group
   limit (on the TPU the megablox kernel) against the reference's routed
   sum on the SAME input, on the tokens with a held pick, as a share of
   the largest value of the reference's routed part; limit ``HELD_TOL``.
   Fault: the group limit dropped (``n_group`` 1).

Exit code 0 only if the sound program passes 1 to 3 and every fault
fails. Also prints the rms of ``y`` beside the embedding's, what an
encode of each image and a prefill chunk took (host clock around a
blocking call), the Pallas kernels the op routing took and the device's
peak memory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TOWER_TOL = 2.0 ** -4     # of max |reference y|: 42 bf16 blocks
E2E_TOL = 2.0 ** -5       # of max |reference logit|; the harness's own
HELD_TOL = 2.0 ** -4      # of max |reference routed part|


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="dots_vlm1_ep16")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--grids", default="64x80,48x64")
    ap.add_argument("--text", type=int, default=120)
    ap.add_argument("--before", type=int, default=24)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--width", type=int, default=416,
                    help="page-table width (the cell's: 6656 tokens)")
    ap.add_argument("--buckets", default="2048,3072,4096,6144,8192,12288")
    ap.add_argument("--tokens", type=int, default=512,
                    help="tokens of the held-experts comparison")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=NUMBER")
    args = ap.parse_args()

    import numpy as np

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    for item in args.set:
        k, v = item.split("=")
        config[k] = float(v)
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from benchmarks.builders import dots_vlm as builder
    from benchmarks.lib import harness
    from benchmarks.references import dots_vlm as ref
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.gluon.model_zoo.vision import navit
    from mxnet_tpu.ops import attention as att_ops
    from mxnet_tpu.ops.contrib import moe_routed_experts

    on_cpu = jax.devices()[0].platform == "cpu"
    telemetry.enable()
    net, _ = builder.build_net(config, args.seed,
                               ctx=mx.cpu(0) if on_cpu else None)
    weights = builder.export_weights({"net": net})
    dtype = jnp.dtype(config["dtype"])
    buckets = tuple(int(b) for b in args.buckets.split(","))
    grids = [tuple(int(v) for v in g.split("x")) for g in args.grids.split(",")]
    rs = np.random.RandomState(args.seed % (2 ** 32))
    images = [(rs.standard_normal((r * c, 588)).astype(np.float32)
               .astype(dtype), (r, c)) for r, c in grids]
    rows_of = [r * c // 4 for r, c in grids]
    holder = config["image_token_id"]
    ids = rs.randint(1, holder, (args.text,)).astype(np.int32)
    prompt = np.concatenate(
        [ids[:args.before]] + [np.full((n,), holder, np.int32)
                               for n in rows_of] + [ids[args.before:]])
    p, new = prompt.size, args.new
    out = {"prompt": int(p), "image_rows": rows_of}

    def engine_of(tag=None):
        pool = serving.PagePool(args.width + 1, args.page_size)
        eng = net.decode_engine(pool)
        eng.vision.configure(buckets, sum(rows_of) + buckets[-1] // 4)
        if tag:                         # its programs are compiled anew
            eng._ident = eng._ident + (tag,)
        return eng

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.abs(a - b).max() / np.abs(b).max())

    # -- 1. the tower alone ---------------------------------------------------
    engine = engine_of()
    buf, row0, enc_ms = engine.vision.new_buffer(), 0, []
    for patches, grid in images:
        for _ in range(2):              # the second call is compiled
            t0 = time.perf_counter()
            buf, bucket = engine.vision.encode(patches, grid, buf, row0)
            ms = (time.perf_counter() - t0) * 1e3
        enc_ms.append({"patches": int(patches.shape[0]), "bucket": bucket,
                       "ms": round(ms, 2)})
        row0 += grid[0] * grid[1] // 4
    y_ref = [np.asarray(ref.vision_encode(weights["vision"], config, pa, g))
             for pa, g in images]
    y_prog = np.asarray(buf[0, :row0], np.float32)
    tower = [rel(y_prog[s:s + n], yr) for s, n, yr in
             zip(np.cumsum([0] + rows_of), rows_of, y_ref)]
    out["encode"] = enc_ms
    out["y_rms"] = float(np.sqrt(np.mean(np.concatenate(y_ref) ** 2)))
    out["embed_rms"] = float(np.sqrt(np.mean(np.asarray(
        weights["embed"][:512], np.float32) ** 2)))
    out["tower_rel_err"] = tower

    w = engine.vision._params

    def encode_pure(patches, pos, n_live, bucket):
        pad = np.zeros((bucket, 588), patches.dtype)
        pad[:patches.shape[0]] = patches
        ppos = np.zeros((bucket, 2), np.int32)
        ppos[:pos.shape[0]] = pos
        with execution_platform(jax.devices()[0].platform):
            return np.asarray(jax.jit(
                lambda w_, a, b, c: navit.navit_encode(
                    w_, a, b, c, cfg=engine.vision.cfg))(
                        w, jnp.asarray(pad), jnp.asarray(ppos),
                        jnp.int32(n_live)), np.float32)

    pa, (r, c) = images[0]
    swapped = encode_pure(pa, navit.patch_positions(r, c)[:, ::-1], r * c,
                          engine.vision.bucket_of(r * c))
    out["fault_rotary_swapped"] = rel(swapped[:rows_of[0]], y_ref[0])
    both = np.concatenate([im[0] for im in images])
    pos_both = np.concatenate([navit.patch_positions(*g) for _, g in images])
    n_both = both.shape[0]
    across = encode_pure(both, pos_both, n_both,
                         engine.vision.bucket_of(n_both))
    out["fault_attention_across_images"] = rel(across[:rows_of[0]], y_ref[0])

    # -- 2. end to end --------------------------------------------------------
    seq_rows = np.arange(p - 1, p - 1 + new)
    at = np.flatnonzero(prompt == holder)

    def serve(eng, embeds):
        pages = eng.pool.alloc("s", p + new)
        table = np.zeros((1, args.width), np.int32)
        table[0, :len(pages)] = pages
        logits, chunk_ms = [], []
        step = args.chunk
        for off in range(0, p, step):
            n = min(step, p - off)
            bucket = step if n == step else max(
                args.page_size, 1 << int(np.ceil(np.log2(n))))
            part = np.zeros((1, bucket), np.int32)
            part[0, :n] = prompt[off:off + n]
            seam = {}
            if embeds is not None:
                rows = np.full((1, bucket), -1, np.int32)
                first = np.searchsorted(at, off)
                here = at[first:np.searchsorted(at, off + n)]
                rows[0, here - off] = first + np.arange(here.size)
                seam = {"embeds": embeds, "embed_rows": rows}
            for _ in range(2 if off == 0 else 1):
                t0 = time.perf_counter()
                tok = eng.prefill(part, np.array([off + n], np.int32), table,
                                  np.array([off], np.int32) if off else None,
                                  **seam)
                chunk_ms.append(round((time.perf_counter() - t0) * 1e3, 2))
        logits.append(eng.last_logits()[0])
        toks = [int(tok[0])]
        for i in range(1, new):
            tok = eng.decode_step(np.array([toks[-1]], np.int32),
                                  np.array([p + i], np.int32), table)
            logits.append(eng.last_logits()[0])
            toks.append(int(tok[0]))
        eng.pool.free("s")
        return np.stack(logits), toks, chunk_ms

    logits, toks, chunk_ms = serve(engine, buf)
    seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
    want = np.asarray(ref.logits_at(weights, config, seq, seq_rows, images))
    out["chunk_ms"] = chunk_ms
    out["e2e_rel_err"] = rel(logits, want)
    tol = np.abs(want).max(axis=1) * E2E_TOL
    out["harness_gap_in_tolerances"] = float(
        ((want.max(axis=1) - want[np.arange(new), toks]) / tol).max())

    def teacher_forced(eng, embeds):
        # the same ids through another program: logits only compare on
        # the reference's own sequence
        return serve(eng, embeds)[0][:1]

    out["fault_ids_alone"] = rel(teacher_forced(engine, None), want[:1])
    plain = att_ops.yarn_inv_freq
    att_ops.yarn_inv_freq = lambda d, theta, *a: 1.0 / (
        theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    try:
        out["fault_yarn_blend_dropped"] = rel(
            teacher_forced(engine_of("yarn_off"), buf), want[:1])
    finally:
        att_ops.yarn_inv_freq = plain
    low = np.asarray(ref.logits_at(weights, config, seq, seq_rows, images,
                                   stream=(4, 3)))       # e4m3
    out["eight_bit_rel_err"] = rel(low, want)
    low_tok = low.argmax(axis=1)
    out["eight_bit_harness_gap"] = float(
        ((want.max(axis=1) - want[np.arange(new), low_tok]) / tol).max())

    # -- 3. the held experts alone --------------------------------------------
    lw = next(lw for lw in weights["layers"] if "moe" in lw)
    m = lw["moe"]
    c = dict(ref.constants(config))
    h = jnp.asarray(rs.standard_normal(
        (args.tokens, config["hidden_size"])).astype(np.float32)).astype(dtype)
    with jax.default_matmul_precision("highest"):
        h32 = h.astype(jnp.float32)
        want_r = np.asarray(ref.routed(h32, m, c))
        idx, _ = ref.router(h32, m, c)
    held_tok = np.asarray(((idx >= c["first_held"]) & (
        idx < c["first_held"] + m["gate_up"].shape[0])).any(axis=1))

    def held(n_group, topk_group):
        with execution_platform(jax.devices()[0].platform):
            got, counts = jax.jit(lambda x, mm: moe_routed_experts(
                x, mm["router"], mm["router_bias"], mm["gate_up"],
                mm["down"], first_held=c["first_held"],
                n_routed=config["router_outputs"], top_k=c["top_k"],
                scale=c["moe_scale"], score="sigmoid", renormalize=True,
                n_group=n_group, topk_group=topk_group))(h, m)
        got = np.asarray(got, np.float32)
        return float(np.abs(got - want_r)[held_tok].max()
                     / np.abs(want_r).max()), [int(v) for v in counts]

    out["held_tokens"] = int(held_tok.sum())
    out["held_rel_err"], out["held_counts"] = held(c["n_group"],
                                                   c["topk_group"])
    out["fault_group_limit_dropped"], _ = held(1, 1)

    sound = (max(tower) <= TOWER_TOL and out["e2e_rel_err"] <= E2E_TOL
             and out["harness_gap_in_tolerances"] <= 1.0
             and out["held_rel_err"] <= HELD_TOL)
    faults = (out["fault_rotary_swapped"] > TOWER_TOL
              and out["fault_attention_across_images"] > TOWER_TOL
              and out["fault_ids_alone"] > E2E_TOL
              and out["fault_yarn_blend_dropped"] > E2E_TOL
              and out["eight_bit_rel_err"] > E2E_TOL
              and out["fault_group_limit_dropped"] > HELD_TOL)
    out["limits"] = {"TOWER_TOL": TOWER_TOL, "E2E_TOL": E2E_TOL,
                     "HELD_TOL": HELD_TOL}
    out["pallas"] = {str(r[0].get("kernel")): r[1]
                     for r in harness.program_counters().get(
                         "mxnet_pallas_dispatch_total", ())}
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    out["sound_passes"], out["faults_fail"] = bool(sound), bool(faults)
    print(json.dumps(out, indent=1))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "dots_vlm_chip_check.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0 if sound and faults else 1


if __name__ == "__main__":
    sys.exit(main())
