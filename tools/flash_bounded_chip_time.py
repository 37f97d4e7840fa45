"""The key-length-bounded flash forward ALONE on the chip, over the vision
tower's six patch-count buckets at its head shape (12 heads of 128,
bfloat16): ms a call and the share of the FLOP roofline over LIVE pairs
(QK^T + PV, ``4 x heads x head_dim x n^2``, over the chip's bf16 peak in
``benchmarks/lib/peaks.json``: the count of
``benchmarks/kernels/vit_flash_attention.py::flops``, which does not
depend on how the kernel is written), with the length at 0.82 of the
bucket (the cell's mean fill) and at the bucket.

    chiprun -- bash -c "python3 tools/flash_bounded_chip_time.py \
        --root _parent --out chiprun_out/flash_parent.json && \
        python3 tools/flash_bounded_chip_time.py \
        --against chiprun_out/flash_parent.json"

``--root`` imports ``mxnet_tpu`` from another checkout (``git archive
<commit> | tar -x -C _parent``), ``--against`` prints that run's numbers
beside this one's and the largest difference of the two outputs' live
rows (both runs make their inputs from the bucket's own key). A chip
belongs to one process at a time, hence two processes in one call.
Under ``JAX_PLATFORMS=cpu`` it runs the kernel in interpret mode at
``--buckets 256,384 --heads 2`` for its control flow; a time from there
is not a device number and is not printed as one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

FILLS = (0.82, 1.0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--buckets", default="2048,3072,4096,6144,8192,12288")
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", help="write this run's rows as JSON")
    ap.add_argument("--against", help="an earlier run's --out")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))

    import numpy as np

    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401  (x64 on, as every real trace has it)
    from benchmarks.lib import peaks
    from mxnet_tpu.pallas_kernels import flash_attention

    dev = jax.devices()[0]
    on_cpu = dev.platform == "cpu"
    # a device with no row in the table is an error, not a default
    peak = None if on_cpu else peaks.load(dev.device_kind)["bf16_flops"]
    h, d = args.heads, args.head_dim
    fn = jax.jit(lambda q, k, v, n: flash_attention(
        q, k, v, kv_len=n, interpret=on_cpu))
    rows = []
    for bucket in (int(b) for b in args.buckets.split(",")):
        q, k, v = (jax.random.normal(x, (1, h, bucket, d), jnp.bfloat16)
                   for x in jax.random.split(jax.random.key(bucket), 3))
        for fill in FILLS:
            live = int(bucket * fill)
            n = jnp.asarray([live], jnp.int32)
            out = fn(q, k, v, n).block_until_ready()
            for _ in range(2):
                fn(q, k, v, n).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = fn(q, k, v, n)
            out.block_until_ready()
            ms = (time.perf_counter() - t0) / args.iters * 1e3
            flop = 4.0 * h * d * live * live
            rows.append({
                "bucket": bucket, "live": live,
                "ms": None if on_cpu else ms,
                "roofline_pct": None if on_cpu
                else 100.0 * flop / peak / (ms * 1e-3),
                "out": np.asarray(out[0, :, :live:max(live // 64, 1)],
                                  np.float32).tolist()})
    result = {"device": f"{dev.platform}:{dev.device_kind}",
              "root": os.path.abspath(args.root), "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f)
    other = None
    if args.against:
        with open(args.against) as f:
            other = json.load(f)
        assert other["device"] == result["device"], (other["device"],
                                                     result["device"])
    print("device", result["device"], "| not a device time"
          if on_cpu else "")
    head = f"{'bucket':>6} {'live':>6} {'ms':>8} {'roof%':>6}"
    if other:
        head += f" | {'other ms':>8} {'roof%':>6} {'x':>5} {'max diff':>9}"
    print(head)

    def num(x, width, digits):
        return f"{'-':>{width}}" if x is None else f"{x:{width}.{digits}f}"

    for i, r in enumerate(rows):
        line = (f"{r['bucket']:6d} {r['live']:6d} {num(r['ms'], 8, 3)} "
                f"{num(r['roofline_pct'], 6, 1)}")
        if other:
            o = other["rows"][i]
            assert (o["bucket"], o["live"]) == (r["bucket"], r["live"])
            ratio = None if on_cpu or o["ms"] is None else r["ms"] / o["ms"]
            diff = float(np.abs(np.asarray(r["out"])
                                - np.asarray(o["out"])).max())
            line += (f" | {num(o['ms'], 8, 3)} "
                     f"{num(o['roofline_pct'], 6, 1)} {num(ratio, 5, 2)} "
                     f"{diff:9.5f}")
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
