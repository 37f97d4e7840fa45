"""A/B harness for ResNet conv-backward formulations on TPU.

Round-3 trace: conv-bwd (dW/dX) runs at ~38% of roofline inside XLA —
57.7 ms of the 106.8 ms batch-256 step (PERF_HISTORY.md "ResNet-50: NHWC").
This tool times, per distinct ResNet-50 conv shape, three dW recipes:

  vjp      XLA's own backward (jax.vjp of conv_general_dilated) — baseline
  patches  dW as an explicit im2col matmul: extract input patches
           (lax.conv_general_dilated_patches), one big MXU dot_general
           contracting over (batch x out-positions)
  both     patches-dW + vjp-dX together (what a custom_vjp would run)

Measurement: each candidate runs CHAINED inside lax.scan (the carry feeds
iteration i+1 from i's output) so asynchronous dispatch cannot hide or
overlap iterations. Report = ms/iter from one end-to-end timed executable.

Usage:  python tools/convbwd_bench.py [--iters 100] [--batch 256]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

# (name, H, W, Cin, Cout, kh, kw, stride) — ResNet-50 distinct conv shapes
# (NHWC, batch from --batch). Counts in ResNet-50: each shape's multiplicity
# matters for projecting step-time savings; listed as `mult`.
SHAPES = [
    ("stem7x7s2", 224, 224, 3, 64, 7, 7, 2, 1),
    ("s1_1x1a", 56, 56, 64, 64, 1, 1, 1, 3),
    ("s1_3x3", 56, 56, 64, 64, 3, 3, 1, 3),
    ("s1_1x1b", 56, 56, 64, 256, 1, 1, 1, 3),
    ("s1_proj", 56, 56, 256, 64, 1, 1, 1, 2),
    ("s2_down3x3", 56, 56, 128, 128, 3, 3, 2, 1),
    ("s2_3x3", 28, 28, 128, 128, 3, 3, 1, 3),
    ("s2_1x1b", 28, 28, 128, 512, 1, 1, 1, 4),
    ("s2_proj", 28, 28, 512, 128, 1, 1, 1, 3),
    ("s3_down3x3", 28, 28, 256, 256, 3, 3, 2, 1),
    ("s3_3x3", 14, 14, 256, 256, 3, 3, 1, 5),
    ("s3_1x1b", 14, 14, 256, 1024, 1, 1, 1, 6),
    ("s3_proj", 14, 14, 1024, 256, 1, 1, 1, 5),
    ("s4_down3x3", 14, 14, 512, 512, 3, 3, 2, 1),
    ("s4_3x3", 7, 7, 512, 512, 3, 3, 1, 2),
    ("s4_1x1b", 7, 7, 512, 2048, 1, 1, 1, 3),
    ("s4_proj", 7, 7, 2048, 512, 1, 1, 1, 2),
]


def conv_fwd(x, w, stride, pad):
    import jax

    # bf16 in/out like the real bf16 TrainStep (MXU accumulates f32
    # internally either way)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def dw_patches(x, dy, kh, kw, stride, pad, cin):
    """dW via im2col: patches (N,Ho,Wo,kh*kw*Cin) x dy (N,Ho,Wo,Cout)
    contracted over (N,Ho,Wo) in ONE dot_general on the MXU."""
    import jax
    import jax.numpy as jnp

    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    n, ho, wo, _ = patches.shape
    dw = jax.lax.dot_general(
        patches.reshape(n * ho * wo, -1), dy.reshape(n * ho * wo, -1),
        (((0,), (0,)), ((), ())),
        preferred_element_type=np.float32)
    # patches feature order is Cin-major: (Cin, kh, kw) per the jax docs
    return dw.reshape(cin, kh, kw, -1).transpose(1, 2, 0, 3)  # -> HWIO


def bench_one(name, h, w, cin, cout, kh, kw, stride, mult, batch, iters):
    import jax
    import jax.numpy as jnp

    pad = "SAME" if (kh > 1 or stride > 1) else "VALID"
    rs = np.random.RandomState(0)
    x0 = jnp.asarray(rs.randn(batch, h, w, cin), jnp.bfloat16)
    w0 = jnp.asarray(rs.randn(kh, kw, cin, cout) * 0.05, jnp.bfloat16)

    def make_chain(body):
        def chained(x, wgt):
            def tick(carry, _):
                xx, ww = carry
                out = body(xx, ww)
                # data dependence: perturb weights by a tiny function of
                # the result so the scan cannot be parallelized/DCE'd
                ww = ww * (1 + 1e-30 * out.astype(jnp.bfloat16).mean())
                return (xx, ww), ()

            (xx, ww), _ = jax.lax.scan(tick, (x, wgt), None, length=iters)
            return ww

        return jax.jit(chained)

    def vjp_dw(x, wgt):
        y, pull = jax.vjp(lambda w_: conv_fwd(x, w_, stride, pad), wgt)
        (dw,) = pull(jnp.ones_like(y))
        return dw

    def vjp_dx(x, wgt):
        y, pull = jax.vjp(lambda x_: conv_fwd(x_, wgt, stride, pad), x)
        (dx,) = pull(jnp.ones_like(y))
        return dx

    def patches_dw(x, wgt):
        y = conv_fwd(x, wgt, stride, pad)
        return dw_patches(x, jnp.ones_like(y), kh, kw, stride, pad, cin)

    results = {}
    for label, body in (("vjp_dw", vjp_dw), ("patches_dw", patches_dw),
                        ("vjp_dx", vjp_dx)):
        fn = make_chain(body)
        out = fn(x0, w0)
        out.block_until_ready()
        t0 = time.perf_counter()
        out = fn(x0, w0)
        float(jnp.sum(out.astype(jnp.float32)))  # data-dependent fetch
        dt = time.perf_counter() - t0
        results[label] = dt / iters * 1e3  # ms per iteration
    results["mult"] = mult
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated shape-name filter")
    args = ap.parse_args()

    only = set(args.only.split(",")) if args.only else None
    total = {"vjp_dw": 0.0, "patches_dw": 0.0}
    for row in SHAPES:
        if only and row[0] not in only:
            continue
        res = bench_one(*row, batch=args.batch, iters=args.iters)
        print(json.dumps({"shape": row[0], **{k: round(v, 3)
                          for k, v in res.items()}}), flush=True)
        for k in total:
            total[k] += res[k] * res["mult"]
    print(json.dumps({"shape": "TOTAL_weighted",
                      **{k: round(v, 2) for k, v in total.items()}}),
          flush=True)


if __name__ == "__main__":
    main()
