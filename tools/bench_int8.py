"""Microbench: s8 x s8 -> s32 MXU matmul vs bf16 (VERDICT round-2 #7).

Chained-matmul harness (300 dependent iterations inside one executable,
data-dependent fetch). Prints one JSON line
with both rates and the ratio; the quantized ops take the s8 path on TPU
when this ratio is why you quantized.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def _bench_chain(fn, x, iters):
    """Chained data-dependent timing loop:
    jit a fori_loop of fn, fetch a scalar that depends on everything,
    best of 2 timed runs."""
    import jax

    f = jax.jit(lambda x: jax.lax.fori_loop(
        0, iters, lambda i, x: fn(x), x))
    r = f(x)
    _ = np.asarray(jax.device_get(r)).ravel()[0]
    best = float("inf")
    for _i in range(2):
        t0 = time.perf_counter()
        r = f(r)
        _ = np.asarray(jax.device_get(r)).ravel()[0]
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3


def main():
    import jax
    import jax.numpy as jnp

    M = N = K = 4096
    iters = 300
    rs = np.random.RandomState(0)
    a8 = jnp.asarray(rs.randint(-127, 128, (M, K)), jnp.int8)
    b8 = jnp.asarray(rs.randint(-127, 128, (K, N)), jnp.int8)
    abf = jnp.asarray(rs.randn(M, K), jnp.bfloat16)
    bbf = jnp.asarray(rs.randn(K, N), jnp.bfloat16)

    def bench(fn, x):
        return _bench_chain(fn, x, iters)

    def mm_s8(x):
        acc = jax.lax.dot_general(x, b8, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return jnp.clip(acc >> 7, -127, 127).astype(jnp.int8)

    def mm_bf(x):
        return jax.lax.dot_general(
            x, bbf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.bfloat16)

    tflop = 2 * M * N * K / 1e12
    ms_s8 = bench(mm_s8, a8)
    ms_bf = bench(mm_bf, abf)
    print(json.dumps({
        "metric": "int8_vs_bf16_matmul_speedup",
        "value": round(ms_bf / ms_s8, 3),
        "unit": "x",
        "s8_tflops": round(tflop / (ms_s8 / 1e3), 1),
        "bf16_tflops": round(tflop / (ms_bf / 1e3), 1),
    }))
    return 0


def main_layers():
    """Per-layer int8-vs-bf16 on representative ResNet-50 shapes
    (VERDICT r4 #5): the REAL quantized_conv/quantized_dense ops (s8xs8
    -> s32 on the MXU, calibrated ranges, fused rescale) against the
    bf16 Convolution/FullyConnected they replace. Chained data-dependent
    loop; NHWC layouts."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.ops.registry import get_op

    qconv = get_op("_contrib_quantized_conv").fn
    conv = get_op("Convolution").fn
    qdense = get_op("_contrib_quantized_dense").fn
    dense = get_op("FullyConnected").fn
    rs = np.random.RandomState(0)
    iters = 60

    def bench(fn, x):
        return _bench_chain(fn, x, iters)

    LAYERS = [
        ("stage1_3x3", (64, 56, 56, 64), 64, (3, 3), (1, 1)),
        ("stage2_1x1", (64, 28, 28, 512), 128, (1, 1), (0, 0)),
        ("stage3_3x3", (64, 14, 14, 256), 256, (3, 3), (1, 1)),
        ("stage4_1x1", (64, 7, 7, 2048), 512, (1, 1), (0, 0)),
    ]
    rows = []
    with execution_platform(jax.devices()[0].platform):
        for name, xshape, cout, kernel, pad in LAYERS:
            cin = xshape[-1]
            x = jnp.asarray(rs.randn(*xshape), jnp.bfloat16)
            w = jnp.asarray(rs.randn(cout, cin, *kernel) * 0.05,
                            jnp.bfloat16)
            wq = jnp.clip(jnp.round(w.astype(jnp.float32) / 0.002),
                          -127, 127).astype(jnp.int8)
            ws = jnp.full((cout,), 1.0 / 0.002, jnp.float32)

            def run_bf(xv, w=w, kernel=kernel, pad=pad, cout=cout):
                y = conv(xv, w, None, kernel=kernel, num_filter=cout,
                         pad=pad, no_bias=True, layout="NHWC")
                return xv * (1 + 1e-12 * jnp.mean(y).astype(jnp.float32)).astype(xv.dtype)

            def run_s8(xv, wq=wq, ws=ws, kernel=kernel, pad=pad,
                       cout=cout):
                y = qconv(xv, wq, ws, None, kernel=kernel,
                          num_filter=cout, pad=pad, no_bias=True,
                          layout="NHWC", min_calib_range=-4.0,
                          max_calib_range=4.0)
                return xv * (1 + 1e-12 * jnp.mean(y).astype(jnp.float32)).astype(xv.dtype)

            ms_bf = bench(run_bf, x)
            ms_s8 = bench(run_s8, x)
            rows.append({"layer": name, "bf16_ms": round(ms_bf, 3),
                         "int8_ms": round(ms_s8, 3),
                         "speedup": round(ms_bf / ms_s8, 2)})
        # the classifier head
        xh = jnp.asarray(rs.randn(256, 2048), jnp.bfloat16)
        wh = jnp.asarray(rs.randn(1000, 2048) * 0.05, jnp.bfloat16)
        whq = jnp.clip(jnp.round(wh.astype(jnp.float32) / 0.002),
                       -127, 127).astype(jnp.int8)
        whs = jnp.full((1000,), 1.0 / 0.002, jnp.float32)

        def head_bf(xv):
            y = dense(xv, wh, None, num_hidden=1000, no_bias=True)
            return xv * (1 + 1e-12 * jnp.mean(y).astype(jnp.float32)).astype(xv.dtype)

        def head_s8(xv):
            y = qdense(xv, whq, whs, None, num_hidden=1000, no_bias=True,
                       min_calib_range=-4.0, max_calib_range=4.0)
            return xv * (1 + 1e-12 * jnp.mean(y).astype(jnp.float32)).astype(xv.dtype)

        rows.append({"layer": "head_dense",
                     "bf16_ms": round(bench(head_bf, xh), 3),
                     "int8_ms": round(bench(head_s8, xh), 3)})
        rows[-1]["speedup"] = round(
            rows[-1]["bf16_ms"] / rows[-1]["int8_ms"], 2)
    print(json.dumps({"metric": "int8_vs_bf16_per_layer",
                      "layers": rows}))
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "layers":
        sys.exit(main_layers())
    sys.exit(main())
