"""Record the small device trace that benchmarks/tests check the trace
reduction against. Run once on the chip:

    python3 benchmarks/record_testdata.py --out chiprun_out/testdata

A few matmuls with host pauses between them, under the benchmark's own
annotations; writes ``small.xplane.pb`` and ``recorded_expect.json`` (what
the reduction gave when the trace was taken).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import harness, trace_reduce

    if jax.devices()[0].platform != "tpu":
        print("record_testdata.py needs a TPU", file=sys.stderr)
        return 3
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.path.join(ROOT, ".cache"))
    prof = harness.ProfileSlice(tmp)
    prof.start()
    for _ in range(4):
        with jax.profiler.TraceAnnotation("bench:dispatch"):
            y = f(x)
        with jax.profiler.TraceAnnotation("bench:block"):
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("bench:pause"):
            time.sleep(0.002)
    prof.stop()
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "small.xplane.pb")
    shutil.copy(trace_reduce.find_xplane(prof.dir), dst)
    trace = trace_reduce.load(dst)
    ev = trace.devices[0]
    expect = {"events": len(ev), "busy_ns": trace_reduce.busy_ns(ev),
              "span_ns": trace_reduce.total([trace_reduce.span_of(ev)]),
              "top_op": max(trace_reduce.by_name(ev).items(),
                            key=lambda kv: kv[1])[0],
              "first_host_event": [e.name for e in trace.host][:1],
              "device_kind": jax.devices()[0].device_kind,
              "describe": trace_reduce.describe(trace, top=10)}
    with open(os.path.join(args.out, "recorded_expect.json"), "w") as fh:
        json.dump(expect, fh, indent=1)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"bytes": os.path.getsize(dst), **expect})[:2000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
