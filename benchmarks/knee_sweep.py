"""Find the knee of an open-loop mix, once, on the chip.

    python3 benchmarks/knee_sweep.py --workload mistral7b_chat_open \
        --rates 2,3,4,5,6 --seconds 20 [--seed 0]

ONE process: weights and executables are loaded once, then each rate runs
the mix's own generator for ``--seconds`` and is drained before the next.
Prints one JSON line per rate: attainment of the limits in the traffic
file's ``knee.limits``, and the pending queue at the midpoint and at the
end of the window. Knee = the highest tried rate at which the queue at the
end is no longer than at the midpoint and at least ``attainment`` of the
requests sent met both limits. The cell then runs at 0.8 of it, written
into the traffic file as ``rate_rps``. Rates run in ascending order and
the sweep stops at the first rate with a failed request: past the knee the
prefill batches grow until one no longer fits the chip.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import run as bench_run

    manifest = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.find_cell(manifest, args.workload, False)
    config = bench_run.load_json(HERE, "configs", cell["config"] + ".json")
    traffic = bench_run.load_json(HERE, "traffic", cell["traffic"] + ".json")
    bench_run.set_environment(config)
    import jax

    from benchmarks.lib import arrivals, harness, serve_loop, stats

    if jax.devices()[0].platform != "tpu":
        print(f"knee_sweep.py needs a TPU; jax.devices() = {jax.devices()}",
              file=sys.stderr)
        return 3
    run = harness.Run(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=False,
        devices=jax.devices()[:1], peaks=None,
        builder=importlib.import_module(
            f"benchmarks.builders.{config['builder']}"),
        reference=None, out_dir="", t0=_T0)
    built = run.builder.build(config, traffic, args.seed, run.devices)
    srv = built["server"]
    serve_loop.warm_up(run, srv, traffic["server"]["warmup"],
                       config["vocab_size"])
    run.log("warm")
    limits = traffic["knee"]["limits"]
    watch = harness.CompileWatch()
    for rate in sorted(float(r) for r in args.rates.split(",")):
        mix = dict(traffic, rate_rps=rate)
        schedule = arrivals.open_loop_schedule(
            args.seed, mix, config["vocab_size"], args.seconds)
        gen = serve_loop.Generator(run, srv, traced=False)
        t0 = time.perf_counter()
        mid = []
        serve_loop.send_open_loop(
            gen, schedule, t0, half_s=args.seconds / 2,
            at_half=lambda: mid.append(srv.stats()["generates_pending"]))
        time.sleep(max(0.0, t0 + args.seconds - time.perf_counter()))
        end_stats = srv.stats()
        gen.drain(serve_loop.DRAIN_TIMEOUT_S)
        drained_s = time.perf_counter() - t0 - args.seconds
        ttft, tpot, met, failed = [], [], 0, 0
        for r in gen.records:
            ok = r.error is None and len(r.times) == r.req.max_new
            if not ok:
                failed += 1
                continue
            a = (r.times[0] - r.due) * 1e3
            b = (r.times[-1] - r.times[0]) * 1e3 / max(1, len(r.times) - 1)
            ttft.append(a)
            tpot.append(b)
            met += a <= limits["ttft_ms"] and b <= limits["tpot_ms"]
        sent = len(gen.records)
        print(json.dumps({
            "rate_rps": rate, "sent": sent, "failed": failed,
            "attainment": met / sent if sent else None,
            "pending_mid": mid[0] if mid else None, "pending_end": end_stats["generates_pending"],
            "active_end": end_stats["generates_active"],
            "drain_s": drained_s,
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p95_ms": stats.percentile(ttft, 95),
            "tpot_p50_ms": stats.percentile(tpot, 50),
            "tpot_p95_ms": stats.percentile(tpot, 95),
            "tokens_s": sum(r.req.prompt.size + len(r.times)
                            for r in gen.records) / args.seconds,
            "compiles_so_far": watch.snapshot()["compiles"],
            "peak_gb": harness.peak_memory_bytes(run.devices) / 1e9}),
            flush=True)
        if failed:
            break
    srv.stop(timeout=60.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
