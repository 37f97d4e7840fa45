"""What a decode round's host turn costs when nobody watches the program.

    chiprun -- python3 tools/untraced_host_turn.py --workload <serving cell> --seed <n>

The benchmark reads its per-layer metrics in a run with ``mx.tracing`` and
``mx.telemetry`` ON (a span per stream per round, three counters a token),
so the device idle it reports per round (``host_turn_ms_per_round``,
``device_idle_pct_serve``) includes what that instrument costs. This runs
the same cell — same builder, traffic, warm-up, load generator and
``jax.profiler`` slice, through the benchmark's own driver — with both
OFF, and reduces the slice with ``benchmarks/lib/trace_reduce.py``: device
idle over the slice, divided by the decode rounds in it, counted by the
decode programs' runs as the cell's own trace readers count them. The
difference to the traced run's figure, same seed, is the tracing's cost a
round.

Prints one JSON line. Needs the chip (exit 3 without one), as
``benchmarks/run.py`` does; ``--rehearse`` runs a cell of
``benchmarks/rehearsal.json`` on the CPU to prove the control flow, and
prints no number (a CPU's profile has no device plane).
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def decode_rounds(inputs: dict) -> float:
    """Decode rounds in the slice, by the configuration's builder."""
    from benchmarks.lib import (glm_dsa_scopes, phi4flash_scopes, readers,
                                xplane_scopes)

    builder = inputs["config"]["builder"]
    if builder == "llama_family_decoder":   # the paged kernel's calls
        return float(readers.decode_rounds_in_trace(inputs))
    by_runs = {"longcat_flash": xplane_scopes, "glm_moe_dsa": glm_dsa_scopes,
               "phi4flash": phi4flash_scopes}[builder]
    return by_runs.decode_rounds(xplane_scopes.first_chip(inputs),
                                 inputs["config"])


def main(argv=None) -> int:
    from benchmarks import run as bench

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    manifest = bench.load_json(ROOT, "BENCHMARK.json")
    cell = bench.find_cell(manifest, args.workload, args.rehearse)
    config = bench.load_json(bench.HERE, "configs", cell["config"] + ".json")
    traffic = bench.load_json(bench.HERE, "traffic",
                              cell["traffic"] + ".json")
    if traffic["driver"] == "train_steps":
        print(f"{cell['name']} serves no requests", file=sys.stderr)
        return 1
    bench.set_environment(config, cell["chips"] if args.rehearse else 0)
    import jax

    from benchmarks.lib import harness, readers, trace_reduce
    from mxnet_tpu import telemetry, tracing

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        print(f"needs a TPU; jax.devices() = {devices}", file=sys.stderr)
        return 3
    # where run.py puts a traced run's profile: the readers look there
    out_dir = os.path.join(ROOT, ".cache", "bench_out",
                           f"{cell['name']}-trace1")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    run = harness.Run(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds if args.seconds is not None
        else float(manifest["run_seconds"]),
        trace=True, devices=devices[:cell["chips"]], peaks=None,
        builder=importlib.import_module(
            f"benchmarks.builders.{config['builder']}"),
        reference=importlib.import_module(
            f"benchmarks.references.{config['builder']}"),
        out_dir=out_dir, t0=_T0, watch=harness.CompileWatch())
    # the driver's traced run switches the program's observers on; this
    # is that run without them, so both switches do nothing here
    telemetry.enable = tracing.enable = lambda: None
    result = importlib.import_module(
        f"benchmarks.drivers.{traffic['driver']}").run(run)
    if telemetry.enabled() or tracing.enabled() or result.layer["spans"]:
        raise RuntimeError("the program was watched after all")

    inputs = dict(result.layer, config=config, traffic=traffic, cell=cell)
    events = readers.first_device(inputs)
    if args.rehearse:
        run.log(f"rehearsal of {cell['name']} ran to its end unwatched: "
                f"correct={result.correct}, failed={result.failed}")
        return 0 if result.correct and not result.failed else 4
    if events is None:
        print("the slice holds no device operations", file=sys.stderr)
        return 1
    window = trace_reduce.span_of(events)
    window_ms = (window[1] - window[0]) / 1e6
    idle_ms = window_ms - trace_reduce.busy_ns(events) / 1e6
    rounds = decode_rounds(inputs)
    print(json.dumps({
        "workload": cell["name"], "seed": args.seed,
        "tracing": False, "telemetry": False,
        "correct": result.correct, "failed": result.failed,
        "tpot_p50_ms": result.end_to_end["tpot_p50_ms"],
        "device_window_ms": window_ms,
        "device_idle_pct": 100.0 * idle_ms / window_ms,
        "decode_rounds": rounds,
        "round_ms": window_ms / rounds if rounds else None,
        "idle_ms_per_round": idle_ms / rounds if rounds else None,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
