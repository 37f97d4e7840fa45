"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ONE cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of stdout, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced). With ``--trace 0`` the metrics are the cell's end-to-end metrics,
measured with the program's tracing and telemetry off; with ``--trace 1``
its per-layer metrics, from a run with both on and a profiler trace of a
short steady slice.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json`` names its ``builders/<builder>.py`` and
``references/<builder>.py``; ``traffic/<mix>.json`` names its
``drivers/<driver>.py``; ``layer_metrics/<metric>.py`` reads one metric.

``--rehearse`` runs a cell of ``rehearsal.json`` (tiny sizes) on the CPU,
on as many virtual devices as the cell has chips, to prove the control
flow. It never prints the result line: a CPU run is not a chip run.

Exit codes: 0 a result line was printed (or a rehearsal passed); 1 the
cell is unknown; 2 the checkout lacks the program; 3 no TPU, too few
chips or a device kind without peaks; 4 a rehearsal failed its checks.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# a failed request is placed at +inf; JSON has no infinity
INF_MS = 1e9


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str, rehearse: bool) -> dict:
    cells = load_json(HERE, "rehearsal.json")["workloads"] if rehearse \
        else manifest["workloads"]
    for cell in cells:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"run.py: no workload {name!r}; known: "
                     f"{[c['name'] for c in cells]}")


def metrics_of(manifest: dict, group: str, cell_name: str) -> list:
    """The metrics of ``group`` that the cell reports: all that list it
    under ``workloads``, and all that list nothing."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def set_environment(config: dict, virtual_cpus: int = 0) -> None:
    """What has to be in the environment before the program or jax is
    imported: the configuration's own variables, the compile cache, and
    for a rehearsal the CPU with ``virtual_cpus`` devices."""
    for k, v in config.get("env", {}).items():
        os.environ[k] = str(v)
    if virtual_cpus:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={virtual_cpus}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a fixed path inside the checkout: the path is part of the key
        os.environ["MXNET_XLA_CACHE_DIR"] = os.path.join(
            ROOT, ".cache", "mxnet_tpu_xla")
    # small programs persist too: the second run of a cell compiles nothing
    os.environ.setdefault("MXNET_XLA_CACHE_MIN_COMPILE_S", "0")
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(manifest, args.workload, args.rehearse)
    # a rehearsal cell reports what the real cell it stands for reports
    metric_cell = cell.get("like", cell["name"])
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    seconds = args.seconds if args.seconds is not None \
        else float(manifest["run_seconds"])

    set_environment(config, cell["chips"] if args.rehearse else 0)
    try:
        import jax
        import mxnet_tpu  # noqa: F401  (sets up the compile cache)
    except ImportError as e:
        print(f"run.py: this checkout cannot run the program: {e}",
              file=sys.stderr)
        return 2

    devices = jax.devices()
    chips = cell["chips"]
    peaks = None
    if not args.rehearse:
        from benchmarks.lib import peaks as peaks_mod

        if devices[0].platform != "tpu":
            print(f"run.py needs a TPU; jax.devices() = {devices}",
                  file=sys.stderr)
            return 3
        try:
            peaks = peaks_mod.load(devices[0].device_kind)
        except peaks_mod.UnknownDevice as e:
            print(f"run.py: {e}", file=sys.stderr)
            return 3
    if len(devices) < chips:
        print(f"run.py: cell {cell['name']} needs {chips} chips; "
              f"jax.devices() = {devices}", file=sys.stderr)
        return 3

    from benchmarks.lib import harness, trace_reduce

    out_dir = os.path.join(ROOT, ".cache", "bench_out",
                           f"{cell['name']}-trace{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    run = harness.Run(
        cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=seconds, trace=bool(args.trace),
        devices=devices[:chips], peaks=peaks,
        builder=importlib.import_module(
            f"benchmarks.builders.{config['builder']}"),
        reference=importlib.import_module(
            f"benchmarks.references.{config['builder']}"),
        out_dir=out_dir, t0=_T0, watch=harness.CompileWatch())
    driver = importlib.import_module(f"benchmarks.drivers.{traffic['driver']}")
    run.log(f"cell {cell['name']}: config {cell['config']}, traffic "
            f"{cell['traffic']}, {chips} chip(s), seed {args.seed}, "
            f"{seconds} s, trace {args.trace}")
    result = driver.run(run)

    # -- metrics ------------------------------------------------------------
    metrics = {}
    if args.trace:
        inputs = dict(result.layer, config=config, traffic=traffic,
                      cell=cell, peaks=peaks)
        for m in metrics_of(manifest, "per_layer", metric_cell):
            reader = importlib.import_module(
                f"benchmarks.layer_metrics.{m['name']}")
            value = reader.read(inputs)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(manifest, "end_to_end", metric_cell):
            value = result.end_to_end[m["name"]]
            if value is None or not math.isfinite(value):
                value = INF_MS
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": result.layer["peak_bytes"]}
    line = {"correct": result.correct, "attempted": result.attempted,
            "failed": result.failed, "metrics": metrics, "device": device}
    trace = result.layer.get("trace")
    if args.trace and trace is not None:
        summary = trace_reduce.summary(trace)
        with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
            json.dump(trace_reduce.describe(trace), f, indent=1)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    print(json.dumps({"info": result.notes,
                      "end_to_end_seen": result.end_to_end}, default=str),
          flush=True)
    if args.rehearse:
        run.log(f"rehearsal of {cell['name']} ran to its end on "
                f"{devices[0].platform}: correct={result.correct}, "
                f"failed={result.failed}, would report {sorted(metrics)}; "
                "a CPU run is not a chip run, so no result line")
        return 0 if result.correct and not result.failed else 4
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
