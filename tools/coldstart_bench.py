#!/usr/bin/env python
"""Cold-start benchmark — the compilation service's acceptance meter.

Measures the two cold-start paths ROADMAP item 5 names, each in a FRESH
subprocess (cold start is a process property; in-process timers lie):

1. **process-start -> first-train-step** — import, build a deep-MLP
   TrainStep, train once at each of six batch signatures (the gated
   headline: time to trained-at-all-signatures);
2. **replica-start -> first-response** — import, build a serving
   ``Server`` over the bucket grid, serve one request (reported, not
   gated: its total is init/machinery-dominated).

Three regimes per path:

* ``cold``          — empty XLA disk cache, no manifest: every
  executable traces AND compiles;
* ``warm_disk``     — persistent XLA cache populated by the cold run:
  compiles become disk loads, traces still pay;
* ``warm_manifest`` — disk cache + signature-manifest replay
  (``compiler.warm_start``) before first traffic: same total path, but
  all compile work happens BEFORE the first batch/request, so
  first-dispatch latency collapses to a steady-state step and the
  steady state records ZERO jit-cache misses.

Gates reported (the ISSUE 10 acceptance criteria):
* ``coldstart_speedup``      >= 2.0 (warm_manifest vs cold, first-step
  path, total process time);
* ``coldstart_bit_identical`` — the post-warm loss equals the cold loss
  bit-for-bit (warmed executables must be the same program);
* ``coldstart_zero_misses_after_warm`` — the warmed child's first +
  steady steps record no ``train_step``/``cached_op`` cache miss.

Emits bench.py's JSON contract — one flushed line per completed stage,
monotonically enriched, ``{"metric", "value", "unit", "vs_baseline"}``
first; ``vs_baseline`` is speedup/2.0 (the acceptance bar).

Forces ``JAX_PLATFORMS=cpu`` like the tier-1 test environment (compile
caching mechanics are platform-independent, and a chip belongs to one
process at a time: the parent bench may hold it). ``COLDSTART_PLATFORM``
overrides for on-device runs.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS",
                      os.environ.get("COLDSTART_PLATFORM", "cpu"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SPEEDUP_TARGET = 2.0
# Deep MLP trained at SIX batch signatures (bucketed-training shape):
# per-executable, XLA:CPU compile is ~4x the trace + disk-load cost, so
# the executable count is what separates cold from warm — the same
# regime a transformer TrainStep is in on TPU, scaled to bench seconds.
N_LAYERS = int(os.environ.get("COLDSTART_LAYERS", "24"))
HIDDEN = int(os.environ.get("COLDSTART_HIDDEN", "1024"))
IMG = (64,)
TRAIN_BATCHES = (4, 8, 12, 16, 24, 32)
SERVE_BUCKETS = (1, 2, 4, 8, 16, 32)


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# child workloads (run in a fresh interpreter; timed from process start)
# ---------------------------------------------------------------------------

def _build_net():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn

    mx.random.seed(0)
    net = nn.HybridSequential(prefix="cold_")
    with net.name_scope():
        for _ in range(N_LAYERS):
            net.add(nn.Dense(HIDDEN, activation="relu"))
        net.add(nn.Dense(10))
    net.initialize()
    return net


def _child_train(t0: float, warm: bool) -> dict:
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import compiler, telemetry
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import loss as gloss

    net = _build_net()
    step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "adam")
    rs = np.random.RandomState(0)
    batches = [
        (mx.nd.array(rs.rand(b, *IMG).astype("float32")),
         mx.nd.array((np.arange(b) % 10).astype("float32")))
        for b in TRAIN_BATCHES]

    warm_report = None
    if warm:
        warm_report = compiler.warm_start(train_steps=[step])
    t_warm = time.perf_counter() - t0

    telemetry.enable()
    x, y = batches[0]
    t1 = time.perf_counter()
    loss, _ = step(x, y)
    loss.asnumpy()
    t_first = time.perf_counter()
    for x, y in batches[1:]:
        loss, _ = step(x, y)
        loss.asnumpy()
    t_all_sigs = time.perf_counter()
    # steady state: repeat signature 0 — must be a pure cache hit
    x, y = batches[0]
    loss, _ = step(x, y)
    loss_host = loss.asnumpy()
    t_steady = time.perf_counter()

    snap = telemetry.snapshot()["metrics"].get(
        "mxnet_jit_cache_total", {"samples": []})
    misses = {
        s["labels"]["cache"]: s["value"] for s in snap["samples"]
        if s["labels"]["result"] == "miss"}
    telemetry.disable()
    return {
        "import_s": round(_IMPORT_DONE - t0, 3),
        "warm_s": round(t_warm - (_IMPORT_DONE - t0), 3) if warm else 0.0,
        "to_first_step_s": round(t_first - t0, 3),
        "first_step_s": round(t_first - t1, 3),
        "all_sigs_s": round(t_all_sigs - t0, 3),
        "steady_step_s": round(t_steady - t_all_sigs, 4),
        "loss_hex": np.asarray(loss_host, np.float32).tobytes().hex(),
        "graph_misses": {k: v for k, v in misses.items()
                         if k in ("train_step", "cached_op")},
        "warm_report": warm_report,
        "coldstart_events": compiler.events(),
    }


def _child_serve(t0: float, warm: bool) -> dict:
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import compiler, serving

    net = _build_net()
    net.hybridize()
    srv = serving.Server(net, batch_buckets=SERVE_BUCKETS,
                         shape_buckets=[IMG], slo_ms=200,
                         name="coldstart")
    # Server._warm_block replays the active manifest automatically when
    # recording is on (MXNET_COMPILE_MANIFEST); nothing extra to do for
    # the warm regime
    srv.start()
    t_started = time.perf_counter()
    fut = srv.submit(np.zeros(IMG, np.float32))
    out = fut.result(timeout=600)
    t_first = time.perf_counter()
    srv.stop(timeout=30)
    return {
        "import_s": round(_IMPORT_DONE - t0, 3),
        "to_first_response_s": round(t_first - t0, 3),
        "start_s": round(t_started - t0, 3),
        "first_response_s": round(t_first - t_started, 4),
        "response_hex": np.asarray(out, np.float32).tobytes().hex(),
        "coldstart_events": compiler.events(),
    }


def _child_main(mode: str, warm: bool, t0: float) -> None:
    global _IMPORT_DONE

    import mxnet_tpu  # noqa: F401  (the timed import)

    _IMPORT_DONE = time.perf_counter()
    rec = (_child_train if mode == "train" else _child_serve)(t0, warm)
    _emit(rec)


# ---------------------------------------------------------------------------
# parent: three regimes x two paths, each in a fresh interpreter
# ---------------------------------------------------------------------------

def _run_child(mode: str, cache_dir: str, manifest: str,
               warm: bool) -> dict:
    # per-path cache namespace (train fleet vs serving fleet — also what
    # a real deployment shards by), and a small min-compile floor so the
    # dozens of trivial utility jits don't persist: the sub-100ms
    # entries carry no warm value. The children measure OUR layout cold
    # against warm on purpose, so a cache the environment handed the
    # parent is not passed on.
    env = dict(os.environ,
               MXNET_XLA_CACHE="1",
               MXNET_XLA_CACHE_DIR=os.path.join(cache_dir, mode),
               MXNET_XLA_CACHE_MIN_COMPILE_S="0.2",
               MXNET_TELEMETRY="0")
    # the manifest is recorder (cold run journals its compiles) and warm
    # source (warm_manifest regime replays it); the warm_disk regime runs
    # with recording OFF so it measures the disk tier alone — a live
    # recorder would auto-replay inside Server._warm_block
    if manifest:
        env["MXNET_COMPILE_MANIFEST"] = manifest + "." + mode
    else:
        env["MXNET_COMPILE_MANIFEST"] = "0"
    env.pop("MXNET_TELEMETRY_OUT", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    argv = [sys.executable, os.path.abspath(__file__), "--child", mode]
    if warm:
        argv.append("--warm")
    out = subprocess.run(argv, capture_output=True, text=True, env=env,
                         timeout=float(os.environ.get(
                             "COLDSTART_CHILD_TIMEOUT_S", "900")))
    if out.returncode != 0:
        raise RuntimeError(
            f"coldstart child {mode} rc={out.returncode}: "
            f"{out.stderr.strip().splitlines()[-5:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> int:
    if "--child" in sys.argv:
        t0 = _T0
        mode = sys.argv[sys.argv.index("--child") + 1]
        _child_main(mode, "--warm" in sys.argv, t0)
        return 0

    base = tempfile.mkdtemp(prefix="coldstart_xla_")
    manifest = os.path.join(base, "signatures.jsonl")
    record: dict = {}
    stages = {}
    # best-of-N per child, applied to EVERY regime symmetrically: this
    # container shares cores with co-tenants and a single noisy child
    # run can swing a regime 2x (warm children measured stable at
    # ±5% back-to-back); the minimum is the capability, the rest is
    # scheduler noise
    repeats = max(1, int(os.environ.get("COLDSTART_REPEATS", "2")))

    def best_of(mode, man, warm, pick, fresh_dirs=False):
        runs = []
        for i in range(repeats):
            # cold repeats must each see an EMPTY cache — scratch dirs
            # for all but the last, which populates the shared layout
            # the warm regimes then read
            d = tempfile.mkdtemp(prefix="coldstart_scratch_") \
                if fresh_dirs and i < repeats - 1 else base
            runs.append(_run_child(mode, d, man, warm))
        return min(runs, key=lambda r: r[pick])

    for regime, warm in (("cold", False), ("warm_disk", False),
                         ("warm_manifest", True)):
        man = "" if regime == "warm_disk" else manifest
        stages[regime] = {
            "train": best_of("train", man, warm, "all_sigs_s",
                             fresh_dirs=regime == "cold"),
            "serve": best_of("serve", man, warm, "to_first_response_s",
                             fresh_dirs=regime == "cold"),
        }
        tr, sv = stages[regime]["train"], stages[regime]["serve"]
        record.update({
            f"coldstart_{regime}_first_step_s": tr["to_first_step_s"],
            f"coldstart_{regime}_all_sigs_s": tr["all_sigs_s"],
            f"coldstart_{regime}_first_step_latency_s": tr["first_step_s"],
            f"coldstart_{regime}_first_response_s":
                sv["to_first_response_s"],
        })
        if regime == "cold":
            # contract keys land after stage 1 so a later-stage failure
            # still leaves a parseable record on stdout
            record.update({"metric": "coldstart_first_step_speedup",
                           "value": None, "unit": "x",
                           "vs_baseline": None})
        _emit(record)

    cold_t = stages["cold"]["train"]
    warm_t = stages["warm_manifest"]["train"]
    # headline (the gated acceptance metric): process start -> trained
    # at every batch signature — the production cold start; a trainer is
    # not "started" while bucket shapes still compile. The serve path is
    # measured and reported (coldstart_serve_speedup,
    # coldstart_*_first_response_s) but not folded into the gate: its
    # total is dominated by model init + server machinery, not compiles.
    speedup = cold_t["all_sigs_s"] / max(warm_t["all_sigs_s"], 1e-9)
    serve_speedup = (stages["cold"]["serve"]["to_first_response_s"]
                     / max(stages["warm_manifest"]["serve"]
                           ["to_first_response_s"], 1e-9))
    bit_identical = (cold_t["loss_hex"] == warm_t["loss_hex"]
                     and stages["cold"]["serve"]["response_hex"]
                     == stages["warm_manifest"]["serve"]["response_hex"])
    zero_misses = warm_t["warm_report"] is not None and \
        sum(warm_t["graph_misses"].values()) == 0
    record.update({
        "metric": "coldstart_first_step_speedup",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup / SPEEDUP_TARGET, 4),
        "coldstart_speedup": round(speedup, 2),
        "coldstart_serve_speedup": round(serve_speedup, 2),
        "coldstart_speedup_target": SPEEDUP_TARGET,
        "coldstart_bit_identical": bit_identical,
        "coldstart_zero_misses_after_warm": zero_misses,
        "coldstart_warm_first_step_latency_s": warm_t["first_step_s"],
        "coldstart_warm_report": warm_t["warm_report"],
        "coldstart_manifest_entries": sum(
            len(open(p).readlines())
            for p in (manifest + ".train", manifest + ".serve")
            if os.path.exists(p)),
    })
    _emit(record)
    ok = (speedup >= SPEEDUP_TARGET and bit_identical and zero_misses)
    return 0 if ok else 1


_T0 = time.perf_counter()
_IMPORT_DONE = _T0

if __name__ == "__main__":
    sys.exit(main())
