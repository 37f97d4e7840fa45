"""Benchmark: Llama pretrain proxy (~0.7B, Llama-3-8B recipe) on one chip.

Prints a JSON line after EVERY completed stage (flushed), monotonically
enriched — the bench.py artifact contract from PERF_HISTORY.md round 4 (a timeout
must not lose a finished stage's numbers):

    stage 1  config               -> line 1 (model/config keys)
    stage 2  pretrain proxy run   -> line 2 (adds value/mfu/params/
             final_loss — the contract keys)
    stage 3  fused-kernel adoption-> line 3 (pallas dispatch counts when
             telemetry is on)

The model is CONFIGS['proxy1b'] from tools/pretrain_llama.py — same
blocks, same fused TrainStep + AdamW path, same remat policy as the 8B
stretch config (BASELINE.json config[4]); only depth/width are scaled so
weights + Adam state fit one v5e chip. MFU = 6 * N * tokens_per_sec /
peak_flops. MXNET_PALLAS_FUSED (default ON here) routes the RMSNorm
sweeps through the fused Pallas layer kernels on TPU.

The full-size recipe artifact is produced by
``tools/pretrain_llama.py --config 8b --compile-only`` (AOT compile of the
sharded step on a virtual mesh; results recorded in PERF_HISTORY.md).
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

MFU_TARGET = 0.65            # ISSUE 7 acceptance bar


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def main():
    os.environ.setdefault("MXNET_PALLAS_FUSED", "1")
    if os.environ.get("BENCH_LLAMA_FUSED_LAYERS") == "0":
        os.environ["MXNET_PALLAS_FUSED"] = "0"
    import jax

    from tools.pretrain_llama import main as pretrain_main

    if jax.devices()[0].platform != "tpu":
        print(f"bench_llama.py needs a TPU; jax.devices() = {jax.devices()}",
              file=sys.stderr)
        return 1
    # no-remat: the 0.7B proxy's full activations fit one v5e at batch
    # 8. Remat is a MEMORY policy — the 8B stretch config keeps it
    # (tools/pretrain_llama --config 8b), the proxy benchmarks the
    # unconstrained step. 16 steps: sync at 8, synced-span over the last
    # 8 (the span MUST start from a synced fetch).
    args = ["--config", "proxy1b", "--steps", "16", "--batch", "8",
            "--seq", "2048", "--no-remat"]
    record = {
        "metric": "llama_proxy_pretrain_tokens_per_sec_per_chip",
        "unit": "tokens/sec",
        "llama_config": args[1],
        "llama_fused_layers": os.environ["MXNET_PALLAS_FUSED"] == "1",
        "llama_mfu_target": MFU_TARGET,
    }
    _emit(record)  # stage 1 — config survives a timeout
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = pretrain_main(args)
    if rc:
        return rc
    rec = json.loads(buf.getvalue().strip().splitlines()[-1])
    mfu = rec["mfu"]
    if mfu is None:
        # pretrain_llama found no bf16 peak for this device kind
        print("bench_llama.py: no MFU — unknown device kind "
              f"{jax.devices()[0].device_kind!r}", file=sys.stderr)
        return 1
    record.update({
        "value": rec["tokens_per_sec"],
        "params": rec["params"],
        "mfu": mfu,
        "final_loss": rec["final_loss"],
        "llama_mfu_vs_target": round(mfu / MFU_TARGET, 4),
    })
    _emit(record)  # stage 2 — the contract keys are on stdout

    from mxnet_tpu import telemetry

    if telemetry.enabled():
        fam = telemetry.snapshot()["metrics"].get(
            "mxnet_pallas_dispatch_total")
        record["llama_pallas_dispatch"] = {
            s["labels"]["kernel"]: s["value"]
            for s in (fam["samples"] if fam else ())}
        _emit(record)  # stage 3 — kernel-adoption counters
    return 0


if __name__ == "__main__":
    sys.exit(main())
