"""Benchmark: BERT-base seq-512 training throughput + MFU.

Prints a JSON line after EVERY completed stage (flushed), monotonically
enriched — the bench.py artifact contract from PERF_HISTORY.md round 4: a driver
reading the LAST line of stdout always gets the richest complete record,
and an external timeout can never erase a finished stage's numbers.

    stage 1  build + compile + warmup -> line 1 (config, compile time)
    stage 2  timed loop               -> line 2 (adds value/vs_baseline/mfu
             — the contract keys)
    stage 3  fused-kernel adoption    -> line 3 (adds pallas dispatch
             counts when telemetry is on)

Baseline = 290 samples/s/chip — the 50%-MFU ceiling from BASELINE.md
(6 * 110M params * 512 tokens ~= 338 GFLOPs/sample on a ~197 bf16-TFLOP/s
v5e chip). Runs the fused TrainStep (fwd + masked-LM CE + bwd + AdamW-style
update in one XLA executable) in bfloat16; attention runs the Pallas flash
kernels in both directions, and MXNET_PALLAS_FUSED (default ON here)
routes LayerNorm/residual/dropout and the bias+GELU epilogues through the
fused layer kernels (pallas_kernels/fused_layers.py) on TPU.

Same synthetic-data methodology as bench.py (see PERF_HISTORY.md): the batch is
staged on device before the timed loop. BENCH_BERT_REMAT=("" | full |
dots) threads the TrainStep remat policy for batch-size headroom runs.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_SAMPLES_S = 290.0   # 50%-MFU ceiling, BASELINE.md row 2
FLOPS_PER_SAMPLE = 6 * 110e6 * 512   # ~338 GF: 6ND with N=110M, D=512 tok
MFU_TARGET = 0.55            # ISSUE 7 acceptance bar


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def main():
    # fused layer kernels ON by default for the published configuration;
    # BENCH_BERT_FUSED_LAYERS=0 A/Bs the eager path
    os.environ.setdefault("MXNET_PALLAS_FUSED", "1")
    if os.environ.get("BENCH_BERT_FUSED_LAYERS") == "0":
        os.environ["MXNET_PALLAS_FUSED"] = "0"
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.callback import device_peak_flops
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.nlp import bert

    if jax.devices()[0].platform != "tpu":
        print(f"bench_bert.py needs a TPU; jax.devices() = {jax.devices()}",
              file=sys.stderr)
        return 1
    peak = device_peak_flops()
    if peak is None:
        # an MFU against an unknown peak is not a number
        print("bench_bert.py: no bf16 peak on record for device kind "
              f"{jax.devices()[0].device_kind!r}", file=sys.stderr)
        return 1
    batch = int(os.environ.get("BENCH_BERT_BATCH", 32))
    seq = 512
    steps = 20

    fused = os.environ.get("BENCH_BERT_FUSED", "1") != "0"
    remat = os.environ.get("BENCH_BERT_REMAT") or None
    rs = np.random.RandomState(0)
    tokens = mx.nd.array(rs.randint(0, 30000, (batch, seq)).astype(np.int32))
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])

    record = {
        "metric": "bert_base_seq512_train_samples_per_sec_per_chip",
        "unit": "samples/sec",
        "bert_batch": batch,
        "bert_seq": seq,
        "bert_fused_ce": fused,
        "bert_fused_layers": os.environ["MXNET_PALLAS_FUSED"] == "1",
        "bert_remat": remat,
        "bert_mfu_target": MFU_TARGET,
    }

    if fused:
        # fused projection+CE head: the (B, L, vocab) logits never
        # materialize (ops/fused_loss.py; same params/math as the
        # decoder path, labels ride as a second data input)
        net = bert.BERTForPretrainFused(
            dropout=0.1,
            chunk=int(os.environ.get("BENCH_BERT_CHUNK", 5120)))
        net.initialize()
        net.cast("bfloat16")
        labels = mx.nd.array(
            rs.randint(0, 30000, (batch, seq)).astype(np.int32))
        step = par.TrainStep(
            net, lambda outs, *a: outs, "adam", mesh=mesh, loss_only=True,
            remat=remat,
            optimizer_params={"learning_rate": 1e-4,
                              "multi_precision": True})
        batch_args = ((tokens, labels), ())
    else:
        net = bert.bert_12_768_12(use_decoder=True, use_pooler=False,
                                  use_classifier=False)
        net.initialize()
        net.cast("bfloat16")
        labels = mx.nd.array(
            rs.randint(0, 30000, (batch, seq)).astype(np.float32))

        class MLMLoss(gloss.SoftmaxCrossEntropyLoss):
            def hybrid_forward(self, F, pred, label):
                # pred: (B, L, vocab) MLM logits; CE over every position
                return super().hybrid_forward(
                    F, pred.reshape(-1, pred.shape[-1]), label.reshape(-1))

        class LossAdapter:
            def __init__(self):
                self._l = MLMLoss()

            def __call__(self, outs, label):
                mlm = outs[1] if isinstance(outs, (list, tuple)) else outs
                return self._l(mlm, label)

        step = par.TrainStep(net, LossAdapter(), "adam", mesh=mesh,
                             remat=remat,
                             optimizer_params={"learning_rate": 1e-4,
                                               "multi_precision": True})
        batch_args = (tokens, labels)

    t_compile = time.perf_counter()
    loss, _ = step(*batch_args)
    loss.asnumpy()
    step.stage_batch(*batch_args)
    loss, _ = step(*batch_args)
    loss.asnumpy()
    record["bert_compile_warmup_s"] = round(
        time.perf_counter() - t_compile, 2)
    _emit(record)  # stage 1 complete — config + compile survive a timeout

    t0 = time.perf_counter()
    for _ in range(steps):
        loss, _ = step(*batch_args)
    loss.asnumpy()
    dt = time.perf_counter() - t0

    samples_s = batch * steps / dt
    mfu = samples_s * FLOPS_PER_SAMPLE / peak
    record.update({
        "value": round(samples_s, 2),
        "vs_baseline": round(samples_s / BASELINE_SAMPLES_S, 4),
        "mfu": round(mfu, 4),
        "bert_mfu_vs_target": round(mfu / MFU_TARGET, 4),
    })
    _emit(record)  # stage 2 complete — the contract keys are on stdout

    from mxnet_tpu import telemetry

    if telemetry.enabled():
        fam = telemetry.snapshot()["metrics"].get(
            "mxnet_pallas_dispatch_total")
        record["bert_pallas_dispatch"] = {
            s["labels"]["kernel"]: s["value"]
            for s in (fam["samples"] if fam else ())}
        _emit(record)  # stage 3 — kernel-adoption counters
    return 0


if __name__ == "__main__":
    sys.exit(main())
