"""Benchmark: ResNet-50 training throughput (images/sec/chip) + extras.

Prints a JSON line {"metric", "value", "unit", "vs_baseline", ...extras}
after EVERY completed stage (flushed), monotonically enriched:

    stage 1  ResNet-50 synthetic   -> line 1 (the required contract keys)
    stage 2  eager-vs-bulk chain   -> line 2 (adds bulk_* — dispatch
             microbench of engine.bulk fused segments; cheap, runs first)
    stage 2.5 comms exchange       -> line 3 (adds comms_* — per-key vs
             bucketed vs bucketed+2bit gradient exchange on the
             ResNet-50-scale param set; dispatch counts + loss gate)
    stage 2.6 optimizer sweep      -> adds opt_sweep_* /
             optimizer_dispatches_per_step (fused multi-tensor sweep vs
             per-param updater loop on the same param set)
    stage 3  BERT-base (in-process)-> line 4 (adds bert_*)
    stage 4  Llama proxy (in-proc) -> line 5 (adds llama_proxy_*)
    stage 5  ResNet-50 real-data   -> line 6 (adds real_data_*)

    Stages are ordered by information value (BASELINE.json tracks resnet,
    bert, llama MFU; real-data measures the host pipeline on a 1-core
    container and is the least portable number), so a tight budget truncates
    from the bottom.

A driver that reads the LAST line of stdout always gets the richest
complete record even if it kills the process mid-chain (round 3's
all-or-nothing print lost the whole round to a timeout). Because every completed stage leaves a full valid
line behind, an external timeout can never erase earlier results — so
BENCH_BUDGET_S (default 1800s) only prevents pointless stage starts,
not data loss. A failed stage is recorded as a <stage>_error key AND
makes the exit code non-zero; a run that finds no TPU fails at once.

One process owns the chip: every stage that needs it runs in THIS
process (the BERT and Llama stages call bench_bert.main /
bench_llama.main), and the only children — the cold-start matrix — are
started with JAX_PLATFORMS=cpu.

Baseline = 800 img/s (the reference's headline ResNet-50 fp16 number on
one V100 — BASELINE.md "Upstream MXNet published figures"). Runs the
fused TrainStep (forward+loss+backward+optimizer in one XLA executable)
in bfloat16 on whatever accelerator jax exposes.

Methodology (PERF_HISTORY.md has the full story): synthetic data is staged on the
device once before the timed loop, mirroring the reference's synthetic-data
benchmark mode (`example/image-classification/benchmark_score.py` uses
`mx.io.NDArrayIter` on pre-generated arrays). Input H2D transfer overlap is
the data pipeline's job (io.DeviceFeedIter — stage 5 runs the full async
path: process decode workers -> shm -> async sharded device_put of uint8
-> on-device normalize), not the step's.

Env knobs: BENCH_BUDGET_S (float, default 1800), BENCH_SKIP_REALDATA,
BENCH_SKIP_BERT, BENCH_SKIP_LLAMA, BENCH_SKIP_BULK, BENCH_SKIP_COMMS,
MXNET_KV_BUCKET_MB.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 800.0  # reference ResNet-50 fp16, 1x V100 (BASELINE.md)
# transformer MFU regression bars (ISSUE 7): the next BENCH round gates
# bert_mfu_vs_target / llama_proxy_mfu_vs_target >= 1.0. The target
# constants live in bench_bert.py / bench_llama.py (single source); the
# extras below surface the children's target/ratio keys verbatim.

_T0 = time.perf_counter()


def _budget_s() -> float:
    return float(os.environ.get("BENCH_BUDGET_S", "1800"))


def _remaining_s() -> float:
    return _budget_s() - (time.perf_counter() - _T0)


def _emit(record: dict) -> None:
    """Print the current (enriched) record as one flushed JSON line."""
    print(json.dumps(record), flush=True)


def _write_telemetry(path: "str | None") -> None:
    if not path:
        return
    from mxnet_tpu import telemetry

    telemetry.write_snapshot(path)


def main():
    # --telemetry-out PATH: enable mx.telemetry for the run and write a
    # JSON snapshot after every stage, so a round's record carries
    # op-mix and cache-hit data
    from mxnet_tpu.telemetry import pop_telemetry_out_flag

    sys.argv[1:], telemetry_out = pop_telemetry_out_flag(sys.argv[1:])
    if telemetry_out:
        from mxnet_tpu import telemetry

        telemetry.enable()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench.py needs a TPU; jax.devices() = {jax.devices()}",
              file=sys.stderr)
        return 1
    batch = int(os.environ.get("BENCH_RESNET_BATCH", 256))
    steps = 30

    step = _make_resnet_step(batch)
    x, y = _make_resnet_batch(batch)
    # warmup: compile + first step
    loss, _ = step(x, y)
    loss.asnumpy()
    # stage the synthetic batch on device with the step's input sharding
    step.stage_batch(x, y)
    loss, _ = step(x, y)
    loss.asnumpy()

    t0 = time.perf_counter()
    for _ in range(steps):
        loss, _ = step(x, y)
    loss.asnumpy()  # sync
    dt = time.perf_counter() - t0

    img_s = batch * steps / dt
    record = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(img_s, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 4),
    }
    _emit(record)  # stage 1 complete — contract keys are now on stdout
    # snapshot after every stage, matching the incremental-emit contract:
    # a mid-chain kill still leaves the latest telemetry on disk
    _write_telemetry(telemetry_out)

    def stage(name, fn, min_budget_s=30):
        """Run one stage: its keys join the record, a failure becomes
        ``<name>_error`` (and, at exit, a non-zero code), and the
        enriched record is emitted either way."""
        if _remaining_s() > min_budget_s:
            try:
                record.update(fn())
            except Exception as e:  # noqa: BLE001 - recorded, then rc != 0
                record[name + "_error"] = repr(e)[:200]
        else:
            record[name + "_skipped"] = "budget"
        _emit(record)
        _write_telemetry(telemetry_out)

    stage("bulk", _bulk_extra)
    stage("comms", _comms_extra)
    # stage 2.6: fused multi-tensor optimizer sweep microbench
    # (optimizer-phase dispatch collapse + sweep time)
    stage("opt_sweep", _optimizer_extra)
    # stage 2.7: compilation-service cold start (subprocess matrix —
    # cold / warm-disk / warm-manifest, train + serve; CPU-only children,
    # which never ask for the chip this process holds)
    stage("coldstart", _coldstart_extra, min_budget_s=120)

    # release this process's step/model buffers before the BERT/Llama
    # stages — they share the chip's HBM, and the resident ResNet state
    # otherwise costs them batch-size headroom
    del step, x, y
    import gc

    gc.collect()

    stage("bert", _bert_extra, min_budget_s=60)
    stage("llama", _llama_extra, min_budget_s=60)
    stage("real_data", lambda: _real_data_extra(batch), min_budget_s=60)
    return 1 if any(k.endswith("_error") for k in record) else 0


def _make_resnet_step(batch):
    """Build the bf16 NHWC ResNet-50 TrainStep.

    channels-last internally (NCHW stays at the API edge — the model
    transposes its input once); kills the activation relayouts XLA
    otherwise inserts around every NCHW conv. See PERF_HISTORY.md round 3.
    """
    import jax
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.resnet50_v1(layout="NHWC")
    net.initialize()
    net.cast("bfloat16")
    mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    return par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                         mesh=mesh,
                         optimizer_params={"learning_rate": 0.1,
                                           "momentum": 0.9,
                                           "multi_precision": True})


def _make_resnet_batch(batch):
    import mxnet_tpu as mx

    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.randn(batch, 3, 224, 224).astype(np.float32)) \
        .astype("bfloat16")
    y = mx.nd.array(rs.randint(0, 1000, (batch,)).astype(np.float32))
    return x, y


def _bulk_extra(chain_len=64, reps=10):
    """Eager-vs-bulk op-chain microbench (engine.bulk fused segments).

    The number the bulking work exists to move: per-op host dispatch time
    of an imperative elementwise chain, eager (one single-op jit dispatch
    per op) vs inside ``engine.bulk`` (whole chain = ONE fused XLA
    dispatch). Also reports the XLA-dispatch reduction and the
    fused-segment cache hit rate over the timed reps — steady state
    should be all hits (CachedOp-style signature reuse). Opt out with
    BENCH_SKIP_BULK=1.
    """
    if os.environ.get("BENCH_SKIP_BULK"):
        return {}
    import mxnet_tpu as mx
    from mxnet_tpu import engine, telemetry

    n = chain_len
    x = mx.nd.array(
        np.random.RandomState(0).rand(256, 256).astype(np.float32))

    def chain(v):
        for _ in range(n // 2):
            v = v * 1.01 + 0.01  # n//2 muls + n//2 adds = n ops
        return v

    def dispatches():
        fam = telemetry.snapshot()["metrics"].get(
            "mxnet_xla_dispatch_total")
        return sum(s["value"] for s in fam["samples"]) if fam else 0.0

    def fused_cache():
        fam = telemetry.snapshot()["metrics"].get("mxnet_jit_cache_total")
        hits = misses = 0.0
        for s in (fam["samples"] if fam else ()):
            if s["labels"].get("cache") == "fused_segment":
                if s["labels"].get("result") == "hit":
                    hits = s["value"]
                else:
                    misses = s["value"]
        return hits, misses

    # counters are read as before/after deltas so a --telemetry-out run's
    # accumulated registry is never reset mid-chain
    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        # warm both paths (per-op jit cache / fused-segment compile)
        chain(x).wait_to_read()
        with engine.bulk(n):
            out_w = chain(x)
        out_w.wait_to_read()

        d0 = dispatches()
        t0 = time.perf_counter()
        for _ in range(reps):
            out_e = chain(x)
        out_e.wait_to_read()
        eager_s = time.perf_counter() - t0
        eager_disp = dispatches() - d0

        h0, m0 = fused_cache()
        d0 = dispatches()
        t0 = time.perf_counter()
        for _ in range(reps):
            with engine.bulk(n):
                out_b = chain(x)
            out_b.wait_to_read()
        bulk_s = time.perf_counter() - t0
        bulk_disp = dispatches() - d0
        h1, m1 = fused_cache()
    finally:
        if not was_enabled:
            telemetry.disable()

    total_ops = n * reps
    hit, mis = h1 - h0, m1 - m0
    return {
        "bulk_chain_ops": n,
        "bulk_eager_dispatch_us_per_op": round(eager_s / total_ops * 1e6, 2),
        "bulk_fused_dispatch_us_per_op": round(bulk_s / total_ops * 1e6, 2),
        "bulk_speedup_vs_eager": round(eager_s / bulk_s, 3),
        "bulk_xla_dispatch_reduction": round(eager_disp / max(bulk_disp, 1.0), 1),
        "bulk_fused_cache_hit_rate": round(hit / max(hit + mis, 1.0), 4),
        # rtol 1e-5: XLA contracts mul+add to FMA inside the fused module
        # (one rounding instead of two) — same class of difference as any
        # jit-vs-op-by-op comparison
        "bulk_allclose_eager": bool(np.allclose(out_b.asnumpy(),
                                                out_e.asnumpy(), rtol=1e-5)),
    }


def _resnet50_param_shapes():
    """The comms/optimizer microbench param set, loaded once from
    tools/comms_bench.py (import is side-effect free)."""
    global _RESNET_SHAPES
    if _RESNET_SHAPES is None:
        import importlib.util as ilu

        spec = ilu.spec_from_file_location(
            "comms_bench", os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools",
                "comms_bench.py"))
        cb = ilu.module_from_spec(spec)
        spec.loader.exec_module(cb)
        _RESNET_SHAPES = cb.resnet50_param_shapes()
    return _RESNET_SHAPES


_RESNET_SHAPES = None


def _comms_extra(copies=2, reps=3):
    """Gradient-exchange microbench (stage 2.5): per-key vs bucketed vs
    bucketed+2bit on the ResNet-50-scale parameter set (ISSUE 5).

    The per-key path reduces each of the 161 parameters with its own
    dispatch (the reference KVStore shape); the bucketed fused
    ``pushpull`` coalesces them into ~25 MB flat buckets — one reduce
    per bucket. Reports the collective-dispatch reduction (from the
    telemetry counters), wall time per exchange for the three variants,
    and the trainer-level loss bit-identity gate (bucketed uncompressed
    must match per-key BIT-exactly). Single-chip note: with one device
    the 'collective' is the store's fused aggregation — the dispatch
    counts and the tax they model are the same, only the wire is
    missing. ``tools/comms_bench.py`` runs the identical measurement
    over a real multi-device psum mesh on the CPU oracle. Opt out with
    BENCH_SKIP_COMMS=1.
    """
    if os.environ.get("BENCH_SKIP_COMMS"):
        return {}
    import mxnet_tpu as mx
    from mxnet_tpu import kvstore as kvmod, telemetry
    from mxnet_tpu.kvstore import bucket_cap_bytes

    shapes = _resnet50_param_shapes()
    cap = bucket_cap_bytes()

    def collectives():
        fam = telemetry.snapshot()["metrics"].get(
            "mxnet_kvstore_collective_dispatch_total")
        return sum(s["value"] for s in (fam["samples"] if fam else ()))

    def run_variant(bucket_bytes, compression=None):
        store = kvmod.create("device")
        store._bucket_bytes = bucket_bytes
        if compression is not None:
            store.set_gradient_compression(compression)
        rs = np.random.RandomState(0)
        keys = list(range(len(shapes)))
        vals, outs = [], []
        for sh in shapes:
            g = mx.nd.array(rs.randn(*sh).astype(np.float32))
            vals.append([g, g * 1.5])          # two copies, one device
            outs.append([mx.nd.zeros(sh), mx.nd.zeros(sh)])
        for k, sh in zip(keys, shapes):
            store.init(k, mx.nd.zeros(sh))
        pr = [-k for k in keys]

        def exchange():
            store.pushpull(keys, vals, out=outs, priority=pr)
            mx.nd.waitall()

        exchange()                              # warm compiles
        c0 = collectives()
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            exchange()
            times.append(time.perf_counter() - t0)
        per_step = (collectives() - c0) / reps
        times.sort()
        return per_step, times[len(times) // 2] * 1e3

    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        perkey_n, perkey_ms = run_variant(0)
        bucket_n, bucket_ms = run_variant(cap)
        _, bucket2bit_ms = run_variant(
            cap, compression={"type": "2bit", "threshold": 0.5})
    finally:
        if not was_enabled:
            telemetry.disable()
    identical = _comms_loss_bit_identity()
    return {
        "comms_params": len(shapes),
        "comms_bucket_mb": round(cap / (1 << 20), 3),
        "comms_perkey_collectives_per_step": round(perkey_n, 1),
        "comms_bucketed_collectives_per_step": round(bucket_n, 1),
        "comms_dispatch_reduction": round(
            perkey_n / max(bucket_n, 1.0), 1),
        "comms_perkey_ms_per_step": round(perkey_ms, 2),
        "comms_bucketed_ms_per_step": round(bucket_ms, 2),
        "comms_bucketed_2bit_ms_per_step": round(bucket2bit_ms, 2),
        "comms_bucketed_loss_bit_identical": bool(identical),
    }


def _comms_loss_bit_identity(steps=4):
    """Trainer-level gate on THIS device: a small net trained through
    kvstore='tpu_sync' with the per-key path (MXNET_KV_BUCKET_MB=0) and
    the bucketed path must produce bit-identical losses and weights."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss

    def run(bucket_mb):
        prev = os.environ.get("MXNET_KV_BUCKET_MB")
        os.environ["MXNET_KV_BUCKET_MB"] = str(bucket_mb)
        try:
            mx.random.seed(0)
            net = nn.Dense(16, in_units=32)
            net.initialize()
            rs = np.random.RandomState(7)
            net.weight.set_data(mx.nd.array(
                rs.randn(16, 32).astype(np.float32)))
            net.bias.set_data(mx.nd.zeros(16))
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.05},
                               kvstore="tpu_sync")
            loss_fn = L2Loss()
            rs2 = np.random.RandomState(11)
            x = mx.nd.array(rs2.randn(8, 32).astype(np.float32))
            y = mx.nd.array(rs2.randn(8, 16).astype(np.float32))
            losses = []
            for _ in range(steps):
                with autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
                tr.step(8)
                losses.append(float(loss.asnumpy().sum()))
            return losses, net.weight.data().asnumpy()
        finally:
            # restore, don't erase: MXNET_KV_BUCKET_MB is a documented
            # bench knob and later stages/subprocesses must see it
            if prev is None:
                os.environ.pop("MXNET_KV_BUCKET_MB", None)
            else:
                os.environ["MXNET_KV_BUCKET_MB"] = prev

    losses_pk, w_pk = run(0)
    losses_bk, w_bk = run(25)
    return losses_pk == losses_bk and bool(np.array_equal(w_pk, w_bk))


def _optimizer_extra(reps=3):
    """Optimizer-sweep microbench (stage 2.6): the eager optimizer phase
    on the ResNet-50-scale parameter set, per-param updater loop vs the
    horizontally-fused multi-tensor sweep (ISSUE 11).

    Reports ``optimizer_dispatches_per_step`` for both paths (from the
    ``mxnet_optimizer_dispatch_total`` counters — the O(params) ->
    O(dtype buckets) collapse is the number this engine exists to move),
    median wall time per optimizer phase, and the bit-identity gate
    (fused Adam must match the per-param reference EXACTLY). Opt out
    with BENCH_SKIP_OPTSWEEP=1.
    """
    if os.environ.get("BENCH_SKIP_OPTSWEEP"):
        return {}
    import mxnet_tpu as mx
    from mxnet_tpu import optimizer as opt_mod, telemetry
    from mxnet_tpu.optimizer import multi_tensor as mt

    shapes = _resnet50_param_shapes()
    rs = np.random.RandomState(0)
    host_w = [rs.randn(*s).astype(np.float32) for s in shapes]
    host_g = [rs.randn(*s).astype(np.float32) for s in shapes]

    def dispatches():
        fam = telemetry.snapshot()["metrics"].get(
            "mxnet_optimizer_dispatch_total")
        return {s["labels"]["path"]: s["value"]
                for s in (fam["samples"] if fam else ())}

    def run_path(fused):
        prev = os.environ.get("MXNET_FUSED_OPTIMIZER")
        os.environ["MXNET_FUSED_OPTIMIZER"] = "1" if fused else "0"
        try:
            o = opt_mod.create("adam", learning_rate=1e-3)
            o.rescale_grad = 1.0 / 256
            upd = opt_mod.get_updater(o)
            ws = [mx.nd.array(w) for w in host_w]
            gs = [mx.nd.array(g) for g in host_g]
            items = [(i, w, g) for i, (w, g) in enumerate(zip(ws, gs))]

            def sweep():
                if fused:
                    assert mt.eager_fused_update(o, upd, items)
                else:
                    for i, w, g in items:
                        telemetry.record_optimizer_dispatch("per_param")
                        upd(i, g, w)
                mx.nd.waitall()

            sweep()                      # warm: states + compiles
            d0 = dispatches()
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                sweep()
                times.append(time.perf_counter() - t0)
            d1 = dispatches()
            per_step = sum(d1.values()) - sum(d0.values())
            times.sort()
            return (per_step / reps, times[len(times) // 2] * 1e3,
                    [w.asnumpy() for w in ws])
        finally:
            if prev is None:
                os.environ.pop("MXNET_FUSED_OPTIMIZER", None)
            else:
                os.environ["MXNET_FUSED_OPTIMIZER"] = prev

    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        fused_n, fused_ms, fused_w = run_path(True)
        perparam_n, perparam_ms, perparam_w = run_path(False)
    finally:
        if not was_enabled:
            telemetry.disable()
    identical = all(np.array_equal(a, b)
                    for a, b in zip(fused_w, perparam_w))
    return {
        "opt_sweep_params": len(shapes),
        "optimizer_dispatches_per_step": round(fused_n, 1),
        "optimizer_dispatches_per_step_unfused": round(perparam_n, 1),
        "opt_sweep_dispatch_reduction": round(
            perparam_n / max(fused_n, 1.0), 1),
        "opt_sweep_fused_ms_per_step": round(fused_ms, 2),
        "opt_sweep_perparam_ms_per_step": round(perparam_ms, 2),
        "opt_sweep_speedup": round(perparam_ms / max(fused_ms, 1e-9), 2),
        "opt_sweep_bit_identical": bool(identical),
    }


def _real_data_extra(batch, steps=10, img_size=224, n_images=2048):
    """Real-data mode (VERDICT round-2 #5, round-4 #3): the same fused
    TrainStep fed by the full async input pipeline (PERF_HISTORY.md round 7) —
    JPEG recordio on disk -> ImageIter with PROCESS decode workers
    (decode + crop + mirror on uint8, shm transport) ->
    io.DeviceFeedIter (async sharded device_put of quarter-size uint8
    batches, normalize+bf16 cast ON DEVICE) -> pre-sharded no-op step
    entry.

    Methodology unchanged from round 5: THREE timed windows, median with
    spread, plus the host-only producer rate and the device-only step
    rate (busy%% = median / device-only). New: the bit-identity key —
    one serial-decoded batch must equal the process-decoded batch under
    the same seed (the acceptance contract for moving decode off-process).
    Opt out with BENCH_SKIP_REALDATA=1; MXNET_DATA_WORKERS overrides the
    decode worker count (default: all cores).
    """
    import tempfile

    if os.environ.get("BENCH_SKIP_REALDATA"):
        return {}
    from mxnet_tpu import image as mximg, io as mxio, recordio

    n_workers = int(os.environ.get(
        "MXNET_DATA_WORKERS",
        os.environ.get("BENCH_REALDATA_THREADS", str(os.cpu_count() or 2))))

    rec_path = os.path.join(tempfile.gettempdir(),
                            f"bench_imgs_{img_size}_{n_images}.rec")
    if not os.path.exists(rec_path):
        # synthetic JPEGs, written once through the real recordio writer
        rs = np.random.RandomState(0)
        writer = recordio.MXRecordIO(rec_path, "w")
        for i in range(n_images):
            img = rs.randint(0, 256, (img_size, img_size, 3), np.uint8)
            header = recordio.IRHeader(0, float(i % 1000), i, 0)
            writer.write(recordio.pack_img(header, img, quality=90))
        writer.close()

    # host augmenters stay on uint8 (crop + mirror); normalization moved
    # onto the device so the wire carries 1/4 the bytes of the old f32
    # host-normalized batch
    def make_iter(mode, workers):
        return mximg.ImageIter(
            batch_size=batch, data_shape=(3, img_size, img_size),
            path_imgrec=rec_path, seed=0, dtype="uint8",
            worker_mode=mode, preprocess_threads=workers,
            aug_list=[mximg.CenterCropAug((img_size, img_size)),
                      mximg.HorizontalFlipAug(0.5)])

    # bit-identity gate: same seed, serial vs process workers
    it_a, it_b = make_iter("serial", 1), make_iter("process", n_workers)
    ba, bb = it_a.next(), it_b.next()
    identical = bool(
        np.array_equal(ba.data[0].asnumpy(), bb.data[0].asnumpy())
        and np.array_equal(ba.label[0].asnumpy(), bb.label[0].asnumpy()))
    it_a.close()
    it_b.close()

    step = _make_resnet_step(batch)
    it = make_iter("process", n_workers)
    feed = mxio.DeviceFeedIter(
        it, step=step, depth=2,
        device_transform=mxio.make_normalize_transform(
            [123.68, 116.78, 103.94], [58.4, 57.1, 57.4], "bfloat16"),
        name="bench_real_data")

    def next_batch():
        try:
            b = next(feed)
        except StopIteration:
            feed.reset()
            b = next(feed)
        return b.data[0], b.label[0]

    try:
        # warm (decoders + step compile on the fed shapes)
        x, y = next_batch()
        loss, _ = step(x, y)
        loss.asnumpy()

        # reference 1: device-only step rate on a staged batch
        step.stage_batch(x, y)
        loss, _ = step(x, y)
        loss.asnumpy()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, _ = step(x, y)
        loss.asnumpy()
        dev_img_s = batch * steps / (time.perf_counter() - t0)

        # reference 2: host-side producer rate (decode + async device
        # dispatch, no step). Drain the prefetch queue first — it filled
        # while the device-only loop ran with nobody consuming, and
        # pre-buffered batches would inflate the producer-bound rate
        for _ in range(3):
            next_batch()
        t0 = time.perf_counter()
        for _ in range(steps):
            next_batch()
        host_img_s = batch * steps / (time.perf_counter() - t0)

        # three measured windows of the full pipeline+train loop
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                xb, yb = next_batch()
                loss, _ = step(xb, yb)
            loss.asnumpy()
            rates.append(batch * steps / (time.perf_counter() - t0))
    finally:
        feed.close()  # closes the ImageIter decode pool through it
    rates.sort()
    med = rates[1]
    return {
        "real_data_images_per_sec_per_chip": round(med, 2),
        "real_data_window_min_max": [round(rates[0], 2),
                                     round(rates[2], 2)],
        "real_data_host_pipeline_images_per_sec": round(host_img_s, 2),
        "real_data_device_only_images_per_sec": round(dev_img_s, 2),
        # fraction of each real-data step the device is actually busy
        "real_data_device_busy_pct": round(100.0 * med / dev_img_s, 1),
        "real_data_preprocess_threads": n_workers,
        "real_data_pipeline": "process-workers+uint8-shm+device-feed",
        "real_data_worker_batches_bit_identical": identical,
    }


def _coldstart_extra():
    """Stage 2.7: cold-start-to-first-step / first-response, cold vs
    warm disk cache vs warm + signature manifest (ROADMAP item 5's
    acceptance metric; tools/coldstart_bench.py). The only stage that
    starts children; they are CPU-only by their environment, because
    this process holds the chip."""
    if os.environ.get("BENCH_SKIP_COLDSTART"):
        return {}
    import subprocess

    cap = float(os.environ.get("BENCH_COLDSTART_TIMEOUT_S", "600"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("COLDSTART_PLATFORM", None)
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "coldstart_bench.py")],
        capture_output=True, text=True, env=env, check=True,
        timeout=min(cap, max(_remaining_s(), 60)))
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    return {k: v for k, v in rec.items() if k.startswith("coldstart_")}


def _run_inprocess(main_fn):
    """Run a sibling bench script's ``main()`` in THIS process (one
    process owns the chip) and return its last-stdout-line JSON record.
    The scripts switch ``MXNET_PALLAS_FUSED`` on for themselves; the
    knob is put back so later stages trace what they always traced."""
    import contextlib
    import io

    fused = os.environ.get("MXNET_PALLAS_FUSED")
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            rc = main_fn()
    finally:
        if fused is None:
            os.environ.pop("MXNET_PALLAS_FUSED", None)
        else:
            os.environ["MXNET_PALLAS_FUSED"] = fused
    if rc:
        raise RuntimeError(f"{main_fn.__module__}.main() returned {rc}")
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _bert_extra():
    """Secondary headline: BERT-base seq-512 training (bench_bert.py)."""
    if os.environ.get("BENCH_SKIP_BERT"):
        return {}
    import bench_bert

    rec = _run_inprocess(bench_bert.main)
    return {
        "bert_samples_per_sec_per_chip": rec["value"],
        "bert_vs_baseline": rec["vs_baseline"],
        # the child script is the single source of the target constant
        # and the vs-target ratio — no duplicate to drift
        "bert_mfu": rec["mfu"],
        "bert_mfu_target": rec["bert_mfu_target"],
        "bert_mfu_vs_target": rec["bert_mfu_vs_target"],
    }


def _llama_extra():
    """Third headline: Llama pretrain proxy (bench_llama.py)."""
    if os.environ.get("BENCH_SKIP_LLAMA"):
        return {}
    import bench_llama

    rec = _run_inprocess(bench_llama.main)
    return {
        "llama_proxy_tokens_per_sec_per_chip": rec["value"],
        "llama_proxy_params": rec["params"],
        "llama_proxy_mfu": rec["mfu"],
        "llama_proxy_mfu_target": rec["llama_mfu_target"],
        "llama_proxy_mfu_vs_target": rec["llama_mfu_vs_target"],
    }


if __name__ == "__main__":
    sys.exit(main())
