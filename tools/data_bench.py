#!/usr/bin/env python
"""Input-pipeline-only benchmark — no accelerator required.

Measures the three legs of the async data path in isolation, so a
pipeline regression is visible without a TPU (or a 30-minute bench.py
run):

1. **decode throughput** — ImageIter JPEG decode + augment, serial vs
   process workers (img/s both ways + speedup);
2. **shm hop latency** — one batch through the dataloader's
   shared-memory transport (`_to_shm` -> `_from_shm_numpy`), ms/batch
   and GB/s;
3. **device-feed overlap** — a synthetic host producer + fake compute
   consumer, serial loop vs `io.DeviceFeedIter`; overlap%% = how much of
   the host time the prefetch hid.

Emits bench.py's JSON contract — one flushed line per completed stage,
monotonically enriched, `{"metric", "value", "unit", "vs_baseline"}`
first — so the same last-line-of-stdout drivers parse it.
`vs_baseline` is against the r05 host-pipeline rate (266.38 img/s, the
number this pipeline exists to beat). Knobs: MXNET_DATA_WORKERS (worker
count, default all cores), DATA_BENCH_IMAGES, DATA_BENCH_BATCH.

Forces JAX_PLATFORMS=cpu (measuring host pipeline mechanics, not a
device), like the tier-1 test environment.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

BASELINE_HOST_IMG_S = 266.38  # host-pipeline rate of the earlier installation (r05)


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _make_rec(img_size: int, n_images: int) -> str:
    import tempfile

    from mxnet_tpu import recordio

    path = os.path.join(tempfile.gettempdir(),
                        f"data_bench_{img_size}_{n_images}.rec")
    if not os.path.exists(path):
        rs = np.random.RandomState(0)
        writer = recordio.MXRecordIO(path, "w")
        for i in range(n_images):
            img = rs.randint(0, 256, (img_size, img_size, 3), np.uint8)
            writer.write(recordio.pack_img(
                recordio.IRHeader(0, float(i % 1000), i, 0), img,
                quality=90))
        writer.close()
    return path


def _decode_stage(rec_path, img_size, batch, n_workers):
    """Stage 1: serial vs process-worker decode throughput."""
    from mxnet_tpu import image as mximg

    def rate(mode, workers):
        it = mximg.ImageIter(
            batch_size=batch, data_shape=(3, img_size, img_size),
            path_imgrec=rec_path, seed=0, dtype="uint8",
            worker_mode=mode, preprocess_threads=workers,
            aug_list=[mximg.CenterCropAug((img_size, img_size)),
                      mximg.HorizontalFlipAug(0.5)])
        try:
            it.next()  # warm (pool spin-up, first-touch buffers)
            n = 0
            t0 = time.perf_counter()
            try:
                while True:
                    b = it.next()
                    n += batch - b.pad
            except StopIteration:
                pass
            return n / (time.perf_counter() - t0)
        finally:
            it.close()

    serial = rate("serial", 1)
    procs = rate("process", n_workers)
    return serial, procs


def _shm_stage(batch, img_size, reps=10):
    """Stage 2: one uint8 batch through the shm transport, round trip.

    Reports the MIN over reps — the transport's latency floor; the mean
    on a busy 2-core container measures allocator/scheduler noise, not
    the hop."""
    from mxnet_tpu.gluon.data.dataloader import _from_shm_numpy, _to_shm

    arr = np.random.RandomState(0).randint(
        0, 256, (batch, 3, img_size, img_size), np.uint8)
    # warm /dev/shm allocation path
    _from_shm_numpy(_to_shm(arr))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = _from_shm_numpy(_to_shm(arr))
        best = min(best, time.perf_counter() - t0)
    assert np.array_equal(out, arr)
    return best * 1e3, arr.nbytes / best / 1e9


def _overlap_stage(n_batches=20, host_ms=20.0, compute_ms=20.0):
    """Stage 3: how much host time DeviceFeedIter hides.

    A producer that takes ``host_ms`` per batch feeding a consumer that
    takes ``compute_ms``: the serial loop costs the sum per batch, the
    pipelined loop max(host, compute) — overlap%% is the fraction of the
    hideable time actually hidden."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import io as mxio

    payload = mx.nd.array(np.zeros((8, 16), np.float32))
    label = mx.nd.array(np.zeros((8,), np.float32))

    class _SleepIter(mxio.DataIter):
        def __init__(self):
            super().__init__(8)
            self.i = 0

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= n_batches:
                raise StopIteration
            self.i += 1
            time.sleep(host_ms / 1e3)
            return mxio.DataBatch(data=[payload], label=[label])

    dev = jax.devices()[0]

    def consume(_b):
        time.sleep(compute_ms / 1e3)

    it = _SleepIter()
    t0 = time.perf_counter()
    try:
        while True:
            b = it.next()
            jax.device_put(b.data[0].data, dev)
            consume(b)
    except StopIteration:
        pass
    serial_s = time.perf_counter() - t0

    feed = mxio.DeviceFeedIter(_SleepIter(), shardings=[dev, dev], depth=2)
    try:
        t0 = time.perf_counter()
        for b in feed:
            consume(b)
        piped_s = time.perf_counter() - t0
    finally:
        feed.close()

    hideable = n_batches * min(host_ms, compute_ms) / 1e3
    overlap = max(0.0, min(1.0, (serial_s - piped_s) / hideable))
    return serial_s, piped_s, overlap * 100.0


def main():
    from mxnet_tpu.telemetry import pop_telemetry_out_flag

    sys.argv[1:], telemetry_out = pop_telemetry_out_flag(sys.argv[1:])
    if telemetry_out:
        from mxnet_tpu import telemetry

        telemetry.enable()

    img_size = 224
    n_images = int(os.environ.get("DATA_BENCH_IMAGES", "512"))
    batch = int(os.environ.get("DATA_BENCH_BATCH", "64"))
    n_workers = int(os.environ.get("MXNET_DATA_WORKERS",
                                   str(os.cpu_count() or 2)))

    rec_path = _make_rec(img_size, n_images)
    serial, procs = _decode_stage(rec_path, img_size, batch, n_workers)
    record = {
        "metric": "data_decode_images_per_sec",
        "value": round(procs, 2),
        "unit": "images/sec",
        "vs_baseline": round(procs / BASELINE_HOST_IMG_S, 4),
        "decode_serial_images_per_sec": round(serial, 2),
        "decode_workers": n_workers,
        "decode_worker_speedup": round(procs / serial, 2),
    }
    _emit(record)

    shm_ms, shm_gbps = _shm_stage(batch, img_size)
    record.update({"shm_hop_ms_per_batch": round(shm_ms, 3),
                   "shm_hop_gbytes_per_sec": round(shm_gbps, 2)})
    _emit(record)

    serial_s, piped_s, overlap = _overlap_stage()
    record.update({"feed_serial_s": round(serial_s, 3),
                   "feed_pipelined_s": round(piped_s, 3),
                   "feed_overlap_pct": round(overlap, 1)})
    _emit(record)

    if telemetry_out:
        from mxnet_tpu import telemetry

        telemetry.write_snapshot(telemetry_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
