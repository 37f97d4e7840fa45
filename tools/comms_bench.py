#!/usr/bin/env python
"""Comms-path benchmark — no accelerator required.

Measures the gradient-exchange path in isolation on the virtual CPU
mesh, so a comms regression (or the bucketing win) is visible without a
TPU (or a 30-minute bench.py run):

1. **collective dispatches per step** — the ResNet-50-scale parameter
   set (161 tensors, ~25.5M params) exchanged through kvstore
   ``tpu_sync``: per-key push/pull (one compiled psum per parameter,
   the reference KVStore shape) vs the fused bucketed ``pushpull``
   (one psum per ~``MXNET_KV_BUCKET_MB`` bucket). The headline metric
   is the dispatch reduction — O(params) -> O(params·bytes / cap).
2. **exchange wall time** — median over reps of the full exchange
   (pack + reduce + scatter, synced), per-key vs bucketed vs
   bucketed + 2-bit compression.
3. **training-loss bit-identity** — a small data-parallel Trainer run
   twice (per-key vs bucketed store): losses and final weights must be
   BIT-identical, the acceptance gate for switching the trainer to the
   fused path.
4. **allreduce-under-backward overlap** — the same trainer with
   ``overlap_comms=True`` (grad-ready hooks dispatch each bucket's
   pushpull INSIDE ``autograd.backward``): reports the % of bucket
   collectives issued before backward() returned (the overlap win —
   their device work runs under the remaining reverse sweep via JAX
   async dispatch) and gates the overlapped run's losses/weights
   bit-identical to the per-key exchange.
5. **ZeRO-sharded optimizer state** — the same trainer under
   ``partition="zero1"`` / ``"zero2"`` (reduce-scatter + shard-local
   sweep + allgather instead of allreduce + replicated sweep): gates
   losses/weights bit-identical to the replicated fused path and
   reports the per-rank optimizer-state bytes against the replicated
   total (the ~1/world memory win) plus the fused ``zero`` collective
   dispatch count.

Emits bench.py's JSON contract — one flushed line per completed stage,
monotonically enriched, ``{"metric", "value", "unit", "vs_baseline"}``
first — so the same last-line-of-stdout drivers parse it.
``vs_baseline`` is the measured dispatch reduction against the 10x
acceptance bar (ISSUE 5): >= 1.0 passes. Knobs: COMMS_BENCH_COPIES
(gradient copies per key, default 2), COMMS_BENCH_REPS (timed reps,
default 3), COMMS_BENCH_SCALE (``resnet50`` | ``tiny``),
MXNET_KV_BUCKET_MB (bucket cap, default 25).

Forces JAX_PLATFORMS=cpu + an 8-device virtual host mesh when run as a
script (measuring exchange mechanics, not a device), like the tier-1
test environment. Importing the module has no side effects (bench.py
borrows :func:`resnet50_param_shapes`).
"""
from __future__ import annotations

import json
import os
import sys
import time

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

import numpy as np

DISPATCH_REDUCTION_BAR = 10.0   # ISSUE 5 acceptance: >= 10x fewer


def resnet50_param_shapes():
    """The 161 trainable-parameter shapes of ResNet-50 v1 (conv weights,
    BN gamma/beta, fc) — ~25.5M params, the ISSUE's 'ResNet-50-scale
    param set'. Generated, not read from the model zoo: this tool must
    not pay a model build + shape inference to know the layout."""
    shapes = [(64, 3, 7, 7), (64,), (64,)]
    in_c = 64
    for n_blocks, width in zip((3, 4, 6, 3), (64, 128, 256, 512)):
        for b in range(n_blocks):
            shapes += [(width, in_c, 1, 1), (width,), (width,),
                       (width, width, 3, 3), (width,), (width,),
                       (width * 4, width, 1, 1), (width * 4,),
                       (width * 4,)]
            if b == 0:
                shapes += [(width * 4, in_c, 1, 1), (width * 4,),
                           (width * 4,)]
            in_c = width * 4
    shapes += [(1000, 2048), (1000,)]
    return shapes


def tiny_param_shapes():
    """Small stand-in set for smoke tests (same code path, <1 MB)."""
    return [(64, 32), (64,), (32, 16, 3, 3), (32,), (128, 64), (128,),
            (8, 8), (2000,)]


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _make_store(copies, bucket_bytes, compression=None):
    import mxnet_tpu as mx
    from mxnet_tpu import kvstore as kv

    store = kv.create("tpu_sync")
    store._bucket_bytes = bucket_bytes
    if compression is not None:
        store.set_gradient_compression(compression)
    return store


def _make_grads(shapes, copies):
    import mxnet_tpu as mx

    rs = np.random.RandomState(0)
    vals, outs = [], []
    for sh in shapes:
        g = rs.randn(*sh).astype(np.float32)
        vals.append([mx.nd.array(g).as_in_context(mx.Context("cpu", c))
                     for c in range(copies)])
        outs.append([mx.nd.zeros(sh, ctx=mx.Context("cpu", c))
                     for c in range(copies)])
    return vals, outs


def _collective_counts():
    from mxnet_tpu import telemetry

    fam = telemetry.snapshot()["metrics"].get(
        "mxnet_kvstore_collective_dispatch_total")
    out = {"per_key": 0.0, "bucketed": 0.0, "hierarchical": 0.0,
           "zero": 0.0}
    for s in (fam["samples"] if fam else ()):
        out[s["labels"]["path"]] = s["value"]
    return out


def _gauge_value(name, **labels):
    from mxnet_tpu import telemetry

    fam = telemetry.snapshot()["metrics"].get(name)
    for s in (fam["samples"] if fam else ()):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["value"]
    return 0.0


def _exchange(store, keys, vals, outs, priorities):
    import mxnet_tpu as mx

    store.pushpull(keys, vals, out=outs, priority=priorities)
    mx.nd.waitall()


def _run_variant(shapes, copies, bucket_bytes, reps, compression=None):
    """Returns (collectives_per_step, median_ms) for one exchange
    configuration."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    store = _make_store(copies, bucket_bytes, compression)
    vals, outs = _make_grads(shapes, copies)
    keys = list(range(len(shapes)))
    priorities = [-k for k in keys]
    for k, sh in zip(keys, shapes):
        store.init(k, mx.nd.zeros(sh))
    was = telemetry.enabled()
    telemetry.enable()
    try:
        _exchange(store, keys, vals, outs, priorities)   # warm compiles
        c0 = _collective_counts()
        t_all = []
        for _ in range(reps):
            t0 = time.perf_counter()
            _exchange(store, keys, vals, outs, priorities)
            t_all.append(time.perf_counter() - t0)
        c1 = _collective_counts()
    finally:
        if not was:
            telemetry.disable()
    per_step = sum(c1.values()) - sum(c0.values())
    t_all.sort()
    return per_step / reps, t_all[len(t_all) // 2] * 1e3


def _trainer_run(bucket_mb, steps=4, overlap=False, n_dense=1,
                 partition=None, opt_args=None, opt_name="sgd"):
    """Small 2-context data-parallel Trainer run; returns (per-step
    losses, final weights sorted by param name, per-step overlap stats).
    ``bucket_mb`` configures the store's fused-pushpull cap for the run
    (0 = per-key); ``n_dense`` > 1 stacks layers so a tiny cap yields
    several buckets (the overlap stage needs a multi-bucket plan);
    ``partition`` engages the ZeRO-sharded optimizer sweep."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss

    prev = os.environ.get("MXNET_KV_BUCKET_MB")
    os.environ["MXNET_KV_BUCKET_MB"] = str(bucket_mb)
    try:
        mx.random.seed(0)
        if n_dense == 1:
            net = nn.Dense(16, in_units=32)
        else:
            net = nn.HybridSequential()
            with net.name_scope():
                for _ in range(n_dense - 1):
                    net.add(nn.Dense(64, in_units=32 if len(net) == 0
                                     else 64))
                net.add(nn.Dense(16))
        net.initialize()
        net(mx.nd.zeros((1, 32)))
        rs = np.random.RandomState(7)
        # definition order, NOT sorted-by-name: the auto-prefix counters
        # advance across runs in one process, and "dense10_" would sort
        # before "dense9_" — the seeded init must land identically
        for p in net.collect_params().values():
            p.set_data(mx.nd.array(
                rs.randn(*p.shape).astype(np.float32) * 0.1))
        ctxs = [mx.Context("cpu", 0), mx.Context("cpu", 1)]
        net.collect_params().reset_ctx(ctxs)
        tr = gluon.Trainer(net.collect_params(), opt_name,
                           dict(opt_args) if opt_args is not None
                           else {"learning_rate": 0.05},
                           kvstore="tpu_sync", overlap_comms=overlap,
                           partition=partition)
        loss_fn = L2Loss()
        rs2 = np.random.RandomState(11)
        x = rs2.randn(8, 32).astype(np.float32)
        y = rs2.randn(8, 16).astype(np.float32)
        losses, stats = [], []
        for _ in range(steps):
            with autograd.record():
                ls = [loss_fn(net(mx.nd.array(x[i * 4:(i + 1) * 4],
                                              ctx=c)),
                              mx.nd.array(y[i * 4:(i + 1) * 4],
                                          ctx=c))
                      for i, c in enumerate(ctxs)]
            autograd.backward(ls)
            tr.step(8)
            if tr.last_overlap_stats is not None:
                stats.append(dict(tr.last_overlap_stats))
            losses.append(float(sum(l.asnumpy().sum() for l in ls)))
        weights = [p.data(ctxs[0]).asnumpy()
                   for p in net.collect_params().values()]
        return losses, weights, stats
    finally:
        if prev is None:
            os.environ.pop("MXNET_KV_BUCKET_MB", None)
        else:
            os.environ["MXNET_KV_BUCKET_MB"] = prev


def _loss_bit_identity(steps=4):
    """Per-key vs bucketed store: per-step losses and the final weight
    must be bit-identical."""
    losses_pk, w_pk, _ = _trainer_run(0, steps)
    losses_bk, w_bk, _ = _trainer_run(25, steps)
    identical = losses_pk == losses_bk and all(
        np.array_equal(a, b) for a, b in zip(w_pk, w_bk))
    return identical, losses_bk[-1]


def _overlap_metrics(steps=5):
    """Backward-overlapped comms: % of bucket collectives dispatched
    inside backward() (steady state — step 1 arms the hooks during
    kvstore init, so it is excluded) plus bit-identity of the overlapped
    run against the per-key exchange."""
    losses_pk, w_pk, _ = _trainer_run(0, steps, n_dense=3)
    # ~0.01 MB cap over the 3-layer param set -> a multi-bucket plan
    losses_ov, w_ov, stats = _trainer_run(0.01, steps, overlap=True,
                                          n_dense=3)
    identical = losses_pk == losses_ov and all(
        np.array_equal(a, b) for a, b in zip(w_pk, w_ov))
    steady = stats[1:] if len(stats) > 1 else stats
    total = sum(s["groups"] for s in steady)
    in_bwd = sum(s["dispatched_in_backward"] for s in steady)
    pct = 100.0 * in_bwd / total if total else 0.0
    groups = steady[-1]["groups"] if steady else 0
    return pct, groups, identical


def _zero_metrics(steps=4):
    """ZeRO-sharded sweep vs the replicated fused path: bit-identity
    over zero1 AND zero2 under adam — deliberately t-DEPENDENT, so the
    gate also covers the per-device update-count streams that keep the
    replicated path's bias-correction clock at one tick per step per
    replica — per-rank vs replicated optimizer-state bytes off the
    gauge pair, and the fused ``zero`` collective dispatch count."""
    from mxnet_tpu import telemetry

    opt = {"learning_rate": 0.01, "wd": 0.01}
    opt_name = "adam"
    losses_rep, w_rep, _ = _trainer_run(25, steps, n_dense=3,
                                        opt_args=opt, opt_name=opt_name)
    was = telemetry.enabled()
    telemetry.enable()
    try:
        c0 = _collective_counts()["zero"]
        losses_z1, w_z1, _ = _trainer_run(25, steps, n_dense=3,
                                          opt_args=opt, opt_name=opt_name,
                                          partition="zero1")
        zero_dispatches = _collective_counts()["zero"] - c0
        per_rank = _gauge_value("mxnet_optimizer_state_bytes",
                                mode="zero1")
        replicated = _gauge_value("mxnet_optimizer_state_bytes",
                                  mode="replicated")
    finally:
        if not was:
            telemetry.disable()
    losses_z2, w_z2, _ = _trainer_run(25, steps, n_dense=3,
                                      opt_args=opt, opt_name=opt_name,
                                      partition="zero2")
    identical = (losses_rep == losses_z1 == losses_z2
                 and all(np.array_equal(a, b)
                         for a, b in zip(w_rep, w_z1))
                 and all(np.array_equal(a, b)
                         for a, b in zip(w_rep, w_z2)))
    return {
        "zero_loss_bit_identical": bool(identical),
        "zero_state_bytes_per_rank": int(per_rank),
        "zero_state_bytes_replicated": int(replicated),
        "zero_state_ratio": round(per_rank / max(replicated, 1.0), 4),
        "zero_collectives_per_step": round(zero_dispatches / steps, 1),
    }


def main():
    from mxnet_tpu.telemetry import pop_telemetry_out_flag

    sys.argv[1:], telemetry_out = pop_telemetry_out_flag(sys.argv[1:])
    if telemetry_out:
        from mxnet_tpu import telemetry

        telemetry.enable()

    scale = os.environ.get("COMMS_BENCH_SCALE", "resnet50")
    shapes = tiny_param_shapes() if scale == "tiny" \
        else resnet50_param_shapes()
    copies = int(os.environ.get("COMMS_BENCH_COPIES", "2"))
    reps = int(os.environ.get("COMMS_BENCH_REPS", "3"))
    from mxnet_tpu.kvstore import bucket_cap_bytes

    cap = bucket_cap_bytes()
    total_bytes = sum(4 * int(np.prod(s)) for s in shapes)

    # stage 1+2 share the variant runs (the dispatch counters come from
    # the same timed exchanges)
    perkey_n, perkey_ms = _run_variant(shapes, copies, 0, reps)
    bucket_n, bucket_ms = _run_variant(shapes, copies, cap, reps)
    reduction = perkey_n / max(bucket_n, 1.0)
    record = {
        "metric": "comms_collective_dispatch_reduction",
        "value": round(reduction, 1),
        "unit": "x",
        "vs_baseline": round(reduction / DISPATCH_REDUCTION_BAR, 4),
        "comms_params": len(shapes),
        "comms_param_mb": round(total_bytes / (1 << 20), 1),
        "comms_copies": copies,
        "comms_bucket_mb": round(cap / (1 << 20), 3),
        "comms_perkey_collectives_per_step": round(perkey_n, 1),
        "comms_bucketed_collectives_per_step": round(bucket_n, 1),
    }
    _emit(record)

    _, bucket2bit_ms = _run_variant(
        shapes, copies, cap, reps,
        compression={"type": "2bit", "threshold": 0.5})
    record.update({
        "comms_perkey_ms_per_step": round(perkey_ms, 2),
        "comms_bucketed_ms_per_step": round(bucket_ms, 2),
        "comms_bucketed_2bit_ms_per_step": round(bucket2bit_ms, 2),
        "comms_bucketed_speedup_vs_perkey": round(
            perkey_ms / max(bucket_ms, 1e-9), 2),
    })
    _emit(record)

    identical, last_loss = _loss_bit_identity()
    record.update({
        "comms_bucketed_loss_bit_identical": bool(identical),
        "comms_trainer_last_loss": round(last_loss, 6),
    })
    _emit(record)

    overlap_pct, overlap_groups, overlap_identical = _overlap_metrics()
    record.update({
        "comms_overlap_dispatch_pct": round(overlap_pct, 1),
        "comms_overlap_groups_per_step": overlap_groups,
        "comms_overlap_loss_bit_identical": bool(overlap_identical),
    })
    _emit(record)

    zero = _zero_metrics()
    record.update(zero)
    _emit(record)

    if telemetry_out:
        from mxnet_tpu import telemetry

        telemetry.write_snapshot(telemetry_out)
    return 0 if (identical and overlap_identical
                 and overlap_pct > 0.0
                 and zero["zero_loss_bit_identical"]) else 1


if __name__ == "__main__":
    sys.exit(main())
