"""Inference serving stack (mxnet_tpu/serving/): bucket grid, cached-
graph warmup/keying, Server continuous batching, SLO close, fault
retry, hot reload, telemetry.

Bitwise comparisons are always made at MATCHED batch buckets (the same
compiled executable): XLA:CPU may pick a different matmul kernel per
batch size (see serving/buckets.py), so cross-bucket comparisons are
an environment property, not a serving invariant — the invariant is
padding transparency within a bucket.
"""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fault, serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.serving.buckets import BucketGrid

pytestmark = pytest.mark.serving


def make_net(in_units=8, units=4, seed=0):
    net = nn.Dense(units, in_units=in_units)
    net.initialize()
    rs = np.random.RandomState(seed)
    net.weight.set_data(mx.nd.array(
        rs.randn(units, in_units).astype(np.float32)))
    net.bias.set_data(mx.nd.array(rs.randn(units).astype(np.float32)))
    net.hybridize()
    return net


def direct(net, rows, cap):
    """Reference: the padded bucket-`cap` dispatch the server makes."""
    pad = np.zeros((cap,) + rows[0].shape, np.float32)
    for i, r in enumerate(rows):
        pad[i] = r
    return net(mx.nd.array(pad)).asnumpy()


class SleepBlock(mx.gluon.Block):
    """Eager block that sleeps per dispatch (queue-pressure tests)."""

    def __init__(self, seconds, **kw):
        super().__init__(**kw)
        self.seconds = seconds

    def forward(self, x):
        time.sleep(self.seconds)
        return x * 2


class BoomBlock(mx.gluon.Block):
    def forward(self, x):
        raise MXNetError("boom")


# ---------------------------------------------------------------------------
# BucketGrid
# ---------------------------------------------------------------------------

def test_batch_bucket_selection():
    g = BucketGrid(batch_buckets=(4, 1, 16))
    assert g.batch_buckets == (1, 4, 16)
    assert g.max_batch == 16
    assert g.batch_bucket(1) == 1
    assert g.batch_bucket(2) == 4
    assert g.batch_bucket(5) == 16
    assert g.batch_bucket(99) == 16   # callers cap n at max_batch


def test_shape_bucket_exact_mode():
    g = BucketGrid()
    assert g.bucket_shape((3, 5)) == (3, 5)


def test_shape_bucket_tightest_fit():
    g = BucketGrid(shape_buckets=[(16,), (8,), (32,)])
    assert g.bucket_shape((5,)) == (8,)
    assert g.bucket_shape((8,)) == (8,)
    assert g.bucket_shape((9,)) == (16,)
    with pytest.raises(MXNetError):
        g.bucket_shape((33,))          # too big for every bucket
    with pytest.raises(MXNetError):
        g.bucket_shape((4, 4))         # rank mismatch


def test_grid_validation():
    with pytest.raises(MXNetError):
        BucketGrid(batch_buckets=())
    with pytest.raises(MXNetError):
        BucketGrid(batch_buckets=(0, 2))
    with pytest.raises(MXNetError):
        BucketGrid(shape_buckets=[])
    with pytest.raises(MXNetError):
        BucketGrid(shape_buckets=[(0, 3)])


def test_pad_sample():
    out = BucketGrid.pad_sample(np.ones((2, 3), np.float32), (4, 3))
    assert out.shape == (4, 3)
    assert np.array_equal(out[:2], np.ones((2, 3), np.float32))
    assert not out[2:].any()
    same = np.ones((2, 3), np.float32)
    assert BucketGrid.pad_sample(same, (2, 3)) is same


def test_input_signatures():
    g = BucketGrid(batch_buckets=(1, 2), shape_buckets=[(8,), (16,)])
    assert sorted(g.input_signatures()) == [
        (1, 8), (1, 16), (2, 8), (2, 16)]
    # exact-shape mode has no inventory without explicit samples
    assert BucketGrid(batch_buckets=(2,)).input_signatures() == []
    assert BucketGrid(batch_buckets=(2,)).input_signatures([(3, 3)]) == \
        [(2, 3, 3)]


# ---------------------------------------------------------------------------
# _CachedGraph warmup + cache keying across padded batch sizes
# ---------------------------------------------------------------------------

def test_warmup_one_entry_per_bucket():
    net = make_net()
    n = net.warmup([(1, 8), (2, 8), (4, 8)])
    assert n == 3
    assert len(net._cached_graph._cache) == 3
    assert net.warmup([(1, 8), (2, 8), (4, 8)]) == 0   # already warm


def test_warmup_requires_hybridize():
    net = nn.Dense(4, in_units=8)
    net.initialize()
    with pytest.raises(MXNetError, match="hybridize"):
        net.warmup([(1, 8)])


def test_warmup_multi_input_spec():
    class TwoIn(mx.gluon.HybridBlock):
        def hybrid_forward(self, F, a, b):
            return a + b

    blk = TwoIn()
    blk.hybridize()
    assert blk.warmup([[(2, 4), (2, 4)]]) == 1
    out = blk(mx.nd.ones((2, 4)), mx.nd.ones((2, 4)))
    assert len(blk._cached_graph._cache) == 1   # the call was a hit
    assert np.array_equal(out.asnumpy(), np.full((2, 4), 2, np.float32))


def test_warmup_zero_retraces_on_repeat_shapes():
    net = make_net()
    net.warmup([(2, 8), (4, 8)])
    was = telemetry.enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        x2 = mx.nd.array(np.ones((2, 8), np.float32))
        x4 = mx.nd.array(np.ones((4, 8), np.float32))
        for _ in range(3):
            net(x2)
            net(x4)
        assert len(net._cached_graph._cache) == 2    # zero new entries
        snap = telemetry.snapshot()["metrics"]["mxnet_jit_cache_total"]
        hits = {tuple(s["labels"].values()): s["value"]
                for s in snap["samples"]}
        assert hits.get(("cached_op", "hit"), 0) == 6
        assert ("cached_op", "miss") not in hits
    finally:
        telemetry.reset()
        if not was:
            telemetry.disable()


def test_warmup_outputs_eager_identical():
    net = make_net()
    net.warmup([(2, 8)])
    x = np.random.RandomState(3).randn(2, 8).astype(np.float32)
    compiled = net(mx.nd.array(x)).asnumpy()
    eager = net._eager_forward(mx.nd.array(x)).asnumpy()
    assert np.array_equal(compiled, eager)


def test_cache_keying_padded_batches_share_entries():
    """Distinct fill levels of one bucket are ONE cache entry; padding
    rows are bit-transparent within the bucket."""
    net = make_net()
    rs = np.random.RandomState(1)
    rows = [rs.randn(8).astype(np.float32) for _ in range(4)]
    full = direct(net, rows, 4)
    assert len(net._cached_graph._cache) == 1
    part = direct(net, rows[:2], 4)      # 2 real + 2 padded rows
    assert len(net._cached_graph._cache) == 1
    assert np.array_equal(part[:2], full[:2])


# ---------------------------------------------------------------------------
# Server: batching, SLO, ordering, errors
# ---------------------------------------------------------------------------

def test_server_basic_bit_identical():
    net = make_net()
    rs = np.random.RandomState(2)
    rows = [rs.randn(8).astype(np.float32) for _ in range(2)]
    ref = direct(net, rows, 2)
    with serving.Server(net, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=200) as srv:
        futs = [srv.submit(r) for r in rows]
        outs = [f.result(timeout=10) for f in futs]
    assert np.array_equal(outs[0], ref[0])
    assert np.array_equal(outs[1], ref[1])


def test_server_pads_single_request():
    net = make_net()
    row = np.random.RandomState(4).randn(8).astype(np.float32)
    ref = direct(net, [row], 2)
    with serving.Server(net, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=50) as srv:
        out = srv.submit(row).result(timeout=10)
        assert srv.stats()["batches"] == 1
    assert np.array_equal(out, ref[0])


def test_server_multi_output_model():
    class TwoOut(mx.gluon.HybridBlock):
        def hybrid_forward(self, F, x):
            return x * 2, (x + 1,)

    blk = TwoOut()
    blk.hybridize()
    row = np.arange(4, dtype=np.float32)
    with serving.Server(blk, batch_buckets=(2,), shape_buckets=[(4,)],
                        slo_ms=50) as srv:
        out = srv.submit(row).result(timeout=10)
    assert isinstance(out, tuple) and isinstance(out[1], tuple)
    assert np.array_equal(out[0], row * 2)
    assert np.array_equal(out[1][0], row + 1)


def test_server_shape_bucket_padding():
    net = make_net()
    short = np.ones(5, np.float32)
    padded = np.zeros(8, np.float32)
    padded[:5] = short
    ref = direct(net, [padded], 2)
    with serving.Server(net, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=50) as srv:
        out = srv.submit(short).result(timeout=10)
    assert np.array_equal(out, ref[0])


def test_server_two_shape_buckets_separate_dispatches():
    class RowSum(mx.gluon.HybridBlock):
        def hybrid_forward(self, F, x):
            return F.sum(x, axis=1)

    blk = RowSum()
    blk.hybridize()
    a = np.ones(3, np.float32)
    b = np.ones(6, np.float32)
    with serving.Server(blk, batch_buckets=(2,),
                        shape_buckets=[(4,), (8,)], slo_ms=100) as srv:
        fa, fb = srv.submit(a), srv.submit(b)
        ra, rb = fa.result(timeout=10), fb.result(timeout=10)
        assert srv.stats()["batches"] == 2     # one per shape bucket
    pa = np.zeros(4, np.float32)
    pa[:3] = a
    pb = np.zeros(8, np.float32)
    pb[:6] = b
    assert np.array_equal(ra, direct(blk, [pa], 2)[0])
    assert np.array_equal(rb, direct(blk, [pb], 2)[0])


def test_server_rejects_unbucketable_shape():
    net = make_net()
    with serving.Server(net, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=50) as srv:
        with pytest.raises(MXNetError, match="no shape bucket"):
            srv.submit(np.ones(9, np.float32))


def test_server_deadline_close_partial_batch():
    net = make_net()
    with serving.Server(net, batch_buckets=(8,), shape_buckets=[(8,)],
                        slo_ms=100, close_margin_ms=10) as srv:
        t0 = time.perf_counter()
        srv.submit(np.ones(8, np.float32)).result(timeout=10)
        dt = time.perf_counter() - t0
    # closed by deadline, not by fill: ~slo, far under the 10 s timeout
    assert dt < 2.0


def test_server_full_close_beats_slo():
    net = make_net()
    with serving.Server(net, batch_buckets=(4,), shape_buckets=[(8,)],
                        slo_ms=5000) as srv:
        rows = [np.ones(8, np.float32)] * 4
        t0 = time.perf_counter()
        futs = [srv.submit(r) for r in rows]
        for f in futs:
            f.result(timeout=10)
        dt = time.perf_counter() - t0
        assert srv.stats()["batches"] >= 1
    assert dt < 2.0     # a full bucket dispatches immediately, not at SLO


def test_tight_deadline_overrides_lazy_head():
    net = make_net()
    with serving.Server(net, batch_buckets=(8,), shape_buckets=[(8,)],
                        slo_ms=30000, close_margin_ms=5) as srv:
        lazy = srv.submit(np.ones(8, np.float32))     # 30 s deadline
        t0 = time.perf_counter()
        tight = srv.submit(np.ones(8, np.float32), deadline_ms=50)
        tight.result(timeout=10)
        dt = time.perf_counter() - t0
        assert lazy.done()      # same key: it rode the tight batch
    assert dt < 2.0             # closed on the TIGHTEST queued deadline


def test_non_batch_major_output_fails_batch_not_server():
    class ScalarOut(mx.gluon.Block):
        def forward(self, x):
            return mx.nd.array(np.float32(1.0))      # no batch axis

    srv = serving.Server(ScalarOut(), batch_buckets=(2,), slo_ms=20,
                         warmup=False).start()
    try:
        f = srv.submit(np.ones(4, np.float32))
        with pytest.raises(Exception):
            f.result(timeout=10)
        assert srv.is_running       # scheduler survived
        assert srv.stats()["errors"] == 1
    finally:
        srv.stop()


def test_server_drains_overflow_into_next_batch():
    net = make_net()
    with serving.Server(net, batch_buckets=(2, 4), shape_buckets=[(8,)],
                        slo_ms=100) as srv:
        futs = [srv.submit(np.ones(8, np.float32)) for _ in range(9)]
        for f in futs:
            f.result(timeout=10)
        assert srv.stats()["batches"] >= 3     # 9 requests, cap 4


def test_submit_requires_running_server():
    net = make_net()
    srv = serving.Server(net, batch_buckets=(2,), shape_buckets=[(8,)])
    with pytest.raises(MXNetError, match="not running"):
        srv.submit(np.ones(8, np.float32))
    srv.start()
    srv.stop()
    with pytest.raises(MXNetError, match="not running"):
        srv.submit(np.ones(8, np.float32))


def test_queue_full_rejects_synchronously():
    blk = SleepBlock(0.3)
    srv = serving.Server(blk, batch_buckets=(1,), slo_ms=20,
                         close_margin_ms=10, max_queue=2, warmup=False)
    srv.start()
    try:
        futs = [srv.submit(np.ones(4, np.float32))]
        time.sleep(0.1)   # first request now dispatched (sleeping)
        futs += [srv.submit(np.ones(4, np.float32)) for _ in range(2)]
        with pytest.raises(MXNetError, match="queue full"):
            srv.submit(np.ones(4, np.float32))
        for f in futs:
            f.result(timeout=10)
    finally:
        srv.stop()


def test_stop_drain_serves_pending():
    blk = SleepBlock(0.1)
    srv = serving.Server(blk, batch_buckets=(2,), slo_ms=5000,
                         warmup=False).start()
    futs = [srv.submit(np.full(4, i, np.float32)) for i in range(3)]
    srv.stop(drain=True)
    outs = [f.result(timeout=1) for f in futs]
    for i, o in enumerate(outs):
        assert np.array_equal(o, np.full(4, 2 * i, np.float32))


def test_stop_no_drain_fails_pending():
    blk = SleepBlock(0.3)
    srv = serving.Server(blk, batch_buckets=(1,), slo_ms=20,
                         close_margin_ms=10, warmup=False).start()
    first = srv.submit(np.ones(4, np.float32))
    time.sleep(0.1)       # first is mid-dispatch; the rest stay queued
    pending = [srv.submit(np.ones(4, np.float32)) for _ in range(2)]
    srv.stop(drain=False)
    first.result(timeout=10)      # in-flight dispatch still completes
    for f in pending:
        with pytest.raises(MXNetError, match="stopped"):
            f.result(timeout=1)


def test_cancelled_future_skipped_not_fatal():
    blk = SleepBlock(0.2)
    srv = serving.Server(blk, batch_buckets=(1,), slo_ms=20,
                         close_margin_ms=10, warmup=False).start()
    try:
        first = srv.submit(np.ones(4, np.float32))
        time.sleep(0.05)     # first now mid-dispatch
        doomed = srv.submit(np.ones(4, np.float32))
        keeper = srv.submit(np.full(4, 3, np.float32))
        assert doomed.cancel()
        first.result(timeout=10)
        out = keeper.result(timeout=10)   # scheduler survived the cancel
        assert np.array_equal(out, np.full(4, 6, np.float32))
        assert srv.is_running
    finally:
        srv.stop()


def test_dispatch_error_fails_futures_not_server():
    srv = serving.Server(BoomBlock(), batch_buckets=(2,), slo_ms=20,
                         warmup=False).start()
    try:
        f1 = srv.submit(np.ones(4, np.float32))
        with pytest.raises(MXNetError, match="boom"):
            f1.result(timeout=10)
        assert srv.is_running
        assert srv.stats()["errors"] == 1
    finally:
        srv.stop()


def test_transient_dispatch_fault_retried():
    net = make_net()
    with serving.Server(net, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=50) as srv:
        row = np.ones(8, np.float32)
        ref = direct(net, [row], 2)
        with fault.inject("serving.dispatch=once"):
            out = srv.submit(row).result(timeout=10)
        assert np.array_equal(out, ref[0])
        assert srv.stats()["errors"] == 0


def test_exhausted_dispatch_fault_surfaces(monkeypatch):
    monkeypatch.setenv("MXNET_COMM_RETRY_ATTEMPTS", "2")
    monkeypatch.setenv("MXNET_COMM_RETRY_DELAY", "0.001")
    net = make_net()
    with serving.Server(net, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=50) as srv:
        with fault.inject("serving.dispatch=every:1"):
            f = srv.submit(np.ones(8, np.float32))
            with pytest.raises(MXNetError, match="serving.dispatch"):
                f.result(timeout=10)
        assert srv.is_running
        assert srv.stats()["errors"] == 1


def test_double_start_raises_and_live_servers_tracks():
    net = make_net()
    srv = serving.Server(net, batch_buckets=(2,), shape_buckets=[(8,)])
    srv.start()
    try:
        assert srv in serving.live_servers()
        with pytest.raises(MXNetError, match="already running"):
            srv.start()
    finally:
        srv.stop()
    assert srv not in serving.live_servers()


def test_server_warms_grid_at_start():
    net = make_net()
    with serving.Server(net, batch_buckets=(2, 4),
                        shape_buckets=[(8,)], slo_ms=50):
        assert len(net._cached_graph._cache) == 2   # (2,8) and (4,8)


# ---------------------------------------------------------------------------
# poll_newest + hot reload
# ---------------------------------------------------------------------------

def test_poll_newest_semantics(tmp_path):
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path), keep_last=5)
    assert mgr.poll_newest("t") is None          # nothing there yet
    net = make_net()
    mgr.save(1, params=net)
    assert mgr.poll_newest("t") == 1
    assert mgr.poll_newest("t") is None          # unchanged
    mgr.save(2, params=net)
    assert mgr.poll_newest("t") == 2
    mgr.save(2, params=net)                      # re-save same step
    assert mgr.poll_newest("t") == 2
    assert mgr.poll_newest("other") == 2         # per-tag state
    assert mgr.poll_newest("t") is None


def test_poll_newest_no_change_path_skips_validation(tmp_path,
                                                     monkeypatch):
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path), keep_last=5)
    mgr.save(1, params=make_net())
    assert mgr.poll_newest("t") == 1
    calls = []
    orig = mx.checkpoint.CheckpointManager.is_valid
    monkeypatch.setattr(mx.checkpoint.CheckpointManager, "is_valid",
                        lambda self, step: calls.append(step)
                        or orig(self, step))
    assert mgr.poll_newest("t") is None
    assert calls == []        # one stat(), zero manifest re-hashes


def _factory_for(tmp_path, seed=0):
    def factory(path):
        net = make_net(seed=seed)
        net.load_parameters(os.path.join(path, "params.params"))
        net.hybridize()
        return net
    return factory


def test_manual_reload_swaps_and_warms(tmp_path):
    old = make_net(seed=0)
    new = make_net(seed=9)
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path))
    mgr.save(7, params=new)
    row = np.ones(8, np.float32)
    ref_new = direct(new, [row], 2)
    with serving.Server(old, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=50) as srv:
        srv.submit(row).result(timeout=10)
        step = srv.reload(mgr, _factory_for(tmp_path))
        assert step == 7 and srv.loaded_step == 7
        # the swapped-in block was warmed BEFORE the swap
        assert len(srv._model._cached_graph._cache) >= 1
        out = srv.submit(row).result(timeout=10)
    assert np.array_equal(out, ref_new[0])
    assert srv.stats()["reloads"] == 1


def test_reload_failure_keeps_old_model(tmp_path):
    old = make_net(seed=0)
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path))
    mgr.save(1, params=old)
    row = np.ones(8, np.float32)
    ref = direct(old, [row], 2)

    def bad_factory(path):
        raise MXNetError("factory exploded")

    with serving.Server(old, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=50) as srv:
        with pytest.raises(MXNetError, match="factory exploded"):
            srv.reload(mgr, bad_factory)
        out = srv.submit(row).result(timeout=10)
    assert np.array_equal(out, ref[0])
    assert srv.loaded_step is None


def test_failed_reload_retried_next_tick(tmp_path):
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path), keep_last=2)
    old = make_net(seed=0)
    mgr.save(0, params=old)
    attempts = []
    real = _factory_for(tmp_path)

    def flaky_factory(path):
        attempts.append(path)
        if len(attempts) == 1:
            raise MXNetError("factory exploded once")
        return real(path)

    with serving.Server(old, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=20) as srv:
        srv.enable_hot_reload(mgr, flaky_factory, interval_s=0.02)
        mgr.save(1, params=make_net(seed=9))
        deadline = time.time() + 10
        while srv.loaded_step != 1 and time.time() < deadline:
            time.sleep(0.02)
        # poll_reset re-offered the bundle after the failed attempt
        assert srv.loaded_step == 1
        assert len(attempts) >= 2


def test_hot_reload_watcher_serves_during_swap(tmp_path):
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path), keep_last=2)
    old = make_net(seed=0)
    new = make_net(seed=9)
    mgr.save(0, params=old)
    row = np.ones(8, np.float32)
    ref_old = direct(old, [row], 2)
    ref_new = direct(new, [row], 2)
    with serving.Server(old, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=20) as srv:
        srv.enable_hot_reload(mgr, _factory_for(tmp_path),
                              interval_s=0.02)
        outs = [srv.submit(row).result(timeout=10)]
        mgr.save(1, params=new)
        deadline = time.time() + 10
        while srv.loaded_step != 1 and time.time() < deadline:
            outs.append(srv.submit(row).result(timeout=10))
        assert srv.loaded_step == 1
        outs.append(srv.submit(row).result(timeout=10))
    for o in outs:      # every response is one model or the other
        assert np.array_equal(o, ref_old[0]) or \
            np.array_equal(o, ref_new[0])
    assert np.array_equal(outs[-1], ref_new[0])
    assert srv._watcher is None     # stop() tore the watcher down


# ---------------------------------------------------------------------------
# int8 serving + quantize_net hybridize propagation
# ---------------------------------------------------------------------------

def _mlp(seed=0):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize()
    rs = np.random.RandomState(seed)
    for p in net.collect_params().values():
        p.set_data(mx.nd.array(rs.randn(*p.shape).astype(np.float32)))
    return net


def test_quantize_net_keeps_hybridized():
    from mxnet_tpu.contrib.quantization import quantize_net

    net = _mlp()
    net.hybridize()
    calib = mx.nd.array(np.random.RandomState(1).randn(8, 8)
                        .astype(np.float32))
    quantize_net(net, calib_data=calib, calib_mode="naive")
    assert net._active
    assert all(getattr(c, "_active", True) for c in net._children.values())
    assert net.warmup([(2, 8)]) == 1     # warms without a manual re-hybridize


def test_server_serves_quantized_net():
    from mxnet_tpu.contrib.quantization import quantize_net

    net = _mlp()
    net.hybridize()
    calib = mx.nd.array(np.random.RandomState(1).randn(8, 8)
                        .astype(np.float32))
    quantize_net(net, calib_data=calib, calib_mode="naive")
    row = np.random.RandomState(2).randn(8).astype(np.float32)
    ref = direct(net, [row], 2)
    with serving.Server(net, batch_buckets=(2,), shape_buckets=[(8,)],
                        slo_ms=50) as srv:
        out = srv.submit(row).result(timeout=10)
    assert np.array_equal(out, ref[0])


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_serving_buckets_are_subms_fine():
    assert telemetry.SERVING_BUCKETS == \
        tuple(sorted(telemetry.SERVING_BUCKETS))
    assert sum(1 for b in telemetry.SERVING_BUCKETS if b < 1e-3) >= 5
    assert telemetry.SERVING_BUCKETS[0] <= 5e-5


def test_serving_metrics_exported():
    was = telemetry.enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        net = make_net()
        with serving.Server(net, batch_buckets=(2,),
                            shape_buckets=[(8,)], slo_ms=20) as srv:
            futs = [srv.submit(np.ones(8, np.float32)) for _ in range(3)]
            for f in futs:
                f.result(timeout=10)
        text = telemetry.prom_text()
        assert 'mxnet_serving_requests_total{outcome="ok"} 3' in text
        assert "mxnet_serving_request_seconds_bucket" in text
        assert "mxnet_serving_time_in_queue_seconds_bucket" in text
        assert "mxnet_serving_batch_occupancy_bucket" in text
        assert "mxnet_serving_batches_total" in text
        assert "mxnet_serving_queue_depth" in text
        snap = telemetry.snapshot()["metrics"]
        occ = snap["mxnet_serving_batch_occupancy"]["samples"][0]
        assert occ["count"] >= 2
    finally:
        telemetry.reset()
        if not was:
            telemetry.disable()


def test_reload_metric(tmp_path):
    was = telemetry.enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        old = make_net(seed=0)
        mgr = mx.checkpoint.CheckpointManager(str(tmp_path))
        mgr.save(3, params=make_net(seed=9))
        with serving.Server(old, batch_buckets=(2,),
                            shape_buckets=[(8,)], slo_ms=50) as srv:
            srv.reload(mgr, _factory_for(tmp_path))
        text = telemetry.prom_text()
        assert 'mxnet_serving_reloads_total{outcome="ok"} 1' in text
    finally:
        telemetry.reset()
        if not was:
            telemetry.disable()
