"""Bucketed gradient fusion tests (ISSUE 5).

The kvstore's batched ``pushpull`` coalesces keys into dtype-segregated
flat buckets (``MXNET_KV_BUCKET_MB``) and reduces each with ONE
collective. Contracts under test:

* bit-identity — bucketed uncompressed exchange == per-key exchange,
  on the local and ``tpu_sync`` stores and through a data-parallel
  Trainer step;
* planning — mixed dtypes split into separate buckets, a single param
  larger than the cap gets its own bucket, dispatch honors the
  descending-priority order;
* compression semantics — per-bucket 2-bit error feedback converges to
  the true gradient sum, residual state survives ``Trainer.save_states``
  and ``CheckpointManager`` resume bit-exactly, unsupported dtypes raise
  ``MXNetError`` instead of silently casting;
* telemetry — the bucketed path records collective-dispatch/bucket-byte
  counters.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import kvstore as kv
from mxnet_tpu.base import MXNetError
from mxnet_tpu.kvstore.bucketing import plan_buckets


SHAPES = [(4, 5), (3,), (2, 2, 2), (7,), (1, 9)]


def _grads(shapes=SHAPES, copies=2, seed=0, dtype=np.float32):
    rs = np.random.RandomState(seed)
    return [[rs.randn(*sh).astype(dtype) for _ in range(copies)]
            for sh in shapes]


def _exchange(store, grads_np, shapes=SHAPES, dtype="float32",
              spread_devices=True):
    """Init + one batched pushpull; returns pulled numpy per key/slot."""
    copies = len(grads_np[0])
    ctx = [mx.Context("cpu", c if spread_devices else 0)
           for c in range(copies)]
    vals = [[mx.nd.array(g, ctx=c, dtype=dtype)
             for c, g in zip(ctx, gl)] for gl in grads_np]
    outs = [[mx.nd.zeros(sh, ctx=c, dtype=dtype) for c in ctx]
            for sh in shapes]
    for i, sh in enumerate(shapes):
        store.init(i, mx.nd.zeros(sh, dtype=dtype))
    keys = list(range(len(shapes)))
    store.pushpull(keys, vals, out=outs,
                   priority=[-k for k in keys])
    return [[o.asnumpy() for o in ol] for ol in outs]


class TestBitIdentity:
    @pytest.mark.parametrize("store_type", ["device", "tpu_sync"])
    def test_bucketed_matches_perkey(self, store_type):
        """The tentpole gate: bucketed uncompressed pushpull is
        BIT-identical (array_equal, not allclose) to the per-key path."""
        grads = _grads()
        s_pk = kv.create(store_type)
        s_pk._bucket_bytes = 0          # per-key decomposition
        r_pk = _exchange(s_pk, grads)
        s_bk = kv.create(store_type)
        assert s_bk._bucket_bytes == 25 << 20   # MXNET_KV_BUCKET_MB def
        r_bk = _exchange(s_bk, grads)
        for a, b in zip(r_pk, r_bk):
            for x, y in zip(a, b):
                assert np.array_equal(x, y)

    def test_values_correct_tpu_sync(self):
        """Bucketed psum result equals the cross-device gradient sum."""
        grads = _grads()
        out = _exchange(kv.create("tpu_sync"), grads)
        for gl, ol in zip(grads, out):
            want = np.sum(gl, axis=0)
            for o in ol:
                np.testing.assert_allclose(o, want, rtol=1e-6)

    def test_scalar_pushpull_thin_wrapper(self):
        """The scalar form is a one-key batch over the same fused path."""
        store = kv.create("device")
        store.init("w", mx.nd.zeros((3,)))
        g = mx.nd.array(np.array([1.0, 2.0, 3.0], np.float32))
        store.pushpull("w", g)           # out defaults to value
        np.testing.assert_allclose(g.asnumpy(), [1, 2, 3])
        out = mx.nd.zeros((3,))
        store.pull("w", out)
        np.testing.assert_allclose(out.asnumpy(), [1, 2, 3])

    def test_store_consistent_after_bucketed_pushpull(self):
        """A later scalar pull sees the bucketed reduction's result."""
        grads = _grads(copies=2)
        store = kv.create("tpu_sync")
        _exchange(store, grads)
        out = mx.nd.zeros(SHAPES[2])
        store.pull(2, out)
        np.testing.assert_allclose(out.asnumpy(),
                                   np.sum(grads[2], axis=0), rtol=1e-6)

    def test_updater_falls_back_per_key(self):
        """Server-side optimizer: the batched form decomposes and the
        updater applies per key, exactly like scalar push/pull."""
        store = kv.create("local")
        store.set_optimizer(mx.optimizer.create("sgd", learning_rate=1.0,
                                                wd=0.0))
        store.init(0, mx.nd.zeros((3,)))
        store.init(1, mx.nd.zeros((2,)))
        g0 = mx.nd.ones((3,))
        g1 = mx.nd.full((2,), 2.0)
        o0, o1 = mx.nd.zeros((3,)), mx.nd.zeros((2,))
        store.pushpull([0, 1], [g0, g1], out=[o0, o1])
        np.testing.assert_allclose(o0.asnumpy(), -np.ones(3))
        np.testing.assert_allclose(o1.asnumpy(), -2 * np.ones(2))
        assert 0 in store._updater.states and 1 in store._updater.states

    def test_trainer_bucketed_step_bit_identical(self):
        """Data-parallel Trainer over tpu_sync: per-key vs bucketed
        training is bit-identical (losses and weights)."""
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss

        def run(bucket_mb):
            prev = os.environ.get("MXNET_KV_BUCKET_MB")
            os.environ["MXNET_KV_BUCKET_MB"] = str(bucket_mb)
            try:
                mx.random.seed(0)
                net = nn.Dense(4, in_units=8)
                net.initialize()
                rs = np.random.RandomState(5)
                net.weight.set_data(mx.nd.array(
                    rs.randn(4, 8).astype(np.float32)))
                net.bias.set_data(mx.nd.zeros(4))
                ctxs = [mx.Context("cpu", 0), mx.Context("cpu", 1)]
                net.collect_params().reset_ctx(ctxs)
                tr = gluon.Trainer(net.collect_params(), "sgd",
                                   {"learning_rate": 0.1},
                                   kvstore="tpu_sync")
                loss_fn = L2Loss()
                rs2 = np.random.RandomState(1)
                x = rs2.randn(8, 8).astype(np.float32)
                y = rs2.randn(8, 4).astype(np.float32)
                losses = []
                for _ in range(3):
                    with autograd.record():
                        ls = [loss_fn(
                            net(mx.nd.array(x[i * 4:(i + 1) * 4],
                                            ctx=c)),
                            mx.nd.array(y[i * 4:(i + 1) * 4], ctx=c))
                            for i, c in enumerate(ctxs)]
                    autograd.backward(ls)
                    tr.step(8)
                    losses.append(
                        [float(l.asnumpy().sum()) for l in ls])
                return losses, net.weight.data(ctxs[0]).asnumpy()
            finally:
                if prev is None:
                    os.environ.pop("MXNET_KV_BUCKET_MB", None)
                else:
                    os.environ["MXNET_KV_BUCKET_MB"] = prev

        losses_pk, w_pk = run(0)
        losses_bk, w_bk = run(25)
        assert losses_pk == losses_bk
        assert np.array_equal(w_pk, w_bk)


class TestBucketPlanning:
    def _entries(self, specs):
        """specs: (shape, dtype_str) in dispatch order."""
        out = []
        for i, (shape, dt) in enumerate(specs):
            n = int(np.prod(shape)) if shape else 1
            nbytes = n * np.dtype(dt).itemsize
            out.append((i, shape, dt, (dt, 1, ("d0",)), nbytes))
        return out

    def test_cap_splits_buckets(self):
        entries = self._entries([((256,), "float32")] * 5)  # 1 KB each
        buckets = plan_buckets(entries, 2 * 1024)
        assert [b.indices for b in buckets] == [[0, 1], [2, 3], [4]]
        assert all(b.nbytes <= 2 * 1024 for b in buckets)

    def test_mixed_dtypes_split(self):
        """fp32/fp16 members never share a flat buffer, even interleaved;
        each dtype keeps its own open bucket."""
        entries = self._entries([((8,), "float32"), ((8,), "float16"),
                                 ((8,), "float32"), ((8,), "float16")])
        buckets = plan_buckets(entries, 1 << 20)
        assert [b.indices for b in buckets] == [[0, 2], [1, 3]]
        assert [b.dtype for b in buckets] == ["float32", "float16"]

    def test_oversize_param_gets_own_bucket(self):
        """A single tensor above the cap is never split and never shares."""
        entries = self._entries([((16,), "float32"),      # 64 B
                                 ((1024,), "float32"),    # 4 KB > cap
                                 ((16,), "float32")])
        buckets = plan_buckets(entries, 256)
        assert [b.indices for b in buckets] == [[0], [1], [2]]

    def test_mixed_dtype_exchange_end_to_end(self):
        """Mixed-dtype batched pushpull reduces each dtype correctly."""
        store = kv.create("device")
        rs = np.random.RandomState(0)
        g32 = rs.randn(4).astype(np.float32)
        g16 = rs.randn(6).astype(np.float16)
        store.init(0, mx.nd.zeros((4,)))
        store.init(1, mx.nd.zeros((6,), dtype="float16"))
        v0 = mx.nd.array(g32)
        v1 = mx.nd.array(g16, dtype="float16")
        store.pushpull([0, 1], [v0, v1], out=[v0, v1])
        np.testing.assert_allclose(v0.asnumpy(), g32)
        np.testing.assert_allclose(v1.asnumpy(), g16)
        assert v1.asnumpy().dtype == np.float16

    def test_priority_order_honored(self):
        """Buckets are dispatched in descending-priority order (the
        trainer's reverse-layer hint), stable for ties."""
        store = kv.create("device")
        store._bucket_bytes = 1          # force one bucket per key
        for i in range(3):
            store.init(i, mx.nd.zeros((2,)))
        seen = []
        orig = store._bucket_exchange_reduce

        def spy(bucket, vals_by_pos):
            seen.extend(vals_by_pos[p][0] for p in bucket.indices)
            return orig(bucket, vals_by_pos)

        store._bucket_exchange_reduce = spy
        vals = [mx.nd.ones((2,)) for _ in range(3)]
        store.pushpull([0, 1, 2], vals, out=vals, priority=[-5, 0, -3])
        assert seen == [1, 2, 0]         # highest priority first
        seen.clear()
        store.pushpull([0, 1, 2], vals, out=vals, priority=0)
        assert seen == [0, 1, 2]         # ties keep the given order

    def test_fallback_keys_keep_priority_position(self):
        """A non-dense payload falls back to per-key exchange but is
        dispatched at ITS priority slot, not banished behind every
        bucket."""
        import jax.numpy as jnp

        from mxnet_tpu.ndarray import NDArray

        class FakeSparse(NDArray):
            stype = "row_sparse"     # shadows the dense default

        store = kv.create("device")
        store._bucket_bytes = 1      # one bucket per dense key
        for i in range(3):
            store.init(i, mx.nd.zeros((2,)))
        calls = []
        orig_reduce = store._bucket_exchange_reduce
        orig_push = store.push

        def spy_reduce(bucket, vals_by_pos):
            calls.extend(vals_by_pos[p][0] for p in bucket.indices)
            return orig_reduce(bucket, vals_by_pos)

        def spy_push(key, value, priority=0):
            calls.append(key)
            return orig_push(key, value, priority)

        store._bucket_exchange_reduce = spy_reduce
        store.push = spy_push
        vals = [mx.nd.ones((2,)),
                FakeSparse(data=jnp.ones((2,))),
                mx.nd.ones((2,))]
        outs = [mx.nd.zeros((2,)) for _ in range(3)]
        store.pushpull([0, 1, 2], vals, out=outs, priority=[0, -1, -2])
        assert calls == [0, 1, 2]

    def test_batched_arg_validation(self):
        store = kv.create("device")
        store.init(0, mx.nd.zeros((2,)))
        with pytest.raises(MXNetError, match="values"):
            store.pushpull([0], [], out=[mx.nd.zeros((2,))])
        with pytest.raises(MXNetError, match="priorities"):
            store.pushpull([0], [mx.nd.zeros((2,))], priority=[0, 1])


class TestBucketedCompression:
    def test_error_feedback_converges_on_bucketed_path(self):
        """Over repeated bucketed pushes the transmitted mean converges
        to the true gradient (residual carries the remainder)."""
        store = kv.create("device")
        store.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        g_np = np.array([0.4, -0.3, 0.1, -0.2, 0.0], np.float32)
        store.init(0, mx.nd.zeros((5,)))
        store.init(1, mx.nd.zeros((3,)))
        total = np.zeros(5, np.float32)
        for _ in range(40):
            v0 = mx.nd.array(g_np)
            v1 = mx.nd.zeros((3,))
            o0, o1 = mx.nd.zeros((5,)), mx.nd.zeros((3,))
            store.pushpull([0, 1], [v0, v1], out=[o0, o1])
            got = o0.asnumpy()
            # every transmitted value sits on the {-t, 0, +t} grid
            assert set(np.round(got / 0.5).astype(int)) <= {-1, 0, 1}
            total += got
        np.testing.assert_allclose(total / 40.0, g_np, atol=0.5 / 40)

    def test_unsupported_dtype_bucket_raises(self):
        """An integer-dtype bucket raises instead of silently casting."""
        store = kv.create("device")
        store.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        store.init(0, mx.nd.zeros((4,), dtype="int32"))
        g = mx.nd.array(np.arange(4, dtype=np.int32), dtype="int32")
        with pytest.raises(MXNetError, match="float gradients only"):
            store.pushpull([0], [g], out=[g])
        # the scalar push path enforces the same contract
        with pytest.raises(MXNetError, match="float gradients only"):
            store.push(0, g)

    def test_trainer_states_carry_residuals(self):
        """Trainer.save_states/load_states round-trips the compression
        residuals bit-exactly (the envelope format)."""
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss
        import tempfile

        def setup():
            mx.random.seed(0)
            net = nn.Dense(2, in_units=4)
            net.initialize()
            net.weight.set_data(mx.nd.array(np.ones((2, 4), np.float32)))
            net.bias.set_data(mx.nd.zeros(2))
            tr = gluon.Trainer(
                net.collect_params(), "sgd", {"learning_rate": 0.1},
                kvstore="tpu_sync",
                compression_params={"type": "2bit", "threshold": 0.3})
            return net, tr

        def step(net, tr, seed):
            rs = np.random.RandomState(seed)
            x = mx.nd.array(rs.randn(4, 4).astype(np.float32))
            y = mx.nd.array(rs.randn(4, 2).astype(np.float32))
            with autograd.record():
                loss = L2Loss()(net(x), y)
            loss.backward()
            tr.step(4)

        net, tr = setup()
        for s in range(3):
            step(net, tr, s)
        fname = os.path.join(tempfile.mkdtemp(), "trainer.states")
        tr.save_states(fname)
        res_before = {
            k: np.asarray(v) for k, v in
            tr._kvstore._compression._residual.items()}
        assert res_before, "compression produced no residual state"

        net2, tr2 = setup()
        # params must match for the updater states to be meaningful
        net2.weight.set_data(net.weight.data())
        net2.bias.set_data(net.bias.data())
        tr2.load_states(fname)
        res_after = tr2._kvstore._compression._residual
        assert set(res_after) == set(res_before)
        for k, v in res_before.items():
            assert np.array_equal(np.asarray(res_after[k]), v)

    def test_checkpoint_manager_resume_bit_exact(self):
        """The full CheckpointManager flow: a resumed compressed run's
        weights track the uninterrupted run bit-exactly (residual stream
        continues, not restarts)."""
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.checkpoint import CheckpointManager
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss
        import tempfile

        def setup():
            mx.random.seed(0)
            net = nn.Dense(2, in_units=4)
            net.initialize()
            net.weight.set_data(mx.nd.array(np.ones((2, 4), np.float32)))
            net.bias.set_data(mx.nd.zeros(2))
            tr = gluon.Trainer(
                net.collect_params(), "sgd", {"learning_rate": 0.1},
                kvstore="tpu_sync",
                compression_params={"type": "2bit", "threshold": 0.3})
            return net, tr

        def step(net, tr, seed):
            rs = np.random.RandomState(seed)
            x = mx.nd.array(rs.randn(4, 4).astype(np.float32))
            y = mx.nd.array(rs.randn(4, 2).astype(np.float32))
            with autograd.record():
                loss = L2Loss()(net(x), y)
            loss.backward()
            tr.step(4)
            return net.weight.data().asnumpy()

        # uninterrupted run: 6 steps
        net, tr = setup()
        for s in range(6):
            w_cont = step(net, tr, s)

        # interrupted run: 3 steps, checkpoint, fresh process state,
        # resume, 3 more
        net2, tr2 = setup()
        for s in range(3):
            step(net2, tr2, s)
        mgr = CheckpointManager(tempfile.mkdtemp())
        mgr.save(3, params=net2, trainer=tr2)
        net3, tr3 = setup()
        mgr.restore(block=net3, trainer=tr3)
        for s in range(3, 6):
            w_res = step(net3, tr3, s)
        assert np.array_equal(w_cont, w_res)

    def test_threshold_mismatch_on_restore_raises(self):
        from mxnet_tpu.kvstore.gradient_compression import (
            GradientCompression)

        a = GradientCompression(threshold=0.5)
        a.compress("w", 0, mx.nd.array(np.ones(3, np.float32)))
        b = GradientCompression(threshold=0.25)
        with pytest.raises(MXNetError, match="threshold"):
            b.set_state(a.get_state())

    def test_legacy_states_clear_live_residuals(self):
        """Loading a residual-less (legacy) state file into a
        compressing trainer must CLEAR its live residuals — the restored
        stream has to match a fresh process loading the same file."""
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss
        import tempfile

        def make(compress):
            mx.random.seed(0)
            net = nn.Dense(2, in_units=4)
            net.initialize()
            net(mx.nd.array(np.ones((1, 4), np.float32)))
            kwargs = {"compression_params":
                      {"type": "2bit", "threshold": 0.3}} if compress \
                else {}
            return net, gluon.Trainer(
                net.collect_params(), "sgd", {"learning_rate": 0.1},
                kvstore="tpu_sync", **kwargs)

        # legacy-format file: a trainer without compression
        net_plain, tr_plain = make(False)
        x = mx.nd.array(np.ones((4, 4), np.float32))
        y = mx.nd.array(np.zeros((4, 2), np.float32))
        with autograd.record():
            loss = L2Loss()(net_plain(x), y)
        loss.backward()
        tr_plain.step(4)
        fname = os.path.join(tempfile.mkdtemp(), "trainer.states")
        tr_plain.save_states(fname)

        net_c, tr_c = make(True)
        with autograd.record():
            loss = L2Loss()(net_c(x), y)
        loss.backward()
        tr_c.step(4)
        assert tr_c._kvstore._compression._residual
        tr_c.load_states(fname)
        assert tr_c._kvstore._compression._residual == {}

    def test_load_states_without_compression_raises(self):
        """A residual-carrying state file loaded into a trainer with no
        compression configured is a loud error, not silent data loss."""
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss
        import tempfile

        mx.random.seed(0)
        net = nn.Dense(2, in_units=4)
        net.initialize()
        tr = gluon.Trainer(
            net.collect_params(), "sgd", {"learning_rate": 0.1},
            kvstore="tpu_sync",
            compression_params={"type": "2bit", "threshold": 0.3})
        x = mx.nd.array(np.ones((4, 4), np.float32))
        y = mx.nd.array(np.zeros((4, 2), np.float32))
        with autograd.record():
            loss = L2Loss()(net(x), y)
        loss.backward()
        tr.step(4)
        fname = os.path.join(tempfile.mkdtemp(), "trainer.states")
        tr.save_states(fname)

        net2 = nn.Dense(2, in_units=4)
        net2.initialize()
        net2(x)
        tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="tpu_sync")
        with pytest.raises(MXNetError, match="compression"):
            tr2.load_states(fname)


class TestBucketTelemetry:
    def test_bucketed_counters_recorded(self):
        from mxnet_tpu import telemetry

        telemetry.enable()
        try:
            telemetry.reset()
            grads = _grads()
            _exchange(kv.create("tpu_sync"), grads)
            snap = telemetry.snapshot()["metrics"]
            coll = {s["labels"]["path"]: s["value"] for s in
                    snap["mxnet_kvstore_collective_dispatch_total"]
                    ["samples"]}
            assert coll.get("bucketed", 0) >= 1
            bb = snap["mxnet_kvstore_bucket_bytes"]["samples"][0]
            assert bb["count"] >= 1 and bb["sum"] > 0
            keys = snap["mxnet_kvstore_bucketed_keys_total"]["samples"]
            assert keys[0]["value"] == len(SHAPES)
            kv_ops = {s["labels"]["op"] for s in
                      snap["mxnet_kvstore_calls_total"]["samples"]}
            assert "pushpull" in kv_ops
        finally:
            telemetry.disable()
            telemetry.reset()

    def test_compression_counters_recorded(self):
        from mxnet_tpu import telemetry

        telemetry.enable()
        try:
            telemetry.reset()
            store = kv.create("device")
            store.set_gradient_compression(
                {"type": "2bit", "threshold": 0.5})
            store.init(0, mx.nd.zeros((8,)))
            g = mx.nd.array(np.ones(8, np.float32))
            store.pushpull([0], [g], out=[g])
            snap = telemetry.snapshot()["metrics"]
            ratio = snap["mxnet_kvstore_compression_ratio"]["samples"]
            assert ratio[0]["value"] == 16.0     # fp32 -> 2 bit
            els = snap["mxnet_kvstore_compressed_elements_total"]
            assert els["samples"][0]["value"] == 8
        finally:
            telemetry.disable()
            telemetry.reset()


class TestBackwardOverlap:
    """Backward-overlapped collectives (ISSUE 7): grad-ready hooks
    dispatch each bucket's pushpull INSIDE autograd.backward, results
    bit-identical to the at-step exchange."""

    def test_plan_pushpull_matches_bucket_plan(self):
        store = kv.create("local")
        store._bucket_bytes = 60  # tiny cap -> several buckets
        vals = _grads()
        nds = [[mx.nd.array(v) for v in vs] for vs in vals]
        for k, sh in enumerate(SHAPES):
            store.init(k, mx.nd.zeros(sh))
        keys = list(range(len(SHAPES)))
        groups = store.plan_pushpull(keys, nds, [-k for k in keys])
        # every key exactly once, in descending-priority dispatch order
        flat = [p for g in groups for p in g]
        assert sorted(flat) == keys
        assert flat == keys  # priority -k => ascending key order
        # each group fits the cap (or is a singleton oversize)
        for g in groups:
            nbytes = sum(4 * int(np.prod(SHAPES[p])) for p in g)
            assert len(g) == 1 or nbytes <= 60

    def test_plan_pushpull_perkey_when_disabled(self):
        store = kv.create("local")
        store._bucket_bytes = 0
        nds = [[mx.nd.array(v) for v in vs] for vs in _grads()]
        groups = store.plan_pushpull(list(range(len(SHAPES))), nds)
        assert groups == [[p] for p in range(len(SHAPES))]

    @staticmethod
    def _trainer_losses(bucket_mb, overlap, steps=4):
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss

        prev = os.environ.get("MXNET_KV_BUCKET_MB")
        os.environ["MXNET_KV_BUCKET_MB"] = str(bucket_mb)
        try:
            mx.random.seed(0)
            net = nn.HybridSequential()
            with net.name_scope():
                net.add(nn.Dense(32, in_units=16), nn.Dense(32),
                        nn.Dense(8))
            net.initialize()
            net(mx.nd.zeros((1, 16)))
            rs = np.random.RandomState(7)
            for p in net.collect_params().values():
                p.set_data(mx.nd.array(
                    rs.randn(*p.shape).astype(np.float32) * 0.1))
            ctxs = [mx.Context("cpu", 0), mx.Context("cpu", 1)]
            net.collect_params().reset_ctx(ctxs)
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.05},
                               kvstore="tpu_sync",
                               overlap_comms=overlap)
            loss_fn = L2Loss()
            rs2 = np.random.RandomState(11)
            x = rs2.randn(8, 16).astype(np.float32)
            y = rs2.randn(8, 8).astype(np.float32)
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
                losses.append(float(sum(l.asnumpy().sum()
                                        for l in ls)))
            weights = [p.data(ctxs[0]).asnumpy()
                       for p in net.collect_params().values()]
            return losses, weights, stats
        finally:
            if prev is None:
                os.environ.pop("MXNET_KV_BUCKET_MB", None)
            else:
                os.environ["MXNET_KV_BUCKET_MB"] = prev

    def test_overlapped_trainer_bit_identical_to_perkey(self):
        l_pk, w_pk, _ = self._trainer_losses(0, False)
        l_ov, w_ov, stats = self._trainer_losses(0.005, True)
        assert l_pk == l_ov
        for a, b in zip(w_pk, w_ov):
            np.testing.assert_array_equal(a, b)
        # steady state (hooks arm during step 1's kvstore init): every
        # bucket dispatched inside backward
        assert stats, "overlap stats not recorded"
        steady = stats[1:]
        assert steady and all(
            s["dispatched_in_backward"] == s["groups"] > 0
            for s in steady)

    def test_overlap_disabled_under_nonfinite_guard(self):
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon import nn

        net = nn.Dense(4, in_units=4)
        net.initialize()
        ctxs = [mx.Context("cpu", 0), mx.Context("cpu", 1)]
        net.collect_params().reset_ctx(ctxs)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, kvstore="tpu_sync",
                           overlap_comms=True, check_nonfinite=True)
        tr._init_kvstore()
        # the guard must see gradients BEFORE any reduce -> no overlap
        assert tr._overlap is None

    def test_watch_grad_ready_fires_inside_backward(self):
        from mxnet_tpu import autograd

        x = mx.nd.array(np.ones((2, 2), np.float32))
        x.attach_grad()
        seen = []

        class Owner:
            def cb(self, arr):
                # the grad buffer is already finalized when we fire
                seen.append(np.asarray(arr.grad.asnumpy()).copy())

        owner = Owner()
        autograd.watch_grad_ready([x], owner.cb)
        try:
            with autograd.record():
                y = (x * 3.0).sum()
            y.backward()
            assert len(seen) == 1
            np.testing.assert_allclose(seen[0], 3.0 * np.ones((2, 2)))
            # grad also visible after backward as usual
            np.testing.assert_allclose(x.grad.asnumpy(),
                                       3.0 * np.ones((2, 2)))
        finally:
            autograd.unwatch_grad_ready([x])

    def test_unwatch_and_dead_owner_are_safe(self):
        from mxnet_tpu import autograd

        x = mx.nd.array(np.ones((2,), np.float32))
        x.attach_grad()

        class Owner:
            hits = 0

            def cb(self, arr):
                Owner.hits += 1

        owner = Owner()
        autograd.watch_grad_ready([x], owner.cb)
        del owner  # weak callback: dead owner must not fire or leak
        with autograd.record():
            y = (x * 2.0).sum()
        y.backward()
        assert Owner.hits == 0
        np.testing.assert_allclose(x.grad.asnumpy(), 2.0 * np.ones(2))
        autograd.unwatch_grad_ready([x])

    def test_overlap_self_heals_after_abandoned_backward(self):
        """A backward not followed by step() (aborted iteration) must
        not leave stale dispatched-state that makes the NEXT step skip
        its exchange — the sweep-seq check resets it."""
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss

        prev = os.environ.get("MXNET_KV_BUCKET_MB")
        os.environ["MXNET_KV_BUCKET_MB"] = "0.005"
        try:
            def run(overlap):
                mx.random.seed(0)
                net = nn.HybridSequential()
                with net.name_scope():
                    net.add(nn.Dense(32, in_units=16), nn.Dense(8))
                net.initialize()
                net(mx.nd.zeros((1, 16)))
                rs = np.random.RandomState(7)
                for p in net.collect_params().values():
                    p.set_data(mx.nd.array(
                        rs.randn(*p.shape).astype(np.float32) * 0.1))
                ctxs = [mx.Context("cpu", 0), mx.Context("cpu", 1)]
                net.collect_params().reset_ctx(ctxs)
                tr = gluon.Trainer(net.collect_params(), "sgd",
                                   {"learning_rate": 0.05},
                                   kvstore="tpu_sync",
                                   overlap_comms=overlap)
                lf = L2Loss()
                rs2 = np.random.RandomState(11)
                x = rs2.randn(8, 16).astype(np.float32)
                y = rs2.randn(8, 8).astype(np.float32)

                def bwd():
                    with autograd.record():
                        ls = [lf(net(mx.nd.array(x[i * 4:(i + 1) * 4],
                                                 ctx=c)),
                                 mx.nd.array(y[i * 4:(i + 1) * 4],
                                             ctx=c))
                              for i, c in enumerate(ctxs)]
                    autograd.backward(ls)

                for step_i in range(3):
                    bwd()
                    if step_i == 1:
                        bwd()   # abandoned first backward: no step()
                    tr.step(8)
                return [p.data(ctxs[0]).asnumpy()
                        for p in net.collect_params().values()]

            w_pk = run(False)
            w_ov = run(True)
            for a, b in zip(w_pk, w_ov):
                np.testing.assert_array_equal(a, b)
        finally:
            if prev is None:
                os.environ.pop("MXNET_KV_BUCKET_MB", None)
            else:
                os.environ["MXNET_KV_BUCKET_MB"] = prev
