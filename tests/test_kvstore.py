"""KVStore tests (reference: tests/python/unittest/test_kvstore.py).

The tpu_sync collective path (round-2: a real shard_map+psum all-reduce,
not a host-side sum) is exercised on the 8-device virtual CPU mesh, and a
2-process jax.distributed bootstrap test covers the DMLC_* env contract.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import kvstore as kv
from mxnet_tpu.base import MXNetError


class TestLocal:
    def test_init_push_pull(self):
        store = kv.create("local")
        store.init(3, mx.nd.ones((2, 3)))
        out = mx.nd.zeros((2, 3))
        store.pull(3, out)
        np.testing.assert_allclose(out.asnumpy(), np.ones((2, 3)))
        store.push(3, mx.nd.full((2, 3), 4.0))
        store.pull(3, out)
        np.testing.assert_allclose(out.asnumpy(), np.full((2, 3), 4.0))

    def test_uninitialized_key_raises(self):
        store = kv.create("local")
        with pytest.raises(MXNetError, match="not initialized"):
            store.push(0, mx.nd.ones((1,)))

    def test_aggregates_multiple_values(self):
        store = kv.create("device")
        store.init("w", mx.nd.zeros((4,)))
        store.push("w", [mx.nd.ones((4,)) * i for i in range(1, 4)])
        out = mx.nd.zeros((4,))
        store.pull("w", out)
        np.testing.assert_allclose(out.asnumpy(), np.full((4,), 6.0))

    def test_server_side_updater(self):
        store = kv.create("local")
        store.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.5))
        store.init(0, mx.nd.ones((3,)))
        store.push(0, mx.nd.ones((3,)))  # w <- w - 0.5 * g
        out = mx.nd.zeros((3,))
        store.pull(0, out)
        np.testing.assert_allclose(out.asnumpy(), np.full((3,), 0.5))

    def test_dist_async_rejected_without_flag(self):
        with pytest.raises(MXNetError, match="MXNET_KVSTORE_DIST_ASYNC_EMU"):
            kv.create("dist_async")

    def test_dist_async_emulation_local_semantics(self, monkeypatch):
        """Single-process slice of the ADR-002 shim: pushes apply the
        server-side optimizer immediately to the local replica, no
        optimizer is a loud error, staleness knob is honored."""
        monkeypatch.setenv("MXNET_KVSTORE_DIST_ASYNC_EMU", "1")
        monkeypatch.setenv("MXNET_KVSTORE_ASYNC_STALENESS", "3")
        store = kv.create("dist_async")
        assert isinstance(store, kv.KVStoreDistAsyncEmu)
        assert store.staleness == 3
        store.init(0, mx.nd.zeros((3,)))
        with pytest.raises(MXNetError, match="server-side optimizer"):
            store.push(0, mx.nd.ones((3,)))
        store.set_optimizer(mx.optimizer.create("sgd", learning_rate=1.0,
                                                wd=0.0))
        for i in range(4):  # crosses the staleness boundary (no-op at P=1)
            store.push(0, mx.nd.ones((3,)))
        out = mx.nd.zeros((3,))
        store.pull(0, out)
        np.testing.assert_allclose(out.asnumpy(), np.full((3,), -4.0))

    def test_dist_async_sync_replicas_bounded_names_key(self, monkeypatch):
        """Uneven per-key push counts must not wedge the replica-sync
        psum forever (ADVICE r5): the pre-collective rendezvous is
        bounded by MXNET_KV_BARRIER_TIMEOUT and the typed error names
        the key, the lockstep contract, and ADR-002."""
        import jax

        from mxnet_tpu.kvstore import kvstore as kvmod

        monkeypatch.setenv("MXNET_KVSTORE_DIST_ASYNC_EMU", "1")
        store = kv.create("dist_async")
        # fake a 2-process world where the peer never announces
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(jax, "process_index", lambda: 0)

        class Stub:
            def __init__(self):
                self.d = {}

            def key_value_set(self, k, v):
                self.d[k] = v

            def key_value_dir_get(self, p):
                return [(k, v) for k, v in self.d.items()
                        if k.startswith(p)]

        monkeypatch.setattr(kvmod, "_coord_client", lambda: Stub())
        monkeypatch.setenv("MXNET_KV_BARRIER_TIMEOUT", "0.15")
        with pytest.raises(kv.BarrierTimeoutError) as ei:
            store._sync_replicas("weight0")
        msg = str(ei.value)
        assert "'weight0'" in msg
        assert "LOCKSTEP" in msg and "ADR-002" in msg
        assert "missing ranks [1]" in msg


class TestTPUSync:
    def test_push_is_one_collective(self):
        """Per-device copies reduce via ONE compiled psum; pulls into the
        participating devices are local views of the replicated result."""
        import jax

        devs = jax.devices()[:4]
        store = kv.create("tpu_sync")
        store.init(0, mx.nd.zeros((8, 16)))
        rs = np.random.RandomState(0)
        grads_np = [rs.randn(8, 16).astype(np.float32) for _ in devs]
        grads = [mx.nd.array(g).as_in_context(mx.Context("cpu", i))
                 for i, g in enumerate(grads_np)]
        # each copy must actually live on its own device
        for g, d in zip(grads, devs):
            assert next(iter(g.data.devices())) == d
        store.push(0, grads)
        outs = [mx.nd.zeros((8, 16), ctx=mx.Context("cpu", i))
                for i in range(len(devs))]
        store.pull(0, outs)
        want = np.sum(grads_np, axis=0)
        for i, o in enumerate(outs):
            np.testing.assert_allclose(o.asnumpy(), want, rtol=1e-6,
                                       err_msg=f"device {i}")
            assert next(iter(o.data.devices())) == devs[i]

    def test_reducer_cache_reused(self):
        store = kv.create("tpu_sync")
        store.init(0, mx.nd.zeros((4,)))
        store.init(1, mx.nd.zeros((4,)))
        for key in (0, 1):
            store.push(key, [mx.nd.ones((4,)).as_in_context(
                mx.Context("cpu", i)) for i in range(2)])
        assert len(store._reducers) == 1  # same signature -> one executable

    def test_trainer_tpu_sync_matches_single_device(self):
        """VERDICT #4 'done' criterion: Trainer with kvstore='tpu_sync'
        over per-device grads matches the plain single-device update."""
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon import nn

        def make_net(seed):
            net = nn.Dense(4, in_units=8)
            net.initialize(mx.init.Xavier(rnd_type="gaussian"), force_reinit=True)
            mx.random.seed(seed)
            w = np.random.RandomState(5).randn(4, 8).astype(np.float32)
            b = np.zeros(4, np.float32)
            net.weight.set_data(mx.nd.array(w))
            net.bias.set_data(mx.nd.array(b))
            return net

        rs = np.random.RandomState(1)
        x = rs.randn(8, 8).astype(np.float32)
        y = rs.randn(8, 4).astype(np.float32)

        # single device reference
        net1 = make_net(0)
        tr1 = gluon.Trainer(net1.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="local")
        from mxnet_tpu import autograd
        from mxnet_tpu.gluon.loss import L2Loss

        loss_fn = L2Loss()
        with autograd.record():
            l = loss_fn(net1(mx.nd.array(x)), mx.nd.array(y))
        l.backward()
        tr1.step(8)

        # 2-device data parallel via tpu_sync
        net2 = make_net(0)
        ctxs = [mx.Context("cpu", 0), mx.Context("cpu", 1)]
        net2.collect_params().reset_ctx(ctxs)
        tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="tpu_sync")
        with autograd.record():
            losses = [loss_fn(net2(mx.nd.array(x[i * 4:(i + 1) * 4],
                                               ctx=c)),
                              mx.nd.array(y[i * 4:(i + 1) * 4], ctx=c))
                      for i, c in enumerate(ctxs)]
        autograd.backward(losses)
        tr2.step(8)

        w1 = net1.weight.data().asnumpy()
        w2 = net2.weight.data(ctxs[0]).asnumpy()
        np.testing.assert_allclose(w2, w1, rtol=1e-5, atol=1e-6)


_DIST_WORKER = r"""
import os, sys
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
DEVS = int(os.environ.get("TEST_DEVS_PER_PROC", "2"))
NPROC = int(os.environ.get("TEST_NUM_PROC", "2"))
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + f" --xla_force_host_platform_device_count={DEVS}")
import mxnet_tpu as mx
from mxnet_tpu import kvstore as kv
store = kv.create("dist_sync")
import jax
assert jax.process_count() == NPROC, jax.process_count()
assert store.num_workers == NPROC
assert store.rank == int(os.environ["DMLC_WORKER_ID"])
# real cross-host reduce: each of the NPROC*DEVS global devices
# contributes rank*DEVS+i+1; the psum must cross process boundaries
rank = store.rank
total = NPROC * DEVS
want = total * (total + 1) / 2.0
store.init(0, mx.nd.zeros((4, 8)))
grads = [mx.nd.full((4, 8), float(rank * DEVS + i + 1),
                    ctx=mx.Context("cpu", i)) for i in range(DEVS)]
store.push(0, grads)
outs = [mx.nd.zeros((4, 8), ctx=mx.Context("cpu", i)) for i in range(DEVS)]
store.pull(0, outs)
for o in outs:
    got = o.asnumpy()
    assert np.allclose(got, want), (rank, got[0, 0], want)
sys.stdout.write(f"DIST_OK {store.rank}\n"); sys.stdout.flush()
"""


_DIST_ASYNC_WORKER = r"""
import os, sys
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1")
os.environ["MXNET_KVSTORE_DIST_ASYNC_EMU"] = "1"
os.environ["MXNET_KVSTORE_ASYNC_STALENESS"] = "2"
import mxnet_tpu as mx
from mxnet_tpu import kvstore as kv
store = kv.create("dist_async")
rank = store.rank
store.set_optimizer(mx.optimizer.create("sgd", learning_rate=1.0, wd=0.0))
store.init(0, mx.nd.zeros((2, 2)))
g = float(rank * 2 + 1)                      # rank0: 1, rank1: 3
# push 1: applied LOCALLY, no cross-process barrier -> replicas diverge
store.push(0, mx.nd.full((2, 2), g))
out = mx.nd.zeros((2, 2)); store.pull(0, out)
assert np.allclose(out.asnumpy(), -g), (rank, out.asnumpy()[0, 0])
# push 2 hits the staleness bound -> replicas averaged: mean(-2,-6) = -4
store.push(0, mx.nd.full((2, 2), g))
store.pull(0, out)
assert np.allclose(out.asnumpy(), -4.0), (rank, out.asnumpy()[0, 0])
# training continues locally on the synced value
store.push(0, mx.nd.full((2, 2), g))
store.pull(0, out)
assert np.allclose(out.asnumpy(), -4.0 - g), (rank, out.asnumpy()[0, 0])
sys.stdout.write(f"ASYNC_OK {rank}\n"); sys.stdout.flush()
"""


class TestDistSync:
    def _run_two_workers(self, tmp_path, source, ok_token):
        script = tmp_path / "worker.py"
        script.write_text(source)
        env_base = {k: v for k, v in os.environ.items()
                    if not k.startswith(("DMLC_", "XLA_FLAGS"))}
        import socket

        with socket.socket() as s:
            s.bind(("", 0))
            port = s.getsockname()[1]
        procs = []
        for rank in range(2):
            repo_root = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            env = dict(env_base,
                       JAX_PLATFORMS="cpu",
                       PYTHONPATH=repo_root + os.pathsep
                       + env_base.get("PYTHONPATH", ""),
                       DMLC_PS_ROOT_URI="127.0.0.1",
                       DMLC_PS_ROOT_PORT=str(port),
                       DMLC_NUM_WORKER="2",
                       DMLC_WORKER_ID=str(rank))
            procs.append(subprocess.Popen(
                [sys.executable, str(script)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            outs.append(out)
        for rank, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0 and f"{ok_token} {rank}" in out, \
                f"rank {rank} failed:\n{out[-2000:]}"

    def test_dist_async_emulation_bounded_staleness(self, tmp_path):
        """ADR-002 shim across 2 processes: pushes apply locally with no
        barrier (replicas diverge), the staleness-th push averages the
        replicas, training continues on the synced value."""
        self._run_two_workers(tmp_path, _DIST_ASYNC_WORKER, "ASYNC_OK")

    def test_two_process_bootstrap(self, tmp_path):
        """create('dist_sync') bootstraps jax.distributed from the DMLC_*
        env contract (SURVEY.md §5.6.4) — 2 local processes."""
        script = tmp_path / "worker.py"
        script.write_text(_DIST_WORKER)
        env_base = {k: v for k, v in os.environ.items()
                    if not k.startswith(("DMLC_", "XLA_FLAGS"))}
        import socket

        with socket.socket() as s:
            s.bind(("", 0))
            port = s.getsockname()[1]
        procs = []
        for rank in range(2):
            repo_root = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            env = dict(env_base,
                       JAX_PLATFORMS="cpu",
                       PYTHONPATH=repo_root + os.pathsep
                       + env_base.get("PYTHONPATH", ""),
                       DMLC_PS_ROOT_URI="127.0.0.1",
                       DMLC_PS_ROOT_PORT=str(port),
                       DMLC_NUM_WORKER="2",
                       DMLC_WORKER_ID=str(rank))
            procs.append(subprocess.Popen(
                [sys.executable, str(script)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            outs.append(out)
        for rank, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0 and f"DIST_OK {rank}" in out, \
                f"rank {rank} failed:\n{out[-2000:]}"


class TestLauncher:
    def test_local_launch_two_workers(self, tmp_path):
        """tools/launch.py local mode: exports the DMLC_* contract and the
        workers rendezvous through jax.distributed."""
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = tmp_path / "worker.py"
        script.write_text(_DIST_WORKER)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("DMLC_", "XLA_FLAGS"))}
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, os.path.join(repo_root, "tools", "launch.py"),
             "-n", "2", sys.executable, str(script)],
            env=env, capture_output=True, text=True, timeout=180)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "DIST_OK 0" in out.stdout and "DIST_OK 1" in out.stdout, \
            out.stdout + out.stderr

    def test_pushed_value_is_snapshotted(self):
        """Round-2 review finding: mutating a pushed NDArray afterwards
        must not change the stored value."""
        store = kv.create("tpu_sync")
        store.init(1, mx.nd.zeros((3,)))
        g = mx.nd.ones((3,))
        store.push(1, g)
        g += 41
        out = mx.nd.zeros((3,))
        store.pull(1, out)
        np.testing.assert_allclose(out.asnumpy(), np.ones(3))

    def test_string_key_updater_state_stable(self):
        """String keys index updater state by the key itself (stable),
        not hash() (process-randomized)."""
        store = kv.create("local")
        store.set_optimizer(mx.optimizer.create("sgd", learning_rate=1.0,
                                                momentum=0.9))
        store.init("fc_weight", mx.nd.ones((2,)))
        store.push("fc_weight", mx.nd.ones((2,)))
        assert "fc_weight" in store._updater.states


class TestGradientCompression:
    def test_2bit_quantization_and_error_feedback(self):
        from mxnet_tpu.kvstore.gradient_compression import (
            GradientCompression, create_compression)

        comp = GradientCompression(threshold=0.5)
        g = mx.nd.array(np.array([0.9, -0.7, 0.1, -0.2, 0.0],
                                  dtype="float32"))
        q = comp.compress("w", 0, g)
        np.testing.assert_allclose(q.asnumpy(), [0.5, -0.5, 0.0, 0.0, 0.0])
        # error feedback: for gradients within +-t, repeated pushes
        # transmit the true mean in the limit (residual carries the
        # remainder; |g| > t saturates at t/round by construction)
        g2 = mx.nd.array(np.array([0.4, -0.3, 0.1, -0.2, 0.0],
                                  dtype="float32"))
        total = np.zeros(5, dtype="float32")
        for _ in range(40):
            total += comp.compress("w2", 0, g2).asnumpy()
        np.testing.assert_allclose(total / 40.0, g2.asnumpy(),
                                   atol=0.5 / 40)

        with pytest.raises(mx.base.MXNetError, match="type"):
            create_compression({"type": "1bit"})
        with pytest.raises(mx.base.MXNetError, match="threshold"):
            create_compression({"type": "2bit", "threshold": -1.0})

    def test_kvstore_push_compressed(self):
        kv = mx.kv.create("local")
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.25})
        v = mx.nd.zeros((4,))
        kv.init("x", v)
        kv.push("x", mx.nd.array(np.array([1.0, -1.0, 0.1, 0.0],
                                           dtype="float32")))
        out = mx.nd.zeros((4,))
        kv.pull("x", out)
        # every transmitted value is on the {-t, 0, +t} grid
        got = out.asnumpy()
        assert set(np.round(got / 0.25).astype(int)) <= {-1, 0, 1}, got

    def test_trainer_with_compression_converges(self):
        from mxnet_tpu.gluon import Trainer, nn
        from mxnet_tpu import autograd

        np.random.seed(21)
        net = nn.Dense(1)
        net.initialize()
        rs = np.random.RandomState(22)
        x = mx.nd.array(rs.randn(64, 4).astype("float32"))
        w_true = np.array([[1.0, -2.0, 0.5, 3.0]], dtype="float32")
        y = mx.nd.array(x.asnumpy() @ w_true.T)
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.05}, kvstore="tpu_sync",
                          compression_params={"type": "2bit",
                                              "threshold": 2.0})
        losses = []
        for _ in range(200):
            with autograd.record():
                loss = ((net(x) - y) ** 2).mean()
            loss.backward()
            trainer.step(1)
            losses.append(float(loss.asnumpy()))
        assert losses[-1] < losses[0] * 0.1, (losses[0], losses[-1])


class TestMultiHostHardening:
    """Round-3 (VERDICT #8): beyond 2 localhost processes."""

    def test_four_process_two_device_composition(self, tmp_path):
        """4 processes x 2 local devices: per-process device meshes
        compose with the cross-process (DCN) psum — 8 global devices."""
        script = tmp_path / "worker.py"
        script.write_text(_DIST_WORKER)
        env_base = {k: v for k, v in os.environ.items()
                    if not k.startswith(("DMLC_", "XLA_FLAGS"))}
        import socket

        with socket.socket() as s:
            s.bind(("", 0))
            port = s.getsockname()[1]
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        procs = []
        for rank in range(4):
            env = dict(env_base,
                       JAX_PLATFORMS="cpu",
                       PYTHONPATH=repo_root + os.pathsep
                       + env_base.get("PYTHONPATH", ""),
                       TEST_NUM_PROC="4", TEST_DEVS_PER_PROC="2",
                       DMLC_PS_ROOT_URI="127.0.0.1",
                       DMLC_PS_ROOT_PORT=str(port),
                       DMLC_NUM_WORKER="4",
                       DMLC_WORKER_ID=str(rank))
            procs.append(subprocess.Popen(
                [sys.executable, str(script)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=240)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            outs.append(out)
        for rank, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {rank}:\n{out}"
            assert f"DIST_OK {rank}" in out, f"rank {rank}:\n{out}"

    def test_ssh_mode_dry_run(self, tmp_path):
        """launch.py -H hostfile fans out over ssh; a stub ssh on PATH
        executes the remote command locally, validating the full export
        + quoting + cd contract without a real cluster."""
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = tmp_path / "worker.py"
        script.write_text(_DIST_WORKER)
        hostfile = tmp_path / "hosts"
        # both "hosts" are loopback so the coordinator (hosts[0]) is
        # reachable; the ssh fanout/quoting contract is what's under test
        hostfile.write_text("127.0.0.1\n127.0.0.1\n")
        ssh_stub = tmp_path / "ssh"
        ssh_stub.write_text(
            "#!/bin/sh\n"
            "# drop ssh options (-o val pairs) and the host, run the rest\n"
            'while [ "$1" = "-o" ]; do shift 2; done\n'
            "host=$1; shift\n"
            'echo "SSH_STUB host=$host" 1>&2\n'
            'exec /bin/sh -c "$*"\n')
        ssh_stub.chmod(0o755)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("DMLC_", "XLA_FLAGS"))}
        env["PATH"] = str(tmp_path) + os.pathsep + env.get("PATH", "")
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, os.path.join(repo_root, "tools", "launch.py"),
             "-n", "2", "-H", str(hostfile),
             "--env", "TEST_NUM_PROC=2", "--env", "TEST_DEVS_PER_PROC=2",
             "--env", "JAX_PLATFORMS=cpu",
             "--env", "PYTHONPATH=" + env["PYTHONPATH"],
             sys.executable, str(script)],
            env=env, capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "DIST_OK 0" in out.stdout and "DIST_OK 1" in out.stdout, \
            out.stdout + out.stderr
        assert out.stderr.count("SSH_STUB host=127.0.0.1") == 2
