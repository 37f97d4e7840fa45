"""Out-of-process serving (mxnet_tpu/serving/{wire,worker,remote,
ingress}.py + the scrape-fed control plane): frame protocol safety
(half-written frames discarded, never mis-parsed), ingress backpressure
as synchronous typed error frames, crash-isolated replica workers
(connection drop / waitpid = typed failure + breaker trip + respawn +
half-open re-admission), and FleetController decisions fed from
/metrics scrapes.

Worker-process semantics are covered two ways: a protocol-faithful
FAKE worker (a thread speaking the wire protocol through the
``RemoteReplica._spawn`` seam — every failure mode, no interpreter
spawn cost) for the tier-1 suite, and one real-subprocess end-to-end
test marked ``slow`` (``tools/chaos_check.py`` gate 8 exercises the
real thing under traffic).
"""
import os
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import fault, serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import wire
from mxnet_tpu.serving.health import CLOSED
from mxnet_tpu.serving.router import FailoverExhausted, ServerOverloaded

pytestmark = pytest.mark.serving

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
if FIXTURES not in sys.path:
    sys.path.insert(0, FIXTURES)

import worker_factory  # noqa: E402  (the fixtures dir is the point)


def traffic(n=16, dim=8):
    return [np.random.RandomState(100 + i).randn(dim).astype(np.float32)
            for i in range(n)]


def wait_until(pred, timeout=10.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture(autouse=True)
def _fast_knobs(monkeypatch):
    monkeypatch.setenv("MXNET_COMM_RETRY_DELAY", "0.01")
    monkeypatch.setenv("MXNET_SERVING_BREAKER_FAILURES", "2")
    monkeypatch.setenv("MXNET_SERVING_BREAKER_COOLDOWN", "0.25")


# ---------------------------------------------------------------------------
# wire.py: framing + payload codec + typed error mapping
# ---------------------------------------------------------------------------

class TestWire:
    def test_payload_round_trip_nested(self):
        obj = {"kind": "result", "id": 7, "ok": True,
               "payload": [np.arange(6, dtype=np.float32).reshape(2, 3),
                           ("s", np.float64(2.5), None,
                            {"k": np.int32(9), "f": 1.25, "b": True})]}
        h, b = wire.encode_payload(obj)
        back = wire.decode_payload(h, b)
        arr = back["payload"][0]
        assert arr.dtype == np.float32 and \
            np.array_equal(arr, obj["payload"][0])
        tail = back["payload"][1]
        assert isinstance(tail, tuple) and tail[0] == "s"
        assert tail[1] == 2.5 and tail[2] is None
        assert tail[3]["k"] == 9 and isinstance(tail[3]["k"], np.int32)

    def test_payload_rejects_unencodable(self):
        with pytest.raises(wire.FrameError):
            wire.encode_payload({"kind": "x", "bad": object()})

    def test_frame_round_trip_over_socketpair(self):
        a, b = socket.socketpair()
        try:
            sent = {"kind": "submit", "id": 1,
                    "sample": np.ones(4, np.float32)}
            wire.send_frame(a, sent)
            got = wire.recv_frame(b)
            assert got["kind"] == "submit" and got["id"] == 1
            assert np.array_equal(got["sample"], sent["sample"])
        finally:
            a.close()
            b.close()

    def test_half_written_frame_discarded_not_misparsed(self):
        """A peer that dies mid-sendall leaves a truncated tail: the
        reader must see ConnectionClosed for it — after cleanly
        delivering every COMPLETE frame before it."""
        a, b = socket.socketpair()
        try:
            wire.send_frame(a, {"kind": "health", "age": 0.0})
            h, body = wire.encode_payload({"kind": "result", "id": 5,
                                           "ok": True, "payload": 1})
            raw = wire._HEADER.pack(wire.MAGIC, len(h), len(body)) \
                + h + body
            a.sendall(raw[: len(raw) // 2])
            a.close()
            assert wire.recv_frame(b)["kind"] == "health"
            with pytest.raises(wire.ConnectionClosed):
                wire.recv_frame(b)
        finally:
            b.close()

    def test_bad_magic_is_frame_error(self):
        a, b = socket.socketpair()
        try:
            a.sendall(b"NOPE" + b"\x00" * 8)
            with pytest.raises(wire.FrameError):
                wire.recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_absurd_length_is_frame_error(self):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack("!4sII", wire.MAGIC, 1 << 30, 1 << 30))
            with pytest.raises(wire.FrameError):
                wire.recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_typed_error_mapping_round_trips(self):
        for exc, etype, back_type in (
                (ServerOverloaded("full"), "overloaded",
                 ServerOverloaded),
                (FailoverExhausted("spent"), "failover_exhausted",
                 FailoverExhausted),
                (fault.FaultInjected("s", 1), "fault_injected",
                 MXNetError),
                (MXNetError("x"), "mxnet_error", MXNetError),
                (RuntimeError("y"), "internal", MXNetError)):
            name, msg = wire.encode_error(exc)
            assert name == etype
            got = wire.decode_error(name, msg)
            assert isinstance(got, back_type)

    def test_fault_sites_registered(self):
        assert "serving.ingress" in fault.SITES
        assert "worker.spawn" in fault.SITES
        # the indexed sub-site form parses (the PR-9 contract)
        spec = fault.parse_spec("worker.spawn.0=once")
        assert "worker.spawn.0" in spec
        with pytest.raises(MXNetError):
            fault.parse_spec("kvstore.push.0=once")

    def test_writer_preserves_order_under_concurrent_senders(self):
        """The inline fast path must never reorder frames: whatever
        interleaving of inline writes and writer-thread drains happens,
        each sender thread's ids arrive in its send() order."""
        a, b = socket.socketpair()
        w = wire.FrameWriter(a, name="t-order")
        per, senders = 200, 4
        try:
            def feed(tid):
                for i in range(per):
                    w.send({"kind": "result", "id": tid * per + i,
                            "ok": True})
            ths = [threading.Thread(target=feed, args=(t,))
                   for t in range(senders)]
            for t in ths:
                t.start()
            got = {t: [] for t in range(senders)}
            rf = wire.reader(b)
            for _ in range(per * senders):
                fid = wire.recv_frame(rf)["id"]
                got[fid // per].append(fid % per)
            for t in ths:
                t.join()
            for tid in range(senders):
                assert got[tid] == list(range(per)), \
                    f"sender {tid} frames reordered"
        finally:
            w.close(flush=False, timeout=2)
            a.close()
            b.close()

    def test_poisoned_writer_raises_frame_error_not_connection_closed(
            self):
        """An unencodable payload poisons the stream; later sends must
        raise FrameError — NOT ConnectionClosed — so a worker can tell
        'parent went away' (swallow, exit clean) from 'this stream can
        never speak again' (die loud, get respawned)."""
        a, b = socket.socketpair()
        w = wire.FrameWriter(a, name="t-poison")
        try:
            with pytest.raises(wire.FrameError):
                w.send({"kind": "x", "bad": object()})
            with pytest.raises(wire.FrameError) as ei:
                w.send({"kind": "result", "id": 1, "ok": True})
            assert not isinstance(ei.value, wire.ConnectionClosed)
        finally:
            w.close(flush=False, timeout=2)
            a.close()
            b.close()

    def test_writer_never_blocks_caller_on_full_socket(self):
        """send() into a peer that is not reading must return
        immediately (inline path defers to the writer thread once the
        socket buffer fills) — the dispatcher-never-blocks contract."""
        a, b = socket.socketpair()
        for s in (a, b):
            for opt in (socket.SO_SNDBUF, socket.SO_RCVBUF):
                s.setsockopt(socket.SOL_SOCKET, opt, 4096)
        w = wire.FrameWriter(a, name="t-noblock")
        try:
            payload = {"kind": "submit", "id": 0,
                       "sample": np.zeros(8192, np.float32)}
            t0 = time.monotonic()
            for i in range(16):     # ~0.5 MB >> the 4 KB buffers
                w.send(dict(payload, id=i))
            assert time.monotonic() - t0 < 1.0, \
                "send() blocked on a full socket buffer"
            # and the frames all arrive intact once the peer reads
            rf = wire.reader(b)
            ids = sorted(wire.recv_frame(rf)["id"] for _ in range(16))
            assert ids == list(range(16))
        finally:
            w.close(flush=False, timeout=2)
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# ingress.py: socket edge over an in-process router
# ---------------------------------------------------------------------------

def make_router(n=2, slo_ms=50, **kw):
    reps = [serving.Server(worker_factory.tiny_net(),
                           batch_buckets=(2, 4), shape_buckets=[(8,)],
                           slo_ms=slo_ms, name=f"rep{i}", **kw)
            for i in range(n)]
    return serving.Router(reps, slo_ms=slo_ms).start()


def make_paced_router(dispatch_ms=60.0, slo_ms=2000):
    srv = serving.Server(worker_factory.paced_block(dispatch_ms),
                         batch_buckets=(2,), shape_buckets=[(8,)],
                         slo_ms=slo_ms, warmup=False, name="paced0")
    return serving.Router([srv], slo_ms=slo_ms).start()


class TestIngress:
    def test_results_bit_identical_through_the_socket(self):
        router = make_router()
        try:
            with serving.Ingress(router, window=64) as ing, \
                    serving.IngressClient("127.0.0.1", ing.port) as cli:
                xs = traffic(12)
                outs = [cli.submit(x).result(timeout=15) for x in xs]
                refs = [router.submit(x).result(timeout=15) for x in xs]
                assert all(np.array_equal(a, b)
                           for a, b in zip(outs, refs))
        finally:
            router.stop(timeout=30)

    def test_window_backpressure_is_typed_and_synchronous(self):
        """A submit past the per-connection window must come back as a
        typed ServerOverloaded error frame IMMEDIATELY — while the
        window's own requests are still in flight — not as a timeout
        or a dropped connection."""
        router = make_paced_router(dispatch_ms=120.0)
        try:
            with serving.Ingress(router, window=2) as ing, \
                    serving.IngressClient("127.0.0.1", ing.port) as cli:
                xs = traffic(3)
                f1, f2 = cli.submit(xs[0]), cli.submit(xs[1])
                # both window slots taken (in flight at the ingress)
                time.sleep(0.05)
                t0 = time.perf_counter()
                f3 = cli.submit(xs[2])
                with pytest.raises(ServerOverloaded):
                    f3.result(timeout=5)
                dt = time.perf_counter() - t0
                assert dt < 0.1, \
                    f"overload frame took {dt:.3f}s (not synchronous)"
                assert not f1.done(), \
                    "window requests should still be in flight"
                assert f1.result(timeout=15) is not None
                assert f2.result(timeout=15) is not None
        finally:
            router.stop(timeout=30)

    def test_router_shed_maps_to_typed_overload_frame(self):
        """The Router's own synchronous admission shed (queue full)
        crosses the wire as the same typed ServerOverloaded."""
        router = make_paced_router(dispatch_ms=120.0)
        router.max_queue = 1
        try:
            with serving.Ingress(router, window=32) as ing, \
                    serving.IngressClient("127.0.0.1", ing.port) as cli:
                xs = traffic(6)
                futs = [cli.submit(x) for x in xs]
                outcomes = []
                for f in futs:
                    try:
                        f.result(timeout=20)
                        outcomes.append("ok")
                    except ServerOverloaded:
                        outcomes.append("shed")
                assert "shed" in outcomes
                assert all(o in ("ok", "shed") for o in outcomes)
        finally:
            router.stop(timeout=30)

    def test_client_disconnect_mid_request_ingress_survives(self):
        router = make_paced_router(dispatch_ms=100.0)
        try:
            ing = serving.Ingress(router, window=8).start()
            try:
                cli = serving.IngressClient("127.0.0.1", ing.port)
                cli.submit(traffic(1)[0])
                cli.close()         # walk away with a request in flight
                # the edge keeps serving: a fresh connection works and
                # the abandoned request's result is discarded, not an
                # ingress crash
                with serving.IngressClient("127.0.0.1",
                                           ing.port) as cli2:
                    out = cli2.submit(traffic(1)[0]).result(timeout=15)
                    assert out is not None
                assert ing.is_running
            finally:
                ing.stop()
        finally:
            router.stop(timeout=30)

    def test_ingress_stop_resolves_client_futures_typed(self):
        router = make_paced_router(dispatch_ms=150.0)
        try:
            ing = serving.Ingress(router, window=8).start()
            cli = serving.IngressClient("127.0.0.1", ing.port)
            futs = [cli.submit(x) for x in traffic(2)]
            ing.stop()
            for f in futs:
                with pytest.raises(MXNetError):   # IngressDisconnected
                    f.result(timeout=5)           # typed, never a hang
            cli.close()
        finally:
            router.stop(timeout=30)

    def test_garbage_stream_closes_connection_only(self):
        router = make_router()
        try:
            with serving.Ingress(router, window=8) as ing:
                raw = socket.create_connection(("127.0.0.1", ing.port))
                raw.sendall(b"\xde\xad\xbe\xef" * 8)
                raw.close()
                # a second, half-written-frame client
                raw2 = socket.create_connection(("127.0.0.1", ing.port))
                h, b = wire.encode_payload(
                    {"kind": "submit", "id": 1,
                     "sample": np.ones(8, np.float32)})
                partial = wire._HEADER.pack(wire.MAGIC, len(h),
                                            len(b)) + h
                raw2.sendall(partial[: len(partial) - 4])
                raw2.close()
                # the edge survives both and keeps serving
                with serving.IngressClient("127.0.0.1",
                                           ing.port) as cli:
                    assert cli.submit(traffic(1)[0]).result(
                        timeout=15) is not None
        finally:
            router.stop(timeout=30)

    def test_ingress_fault_site_rejects_typed(self):
        router = make_router()
        try:
            with serving.Ingress(router, window=8) as ing, \
                    serving.IngressClient("127.0.0.1", ing.port) as cli:
                with fault.inject("serving.ingress=once"):
                    f1 = cli.submit(traffic(1)[0])
                    with pytest.raises(MXNetError):
                        f1.result(timeout=5)
                    assert cli.submit(traffic(2)[1]).result(
                        timeout=15) is not None
                assert ing.n_rejected >= 1
        finally:
            router.stop(timeout=30)

    def test_ingress_metrics_exported(self):
        telemetry.enable()
        try:
            telemetry.reset()
            router = make_paced_router(dispatch_ms=60.0)
            try:
                with serving.Ingress(router, window=1) as ing, \
                        serving.IngressClient("127.0.0.1",
                                              ing.port) as cli:
                    f1 = cli.submit(traffic(1)[0])
                    time.sleep(0.03)
                    f2 = cli.submit(traffic(2)[1])   # past the window
                    with pytest.raises(ServerOverloaded):
                        f2.result(timeout=5)
                    f1.result(timeout=15)
                    # the edge counts a request AFTER it wrote the reply
                    # frame: the client can be here first
                    wait_until(lambda: 'mxnet_ingress_requests_total'
                               '{outcome="ok"}' in telemetry.prom_text(),
                               msg="ok request counted")
                    txt = telemetry.prom_text()
                    assert 'mxnet_ingress_connections{state="open"}' \
                        in txt
                    assert 'mxnet_ingress_rejected_total' \
                        '{reason="window_full"} 1' in txt
            finally:
                router.stop(timeout=30)
        finally:
            telemetry.disable()
            telemetry.reset()


# ---------------------------------------------------------------------------
# remote.py against a protocol-faithful fake worker (the _spawn seam)
# ---------------------------------------------------------------------------

class FakeProc:
    """Stand-in for subprocess.Popen: poll/wait/terminate/kill backed
    by an Event, so waitpid semantics are testable without an exec."""

    _next_pid = [50000]

    def __init__(self):
        self._rc = None
        self._done = threading.Event()
        FakeProc._next_pid[0] += 1
        self.pid = FakeProc._next_pid[0]
        self.on_terminate = None

    def poll(self):
        return self._rc

    def wait(self, timeout=None):
        if not self._done.wait(timeout):
            raise subprocess.TimeoutExpired("fake-worker", timeout)
        return self._rc

    def exit(self, rc):
        if self._rc is None:
            self._rc = rc
            self._done.set()

    def terminate(self):
        if self.on_terminate is not None:
            self.on_terminate()
        self.exit(-15)

    kill = terminate


class FakeWorker:
    """A thread speaking the worker wire protocol. ``mode``:
    ``"echo"`` serves ``sample * 2``; ``"drop_after_submit"`` closes
    the connection (no result) on the first submit;
    ``"torn_frame_after_submit"`` writes HALF a result frame then
    dies; ``"hold"`` accepts submits and never answers (hung worker:
    health frames keep flowing with a growing scheduler age)."""

    def __init__(self, rep, mode="echo"):
        self.rep = rep
        self.mode = mode
        self.proc = FakeProc()
        self.stop_health = threading.Event()

    def spawn(self, port):
        threading.Thread(target=self._run, args=(port,),
                         daemon=True).start()
        return self.proc

    def _run(self, port):
        sock = wire.connect("127.0.0.1", port, timeout=10)
        self.proc.on_terminate = sock.close
        send_lock = threading.Lock()
        grid = self.rep.grid
        t_start = time.monotonic()

        def send(frame):
            with send_lock:
                wire.send_frame(sock, frame)

        send({"kind": "hello", "name": self.rep.name,
              "pid": self.proc.pid,
              "batch_buckets": list(grid.batch_buckets),
              "shape_buckets": [list(s) for s in grid.shape_buckets]
              if grid.shape_buckets else None,
              "slo_ms": self.rep.slo_s * 1e3, "metrics_port": None})

        def health_loop():
            while not self.stop_health.wait(0.02):
                age = (time.monotonic() - t_start
                       if self.mode == "hold" else 0.0)
                try:
                    send({"kind": "health", "age": age,
                          "queue_depth": 0, "requests": 0,
                          "batches": 0, "errors": 0})
                except OSError:
                    return

        threading.Thread(target=health_loop, daemon=True).start()
        try:
            while True:
                frame = wire.recv_frame(sock)
                if frame["kind"] == "submit":
                    if self.mode == "drop_after_submit":
                        sock.close()
                        self.proc.exit(-9)
                        return
                    if self.mode == "torn_frame_after_submit":
                        h, b = wire.encode_payload(
                            {"kind": "result", "id": frame["id"],
                             "ok": True,
                             "payload": np.ones(64, np.float32)})
                        raw = wire._HEADER.pack(
                            wire.MAGIC, len(h), len(b)) + h + b
                        with send_lock:
                            sock.sendall(raw[: len(raw) // 2])
                            sock.close()
                        self.proc.exit(-9)
                        return
                    if self.mode == "hold":
                        continue
                    send({"kind": "result", "id": frame["id"],
                          "ok": True,
                          "payload": frame["sample"] * 2})
                elif frame["kind"] == "stop":
                    send({"kind": "bye"})
                    sock.close()
                    self.proc.exit(0)
                    return
        except (wire.FrameError, OSError):
            self.proc.exit(self.proc._rc if self.proc._rc is not None
                           else -9)
        finally:
            self.stop_health.set()


def fake_remote(mode="echo", name="w0", respawn=True, **kw):
    """A RemoteReplica whose spawns produce FakeWorkers (list of all
    incarnations returned for inspection)."""
    kw.setdefault("batch_buckets", (2, 4))
    kw.setdefault("shape_buckets", [(8,)])
    kw.setdefault("slo_ms", 50)
    kw.setdefault("respawn_backoff_s", 0.05)
    rep = serving.RemoteReplica("worker_factory:tiny_net", name=name,
                                python_paths=[FIXTURES],
                                respawn=respawn, **kw)
    incarnations = []

    def spawn(port):
        w = FakeWorker(rep, mode=mode)
        incarnations.append(w)
        return w.spawn(port)

    rep._spawn = spawn
    return rep, incarnations


class TestRemoteReplica:
    def test_submit_resolves_through_fake_worker(self):
        rep, _ = fake_remote()
        rep.start()
        try:
            x = traffic(1)[0]
            out = rep.submit(x).result(timeout=10)
            assert np.array_equal(out, x * 2)
            assert rep.is_running and rep.crash_count == 0
        finally:
            rep.stop()
        assert not rep.is_running

    def test_connection_drop_mid_request_resolves_typed(self):
        rep, _ = fake_remote(mode="drop_after_submit", respawn=False)
        rep.start()
        try:
            fut = rep.submit(traffic(1)[0])
            with pytest.raises(serving.WorkerCrashed):
                fut.result(timeout=10)      # typed, never a hang
            wait_until(lambda: not rep.is_running, 5,
                       msg="handle marks worker down")
            assert rep.crash_count == 1
            with pytest.raises(MXNetError):
                rep.submit(traffic(1)[0])   # down = synchronous typed
        finally:
            rep.stop()

    def test_half_written_result_frame_is_discarded(self):
        """A worker that dies mid-result leaves a torn frame: the
        request resolves WorkerCrashed — it must never resolve with a
        mis-parsed payload."""
        rep, _ = fake_remote(mode="torn_frame_after_submit",
                             respawn=False)
        rep.start()
        try:
            fut = rep.submit(traffic(1)[0])
            with pytest.raises(serving.WorkerCrashed):
                fut.result(timeout=10)
            assert rep.crash_count == 1
        finally:
            rep.stop()

    def test_waitpid_detects_death_without_socket_close(self):
        """The second unambiguous signal: the process is reaped while
        the socket happens to stay open (fake keeps it) — waitpid
        alone must fail the in-flight future typed."""
        rep, workers = fake_remote(mode="hold", respawn=False)
        rep.start()
        try:
            fut = rep.submit(traffic(1)[0])
            workers[0].proc.exit(-9)        # reaped, socket untouched
            with pytest.raises(serving.WorkerCrashed):
                fut.result(timeout=10)
            assert rep.crash_count == 1
        finally:
            workers[0].stop_health.set()
            rep.stop()

    def test_respawn_backoff_and_restart_metric(self):
        telemetry.enable()
        try:
            telemetry.reset()
            rep, workers = fake_remote(mode="echo")
            rep.start()
            try:
                workers[0].proc.on_terminate()   # kill the connection
                workers[0].proc.exit(-9)
                wait_until(lambda: rep.is_running and
                           rep.n_restarts == 1, 10,
                           msg="respawn re-establishes the worker")
                out = rep.submit(traffic(1)[0]).result(timeout=10)
                assert out is not None
                assert 'mxnet_worker_restarts_total{replica="w0"} 1' \
                    in telemetry.prom_text()
            finally:
                rep.stop()
        finally:
            telemetry.disable()
            telemetry.reset()

    def test_respawn_budget_bounds_failed_attempts(self):
        """A permanently-broken spawn path must reach a terminal state:
        max_respawns bounds FAILED attempts, not only successes."""
        rep, workers = fake_remote(mode="echo", max_respawns=2,
                                   respawn_backoff_s=0.01)
        rep.start()
        try:
            def broken_spawn(port):
                raise RuntimeError("factory module deleted")
            rep._spawn = broken_spawn
            workers[0].proc.on_terminate()      # crash the worker
            workers[0].proc.exit(-9)
            wait_until(lambda: rep._respawner is not None and
                       not rep._respawner.is_alive(), 10,
                       msg="respawner gives up after the budget")
            assert rep.n_restarts == 0 and not rep.is_running
        finally:
            rep.stop()

    def test_rolling_upgrade_refuses_remote_fleet_typed(self):
        """rolling_upgrade over out-of-process workers must refuse
        typed BEFORE anything swaps (RemoteReplica has no in-place
        swap_model), not die with an AttributeError mid-rollout."""
        rep, _ = fake_remote()
        router = serving.Router([rep], slo_ms=50).start()
        try:
            with pytest.raises(MXNetError, match="swap_model"):
                serving.rolling_upgrade(router, lambda srv: None)
        finally:
            router.stop(drain=False, timeout=30)

    def test_spawn_fault_site_and_indexed_subsite(self):
        rep, _ = fake_remote(respawn=False)
        with fault.inject("worker.spawn=once"):
            with pytest.raises(fault.FaultInjected):
                rep.start()
        # the indexed sub-site targets exactly this worker's spawns
        rep2, _ = fake_remote(name="w1", respawn=False)
        other = f"worker.spawn.{rep2.worker_index + 1000}"
        with fault.inject(f"{other}=once"):
            rep2.start()                    # someone else's index
            rep2.stop()
        rep3, _ = fake_remote(name="w2", respawn=False)
        with fault.inject(f"worker.spawn.{rep3.worker_index}=once"):
            with pytest.raises(fault.FaultInjected):
                rep3.start()

    def test_router_failover_crash_trip_and_readmission(self):
        """The whole loop at router level: a crashed worker's in-flight
        requests fail over typed (zero lost), its breaker trips
        IMMEDIATELY on the crash signal (no failure-threshold grace),
        and the respawned worker is re-admitted via half-open probe."""
        rep, workers = fake_remote(mode="echo",
                                   respawn_backoff_s=0.05)
        sibling = serving.Server(worker_factory.tiny_net(),
                                 batch_buckets=(2, 4),
                                 shape_buckets=[(8,)], slo_ms=50,
                                 name="local0")
        router = serving.Router([rep, sibling], slo_ms=200,
                                dispatch_timeout_s=2.0).start()
        try:
            xs = traffic(8)
            futs = [router.submit(x) for x in xs]
            workers[0].proc.on_terminate()          # SIGKILL stand-in
            workers[0].proc.exit(-9)
            futs += [router.submit(x) for x in xs]
            resolved = 0
            for f in futs:
                try:
                    f.result(timeout=20)
                    resolved += 1
                except MXNetError:
                    resolved += 1           # typed counts as resolved
            assert resolved == len(futs)    # zero lost futures
            wait_until(lambda: {r["name"]: r for r in
                                router.stats()["replicas"]
                                }["w0"]["trips"] >= 1, 10,
                       msg="crash trips the breaker")
            # respawn + half-open probe re-admission under traffic
            ok0 = {r["name"]: r for r in
                   router.stats()["replicas"]}["w0"]["ok"]

            def readmitted():
                try:
                    router.submit(traffic(1)[0]).result(timeout=5)
                except MXNetError:
                    pass
                st = {r["name"]: r
                      for r in router.stats()["replicas"]}["w0"]
                return st["state"] == CLOSED and st["ok"] > ok0
            wait_until(readmitted, 20,
                       msg="respawned worker re-admitted by probe")
        finally:
            router.stop(drain=False, timeout=30)


# ---------------------------------------------------------------------------
# scrape-fed control plane
# ---------------------------------------------------------------------------

class TestScrapeFedController:
    def test_scrape_signals_read_router_gauges(self):
        telemetry.enable()
        try:
            telemetry.reset()
            router = make_router(n=2)
            exporter = telemetry.start_exporter()
            try:
                src = serving.ScrapeFleetSignals(
                    exporter.url, slo_s=router.slo_s,
                    max_batch=router.grid.max_batch)
                wait_until(lambda: src() is not None, 10,
                           msg="router monitor publishes its gauges")
                s = src()
                assert s.n_replicas == 2
                assert s.queue_depth == 0 and s.inflight == 0
                assert s.slo_s == router.slo_s
                # a shed bumps the counter; the NEXT scrape sees the
                # delta exactly once
                telemetry.record_serving_shed("queue_full")
                s2 = src()
                assert s2.shed_delta == 1
                assert src().shed_delta == 0
            finally:
                exporter.stop()
                router.stop(timeout=30)
        finally:
            telemetry.disable()
            telemetry.reset()

    def test_failed_scrape_skips_the_tick(self):
        src = serving.ScrapeFleetSignals(
            "http://127.0.0.1:9/metrics", slo_s=0.05, max_batch=4,
            timeout_s=0.2)
        assert src() is None
        router = make_router(n=1)
        try:
            ctl = serving.FleetController(
                router, lambda i: None, signals_source=src,
                policy=serving.ScalePolicy(1, 3))
            assert ctl.tick() is None       # no data, no action
            assert ctl.n_scale_up == 0 and ctl.n_scale_failed == 0
        finally:
            router.stop(timeout=30)

    def test_scrape_fed_scale_up_then_down(self):
        """End-to-end control loop with the signal path over HTTP: the
        controller sees pressure only through /metrics scrapes, scales
        the fleet up, and scales back down after the hold window."""
        telemetry.enable()
        try:
            telemetry.reset()
            router = make_router(n=1)
            exporter = telemetry.start_exporter()
            try:
                def factory(i):
                    return serving.Server(
                        worker_factory.tiny_net(),
                        batch_buckets=(2, 4), shape_buckets=[(8,)],
                        slo_ms=50, name=f"scaled{i}")

                src = serving.ScrapeFleetSignals(
                    exporter.url, slo_s=router.slo_s,
                    max_batch=router.grid.max_batch)
                policy = serving.ScalePolicy(
                    1, 2, up_cooldown_s=0.1, down_utilization=0.5,
                    down_hold_s=0.4, down_cooldown_s=0.1)
                ctl = serving.FleetController(
                    router, factory, policy=policy,
                    signals_source=src)
                wait_until(lambda: src() is not None, 10,
                           msg="gauges published")
                # synthetic pressure: the admission controller's
                # predicted wait, surfaced ONLY through the scrape
                router.predicted_wait = lambda: 10.0
                wait_until(lambda: ctl.tick() == "up", 10, 0.05,
                           msg="scrape-fed scale-up")
                assert router.fleet_size() == 2
                router.predicted_wait = lambda: 0.0
                t0 = time.monotonic()
                wait_until(lambda: ctl.tick() == "down", 15, 0.05,
                           msg="scale-down after the hold window")
                assert time.monotonic() - t0 >= 0.3   # held, not eager
                assert router.fleet_size() == 1
            finally:
                exporter.stop()
                router.stop(timeout=30)
        finally:
            telemetry.disable()
            telemetry.reset()


# ---------------------------------------------------------------------------
# the real thing: one subprocess worker end to end (slow; chaos gate 8
# drives the full kill-under-traffic scenario)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestRealWorkerProcess:
    def test_spawn_serve_sigkill_respawn(self):
        import signal as _signal

        rep = serving.RemoteReplica(
            "worker_factory:tiny_net", name="real0",
            batch_buckets=(2, 4), shape_buckets=[(8,)], slo_ms=50,
            python_paths=[FIXTURES], respawn_backoff_s=0.2,
            spawn_timeout_s=300)
        rep.start()
        try:
            x = traffic(1)[0]
            out = rep.submit(x).result(timeout=60)
            oracle = serving.Server(
                worker_factory.tiny_net(), batch_buckets=(2, 4),
                shape_buckets=[(8,)], slo_ms=50, name="oracle").start()
            try:
                ref = oracle.submit(x).result(timeout=60)
            finally:
                oracle.stop()
            assert np.array_equal(out, ref)

            fut = rep.submit(x)
            os.kill(rep.proc.pid, _signal.SIGKILL)
            with pytest.raises(serving.WorkerCrashed):
                fut.result(timeout=30)
            wait_until(lambda: rep.is_running, 120, 0.1,
                       msg="worker respawned")
            assert rep.n_restarts == 1
            out2 = rep.submit(x).result(timeout=60)
            assert np.array_equal(out2, ref)
        finally:
            rep.stop()
        assert rep.proc.poll() is not None

    def test_trace_propagates_across_the_process_boundary(self):
        """One traced request through a REAL worker subprocess: the
        span context rides the submit frame header, the worker's
        batch.wait/dispatch spans come home on the result frame, and
        the merged trace carries one trace_id across two pids plus the
        reconstructed wire.return leg."""
        from mxnet_tpu import tracing

        rep = serving.RemoteReplica(
            "worker_factory:tiny_net", name="traced0",
            batch_buckets=(2, 4), shape_buckets=[(8,)], slo_ms=50,
            python_paths=[FIXTURES], spawn_timeout_s=300,
            env={"MXNET_TRACING": "1"})
        tracing.reset()         # clean ring: this test counts traces
        tracing.enable()
        try:
            rep.start()
            router = serving.Router([rep], slo_ms=5000).start()
            try:
                x = traffic(1)[0]
                router.submit(x).result(timeout=60)
                wait_until(
                    lambda: any(
                        r["status"] == "ok"
                        for r in tracing.recorder().traces()),
                    30, msg="router seals the merged trace")
            finally:
                router.stop(timeout=60)
            recs = [r for r in tracing.recorder().traces()
                    if r["status"] == "ok"]
            assert len(recs) == 1
            rec = recs[0]
            spans = rec["spans"]
            names = {s["name"] for s in spans}
            # router-side stages AND worker-side stages in ONE record
            assert {"request", "router.queue", "router.attempt",
                    "batch.wait", "dispatch", "wire.return"} <= names
            pids = {s["pid"] for s in spans}
            assert len(pids) == 2, f"expected two pids, got {pids}"
            procs = {s["proc"] for s in spans}
            assert "traced0" in procs   # worker set_process_name
            assert all(s["trace_id"] == rec["trace_id"] for s in spans)
            # worker-side spans hang off the router's attempt span
            # via the wire context, not off a disconnected root
            attempt = [s for s in spans
                       if s["name"] == "router.attempt"][0]
            worker_side = [s for s in spans if s["pid"] != os.getpid()]
            assert worker_side
            assert any(s.get("parent_id") == attempt["span_id"]
                       for s in worker_side)
        finally:
            rep.stop()
            tracing.reset()
