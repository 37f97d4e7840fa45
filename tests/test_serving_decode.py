"""Continuous-batching autoregressive decode (mxnet_tpu/serving/
{kvcache,buckets,server}.py + the LLaMA paged decode engine): paged
KV-cache accounting (all-or-nothing admission, typed ``CacheFull``,
defrag), decode bit-identity against the full-recompute oracle,
requests joining and leaving the decode batch mid-stream, hot reload
deferred to completion boundaries, token streaming across the worker
wire protocol (crash mid-generate = typed failure, never a wedge), and
the zero-steady-state-retrace contract on the ``serving_decode``
compile-cache site.

The Pallas paged-attention kernel is checked in interpret mode against
the eager gather oracle (the same CPU-reference pattern as
test_pallas_kernels.py).
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import wire
from mxnet_tpu.serving.buckets import BucketGrid
from mxnet_tpu.serving.kvcache import (CacheFull, PagePool, apply_defrag,
                                       make_latent_arena)

pytestmark = pytest.mark.serving

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
if FIXTURES not in sys.path:
    sys.path.insert(0, FIXTURES)

import worker_factory  # noqa: E402  (the fixtures dir is the point)

_NETS = {}


def get_net(seed=7):
    """One tiny LLaMA per seed, shared across tests: the decode engine's
    compile cache is keyed by architecture, so every server built from
    the same config re-hits the warm executables."""
    if seed not in _NETS:
        _NETS[seed] = worker_factory.tiny_llama(seed=seed)
    return _NETS[seed]


def oracle(net, prompt, n_new):
    """Full-recompute argmax decode — the bit-identity reference."""
    toks = list(prompt)
    for _ in range(n_new):
        logits = net(mx.nd.array(np.asarray(toks, np.int32)[None, :],
                                 dtype="int32")).asnumpy()
        toks.append(int(np.argmax(logits[0, -1])))
    return np.asarray(toks[len(prompt):], dtype=np.int32)


def make_server(net=None, **kw):
    kw.setdefault("batch_buckets", (1, 2))
    kw.setdefault("shape_buckets", [(8,)])
    kw.setdefault("slo_ms", 500.0)
    kw.setdefault("dtype", "int32")
    kw.setdefault("warmup", False)
    kw.setdefault("decode_pages", 96)
    kw.setdefault("page_size", 4)
    kw.setdefault("len_buckets", (8, 16))
    return serving.Server(net if net is not None else get_net(), **kw)


PROMPT_A = np.array([3, 1, 4, 1, 5], dtype=np.int32)
PROMPT_B = np.array([2, 7, 1, 8, 2, 8, 1], dtype=np.int32)


def wait_until(pred, timeout=30.0, interval=0.01, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# PagePool accounting
# ---------------------------------------------------------------------------

class TestPagePool:
    def test_alloc_free_roundtrip(self):
        pool = PagePool(8, page_size=4)
        assert pool.capacity_tokens == 28          # scratch excluded
        pages = pool.alloc("a", 10)                # 3 pages
        assert len(pages) == 3 and 0 not in pages  # page 0 reserved
        assert pool.stats()["used"] == 3
        assert pool.free("a") == 3
        assert pool.stats() == {"free": 7, "used": 0, "reserved": 1,
                                "owners": 0, "page_size": 4, "n_pages": 8}
        assert pool.free("a") == 0                 # idempotent

    def test_exhaustion_is_all_or_nothing(self):
        pool = PagePool(4, page_size=4)            # 3 usable pages
        pool.alloc("a", 8)                         # 2 pages
        free_before = pool.stats()["free"]
        with pytest.raises(CacheFull):
            pool.alloc("b", 8)                     # needs 2, 1 free
        assert pool.stats()["free"] == free_before  # nothing leaked
        with pytest.raises(MXNetError):
            pool.alloc("a", 4)                     # double-alloc typed

    def test_extend_grows_or_fails_cleanly(self):
        pool = PagePool(5, page_size=4)
        pool.alloc("a", 4)
        assert len(pool.extend("a", 9)) == 3
        held = list(pool.page_table("a"))
        with pytest.raises(CacheFull):
            pool.extend("a", 100)
        assert list(pool.page_table("a")) == held  # unchanged on failure

    def test_page_table_pads_with_scratch(self):
        pool = PagePool(8, page_size=4)
        pool.alloc("a", 6)
        pt = pool.page_table("a", width=5)
        assert pt.dtype == np.int32 and pt.shape == (5,)
        assert list(pt[2:]) == [0, 0, 0]           # scratch-padded tail
        with pytest.raises(MXNetError):
            pool.page_table("a", width=1)

    def test_defrag_packs_and_moves_arena_rows(self):
        pool = PagePool(10, page_size=2)
        pool.alloc("a", 4)
        pool.alloc("b", 4)
        pool.alloc("c", 2)
        pool.free("a")                             # holes at the front
        # a GQA layer's pair: 2 kv heads x 64 side by side in one row
        rs = np.random.RandomState(0)
        pair = [jnp.asarray(rs.randn(*a.shape).astype(np.float32))
                for a in make_latent_arena(2, pool, 2 * 64)]
        assert all(a.shape == (10, 2, 128) for a in pair)
        # remember each live owner's pages of tokens pre-defrag
        def rows_of(arena, owner):
            return np.asarray(arena[pool.page_table(owner)])
        before = [{o: rows_of(a, o) for o in "bc"} for a in pair]
        moves = pool.defrag()
        assert moves                               # something moved
        live = sorted(p for o in "bc" for p in pool.page_table(o))
        assert live == list(range(1, len(live) + 1))   # packed low
        pair = [apply_defrag(a, moves) for a in pair]
        for a, was in zip(pair, before):           # bytes followed pages
            for o in "bc":
                np.testing.assert_array_equal(rows_of(a, o), was[o])

    def test_defrag_takes_an_arena_and_its_moves(self):
        """There is one kind of arena: a page is an index of axis 0, and
        a chain of moves is replayed from one snapshot."""
        pool = PagePool(4, page_size=2)
        (arena,) = make_latent_arena(1, pool, 128)
        arena = arena + jnp.arange(4, dtype=arena.dtype)[:, None, None]
        assert apply_defrag(arena, []) is arena
        moved = np.asarray(apply_defrag(arena, [(2, 1), (3, 2)]))
        assert [float(moved[p, 0, 0]) for p in range(4)] == [0, 2, 3, 3]
        with pytest.raises(TypeError):
            apply_defrag(arena, [(2, 1)], "pages", page_size=2)


# ---------------------------------------------------------------------------
# BucketGrid length buckets
# ---------------------------------------------------------------------------

class TestLenBuckets:
    def test_prefill_bucket_rounds_up_and_rejects(self):
        grid = BucketGrid((1, 2), [(8,)], len_buckets=(8, 16))
        assert grid.prefill_bucket(1) == 8
        assert grid.prefill_bucket(8) == 8
        assert grid.prefill_bucket(9) == 16
        with pytest.raises(MXNetError):
            grid.prefill_bucket(17)

    def test_generate_signatures_include_decode_column(self):
        grid = BucketGrid((1, 2), [(8,)], len_buckets=(8, 16))
        sigs = set(grid.generate_signatures())
        assert (1, 1) in sigs and (2, 1) in sigs   # the decode column
        assert (2, 8) in sigs and (2, 16) in sigs  # prefill grid


# ---------------------------------------------------------------------------
# decode correctness on the serving path
# ---------------------------------------------------------------------------

class TestDecodeBitIdentity:
    def test_tokens_match_full_recompute_oracle(self):
        net = get_net()
        want_a = oracle(net, PROMPT_A, 6)
        want_b = oracle(net, PROMPT_B, 5)
        srv = make_server().start()
        try:
            got = []
            ha = srv.submit_generate(
                PROMPT_A, 6, on_token=lambda i, t: got.append((i, t)))
            hb = srv.submit_generate(PROMPT_B, 5)
            np.testing.assert_array_equal(ha.result(timeout=120), want_a)
            np.testing.assert_array_equal(hb.result(timeout=120), want_b)
            # streaming saw every token, in order, exactly once
            assert got == list(enumerate(want_a))
            assert ha.tokens() == list(want_a)
            assert ha.next_token(2, timeout=5) == int(want_a[2])
            assert ha.next_token(99, timeout=5) is None  # ended first
            st = srv.stats()
            assert st["tokens"] == 11
            assert st["kvcache"]["used"] == 0      # all pages returned
        finally:
            srv.stop()

    def test_join_and_leave_mid_stream(self):
        net = get_net()
        want_a = oracle(net, PROMPT_A, 24)
        want_b = oracle(net, PROMPT_B, 4)
        done = {}
        srv = make_server().start()
        try:
            # pace A so B provably joins while A is mid-decode
            ha = srv.submit_generate(
                PROMPT_A, 24,
                on_token=lambda i, t: time.sleep(0.01))
            assert ha.next_token(0, timeout=120) == int(want_a[0])
            hb = srv.submit_generate(PROMPT_B, 4)
            hb.future.add_done_callback(
                lambda f: done.setdefault("b", time.monotonic()))
            ha.future.add_done_callback(
                lambda f: done.setdefault("a", time.monotonic()))
            np.testing.assert_array_equal(hb.result(timeout=120), want_b)
            np.testing.assert_array_equal(ha.result(timeout=120), want_a)
            assert done["b"] < done["a"]           # B left the batch first
            assert srv.stats()["kvcache"]["used"] == 0
        finally:
            srv.stop()

    def test_cache_admission(self):
        srv = make_server(decode_pages=8, page_size=4).start()
        # 8 pages -> 28-token budget; a request past it sheds typed NOW
        try:
            with pytest.raises(CacheFull):
                srv.submit_generate(PROMPT_A, 300)
            # two requests that cannot coexist (4 pages each, 7 free)
            # serialize through the pool instead of failing: the second
            # waits for the first's pages to come home
            net = get_net()
            want = oracle(net, PROMPT_A, 8)
            h1 = srv.submit_generate(PROMPT_A, 8)
            h2 = srv.submit_generate(PROMPT_A, 8)
            np.testing.assert_array_equal(h1.result(timeout=120), want)
            np.testing.assert_array_equal(h2.result(timeout=120), want)
            assert srv.stats()["kvcache"]["used"] == 0
        finally:
            srv.stop()

    def test_prompt_validation_is_synchronous(self):
        srv = make_server().start()
        try:
            with pytest.raises(MXNetError):
                srv.submit_generate(np.zeros((0,), np.int32), 4)
            with pytest.raises(MXNetError):
                srv.submit_generate(PROMPT_A, 0)
            with pytest.raises(MXNetError):       # no len bucket fits
                srv.submit_generate(np.zeros(17, np.int32), 4)
        finally:
            srv.stop()


class TestHotReload:
    def test_swap_never_lands_mid_request(self):
        net_a, net_b = get_net(7), get_net(8)
        want_a = oracle(net_a, PROMPT_A, 12)
        want_after = oracle(net_b, PROMPT_A, 4)
        srv = make_server(net_a).start()
        try:
            h = srv.submit_generate(
                PROMPT_A, 12, on_token=lambda i, t: time.sleep(0.01))
            assert h.next_token(0, timeout=120) is not None
            srv.swap_model(net_b)                  # mid-generate
            # the in-flight completion ran ENTIRELY on the old weights
            np.testing.assert_array_equal(h.result(timeout=120), want_a)
            # the next completion sees the new ones
            h2 = srv.submit_generate(PROMPT_A, 4)
            np.testing.assert_array_equal(h2.result(timeout=120),
                                          want_after)
        finally:
            srv.stop()


class TestRetracesAndTelemetry:
    def test_zero_steady_state_retraces(self):
        net = get_net()
        srv = make_server(net).start()
        was = telemetry.enabled()
        telemetry.reset()
        try:
            srv.submit_generate(PROMPT_A, 4).result(timeout=120)  # warm
            telemetry.enable()
            srv.submit_generate(PROMPT_B, 6).result(timeout=120)
            snap = telemetry.snapshot()["metrics"]["mxnet_jit_cache_total"]
            lookups = {tuple(s["labels"].values()): s["value"]
                       for s in snap["samples"]}
            assert lookups.get(("serving_decode", "hit"), 0) > 0
            assert ("serving_decode", "miss") not in lookups
        finally:
            srv.stop()
            telemetry.reset()
            if not was:
                telemetry.disable()

    def test_decode_metrics_published(self):
        was = telemetry.enabled()
        telemetry.reset()
        telemetry.enable()
        try:
            srv = make_server().start()
            try:
                srv.submit_generate(PROMPT_A, 3).result(timeout=120)
            finally:
                srv.stop()
            text = telemetry.prom_text()
            assert "mxnet_serving_decode_steps_total" in text
            assert "mxnet_serving_tokens_total 3" in text
            assert 'mxnet_serving_kvcache_pages{state="free"}' in text
            assert "mxnet_serving_token_seconds_bucket" in text
            assert "mxnet_serving_decode_batch_width_bucket" in text
        finally:
            telemetry.reset()
            if not was:
                telemetry.disable()

    @pytest.mark.parametrize("on", [True, False], ids=["on", "off"])
    def test_a_dispatch_fetches_four_bytes_a_row(self, on):
        """Token ids cross to the host, not logits: 4 bytes a row of the
        batch bucket per dispatch, counted by phase; nothing is counted
        with telemetry off."""
        was = telemetry.enabled()
        telemetry.reset()
        (telemetry.enable if on else telemetry.disable)()
        rows = {"prefill": 0, "decode": 0}
        try:
            srv = make_server().start()
            engine = srv._tenants["default"].engine
            inner = engine.forward

            def spy(tokens, *rest):
                b, l = np.shape(tokens)
                rows["decode" if l == 1 else "prefill"] += b
                return inner(tokens, *rest)

            engine.forward = spy
            try:
                srv.submit_generate(PROMPT_A, 4).result(timeout=120)
                for h in [srv.submit_generate(p, 3)
                          for p in (PROMPT_A, PROMPT_B)]:
                    h.result(timeout=120)
            finally:
                srv.stop()
            assert rows["prefill"] >= 2 and rows["decode"] >= 5
            telemetry.enable()
            metrics = telemetry.snapshot()["metrics"]
            if on:
                fetched = {s["labels"]["phase"]: s["value"] for s in metrics[
                    "mxnet_serving_host_fetch_bytes_total"]["samples"]}
                assert fetched == {k: 4 * n for k, n in rows.items()}
            else:
                assert "mxnet_serving_host_fetch_bytes_total" not in metrics
        finally:
            telemetry.reset()
            if not was:
                telemetry.disable()


# ---------------------------------------------------------------------------
# token streaming across the worker wire protocol (fake-worker seam:
# same pattern as test_serving_worker.py — every failure mode, no exec)
# ---------------------------------------------------------------------------

class GenFakeProc:
    _next_pid = [60000]

    def __init__(self):
        self._rc = None
        self._done = threading.Event()
        GenFakeProc._next_pid[0] += 1
        self.pid = GenFakeProc._next_pid[0]
        self.on_terminate = None

    def poll(self):
        return self._rc

    def wait(self, timeout=None):
        if not self._done.wait(timeout):
            import subprocess
            raise subprocess.TimeoutExpired("fake-gen-worker", timeout)
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


class GenFakeWorker:
    """Wire-protocol generate server. ``mode``:

    * ``"reconcile"`` — streams token frames for the FIRST TWO tokens
      only, then a gen_done carrying the full payload: the client must
      reconcile the missing tail (token frames are best-effort; the
      finale is authoritative).
    * ``"crash_mid_generate"`` — one token frame, then the connection
      dies: every streaming handle must resolve typed.
    """

    TOKENS = [11, 12, 13, 14]

    def __init__(self, rep, mode="reconcile"):
        self.rep = rep
        self.mode = mode
        self.proc = GenFakeProc()
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

        def send(frame):
            with send_lock:
                wire.send_frame(sock, frame)

        send({"kind": "hello", "name": self.rep.name,
              "pid": self.proc.pid,
              "batch_buckets": list(grid.batch_buckets),
              "shape_buckets": [list(s) for s in grid.shape_buckets]
              if grid.shape_buckets else None,
              "len_buckets": list(grid.len_buckets),
              "slo_ms": self.rep.slo_s * 1e3, "metrics_port": None})

        def health_loop():
            while not self.stop_health.wait(0.02):
                try:
                    send({"kind": "health", "age": 0.0, "queue_depth": 0,
                          "requests": 0, "batches": 0, "errors": 0})
                except OSError:
                    return

        threading.Thread(target=health_loop, daemon=True).start()
        try:
            while True:
                frame = wire.recv_frame(sock)
                if frame["kind"] == "generate":
                    rid = frame["id"]
                    if self.mode == "crash_mid_generate":
                        send({"kind": "token", "id": rid, "i": 0,
                              "token": self.TOKENS[0]})
                        sock.close()
                        self.proc.exit(-9)
                        return
                    for i, t in enumerate(self.TOKENS[:2]):
                        send({"kind": "token", "id": rid, "i": i,
                              "token": t})
                    send({"kind": "gen_done", "id": rid, "ok": True,
                          "payload": np.asarray(self.TOKENS, np.int32)})
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


def gen_fake_remote(mode="reconcile", name="g0"):
    rep = serving.RemoteReplica(
        "worker_factory:tiny_llama", name=name,
        batch_buckets=(1, 2), shape_buckets=[(8,)], slo_ms=500,
        python_paths=[FIXTURES], respawn=False,
        decode_pages=16, page_size=4, len_buckets=(8, 16))
    workers = []

    def spawn(port):
        w = GenFakeWorker(rep, mode=mode)
        workers.append(w)
        return w.spawn(port)

    rep._spawn = spawn
    return rep, workers


class TestRemoteStreaming:
    def test_token_frames_stream_and_finale_reconciles(self):
        rep, _ = gen_fake_remote(mode="reconcile")
        rep.start()
        try:
            seen = []
            h = rep.submit_generate(
                PROMPT_A, 4, on_token=lambda i, t: seen.append((i, t)))
            out = h.result(timeout=30)
            np.testing.assert_array_equal(
                out, np.asarray(GenFakeWorker.TOKENS, np.int32))
            # 2 streamed + 2 reconciled from the finale, still in order
            assert seen == list(enumerate(GenFakeWorker.TOKENS))
            assert h.tokens() == GenFakeWorker.TOKENS
        finally:
            rep.stop()

    def test_crash_mid_generate_resolves_typed(self):
        rep, _ = gen_fake_remote(mode="crash_mid_generate")
        rep.start()
        try:
            h = rep.submit_generate(PROMPT_A, 4)
            with pytest.raises(serving.WorkerCrashed):
                h.result(timeout=30)               # typed, never a hang
            # pre-crash token frames are best-effort (waitpid may beat
            # the reader to the buffered frame): whatever arrived is a
            # prefix, and the stream is sealed either way
            got = h.tokens()
            assert got == GenFakeWorker.TOKENS[:len(got)]
            assert h.next_token(len(got), timeout=5) is None
            wait_until(lambda: not rep.is_running, 10,
                       msg="crash marks worker down")
            assert rep.crash_count == 1
        finally:
            rep.stop()

    def test_generate_without_decode_config_is_synchronous_typed(self):
        rep = serving.RemoteReplica(
            "worker_factory:tiny_net", name="nogen",
            batch_buckets=(2,), shape_buckets=[(8,)], slo_ms=50,
            python_paths=[FIXTURES])
        with pytest.raises(MXNetError):
            rep.submit_generate(PROMPT_A, 4)


# ---------------------------------------------------------------------------
# Pallas paged-attention kernel (interpret mode vs the eager oracle)
# ---------------------------------------------------------------------------

class TestPagedKernel:
    def _case(self, b=2, h=4, kv=2, d=128, n_pages=8, ps=8, seed=0):
        from mxnet_tpu.ops.attention import _paged_reference

        rs = np.random.RandomState(seed)
        k_arena = jnp.asarray(
            rs.randn(n_pages * ps, kv, d).astype(np.float32))
        v_arena = jnp.asarray(
            rs.randn(n_pages * ps, kv, d).astype(np.float32))
        q = jnp.asarray(rs.randn(b, h, 1, d).astype(np.float32))
        # row 0: 13 tokens over 2 pages + scratch-padded tail page;
        # row 1: 24 tokens over all 3 table slots
        page_table = jnp.asarray(
            np.array([[1, 2, 0], [3, 4, 5]], np.int32))
        lengths = jnp.asarray(np.array([13, 24], np.int32))
        scale = 1.0 / np.sqrt(d)
        ref = _paged_reference(q, k_arena, v_arena, page_table, lengths,
                               (lengths - 1)[:, None], ps, scale)
        return q, k_arena, v_arena, page_table, lengths, scale, ref

    def test_interpret_matches_eager_oracle(self):
        from mxnet_tpu.pallas_kernels import paged_attention_kernel

        q, ka, va, pt, ln, scale, ref = self._case()
        out = paged_attention_kernel(q, ka, va, pt, ln, page_size=8,
                                     scale=scale, interpret=True)
        assert not np.isnan(np.asarray(out)).any()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_shape_gates(self):
        from mxnet_tpu.pallas_kernels import paged_shape_supported

        q, ka, _, _, _, _, _ = self._case()
        assert paged_shape_supported(q, ka, 8)
        assert not paged_shape_supported(q, ka, 4)      # page tiling
        assert not paged_shape_supported(q[:, :, :, :64], ka[:, :, :64],
                                         8)             # lane width
        q2 = jnp.concatenate([q, q], axis=2)            # two query rows
        assert not paged_shape_supported(q2, ka, 8)
        half = ka.astype(jnp.bfloat16)
        assert not paged_shape_supported(q, half, 16)   # one dtype
        assert paged_shape_supported(q.astype(jnp.bfloat16), half, 16)
        assert not paged_shape_supported(q.astype(jnp.bfloat16), half, 8)

    # heads / kv heads / dtype: query groups of 4 (Mistral's 32 / 8) and of
    # 5 (Falcon-H1's 20 / 4: the head rows padded to a sublane tile)
    GROUPS = {"g4_f32": (8, 2, "float32"), "g4_bf16": (8, 2, "bfloat16"),
              "g5_f32": (20, 4, "float32"), "g5_bf16": (20, 4, "bfloat16")}
    # a block of the walk is 512 tokens = 32 pages of 16; the table is 66
    # pages wide
    ROWS = {"zero_at_head": [0, 1, 16],
            "zero_in_middle": [16, 0, 0, 511],
            "zero_at_tail": [512, 1, 0],
            "block_edges": [511, 512, 513],
            "two_blocks_and_one": [1025, 1, 1025],
            "no_live_row": [0, 0]}

    @pytest.mark.parametrize("rows", list(ROWS))
    @pytest.mark.parametrize("group", list(GROUPS))
    def test_live_page_walk_matches_the_gather(self, group, rows):
        """The kernel in interpret mode against ``_paged_reference`` (on
        float32 copies of the same values): page ids out of order, the
        scratch page 0 padding every tail and holding numbers that must
        reach no row, like the slots behind a row's last token; a row of
        length 0 emits zeros."""
        from mxnet_tpu.ops.attention import _paged_reference
        from mxnet_tpu.pallas_kernels import (paged_attention_kernel,
                                              paged_shape_supported)

        h, kv, dtype = self.GROUPS[group]
        lengths = np.array(self.ROWS[rows], np.int32)
        b, d, ps, table_w = len(lengths), 128, 16, 66
        rs = np.random.RandomState(len(group) * 31 + len(rows))
        n_pages = 1 + int(np.sum(-(-lengths // ps)))
        arenas = [rs.randn(n_pages, ps, kv, d).astype(np.float32)
                  for _ in range(2)]
        free = rs.permutation(np.arange(1, n_pages))
        table = np.zeros((b, table_w), np.int32)
        used = 0
        for i, n in enumerate(lengths):
            live = -(-n // ps)
            table[i, :live] = free[used:used + live]
            used += live
            if n % ps:
                for a in arenas:
                    a[table[i, live - 1], n % ps:] = -1e4
        for a in arenas:
            a[0] = 1e4
        ka, va = (jnp.asarray(a.reshape(n_pages * ps, kv, d), dtype)
                  for a in arenas)
        q = jnp.asarray(rs.randn(b, h, 1, d), dtype)
        assert paged_shape_supported(q, ka, ps)
        scale = d ** -0.5
        got = np.asarray(paged_attention_kernel(
            q, ka, va, jnp.asarray(table), jnp.asarray(lengths),
            page_size=ps, scale=scale, interpret=True), np.float32)
        assert got.shape == (b, h, 1, d)
        f32 = lambda x: x.astype(jnp.float32)           # noqa: E731
        want = np.asarray(_paged_reference(
            f32(q), f32(ka), f32(va), jnp.asarray(table),
            jnp.asarray(lengths), jnp.asarray(lengths - 1)[:, None], ps,
            scale))
        live = lengths > 0
        if live.any():
            tol = 1e-5 if dtype == "float32" else 2e-2
            np.testing.assert_allclose(got[live], want[live], rtol=0,
                                       atol=tol * np.abs(want[live]).max())
        assert not got[~live].any()


# ---------------------------------------------------------------------------
# requests that carry images (an engine that declares a vision encoder)
# ---------------------------------------------------------------------------

def _dots():
    """The tiny dots.vlm1 net (float32, seeded by the benchmark's builder)
    with its config and reference weights, shared across tests."""
    if "dots" not in _NETS:
        import json

        from benchmarks.builders import dots_vlm as builder

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmarks", "configs",
                               "tiny_dots_vlm.json")) as f:
            config = json.load(f)
        net, _ = builder.build_net(config, 11, ctx=mx.cpu(0))
        _NETS["dots"] = (config, net,
                         builder.export_weights({"net": net}))
    return _NETS["dots"]


def _dots_server(**kw):
    config, net, _ = _dots()
    opts = dict(batch_buckets=(1, 4), dtype="int32", ctx=mx.cpu(0),
                slo_ms=60000.0, decode_pages=41, page_size=8,
                len_buckets=(8, 16), max_generate_tokens=64,
                defrag_threshold=None, max_prefill_tokens=16,
                patch_buckets=(128, 256), max_image_tokens=40)
    opts.update(kw)
    return serving.Server(net, **opts).start()


def _image_request(config, seed, grids, before=4, after=7):
    rs = np.random.RandomState(seed)
    holder = config["image_token_id"]
    images = [(rs.standard_normal((r * c, 588)).astype(np.float32), (r, c))
              for r, c in grids]
    prompt = np.concatenate(
        [rs.randint(1, holder, (before,))]
        + [np.full((r * c // 4,), holder) for r, c in grids]
        + [rs.randint(1, holder, (after,))]).astype(np.int32)
    return prompt, images


def _reference_greedy(prompt, images, n_new):
    from benchmarks.references import dots_vlm as ref

    config, _, weights = _dots()
    seq = np.asarray(prompt, np.int32)
    for _ in range(n_new):
        logits = np.asarray(ref.logits_at(weights, config, seq,
                                          [seq.size - 1], images))
        seq = np.append(seq, np.int32(logits[0].argmax()))
    return seq[len(prompt):]


class TestImages:
    def test_with_and_without_images_in_one_decode_round(self):
        """A request with two images (its prompt in three chunks) and one
        of ids alone in the same server: both answer as the reference
        does, they share decode rounds, and the image request's trace
        holds a ``vision.encode`` span an image."""
        from mxnet_tpu import tracing

        config, net, _ = _dots()
        prompt, images = _image_request(config, 1, [(4, 6), (6, 2)])
        plain = np.random.RandomState(2).randint(
            1, config["image_token_id"], (11,)).astype(np.int32)
        tracing.enable()
        srv = _dots_server()
        try:
            traces = [tracing.new_trace("t"), tracing.new_trace("t")]
            with tracing.active(traces[0]):
                a = srv.submit_generate(prompt, 12, images=images)
            with tracing.active(traces[1]):
                b = srv.submit_generate(plain, 12)
            got_a, got_b = a.result(timeout=120), b.result(timeout=120)
        finally:
            srv.stop(timeout=30)
            tracing.disable()
        assert np.array_equal(got_a, _reference_greedy(prompt, images, 12))
        assert np.array_equal(got_b, oracle(net, plain, 12))
        spans = [s for t in traces for s in t.export_spans()]
        encodes = [s for s in spans if s["name"] == "vision.encode"]
        assert [s["tags"]["patches"] for s in encodes] == [24, 12]
        assert {s["tags"]["bucket"] for s in encodes} == {128}
        assert all(s["trace_id"] == traces[0].trace_id for s in encodes)
        rounds = [s for s in spans if s["name"] == "decode.round"]
        assert max(s["tags"]["streams"] for s in rounds) == 2
        chunks = [s for s in spans if s["name"] == "prefill"
                  and s["trace_id"] == traces[0].trace_id]
        assert len(chunks) == -(-prompt.size // 16) == 2

    def test_placeholder_count_that_disagrees_is_refused_typed(self):
        config, _, _ = _dots()
        prompt, images = _image_request(config, 3, [(4, 6)])
        srv = _dots_server()
        try:
            with pytest.raises(serving.ImageMismatch, match="placeholder"):
                srv.submit_generate(prompt[:-8], 4,
                                    images=images + images)
            with pytest.raises(serving.ImageMismatch, match="placeholder"):
                srv.submit_generate(
                    np.concatenate([prompt, prompt[4:6]]), 4, images=images)
            with pytest.raises(serving.ImageMismatch, match="even x even"):
                srv.submit_generate(prompt, 4,
                                    images=[(images[0][0], (3, 8))])
            big = (np.zeros((18 * 16, 588), np.float32), (18, 16))
            with pytest.raises(serving.ImageMismatch, match="bucket"):
                srv.submit_generate(prompt, 4, images=[big])
            # an H x W x 3 array is cut here
            pixels = np.random.RandomState(4).standard_normal(
                (56, 84, 3)).astype(np.float32)
            out = srv.submit_generate(prompt, 3, images=[pixels])
            assert out.result(timeout=120).shape == (3,)
        finally:
            srv.stop(timeout=30)
        assert issubclass(serving.ImageMismatch, MXNetError)

    def test_a_model_without_a_tower_refuses_images(self):
        config, _, _ = _dots()
        prompt, images = _image_request(config, 5, [(4, 6)])
        with make_server() as srv:
            with pytest.raises(serving.ImageMismatch, match="no images"):
                srv.submit_generate(prompt % 50 + 1, 4, images=images)

    def test_preemption_drops_the_image_rows_with_the_pages(self):
        """A low-priority request with an image is evicted between two of
        its prefill chunks, while it still holds its rows of ``y``: the
        handle resolves ``Preempted`` and the rows go with the pages."""
        from mxnet_tpu.serving.kvcache import Preempted

        config, _, _ = _dots()
        prompt, images = _image_request(config, 6, [(8, 12)], before=5,
                                        after=11)           # 40 tokens
        # no prefill bound: the arrival is admitted in the tick of the
        # victim's second chunk, not behind its chunks
        srv = _dots_server(decode_pages=8, max_generate_tokens=48,
                           max_image_tokens=24, max_prefill_tokens=None)
        gate, held = threading.Event(), threading.Event()

        def hook(sig):
            if sig == (1, 16) and not held.is_set():
                held.set()
                gate.wait(30)

        srv._pre_dispatch = hook
        try:
            low = srv.submit_generate(prompt, 8, images=images, priority=0)
            assert held.wait(60)
            victim = srv._gen_active[0]
            assert victim.embeds is not None and victim.pages is not None
            high = srv.submit_generate(prompt[:5], 8, priority=10)
            gate.set()
            assert high.result(timeout=120).shape == (8,)
            with pytest.raises(Preempted):
                low.result(timeout=120)
            assert victim.embeds is None and victim.pages is None
            assert victim.prefilled < prompt.size
            assert srv.stats()["kvcache"]["used"] == 0
        finally:
            gate.set()
            srv.stop(timeout=30)
