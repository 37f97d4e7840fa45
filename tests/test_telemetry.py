"""Runtime telemetry subsystem tests (mxnet_tpu/telemetry.py).

Covers: counter/gauge/histogram semantics, enable/disable toggling (env
var and API), thread safety under concurrent increments, the three
exporters (JSON, Prometheus text — validated by a minimal line-format
checker, chrome-trace counter events merged into profiler.dumps), the
instrumented layers (op dispatch, engine, kvstore, jit caches), the
TrainingTelemetry step hook, and that disabled-mode dispatch records
nothing and allocates nothing in telemetry.py.
"""
import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, profiler, telemetry

pytestmark = pytest.mark.telemetry


@pytest.fixture
def tel():
    """Fresh, enabled telemetry for one test; always disabled + cleared
    after (the conftest leak guard fails tests that forget this)."""
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


def _metric(name):
    return json.loads(telemetry.dumps())["metrics"].get(name)


def _samples(name):
    fam = _metric(name)
    return fam["samples"] if fam else []


def _value(name, **labels):
    for s in _samples(name):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value", s.get("count"))
    return None


# ---------------------------------------------------------------------------
# primitive semantics
# ---------------------------------------------------------------------------

class TestPrimitives:
    def test_counter(self, tel):
        c = telemetry.counter("t_counter", "help text", ("k",))
        c.labels("a").inc()
        c.labels("a").inc(2.5)
        c.labels("b").inc()
        assert _value("t_counter", k="a") == 3.5
        assert _value("t_counter", k="b") == 1.0
        with pytest.raises(ValueError, match="only go up"):
            c.labels("a").inc(-1)

    def test_gauge(self, tel):
        g = telemetry.gauge("t_gauge")
        g.set(5)
        g.inc(2)
        g.dec(3)
        assert _value("t_gauge") == 4.0

    def test_histogram_buckets_cumulative(self, tel):
        h = telemetry.histogram("t_hist", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        s = _samples("t_hist")[0]
        assert s["count"] == 5
        assert s["sum"] == pytest.approx(56.05)
        # bucket counts are cumulative and end at +Inf == count
        assert s["buckets"]["0.1"] == 1
        assert s["buckets"]["1"] == 3
        assert s["buckets"]["10"] == 4
        assert s["buckets"]["+Inf"] == 5

    def test_reregistration_same_family(self, tel):
        a = telemetry.counter("t_same", labelnames=("x",))
        b = telemetry.counter("t_same", labelnames=("x",))
        assert a is b
        with pytest.raises(ValueError, match="already registered"):
            telemetry.gauge("t_same")
        with pytest.raises(ValueError, match="already registered"):
            telemetry.counter("t_same", labelnames=("y",))

    def test_label_arity_checked(self, tel):
        c = telemetry.counter("t_arity", labelnames=("a", "b"))
        with pytest.raises(ValueError, match="expected labels"):
            c.labels("only-one")

    def test_child_cap_degrades_to_overflow(self, tel, monkeypatch):
        monkeypatch.setattr(telemetry, "_MAX_CHILDREN", 3)
        c = telemetry.counter("t_cap", labelnames=("k",))
        for i in range(10):
            c.labels(f"v{i}").inc()
        fam = _metric("t_cap")
        # 3 real children + one overflow catch-all, never 10
        assert len(fam["samples"]) == 4
        assert _value("t_cap", k=telemetry._OVERFLOW_LABEL) == 7.0

    def test_thread_safety(self, tel):
        c = telemetry.counter("t_mt").labels()
        h = telemetry.histogram("t_mt_h", buckets=(0.5,)).labels()
        n_threads, per_thread = 8, 2000

        def worker():
            for _ in range(per_thread):
                c.inc()
                h.observe(0.25)

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert _value("t_mt") == n_threads * per_thread
        s = _samples("t_mt_h")[0]
        assert s["count"] == n_threads * per_thread
        assert s["buckets"]["0.5"] == n_threads * per_thread


# ---------------------------------------------------------------------------
# enable/disable
# ---------------------------------------------------------------------------

class TestToggle:
    def test_api_toggle(self):
        assert not telemetry.enabled()
        telemetry.enable()
        try:
            assert telemetry.enabled()
        finally:
            telemetry.disable()
        assert not telemetry.enabled()

    def test_env_var_enables_at_import(self):
        env = dict(os.environ, MXNET_TELEMETRY="1")
        out = subprocess.run(
            [sys.executable, "-c",
             "from mxnet_tpu import telemetry; print(telemetry.enabled())"],
            capture_output=True, text=True, env=env, timeout=300)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "True"

    def test_record_helpers_noop_when_disabled(self):
        telemetry.reset()
        assert not telemetry.enabled()
        telemetry.record_op_dispatch("x", 0.001)
        telemetry.record_cache("c", True)
        telemetry.record_kv("push", 10, 0.001)
        telemetry.record_engine_wait(0.001)
        telemetry.set_live_arrays(3)
        telemetry.record_live_evictions(2)
        telemetry.record_training_step(0.1, 8, 50.0)
        assert json.loads(telemetry.dumps())["metrics"] == {}


# ---------------------------------------------------------------------------
# instrumented layers
# ---------------------------------------------------------------------------

class TestDispatchInstrumentation:
    def test_disabled_dispatch_records_and_allocates_nothing(self):
        """Disabled mode: the instrumentation branch runs, but records
        nothing and allocates nothing inside telemetry.py."""
        import tracemalloc

        telemetry.reset()
        assert not telemetry.enabled()
        x = mx.nd.ones((4, 4))
        (x * 2).asnumpy()  # warm the executable cache outside the window
        tracemalloc.start()
        try:
            for _ in range(20):
                x = x * 2 + 1
            x.asnumpy()
            snap = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        assert json.loads(telemetry.dumps())["metrics"] == {}
        tel_allocs = snap.filter_traces(
            [tracemalloc.Filter(True, telemetry.__file__)]).statistics("lineno")
        assert not tel_allocs, tel_allocs

    def test_eager_dispatch_counts_and_latency(self, tel):
        x = mx.nd.ones((4, 4))
        for _ in range(3):
            x = x + 1
        x.asnumpy()
        ops = {s["labels"]["op"]: s["value"]
               for s in _samples("mxnet_op_dispatch_total")}
        assert ops and sum(ops.values()) >= 3
        hist = _samples("mxnet_op_dispatch_seconds")
        assert sum(s["count"] for s in hist) >= 3

    def test_recording_path_counts_ops(self, tel):
        x = mx.nd.ones((2, 3))
        x.attach_grad()
        with autograd.record():
            y = (x * 2).sum()
        y.backward()
        ops = {s["labels"]["op"] for s in _samples("mxnet_op_dispatch_total")}
        assert ops, "recording-path dispatch not counted"

    def test_eager_op_cache_hit_miss(self, tel):
        x = mx.nd.ones((5, 5))
        (x * 3).asnumpy()
        (x * 3).asnumpy()  # same op+attrs+platform -> lru hit
        hits = _value("mxnet_jit_cache_total", cache="eager_op", result="hit")
        assert hits and hits >= 1


class TestEngineInstrumentation:
    def test_wait_for_all_and_live_gauge(self, tel):
        import jax.numpy as jnp

        engine.track(jnp.ones((8,)))
        engine.wait_for_all()
        assert _samples("mxnet_engine_wait_all_seconds")[0]["count"] >= 1
        assert _value("mxnet_engine_live_arrays") == 0.0

    def test_overflow_evicts_dead_first_and_counts_live_evictions(
            self, tel, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setattr(engine, "_MAX_LIVE", 4)
        monkeypatch.setattr(engine, "_live_arrays", [])
        live = [jnp.full((2,), i) for i in range(5)]
        for a in live:
            engine.track(a)
        # all 5 refs live: compaction finds no dead entries and must evict
        # live ones — counted, not silent
        assert _value("mxnet_engine_live_evictions_total") == 2.0
        # dead refs are preferred: drop our strong refs, track more — the
        # collected entries compact away without touching the live counter
        evictions_before = _value("mxnet_engine_live_evictions_total")
        del live
        import gc

        gc.collect()
        fresh = [jnp.full((2,), i) for i in range(3)]
        for a in fresh:
            engine.track(a)
        assert _value("mxnet_engine_live_evictions_total") == evictions_before


class TestKVStoreInstrumentation:
    def test_local_push_pull_bytes(self, tel):
        kv = mx.kv.create("local")
        v = mx.nd.ones((16, 4))  # float32: 256 bytes
        kv.init(7, v)
        kv.push(7, v)
        out = mx.nd.zeros((16, 4))
        kv.pull(7, out)
        assert _value("mxnet_kvstore_calls_total", op="push") == 1.0
        assert _value("mxnet_kvstore_calls_total", op="pull") == 1.0
        assert _value("mxnet_kvstore_bytes_total", op="push") == 256.0
        assert _value("mxnet_kvstore_bytes_total", op="pull") == 256.0
        lat = {s["labels"]["op"]: s["count"]
               for s in _samples("mxnet_kvstore_seconds")}
        assert lat.get("push") == 1 and lat.get("pull") == 1

    def test_tpu_sync_allreduce_counted(self, tel):
        import jax

        if len(jax.local_devices(backend="cpu")) < 2:
            pytest.skip("needs the 8-device virtual CPU mesh")
        kv = mx.kv.create("tpu_sync")
        a = mx.nd.ones((8,), ctx=mx.cpu(0))
        b = mx.nd.ones((8,), ctx=mx.cpu(1))
        kv.init("g", a)
        kv.push("g", [a, b])  # copies on distinct devices -> one psum
        assert _value("mxnet_kvstore_calls_total", op="allreduce") == 1.0
        # payload entering the psum: one f32 copy per mesh slot
        assert _value("mxnet_kvstore_bytes_total", op="allreduce") == 64.0


class TestJitCacheInstrumentation:
    def test_cached_op_hit_miss(self, tel):
        from mxnet_tpu.gluon import nn

        net = nn.Dense(3)
        net.initialize()
        net.hybridize()
        x = mx.nd.ones((2, 4))
        net(x).asnumpy()   # miss (build+compile)
        net(x).asnumpy()   # hit
        assert _value("mxnet_jit_cache_total",
                      cache="cached_op", result="miss") == 1.0
        assert _value("mxnet_jit_cache_total",
                      cache="cached_op", result="hit") == 1.0

    def test_executor_cache_hit_miss(self, tel):
        data = mx.sym.var("data")
        net = mx.sym.FullyConnected(data, name="fc", num_hidden=2)
        exe = net.simple_bind(ctx=mx.cpu(), data=(2, 3))
        exe.forward(data=mx.nd.ones((2, 3)))
        exe.forward(data=mx.nd.ones((2, 3)))
        assert _value("mxnet_jit_cache_total",
                      cache="executor", result="miss") == 1.0
        hits = _value("mxnet_jit_cache_total",
                      cache="executor", result="hit")
        assert hits and hits >= 1.0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

# minimal Prometheus text-format (0.0.4) line checker — no dependency
_PROM_HELP = re.compile(r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .*$")
_PROM_TYPE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
    r"(counter|gauge|histogram|summary|untyped)$")
_PROM_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"                      # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""   # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\})?"  # more labels
    r" (-?[0-9]+(\.[0-9]+)?([eE][-+]?[0-9]+)?|\+Inf|-Inf|NaN)$")


def check_prom_text(text):
    """Validate exposition format; returns {family: type}."""
    types = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP"):
            assert _PROM_HELP.match(line), line
            continue
        if line.startswith("# TYPE"):
            m = _PROM_TYPE.match(line)
            assert m, line
            types[m.group(1)] = m.group(2)
            continue
        m = _PROM_SAMPLE.match(line)
        assert m, f"bad sample line: {line!r}"
        name = m.group(1)
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        fam = name if name in types else base
        assert fam in types, f"sample before TYPE: {line!r}"
        if types[fam] == "histogram" and name.endswith("_bucket"):
            assert 'le="' in line, f"histogram bucket missing le: {line!r}"
    return types


class TestExporters:
    def _populate(self):
        telemetry.counter("exp_total", "a counter", ("op",)).labels(
            'weird"\\name').inc(2)
        telemetry.gauge("exp_gauge", "a gauge").set(1.5)
        telemetry.histogram("exp_lat", "a histogram", ("op",),
                            buckets=(0.1, 1.0)).labels("x").observe(0.5)

    def test_json_dumps(self, tel):
        self._populate()
        snap = json.loads(telemetry.dumps())
        assert snap["enabled"] is True
        m = snap["metrics"]
        assert m["exp_total"]["type"] == "counter"
        assert m["exp_gauge"]["samples"][0]["value"] == 1.5
        h = m["exp_lat"]["samples"][0]
        assert h["count"] == 1 and h["buckets"]["+Inf"] == 1

    def test_prom_text_valid(self, tel):
        self._populate()
        types = check_prom_text(telemetry.prom_text())
        assert types["exp_total"] == "counter"
        assert types["exp_gauge"] == "gauge"
        assert types["exp_lat"] == "histogram"

    def test_prom_text_of_real_run_valid(self, tel):
        x = mx.nd.ones((4, 4))
        (x + x).asnumpy()
        kv = mx.kv.create("local")
        kv.init(0, x)
        kv.push(0, x)
        types = check_prom_text(telemetry.prom_text())
        assert types.get("mxnet_op_dispatch_total") == "counter"
        assert types.get("mxnet_op_dispatch_seconds") == "histogram"

    def test_chrome_counter_events(self, tel):
        self._populate()
        events = telemetry.chrome_counter_events(ts_us=123.0)
        assert events and all(e["ph"] == "C" for e in events)
        names = {e["name"] for e in events}
        assert {"exp_total", "exp_gauge", "exp_lat"} <= names
        lat = next(e for e in events if e["name"] == "exp_lat")
        assert lat["args"]["x_count"] == 1

    def test_chrome_trace_merged_into_profiler_dumps(self, tel):
        self._populate()
        with profiler.Task("merge-task"):
            pass
        profiler.Marker("merge-marker").mark()
        doc = json.loads(profiler.dumps(format="chrome_trace"))
        names = {e["name"] for e in doc["traceEvents"]}
        assert "Task::merge-task" in names       # profiler span
        assert "merge-marker" in names           # profiler marker
        assert "exp_total" in names              # telemetry counter
        profiler.dumps(reset=True)


# ---------------------------------------------------------------------------
# scrape parser: the /metrics channel must be lossless, or the
# cross-process control plane acts on corrupted signals
# ---------------------------------------------------------------------------

class TestScrapeParser:
    def test_prom_text_round_trips_through_the_parser(self, tel):
        """parse -> emit -> parse is the identity on a real payload —
        including label values holding every escaped character ('"',
        newline, backslash). A scrape channel that mangles one label
        would silently mis-attribute a replica's metrics."""
        telemetry.counter("rt_total", "labels with teeth", ("op",)) \
            .labels('quote " backslash \\ newline \n mix \\"\n').inc(3)
        telemetry.counter("rt_total", "labels with teeth", ("op",)) \
            .labels("plain").inc(1)
        telemetry.gauge("rt_gauge", "a gauge", ("k",)) \
            .labels("\\n is two chars, \n is one").set(-2.5)
        telemetry.histogram("rt_lat", "a histogram", ("op",),
                            buckets=(0.1, 1.0)).labels("x").observe(0.5)
        text = telemetry.prom_text()
        parsed = telemetry.parse_prom_text(text)
        emitted = telemetry.emit_prom_text(parsed)
        assert telemetry.parse_prom_text(emitted) == parsed
        # and the re-emitted text is still valid exposition format
        check_prom_text(emitted)
        # the hairy label survived BOTH trips byte-for-byte
        hairy = 'quote " backslash \\ newline \n mix \\"\n'
        ops = [s["labels"]["op"]
               for s in parsed["rt_total"]["samples"]]
        assert hairy in ops
        assert telemetry.prom_value(parsed, "rt_total",
                                    {"op": hairy}) == 3.0
        assert telemetry.prom_value(
            parsed, "rt_gauge",
            {"k": "\\n is two chars, \n is one"}) == -2.5

    def test_histogram_samples_attributed_to_family(self, tel):
        telemetry.histogram("rt_h", "h", buckets=(0.5,)).observe(0.2)
        parsed = telemetry.parse_prom_text(telemetry.prom_text())
        names = {s["name"] for s in parsed["rt_h"]["samples"]}
        assert {"rt_h_bucket", "rt_h_sum", "rt_h_count"} <= names
        assert "rt_h_bucket" not in parsed     # no orphan family
        assert parsed["rt_h"]["type"] == "histogram"

    def test_prom_value_sums_label_series(self, tel):
        c = telemetry.counter("rt_sum_total", "c", ("reason",))
        c.labels("a").inc(2)
        c.labels("b").inc(3)
        parsed = telemetry.parse_prom_text(telemetry.prom_text())
        assert telemetry.prom_value(parsed, "rt_sum_total") == 5.0
        assert telemetry.prom_value(parsed, "rt_sum_total",
                                    {"reason": "b"}) == 3.0
        assert telemetry.prom_value(parsed, "rt_sum_total",
                                    {"reason": "nope"},
                                    default=-1.0) == -1.0
        assert telemetry.prom_value(parsed, "rt_missing",
                                    default=7.0) == 7.0

    def test_malformed_lines_raise(self, tel):
        for bad in ('rt{op="unterminated 1',
                    'rt{op="v"',
                    "rt notafloat"):
            with pytest.raises(ValueError):
                telemetry.parse_prom_text(bad)

    def test_exporter_serves_metrics_and_healthz(self, tel):
        import urllib.error
        import urllib.request

        telemetry.counter("rt_exp_total", "c").inc(4)
        exp = telemetry.start_exporter(
            healthz_fn=lambda: {"ok": True, "who": "test"})
        try:
            parsed = telemetry.scrape(exp.url)
            assert telemetry.prom_value(parsed, "rt_exp_total") == 4.0
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{exp.port}/healthz",
                    timeout=5) as resp:
                hz = json.loads(resp.read())
            assert hz == {"ok": True, "who": "test"}
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{exp.port}/nope", timeout=5)
        finally:
            exp.stop()


# ---------------------------------------------------------------------------
# training-step observability
# ---------------------------------------------------------------------------

class TestTrainingTelemetry:
    def test_step_scope_records_mfu(self, tel):
        tt = telemetry.TrainingTelemetry(
            batch_size=8, flops_per_step=1e9, peak_flops=1e12)
        with tt.step():
            pass
        assert tt.steps == 1
        assert tt.last_step_seconds > 0
        assert tt.last_examples_per_sec == pytest.approx(
            8 / tt.last_step_seconds)
        # MFU = 100 * flops / (dt * peak)
        assert tt.last_mfu_pct == pytest.approx(
            100.0 * 1e9 / (tt.last_step_seconds * 1e12))
        assert _value("mxnet_training_steps_total") == 1.0
        assert _value("mxnet_training_examples_total") == 8.0
        assert _value("mxnet_training_mfu_pct") == pytest.approx(
            tt.last_mfu_pct)

    def test_flops_per_sample_and_unknown_peak(self, tel):
        tt = telemetry.TrainingTelemetry(batch_size=4, flops_per_sample=2e6)
        assert tt.flops_per_step == 8e6
        with tt.step():
            pass
        # CPU has no known peak -> MFU skipped, throughput still recorded
        if tt.last_mfu_pct is None:
            assert _metric("mxnet_training_mfu_pct") is None
        assert _value("mxnet_training_examples_per_sec") > 0

    def test_batch_end_adapter(self, tel):
        tt = telemetry.TrainingTelemetry(batch_size=2)
        tt.batch_end(None)   # arms the clock
        assert tt.steps == 0
        tt.batch_end(None)
        tt(None)             # __call__ alias
        assert tt.steps == 2
        assert _value("mxnet_training_steps_total") == 2.0

    def test_batch_end_epoch_rollover_rearms(self, tel):
        """nbatch == 0 (first batch of an epoch) re-arms the clock — the
        gap since the last batch of the previous epoch spans validation/
        checkpointing, not a training step."""
        class P:
            def __init__(self, nbatch):
                self.nbatch = nbatch

        tt = telemetry.TrainingTelemetry(batch_size=2)
        tt.batch_end(P(0))   # epoch 0 first batch: arm only
        tt.batch_end(P(1))   # one real step
        assert tt.steps == 1
        tt.batch_end(P(0))   # epoch 1 first batch: eval gap NOT observed
        assert tt.steps == 1
        tt.batch_end(P(1))
        assert tt.steps == 2


# ---------------------------------------------------------------------------
# acceptance: a short Gluon training run
# ---------------------------------------------------------------------------

class TestGluonRunAcceptance:
    def test_training_run_populates_all_surfaces(self, tel):
        from mxnet_tpu.gluon import Trainer, loss as gloss, nn

        net = nn.Dense(4)
        net.initialize()
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1}, kvstore="tpu_sync")
        lfn = gloss.SoftmaxCrossEntropyLoss()
        tt = telemetry.TrainingTelemetry(
            batch_size=8, flops_per_step=1e6, peak_flops=1e12)
        x = mx.nd.ones((8, 3))
        y = mx.nd.zeros((8,))
        for _ in range(2):
            with tt.step():
                with autograd.record():
                    loss = lfn(net(x), y)
                loss.backward()
                trainer.step(8)
        mx.nd.waitall()
        snap = json.loads(telemetry.dumps())["metrics"]
        # per-op dispatch counts
        assert sum(s["value"]
                   for s in snap["mxnet_op_dispatch_total"]["samples"]) > 0
        # kvstore byte counters — the trainer's gradient exchange now
        # goes through the fused bucketed pushpull
        kv_bytes = {s["labels"]["op"]: s["value"]
                    for s in snap["mxnet_kvstore_bytes_total"]["samples"]}
        assert kv_bytes.get("pushpull", 0) > 0
        # one bucketed collective dispatch per step, not one per param
        coll = {s["labels"]["path"]: s["value"] for s in
                snap["mxnet_kvstore_collective_dispatch_total"]["samples"]}
        assert coll.get("bucketed", 0) > 0
        # jit-cache hit/miss
        cache = {(s["labels"]["cache"], s["labels"]["result"])
                 for s in snap["mxnet_jit_cache_total"]["samples"]}
        assert any(c == "eager_op" for c, _ in cache)
        # per-step MFU
        assert snap["mxnet_training_mfu_pct"]["samples"][0]["value"] > 0
        assert snap["mxnet_training_steps_total"]["samples"][0]["value"] == 2
        # and the prom exporter stays valid on the full real payload
        check_prom_text(telemetry.prom_text())


# ---------------------------------------------------------------------------
# tool plumbing: the shared --telemetry-out contract
# ---------------------------------------------------------------------------

class TestTelemetryOutFlag:
    def test_strips_both_forms(self):
        argv, path = telemetry.pop_telemetry_out_flag(
            ["bert", "--telemetry-out", "/tmp/t.json", "40"])
        assert argv == ["bert", "40"] and path == "/tmp/t.json"
        argv, path = telemetry.pop_telemetry_out_flag(
            ["--telemetry-out=/x.json", "resnet"])
        assert argv == ["resnet"] and path == "/x.json"
        argv, path = telemetry.pop_telemetry_out_flag(["bert", "40"])
        assert argv == ["bert", "40"] and path is None

    def test_missing_path_is_an_error(self):
        with pytest.raises(SystemExit, match="requires a PATH"):
            telemetry.pop_telemetry_out_flag(["bert", "--telemetry-out"])
        with pytest.raises(SystemExit, match="requires a PATH"):
            telemetry.pop_telemetry_out_flag(["--telemetry-out="])
        with pytest.raises(SystemExit, match="requires a PATH"):
            # a following option is not a path
            telemetry.pop_telemetry_out_flag(
                ["--telemetry-out", "--some-flag"])

    def test_write_snapshot(self, tel, tmp_path):
        telemetry.counter("snap_total").inc(3)
        out = tmp_path / "snap.json"
        telemetry.write_snapshot(str(out))
        snap = json.loads(out.read_text())
        assert snap["metrics"]["snap_total"]["samples"][0]["value"] == 3.0

    def test_env_out_enables_and_writes_at_exit(self, tmp_path):
        """MXNET_TELEMETRY_OUT=PATH: subprocess records without any CLI
        plumbing and drops a snapshot at interpreter exit."""
        out = tmp_path / "child.json"
        env = dict(os.environ, MXNET_TELEMETRY_OUT=str(out))
        r = subprocess.run(
            [sys.executable, "-c",
             "from mxnet_tpu import telemetry\n"
             "assert telemetry.enabled()\n"
             "telemetry.counter('child_total').inc(2)"],
            capture_output=True, text=True, env=env, timeout=300)
        assert r.returncode == 0, r.stderr
        snap = json.loads(out.read_text())
        assert snap["metrics"]["child_total"]["samples"][0]["value"] == 2.0


# ---------------------------------------------------------------------------
# profiler satellite fixes
# ---------------------------------------------------------------------------

class TestProfilerSatellites:
    def test_markers_in_aggregate_table(self):
        profiler.dumps(reset=True)
        profiler.Marker("tele-marker").mark()
        profiler.Marker("tele-marker").mark(scope="global")
        table = profiler.dumps(reset=True)
        assert "Marker::tele-marker (process)" in table
        assert "Marker::tele-marker (global)" in table

    def test_counters_in_aggregate_table(self):
        profiler.Counter("tele-counter", 7).increment(5)
        table = profiler.dumps(reset=True)
        assert "tele-counter" in table
        assert "12.000" in table

    def test_reset_while_paused_rebases_open_window(self, tmp_path):
        """dumps(reset=True) during an open pause must not leave the
        original pause start behind — resume() would re-account the
        already-reported (and reset) portion."""
        import time as _time

        profiler.set_config(filename=str(tmp_path / "p.json"))
        profiler.dumps(reset=True)
        profiler.start()
        try:
            profiler.pause()
            _time.sleep(0.05)
            assert "excluded paused time" in profiler.dumps(reset=True)
            t_rebase = _time.perf_counter()
            profiler.resume()
        finally:
            profiler.stop()
        table = profiler.dumps(reset=True)
        m = re.search(r"excluded paused time: ([0-9.]+) ms", table)
        if m:  # only the post-rebase sliver may remain, never the 50 ms
            assert float(m.group(1)) <= (
                _time.perf_counter() - t_rebase) * 1e3 + 1.0

    def test_chrome_trace_includes_open_pause_window(self, tmp_path):
        profiler.set_config(filename=str(tmp_path / "p.json"))
        profiler.dumps(format="chrome_trace", reset=True)
        profiler.start()
        try:
            profiler.pause()
            import time as _time

            _time.sleep(0.02)
            doc = json.loads(profiler.dumps(format="chrome_trace"))
            assert doc["otherData"]["excluded_paused_ms"] >= 20.0
            profiler.resume()
        finally:
            profiler.stop()
        profiler.dumps(reset=True)

    def test_pause_resume_excluded_time_in_header(self, tmp_path):
        profiler.set_config(filename=str(tmp_path / "p.json"))
        profiler.dumps(reset=True)
        profiler.start()
        try:
            profiler.pause()
            profiler.resume()
        finally:
            profiler.stop()
        table = profiler.dumps(reset=True)
        assert "excluded paused time" in table
        # reset clears the pause accounting
        assert "excluded paused time" not in profiler.dumps()

    def test_chrome_trace_format_parses(self):
        with profiler.Event("ct-span"):
            pass
        doc = json.loads(profiler.dumps(format="chrome_trace", reset=True))
        spans = [e for e in doc["traceEvents"]
                 if e["name"] == "Event::ct-span"]
        assert spans and spans[0]["ph"] == "X"
        assert spans[0]["args"]["calls"] == 1
        with pytest.raises(ValueError, match="unknown dumps format"):
            profiler.dumps(format="bogus")
