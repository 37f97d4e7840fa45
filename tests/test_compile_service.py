"""Compilation service tests (mxnet_tpu/compiler/): canonical signature
keying, the signature manifest, AOT warm-start, the in-process executable
table, eviction observability, and the retrace-regression guard that pins
the "starts hot, stays hot" invariant."""
import json
import os
import tempfile
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compiler, telemetry
from mxnet_tpu import parallel as par
from mxnet_tpu.compiler import keys, manifest as manifest_mod, service
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon import nn


def _counter(snap, name, **labels):
    fam = snap["metrics"].get(name)
    if not fam:
        return 0.0
    total = 0.0
    for s in fam["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            total += s["value"]
    return total


def _cache_misses(snap, base=None):
    """Per-cache miss counts (positive only), optionally as the DELTA
    from a ``base`` snapshot — the registry is process-global, so a
    guard reading absolutes would blame misses other tests legitimately
    recorded in THEIR telemetry windows (order fragility)."""
    def read(s):
        fam = s["metrics"].get("mxnet_jit_cache_total", {"samples": []})
        return {sm["labels"]["cache"]: sm["value"]
                for sm in fam["samples"]
                if sm["labels"]["result"] == "miss"}

    now = read(snap)
    before = read(base) if base is not None else {}
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v - before.get(k, 0) > 0}


def _make_net(width=16, seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential(prefix="svc_")
    with net.name_scope():
        net.add(nn.Dense(width, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize()
    return net


def _make_step(width=16, seed=0):
    net = _make_net(width=width, seed=seed)
    return par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                         optimizer_params={"learning_rate": 0.1})


def _batch(b=4):
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.rand(b, 8).astype("float32"))
    y = mx.nd.array((np.arange(b) % 4).astype("float32"))
    return x, y


# ---------------------------------------------------------------------------
# canonical keying
# ---------------------------------------------------------------------------

class TestKeys:
    def test_same_signature_is_equal_and_hashable(self):
        k1 = compiler.signature("eager_op", "relu", attrs=(("a", 1),),
                                platform="cpu", extra=(2, False))
        k2 = compiler.signature("eager_op", "relu", attrs=(("a", 1),),
                                platform="cpu", extra=(2, False))
        assert k1 == k2 and hash(k1) == hash(k2)
        assert compiler.fingerprint(k1) == compiler.fingerprint(k2)

    def test_routing_knob_toggle_changes_key(self, monkeypatch):
        k1 = compiler.signature("eager_op", "relu", platform="cpu")
        monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")
        k2 = compiler.signature("eager_op", "relu", platform="cpu")
        assert k1 != k2

    def test_every_site_component_distinguishes(self):
        base = dict(avals=((2, 2),), attrs=(("k", 1),), platform="cpu",
                    routing=(False,), extra=(True,))
        k = compiler.signature("cached_op", "g", **base)
        for field, mutated in [
                ("avals", ((4, 4),)), ("attrs", (("k", 2),)),
                ("platform", "tpu"), ("routing", (True,)),
                ("extra", (False,))]:
            other = dict(base, **{field: mutated})
            assert compiler.signature("cached_op", "g", **other) != k
        assert compiler.signature("train_step", "g", **base) != k
        assert compiler.signature("cached_op", "h", **base) != k

    def test_codec_round_trips_tuples_exactly(self):
        obj = ((1, 2), "a", [3.5, None], {"k": (True, "x")},
               ("s", ("r", 0, 1)))
        dec = keys.decode(keys.encode(obj))
        assert dec == obj
        assert isinstance(dec[0], tuple) and isinstance(dec[2], list)

    def test_graph_ident_matches_factory_twins_only(self):
        a, b = _make_net(seed=0), _make_net(seed=1)
        assert compiler.graph_ident(a) == compiler.graph_ident(b)

        class Custom(nn.HybridSequential):
            def hybrid_forward(self, F, x):
                return super().hybrid_forward(F, x) * 2

        c = Custom(prefix="svc_")
        with c.name_scope():
            c.add(nn.Dense(16, activation="relu"))
            c.add(nn.Dense(4))
        c.initialize()
        # same children, different forward BYTECODE -> different ident
        assert compiler.graph_ident(c) != compiler.graph_ident(a)

    def test_callable_ident_sees_bytecode(self):
        f1 = lambda x: x + 1            # noqa: E731
        f2 = lambda x: x + 1            # noqa: E731
        g = lambda x: x * 3             # noqa: E731
        assert keys.callable_ident(f1).split(":")[-1] \
            == keys.callable_ident(f2).split(":")[-1]
        assert keys.callable_ident(f1) != keys.callable_ident(g)


# ---------------------------------------------------------------------------
# site caches + executable table
# ---------------------------------------------------------------------------

class TestSiteCache:
    def test_lru_policy_and_eviction_telemetry(self):
        c = service.SiteCache("svc_test", maxsize=2)
        telemetry.enable()
        try:
            c.insert("a", 1)
            c.insert("b", 2)
            assert c.lookup("a") == 1          # touch: a is now MRU
            c.insert("c", 3)                   # evicts b
            assert "b" not in c and "a" in c and "c" in c
            snap = telemetry.snapshot()
            assert _counter(snap, "mxnet_jit_cache_evictions_total",
                            cache="svc_test") == 1
            assert _counter(snap, "mxnet_jit_cache_total",
                            cache="svc_test", result="hit") == 1
        finally:
            telemetry.disable()

    def test_lookup_insert_round_trip(self):
        c = service.SiteCache("svc_test2")
        assert c.lookup("k") is c.MISS
        c.insert("k", "v")
        assert c.lookup("k") == "v" and "k" in c and len(c) == 1


class TestExecutableTable:
    def test_single_flight_dedupes_concurrent_builds(self):
        t = service.ExecutableTable()
        builds = []

        def build():
            import time

            time.sleep(0.02)
            builds.append(1)
            return object()

        results = []
        threads = [threading.Thread(
            target=lambda: results.append(t.get_or_build("fp", build)))
            for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(builds) == 1
        assert all(r is results[0] for r in results)
        assert t.stats()["dedup_hits"] == 7

    def test_failed_build_releases_the_slot(self):
        t = service.ExecutableTable()
        with pytest.raises(RuntimeError):
            t.get_or_build("fp", lambda: (_ for _ in ()).throw(
                RuntimeError("boom")))
        assert t.get_or_build("fp", lambda: "ok") == "ok"

    def test_guarded_exec_tracer_calls_use_fallback_per_call(self):
        import jax

        sds = jax.ShapeDtypeStruct((4,), np.float32)
        jitted = jax.jit(lambda v: v * 2)
        compiled = jitted.lower(sds).compile()
        g = service.GuardedExec(compiled, lambda: jitted)
        x = np.ones((4,), np.float32)
        assert np.array_equal(np.asarray(g(x)), [2.0] * 4)
        # inside someone else's trace (autograd's jax.vjp over a
        # hybridized block): a Compiled can't take tracers — the guard
        # must route through the traceable fallback for that call...
        out = jax.jit(lambda v: g(v))(x)
        assert np.array_equal(np.asarray(out), [2.0] * 4)
        # ...WITHOUT permanently abandoning the compiled executable
        assert not g._permanent
        assert np.array_equal(np.asarray(g(x)), [2.0] * 4)

    def test_recorded_training_through_sealed_graph(self):
        from mxnet_tpu import autograd

        net = _make_net()
        net.hybridize()
        x = mx.nd.array(np.ones((2, 8), np.float32))
        net(x)                       # inference entry: sealed, compiled
        with autograd.record():      # training entry: traceable jit
            out = net(x)
            out.sum().backward()
        grads = [p.grad() for p in net.collect_params().values()
                 if p.grad_req != "null"]
        assert all(np.isfinite(g.asnumpy()).all() for g in grads)

    def test_guarded_exec_falls_back_on_aval_mismatch(self):
        calls = []

        def bad(*args):
            raise TypeError("aval mismatch")

        g = service.GuardedExec(bad, lambda: lambda *a: calls.append(a)
                                or "fb")
        assert g(1, 2) == "fb"
        assert g(3) == "fb"              # stays on the fallback
        assert calls == [(1, 2), (3,)]


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------

class TestManifest:
    def test_round_trip_and_dedupe(self, tmp_path):
        m = compiler.Manifest(str(tmp_path / "sig.jsonl"))
        spec = {"op": "relu", "avals": ((3, 4), "float32")}
        assert m.record("eager_op", spec) is not None
        assert m.record("eager_op", spec) is None       # dedupe
        m.record("train_step", {"ident": "x", "data": (((2,), "f4"),)})
        loaded = compiler.Manifest(str(tmp_path / "sig.jsonl")).entries()
        assert [e["site"] for e in loaded] == ["eager_op", "train_step"]
        assert loaded[0]["spec"] == spec    # tuples restored exactly

    def test_corrupt_and_stale_lines_are_skipped(self, tmp_path):
        path = str(tmp_path / "sig.jsonl")
        m = compiler.Manifest(path)
        m.record("eager_op", {"op": "relu"})
        with open(path, "a") as f:
            f.write("{not json\n")
            f.write(json.dumps({"v": 99, "site": "eager_op",
                                "fp": "z", "spec": None}) + "\n")
            f.write(json.dumps({"v": 1, "site": "no_such_site",
                                "fp": "y", "spec": None}) + "\n")
            f.write(json.dumps({"v": 1, "site": "eager_op",
                                "spec": None}) + "\n")   # no fp
        m2 = compiler.Manifest(path)
        assert len(m2.entries()) == 1
        assert m2.n_skipped == 3 + 1

    def test_missing_file_is_empty_not_fatal(self, tmp_path):
        m = compiler.Manifest(str(tmp_path / "absent.jsonl"))
        assert m.entries() == []

    def test_env_recorder_gating(self, monkeypatch, tmp_path):
        monkeypatch.setattr(manifest_mod, "_env_checked", False)
        monkeypatch.setattr(manifest_mod._recorder, "manifest", None)
        monkeypatch.setenv("MXNET_COMPILE_MANIFEST", "0")
        assert compiler.recorder() is None
        monkeypatch.setattr(manifest_mod, "_env_checked", False)
        monkeypatch.setenv("MXNET_COMPILE_MANIFEST",
                           str(tmp_path / "m.jsonl"))
        rec = compiler.recorder()
        assert rec is not None and rec.path.endswith("m.jsonl")
        manifest_mod.disable_recording()


# ---------------------------------------------------------------------------
# warm start
# ---------------------------------------------------------------------------

class TestWarmStart:
    def test_cached_op_warm_means_zero_retrace_on_first_call(
            self, tmp_path):
        m = compiler.enable_recording(str(tmp_path / "m.jsonl"))
        try:
            x = mx.nd.array(np.ones((3, 8), np.float32))
            cold = _make_net()
            cold.hybridize()
            y_cold = cold(x).asnumpy()

            warm = _make_net()      # same factory, fresh process-proxy
            report = compiler.warm_start(m, blocks=[warm])
            assert report["failed"] == 0
            assert report["replayed"] + report["deduped"] >= 1

            telemetry.enable()
            try:
                y_warm = warm(x).asnumpy()
                snap = telemetry.snapshot()
                assert _counter(snap, "mxnet_jit_cache_total",
                                cache="cached_op", result="miss") == 0
                assert _counter(snap, "mxnet_jit_cache_total",
                                cache="cached_op", result="hit") >= 1
            finally:
                telemetry.disable()
            # warmed execution must be bit-identical to cold execution
            assert y_warm.tobytes() == y_cold.tobytes()
        finally:
            compiler.disable_recording()

    def test_train_step_warm_means_zero_retrace_and_bit_identity(
            self, tmp_path):
        m = compiler.enable_recording(str(tmp_path / "m.jsonl"))
        try:
            x, y = _batch()
            cold = _make_step()
            loss_cold, _ = cold(x, y)
            loss_cold = loss_cold.asnumpy()

            warm = _make_step()
            report = compiler.warm_start(m, train_steps=[warm])
            assert report["failed"] == 0

            telemetry.enable()
            try:
                base = telemetry.snapshot()
                loss_warm, _ = warm(x, y)
                loss_warm = loss_warm.asnumpy()
                snap = telemetry.snapshot()
                assert _counter(snap, "mxnet_jit_cache_total",
                                cache="train_step", result="miss") \
                    == _counter(base, "mxnet_jit_cache_total",
                                cache="train_step", result="miss")
                assert _counter(snap, "mxnet_jit_cache_total",
                                cache="train_step", result="hit") \
                    - _counter(base, "mxnet_jit_cache_total",
                               cache="train_step", result="hit") == 1
            finally:
                telemetry.disable()
            assert loss_warm.tobytes() == loss_cold.tobytes()
        finally:
            compiler.disable_recording()

    def test_fused_segment_warm_replay(self, tmp_path):
        from mxnet_tpu import engine
        from mxnet_tpu.ops import registry

        m = compiler.enable_recording(str(tmp_path / "m.jsonl"))
        try:
            def run_chain():
                with engine.bulk(8):
                    t = mx.nd.ones((4, 4))
                    for _ in range(5):
                        t = mx.nd.relu(t + 1)
                return t.asnumpy()

            ref = run_chain()
            registry.fused_segment_cache_clear()
            report = compiler.warm_start(m)
            assert report["failed"] == 0
            telemetry.enable()
            try:
                out = run_chain()
                snap = telemetry.snapshot()
                assert _counter(snap, "mxnet_jit_cache_total",
                                cache="fused_segment", result="miss") == 0
                assert _counter(snap, "mxnet_jit_cache_total",
                                cache="fused_segment", result="hit") >= 1
            finally:
                telemetry.disable()
            assert np.array_equal(out, ref)
        finally:
            compiler.disable_recording()

    def test_unmatched_providers_are_skipped_not_fatal(self, tmp_path):
        m = compiler.Manifest(str(tmp_path / "m.jsonl"))
        m.record("cached_op", {"graph": "nope", "args": (((1,), "f4"),),
                               "training": False})
        m.record("train_step", {"ident": "nope", "data": ()})
        m.record("executor", {"training": True})
        report = compiler.warm_start(m)
        assert report == {"replayed": 0, "deduped": 0, "skipped": 3,
                          "failed": 0, "entries": 3,
                          "seconds": report["seconds"]}

    def test_concurrent_warm_start_is_thread_safe(self, tmp_path):
        m = compiler.enable_recording(str(tmp_path / "m.jsonl"))
        try:
            x, y = _batch()
            cold = _make_step()
            cold(x, y)

            warm = _make_step()
            reports = []
            threads = [threading.Thread(
                target=lambda: reports.append(
                    compiler.warm_start(m, train_steps=[warm])))
                for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert len(reports) == 4
            assert all(r["failed"] == 0 for r in reports)
            assert len(warm._cache) == 1    # one signature, once
            loss, _ = warm(x, y)            # still trains fine
            assert np.isfinite(loss.asnumpy()).all()
        finally:
            compiler.disable_recording()


class TestElasticWarmHook:
    def test_warm_start_hook_fires_after_bootstrap(self, tmp_path):
        from mxnet_tpu.parallel import elastic

        seen = []
        net = _make_net()
        runner = elastic.ElasticRunner(
            str(tmp_path), params=net, world_size=1, rank=0,
            heartbeat_interval=0.05,
            warm_start=lambda membership: seen.append(
                membership.world_size))
        try:
            runner.start()
            assert seen == [1]
            assert "elastic_warm_done" in compiler.events()
        finally:
            runner.stop()

    def test_warm_hook_failure_is_contained(self, tmp_path):
        from mxnet_tpu.parallel import elastic

        def boom(membership):
            raise RuntimeError("warm hook failed")

        net = _make_net()
        runner = elastic.ElasticRunner(
            str(tmp_path), params=net, world_size=1, rank=0,
            heartbeat_interval=0.05, warm_start=boom)
        try:
            runner.start()      # must not raise: warm is best-effort
            assert runner.membership.world_size == 1
        finally:
            runner.stop()


# ---------------------------------------------------------------------------
# cold-start events + persistent tier
# ---------------------------------------------------------------------------

class TestColdStartAccounting:
    def test_mark_event_records_first_occurrence_only(self):
        name = f"svc_test_event_{os.getpid()}"
        t1 = service.mark_event(name)
        assert t1 is not None and t1 >= 0
        assert service.mark_event(name) is None
        assert service.events()[name] == t1

    def test_first_train_step_event_is_marked(self):
        x, y = _batch()
        step = _make_step()
        step(x, y)
        assert "first_train_step" in compiler.events()


class TestPersistentTier:
    def test_gc_evicts_oldest_past_cap(self, tmp_path):
        d = str(tmp_path)
        stem = "jit_f-" + "0" * 63
        for i in range(4):
            with open(os.path.join(d, f"{stem}{i}-cache"), "wb") as f:
                f.write(b"x" * 100)
            with open(os.path.join(d, f"{stem}{i}-atime"), "wb") as f:
                f.write(b"")
            os.utime(os.path.join(d, f"{stem}{i}-atime"), (i, i))
        from mxnet_tpu.compiler import persistent

        removed = persistent.gc_cache(max_bytes=250, directory=d)
        assert removed == 2
        left = {f for f in os.listdir(d) if f.endswith("-cache")}
        # oldest-used entries went first
        assert left == {f"{stem}2-cache", f"{stem}3-cache"}

    @staticmethod
    def _setup_recording(monkeypatch):
        """Run ``persistent.setup()`` with the disk tier on and every
        ``jax.config.update`` recorded instead of applied (the suite's
        own jax config stays as it was)."""
        import jax

        from mxnet_tpu.compiler import persistent

        updates = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.__setitem__(k, v))
        monkeypatch.setattr(persistent, "_cache_dir", None)
        monkeypatch.setenv("MXNET_XLA_CACHE", "1")
        return persistent, updates, persistent.setup()

    def test_cache_dir_handed_by_the_environment_is_left_alone(
            self, tmp_path, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, so no
        directory is set in code, no sub-directory is appended, nothing
        there is deleted, and the whole layout sits under it."""
        handed = tmp_path / "handed"
        handed.mkdir()
        stem = "jit_f-" + "0" * 64
        for suffix in ("-cache", "-atime"):
            (handed / (stem + suffix)).write_bytes(b"x" * 4096)
        (handed / "someone_elses_file").write_bytes(b"y")
        before = sorted(os.listdir(handed))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(handed))
        monkeypatch.setenv("MXNET_XLA_CACHE_DIR", str(tmp_path / "ours"))
        monkeypatch.setenv("MXNET_XLA_CACHE_MAX_BYTES", "1")
        persistent, updates, got = self._setup_recording(monkeypatch)

        assert "jax_compilation_cache_dir" not in updates
        assert got == str(handed) == persistent.cache_dir()
        assert persistent.base_dir() == str(handed)
        assert persistent.stats() == {"dir": str(handed), "entries": 2,
                                      "bytes": 8192}
        assert persistent.gc_cache() == 0          # over the cap, kept
        assert sorted(os.listdir(handed)) == before
        assert not (tmp_path / "ours").exists()
        assert manifest_mod.default_path() == str(
            handed / "manifests" / "signatures.jsonl")
        assert service._exported_path("fp") == str(
            handed / "exported" / "fp.shlo")

    def test_default_cache_dir_is_a_fixed_path_in_the_checkout(
            self, monkeypatch):
        """Nothing handed, nothing overridden: the cache is at
        ``<checkout>/.cache/mxnet_tpu_xla`` — git-ignored, and never a
        path made from a pid, the clock or a temp dir (the path is part
        of jax's cache key)."""
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delenv("MXNET_XLA_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        base = os.path.join(repo, ".cache", "mxnet_tpu_xla")
        persistent, updates, got = self._setup_recording(monkeypatch)

        assert persistent.base_dir() == base
        assert got == os.path.join(
            base, "host-" + persistent._host_cpu_tag())
        assert updates["jax_compilation_cache_dir"] == got
        assert os.path.isdir(got)
        assert manifest_mod.default_path().startswith(base + os.sep)
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".cache/" in f.read().split()

    def test_exported_blob_roundtrip_and_table_dedupe(self, tmp_path,
                                                      monkeypatch):
        import jax

        from mxnet_tpu.compiler import persistent

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("MXNET_XLA_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(persistent, "_cache_dir",
                            str(tmp_path / "host-x"))
        os.makedirs(str(tmp_path / "host-x"), exist_ok=True)
        sds = jax.ShapeDtypeStruct((4,), np.float32)
        jitted = jax.jit(lambda v: v * 2 + 1)
        fp = f"svc_blob_test_{os.getpid()}"
        g1 = service.seal_executable(fp, jitted, (sds,),
                                     fallback=lambda: jitted)
        assert isinstance(g1, service.GuardedExec)
        blob = str(tmp_path / "exported" / (fp + ".shlo"))
        assert os.path.exists(blob)
        out = g1(np.ones((4,), np.float32))
        assert np.array_equal(np.asarray(out), [3.0] * 4)
        # second seal at the same signature: table hit, no rebuild
        before = service.exec_table.stats()["builds"]
        g2 = service.seal_executable(fp, jitted, (sds,),
                                     fallback=lambda: jitted)
        assert service.exec_table.stats()["builds"] == before
        assert g2.compiled is g1.compiled


# ---------------------------------------------------------------------------
# retrace-regression guard (the "starts hot, stays hot" CI gate)
# ---------------------------------------------------------------------------

@pytest.mark.retrace
class TestRetraceGuard:
    """Fails when a steady-state train or serve step records ANY jit
    cache miss after warmup — the invariant every cache-keying change
    must preserve (a key component computed differently per call, an
    unstable hash, a knob read at the wrong time all break it)."""

    def test_steady_state_train_records_zero_misses(self):
        x, y = _batch()
        step = _make_step()
        step(x, y)                       # warm: compile once
        telemetry.enable()
        try:
            base = telemetry.snapshot()
            for _ in range(3):
                loss, _ = step(x, y)
            loss.asnumpy()
            misses = _cache_misses(telemetry.snapshot(), base)
            assert not misses, (
                f"steady-state training re-traced after warmup: {misses}")
        finally:
            telemetry.disable()

    def test_steady_state_serving_records_zero_misses(self):
        from mxnet_tpu import serving

        net = _make_net()
        net.hybridize()
        srv = serving.Server(net, batch_buckets=(1, 2),
                             shape_buckets=[(8,)], slo_ms=100,
                             name="retrace_guard")
        with srv:
            srv.submit(np.zeros((8,), np.float32)).result(timeout=60)
            telemetry.enable()
            try:
                base = telemetry.snapshot()
                for _ in range(3):
                    srv.submit(
                        np.zeros((8,), np.float32)).result(timeout=60)
                misses = _cache_misses(telemetry.snapshot(), base)
                assert not misses, (
                    f"steady-state serving re-traced after warmup: "
                    f"{misses}")
            finally:
                telemetry.disable()
