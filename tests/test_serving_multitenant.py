"""Multi-tenant serving (ISSUE 18): the tenant registry behind one
replica fleet, SLO classes, weighted admission (token buckets +
weighted-fair decode slots), priority preemption at the decode-step
boundary, per-model rolling upgrade, and the wire's absent-field-=-
default forward-compat contract. ``tools/chaos_check.py`` gate 10
exercises the same machinery under load; here each contract is pinned in
isolation.
"""
import os
import socket
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import wire
from mxnet_tpu.serving.controller import rolling_upgrade
from mxnet_tpu.serving.kvcache import Preempted
from mxnet_tpu.serving.server import DEFAULT_MODEL, TenantThrottled

pytestmark = [pytest.mark.serving, pytest.mark.multitenant]

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
if FIXTURES not in sys.path:
    sys.path.insert(0, FIXTURES)

import worker_factory  # noqa: E402  (the fixtures dir is the point)

_NETS = {}


def get_llama(seed=7):
    """One tiny LLaMA per seed, shared across tests (the decode
    engine's compile cache is keyed by architecture)."""
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


def make_decode_server(net=None, **kw):
    kw.setdefault("batch_buckets", (1, 2))
    kw.setdefault("shape_buckets", [(8,)])
    kw.setdefault("slo_ms", 60000.0)
    kw.setdefault("dtype", "int32")
    kw.setdefault("warmup", False)
    kw.setdefault("decode_pages", 96)
    kw.setdefault("page_size", 4)
    kw.setdefault("len_buckets", (8, 16))
    return serving.Server(net if net is not None else get_llama(), **kw)


def make_classify_server(net, **kw):
    kw.setdefault("batch_buckets", (1,))
    kw.setdefault("shape_buckets", [(8,)])
    kw.setdefault("slo_ms", 2000.0)
    kw.setdefault("warmup", False)
    return serving.Server(net, **kw)


def classify_oracle(net, x):
    return net(mx.nd.array(np.asarray(x, np.float32)[None, :])).asnumpy()[0]


PROMPT = np.array([3, 1, 4, 1, 5], dtype=np.int32)
X = np.linspace(-1.0, 1.0, 8).astype(np.float32)


def wait_until(pred, timeout=60.0, interval=0.01, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# tenant registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_register_models_and_stats(self):
        srv = make_classify_server(worker_factory.tiny_net(seed=0))
        srv.register_model("b", worker_factory.tiny_net(seed=1),
                           slo_class="premium", priority=5, weight=2.0)
        assert srv.models() == ["b", DEFAULT_MODEL]
        ms = srv.stats()["models"]
        assert ms["b"]["slo_class"] == "premium"
        assert ms["b"]["priority"] == 5 and ms["b"]["weight"] == 2.0
        assert ms[DEFAULT_MODEL]["slo_class"] == "standard"
        with pytest.raises(MXNetError):
            srv.register_model("b", worker_factory.tiny_net(seed=2))

    def test_unknown_model_refused_synchronously(self):
        with make_classify_server(worker_factory.tiny_net(seed=0)) as srv:
            with pytest.raises(MXNetError, match="unknown model"):
                srv.submit(X, model="ghost")

    def test_submit_routes_to_registered_tenant_bit_identical(self):
        net_a = worker_factory.tiny_net(seed=0)
        net_b = worker_factory.tiny_net(seed=1)
        ref_a = classify_oracle(net_a, X)
        ref_b = classify_oracle(net_b, X)
        assert not np.array_equal(ref_a, ref_b)
        with make_classify_server(net_a) as srv:
            srv.register_model("b", net_b)
            out_a = srv.submit(X).result(timeout=60)
            out_b = srv.submit(X, model="b").result(timeout=60)
        assert np.array_equal(out_a, ref_a)
        assert np.array_equal(out_b, ref_b)

    def test_router_unknown_model_refused_before_routing(self):
        srv = make_classify_server(worker_factory.tiny_net(seed=0))
        with serving.Router([srv], slo_ms=2000.0) as router:
            with pytest.raises(MXNetError, match="register_model"):
                router.submit(X, model="ghost")


# ---------------------------------------------------------------------------
# weighted admission: per-tenant token buckets
# ---------------------------------------------------------------------------

class TestThrottle:
    def test_token_bucket_sheds_typed_and_scoped_to_one_tenant(self):
        with make_classify_server(worker_factory.tiny_net(seed=0)) as srv:
            # a refill rate of ~0/s makes the burst the whole budget:
            # admission is deterministic, not a race with the clock
            srv.register_model("lim", worker_factory.tiny_net(seed=1),
                               rate_limit=1e-6, burst=2)
            futs = [srv.submit(X, model="lim") for _ in range(2)]
            with pytest.raises(TenantThrottled):
                srv.submit(X, model="lim")
            # the neighbor tenant is untouched by lim's empty bucket
            out = srv.submit(X).result(timeout=60)
            for f in futs:
                f.result(timeout=60)
            ms = srv.stats()["models"]
        assert ms["lim"]["shed"] == 1
        assert ms[DEFAULT_MODEL]["shed"] == 0
        assert out is not None

    def test_router_throttle_terminal_not_fleet_multiplied(self):
        reps = [make_classify_server(worker_factory.tiny_net(seed=0),
                                     name=f"thr{i}") for i in range(2)]
        with serving.Router(reps, slo_ms=2000.0) as router:
            router.register_model(
                "lim", lambda: worker_factory.tiny_net(seed=1),
                rate_limit=1e-6, burst=1)
            n_throttled = 0
            for _ in range(4):
                try:
                    router.submit(X, deadline_ms=2000,
                                  model="lim").result(timeout=60)
                except TenantThrottled:
                    n_throttled += 1
            # each replica's burst admits AT MOST one request (where
            # the least-loaded picks land is the router's business);
            # the rest MUST shed — and each shed counts exactly once
            # fleet-wide: a sibling retry would multiply lim's
            # configured rate by the replica count
            total_shed = sum(r.stats()["models"]["lim"]["shed"]
                             for r in reps)
        assert 2 <= n_throttled <= 3
        assert total_shed == n_throttled


# ---------------------------------------------------------------------------
# weighted-fair decode slots
# ---------------------------------------------------------------------------

class TestDecodeFairness:
    def test_token_share_tracks_weights(self):
        net_a, net_b = get_llama(7), get_llama(11)
        n_new, streams = 48, 4
        pages_per = -(-(PROMPT.size + n_new) // 4)
        srv = make_decode_server(
            net_a, batch_buckets=(4,),
            decode_pages=2 * streams * pages_per + 1,
            max_generate_tokens=PROMPT.size + n_new, weight=1.0)
        srv.start()
        try:
            srv.register_model("fast", net_b, weight=3.0)
            srv.submit_generate(PROMPT, 2).result(timeout=600)
            srv.submit_generate(PROMPT, 2,
                                model="fast").result(timeout=600)

            def tokens():
                ms = srv.stats()["models"]
                return (ms[DEFAULT_MODEL]["tokens"],
                        ms["fast"]["tokens"])

            handles = []
            for _ in range(streams):
                handles.append(srv.submit_generate(PROMPT, n_new))
                handles.append(srv.submit_generate(PROMPT, n_new,
                                                   model="fast"))
            base = tokens()
            wait_until(
                lambda: (srv.stats()["generates_active"] == 2 * streams
                         and sum(tokens()) - sum(base) >= 24),
                timeout=120, msg="both tenants decoding steadily")
            a1, b1 = tokens()
            wait_until(
                lambda: (tokens()[0] - a1) + (tokens()[1] - b1) >= 96,
                timeout=120, msg="measurement window tokens")
            a2, b2 = tokens()
            share_fast = (b2 - b1) / ((a2 - a1) + (b2 - b1))
            # weights 3:1 with 4 decode slots per round -> the smooth
            # WRR hands tenant "fast" exactly 3 of 4 slots each round
            assert abs(share_fast - 0.75) / 0.75 <= 0.10
            for h in handles:
                h.result(timeout=600)
        finally:
            srv.stop(drain=False)


# ---------------------------------------------------------------------------
# priority preemption at the decode-step boundary
# ---------------------------------------------------------------------------

class TestPreemption:
    def test_preemption_contract_end_to_end(self):
        net_lo, net_hi = get_llama(7), get_llama(11)
        low_new, hi_new = 40, 8
        orc_lo = oracle(net_lo, PROMPT, low_new)
        orc_hi = oracle(net_hi, PROMPT, hi_new)
        tracing.reset()
        tracing.enable()
        srv = make_decode_server(
            net_lo, decode_pages=40, len_buckets=(8, 16, 32, 64),
            max_generate_tokens=PROMPT.size + low_new, priority=0)
        srv.start()
        try:
            srv.register_model("premium", net_hi, slo_class="premium",
                               priority=10)
            srv.submit_generate(PROMPT, 2).result(timeout=600)
            srv.submit_generate(PROMPT, 2,
                                model="premium").result(timeout=600)
            # 3 low-priority squatters reserve 3 x 12 of 39 usable
            # pages; the premium arrival needs 4 -> must preempt
            lows = [srv.submit_generate(PROMPT, low_new)
                    for _ in range(3)]
            wait_until(lambda: srv.stats()["generates_active"] >= 3,
                       msg="squatters admitted")
            his = [srv.submit_generate(PROMPT, hi_new, model="premium")
                   for _ in range(2)]
            for h in his:
                assert np.array_equal(h.result(timeout=600), orc_hi)
            n_preempted = 0
            for h in lows:
                try:
                    got = h.result(timeout=600)
                except Preempted:
                    n_preempted += 1
                    got = h.tokens()
                    # sealed clean prefix: every token emitted before
                    # the eviction matches the oracle, and the stream
                    # never yields another token after the typed end
                    assert h.next_token(len(got), timeout=1) is None
                assert np.array_equal(
                    np.asarray(got, np.int32), orc_lo[:len(got)])
            assert n_preempted >= 1
            events = tracing.events("preempted")
            assert events, "flight recorder lost the preemption"
            for e in events:
                assert e["victim_model"] == DEFAULT_MODEL
                assert e["beneficiary_model"] == "premium"
                assert e["victim"] is not None
                assert e["beneficiary"] is not None
            st = srv.stats()
            assert st["preemptions"] == n_preempted
            assert st["models"][DEFAULT_MODEL]["preempted"] == \
                n_preempted
        finally:
            srv.stop(drain=False)
            tracing.reset()

    def test_lower_priority_arrival_never_evicts(self):
        net_hi, net_lo = get_llama(7), get_llama(11)
        tracing.reset()
        tracing.enable()
        # default tenant IS the high-priority one here: its streams
        # hold the pool while a low-priority arrival waits its turn
        srv = make_decode_server(
            net_hi, decode_pages=40, len_buckets=(8, 16, 32, 64),
            max_generate_tokens=PROMPT.size + 40, priority=10)
        srv.start()
        try:
            srv.register_model("low", net_lo, priority=0)
            srv.submit_generate(PROMPT, 2).result(timeout=600)
            srv.submit_generate(PROMPT, 2,
                                model="low").result(timeout=600)
            highs = [srv.submit_generate(PROMPT, 40) for _ in range(3)]
            wait_until(lambda: srv.stats()["generates_active"] >= 3,
                       msg="high-priority streams admitted")
            lo = srv.submit_generate(PROMPT, 8, model="low")
            # the low arrival must WAIT (head-of-line on its own
            # tenant queue), not evict anyone, and complete correctly
            # once the actives release their pages
            for h in highs:
                h.result(timeout=600)
            got = lo.result(timeout=600)
            assert np.array_equal(got, oracle(net_lo, PROMPT, 8))
            assert tracing.events("preempted") == []
            assert srv.stats()["preemptions"] == 0
        finally:
            srv.stop(drain=False)
            tracing.reset()


# ---------------------------------------------------------------------------
# automatic defrag trigger
# ---------------------------------------------------------------------------

class TestAutoDefrag:
    def test_defrag_fires_under_fragmentation_and_streams_stay_clean(self):
        net = get_llama(7)
        orc_long = oracle(net, PROMPT, 60)
        srv = make_decode_server(
            net, decode_pages=40, len_buckets=(8, 16, 32, 64),
            max_generate_tokens=PROMPT.size + 60,
            defrag_threshold=0.1)
        srv.start()
        try:
            srv.submit_generate(PROMPT, 2).result(timeout=600)
            # two short streams allocate LOW pages and finish early;
            # the long stream's pages sit above the holes they leave —
            # free-below-high-water crosses the 10% threshold and the
            # between-steps trigger must pack the pool while the long
            # stream keeps decoding
            shorts = [srv.submit_generate(PROMPT, 8) for _ in range(2)]
            wait_until(lambda: srv.stats()["generates_active"] >= 2,
                       msg="short streams admitted")
            long = srv.submit_generate(PROMPT, 60)
            for h in shorts:
                h.result(timeout=600)
            got = long.result(timeout=600)
            st = srv.stats()
        finally:
            srv.stop(drain=False)
        assert st["defrags"] >= 1
        assert np.array_equal(got, orc_long)


# ---------------------------------------------------------------------------
# per-model rolling upgrade
# ---------------------------------------------------------------------------

class TestPerModelUpgrade:
    def test_upgrading_tenant_b_leaves_default_untouched(self):
        net_a = worker_factory.tiny_net(seed=0)
        ref_a = classify_oracle(net_a, X)
        ref_b2 = classify_oracle(worker_factory.tiny_net(seed=2), X)
        reps = [make_classify_server(worker_factory.tiny_net(seed=0),
                                     name=f"up{i}") for i in range(2)]
        with serving.Router(reps, slo_ms=2000.0) as router:
            router.register_model(
                "b", lambda: worker_factory.tiny_net(seed=1))
            v0 = reps[0].model_versions()
            out = rolling_upgrade(
                router, lambda server: worker_factory.tiny_net(seed=2),
                bake_s=0.05, model="b")
            assert out["model"] == "b"
            assert sorted(out["upgraded"]) == ["up0", "up1"]
            for r in reps:
                v1 = r.model_versions()
                assert v1["b"] == v0["b"] + 1
                assert v1[DEFAULT_MODEL] == v0[DEFAULT_MODEL]
            out_a = router.submit(X, deadline_ms=2000).result(timeout=60)
            out_b = router.submit(X, deadline_ms=2000,
                                  model="b").result(timeout=60)
        assert np.array_equal(out_a, ref_a)
        assert np.array_equal(out_b, ref_b2)

    def test_upgrade_refuses_partially_registered_tenant(self):
        reps = [make_classify_server(worker_factory.tiny_net(seed=0),
                                     name=f"part{i}") for i in range(2)]
        # tenant "b" registered on ONE replica behind the router's
        # back: upgrading it fleet-wide would swap a model half the
        # fleet does not serve
        reps[0].register_model("b", worker_factory.tiny_net(seed=1))
        with serving.Router(reps, slo_ms=2000.0) as router:
            with pytest.raises(MXNetError, match="whole fleet"):
                rolling_upgrade(
                    router,
                    lambda server: worker_factory.tiny_net(seed=2),
                    bake_s=0.05, model="b")


# ---------------------------------------------------------------------------
# wire forward-compat: absent field = default tenant
# ---------------------------------------------------------------------------

class TestWireForwardCompat:
    def _roundtrip(self, frame):
        a, b = socket.socketpair()
        try:
            wire.send_frame(a, frame)
            return wire.recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_new_fields_survive_and_old_reader_ignores_them(self):
        frame = {"kind": "submit", "id": 3,
                 "payload": np.arange(8, dtype=np.float32),
                 "model": "premium", "priority": 7,
                 "a_field_from_the_future": True}
        back = self._roundtrip(frame)
        # a new peer reads the tenant fields...
        assert back["model"] == "premium" and back["priority"] == 7
        # ...an old peer never looks: unknown fields ride through the
        # codec untouched, so the frame still parses and serves
        assert back["kind"] == "submit" and back["id"] == 3
        assert back["a_field_from_the_future"] is True
        assert np.array_equal(back["payload"], frame["payload"])

    def test_absent_fields_mean_default_tenant(self):
        # a frame from a peer that predates multi-tenancy: no model,
        # no priority — the .get() read every handler uses yields the
        # default-tenant sentinel, never a KeyError
        back = self._roundtrip({"kind": "submit", "id": 1,
                                "payload": np.zeros(8, np.float32)})
        assert back.get("model") is None
        assert back.get("priority") is None

    def test_error_registry_roundtrips_tenant_errors(self):
        for exc, etype in ((Preempted("evicted at step 3"),
                            "preempted"),
                           (TenantThrottled("lim over rate"),
                            "throttled")):
            name, msg = wire.encode_error(exc)
            assert name == etype
            again = wire.decode_error(name, msg)
            assert isinstance(again, type(exc))
            assert str(exc) in str(again)


# ---------------------------------------------------------------------------
# tenant context across the socket edge
# ---------------------------------------------------------------------------

class TestIngressTenants:
    def test_model_field_crosses_the_socket_and_absent_is_default(self):
        net_a = worker_factory.tiny_net(seed=0)
        net_b = worker_factory.tiny_net(seed=1)
        ref_a = classify_oracle(net_a, X)
        ref_b = classify_oracle(net_b, X)
        srv = make_classify_server(net_a, name="ing_mt")
        with serving.Router([srv], slo_ms=2000.0) as router:
            router.register_model(
                "b", lambda: worker_factory.tiny_net(seed=1))
            with serving.Ingress(router, window=16) as ing, \
                    serving.IngressClient("127.0.0.1", ing.port) as cli:
                out_b = cli.submit(X, deadline_ms=2000,
                                   model="b").result(timeout=60)
                # no model field on the wire -> default tenant
                out_a = cli.submit(X,
                                   deadline_ms=2000).result(timeout=60)
                with pytest.raises(MXNetError):
                    cli.submit(X, deadline_ms=2000,
                               model="ghost").result(timeout=60)
        assert np.array_equal(out_a, ref_a)
        assert np.array_equal(out_b, ref_b)


# ---------------------------------------------------------------------------
# tools/latency_report.py: per-tenant rollup + preemption pairing
# ---------------------------------------------------------------------------

class TestLatencyReportTenants:
    def _report_mod(self):
        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), os.pardir, "tools"))
        try:
            import latency_report
        finally:
            sys.path.pop(0)
        return latency_report

    @staticmethod
    def _trace(model, slo, dur_us, status="ok"):
        spans = [{"name": "request", "ts": 0, "dur": dur_us,
                  "tags": {"model": model, "slo_class": slo}}] \
            if model else [{"name": "request", "ts": 0, "dur": dur_us}]
        return {"trace_id": f"{model}-{dur_us}", "status": status,
                "spans": spans}

    def test_tables_split_by_tenant_and_preemptions_pair_up(self):
        lr = self._report_mod()
        traces = (
            [self._trace("premium", "premium", 1000)] * 4
            + [self._trace(None, None, 9000)] * 4)
        events = [
            {"event": "preempted", "victim_model": "default",
             "beneficiary_model": "premium", "victim_tokens": 12},
            {"event": "preempted", "victim_model": "default",
             "beneficiary_model": "premium", "victim_tokens": 20},
            {"event": "preempted", "victim_model": "default",
             "beneficiary_model": "premium", "victim_tokens": 30},
            {"event": "shed", "reason": "throttled", "model": "premium"},
        ]
        rows = {r["model"]: r for r in lr.tenant_rollup(traces, events)}
        assert set(rows) == {"default", "premium"}
        # whose p99: the untagged traces ARE the default tenant, and
        # the split keeps each tenant's percentiles apart
        assert rows["default"]["request_p99_ms"] == 9.0
        assert rows["premium"]["request_p99_ms"] == 1.0
        assert rows["premium"]["sheds"] == {"throttled": 1}
        pre = lr.preemption_rollup(events)
        assert pre["events"] == 3
        pair = pre["pairs"]["premium preempted default"]
        assert pair["count"] == 3
        assert pair["victim_clean_prefix_p50_tokens"] == 20.0
        rep = lr.report(traces, events)
        assert "tenants" in rep and "preemptions" in rep
