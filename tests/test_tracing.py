"""Request tracing + flight recorder (mxnet_tpu/tracing.py): the span
layer (mint/adopt/ambient, batch flow linkage), the bounded recorder
ring and its crash dumps, exemplar round-trips through the Prometheus
text codec, the exporter's /varz + /traces endpoints under concurrent
scrapes, and tools/latency_report.py's per-stage decomposition.

The cross-PROCESS half (span context in the wire frame header, worker
spans piggybacked on result frames) lives in
tests/test_serving_worker.py::TestRealWorkerProcess — it needs a real
subprocess. Here everything is in-process and tier-1 fast.
"""
import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

from mxnet_tpu import serving, telemetry, tracing

pytestmark = pytest.mark.tracing

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
if FIXTURES not in sys.path:
    sys.path.insert(0, FIXTURES)

import worker_factory  # noqa: E402  (the fixtures dir is the point)


@pytest.fixture(autouse=True)
def _clean_ring():
    tracing.reset()
    yield
    tracing.reset()


# ---------------------------------------------------------------------------
# span layer
# ---------------------------------------------------------------------------

class TestSpanLayer:
    def test_default_off_and_inert(self):
        assert not tracing.enabled()
        assert tracing.ambient() is None
        tracing.note("dropped on the floor")        # no ambient: no-op
        tracing.record_event("shed", reason="x")    # disabled: no-op
        assert tracing.recorder().events() == []
        assert tracing.recorder().traces() == []

    def test_trace_finish_hands_record_to_ring(self):
        tracing.enable()
        tr = tracing.new_trace("request", router="r0")
        sp = tr.begin("router.queue", router="r0")
        sp.end(outcome="ok")
        tr.finish("ok")
        recs = tracing.recorder().traces()
        assert len(recs) == 1
        rec = recs[0]
        assert rec["trace_id"] == tr.trace_id
        assert rec["status"] == "ok"
        names = [s["name"] for s in rec["spans"]]
        assert "router.queue" in names and "request" in names
        # every span carries the ids that make a dump self-describing
        for s in rec["spans"]:
            assert s["trace_id"] == tr.trace_id
            assert s["span_id"] and s["proc"] and s["pid"] == os.getpid()

    def test_finish_first_wins(self):
        tracing.enable()
        tr = tracing.new_trace("request")
        tr.finish("ok")
        tr.finish("ReplicaFault")       # late loser must not re-record
        assert tr.status == "ok"
        assert len(tracing.recorder().traces()) == 1

    def test_span_end_is_idempotent(self):
        tracing.enable()
        tr = tracing.new_trace("request")
        sp = tr.begin("dispatch")
        sp.end(outcome="ok")
        sp.end(outcome="error")         # racing second end: dropped
        tr.finish("ok")
        spans = [s for s in tr.export_spans() if s["name"] == "dispatch"]
        assert len(spans) == 1
        assert spans[0]["tags"]["outcome"] == "ok"

    def test_wire_adopt_round_trip(self):
        tracing.enable()
        tr = tracing.new_trace("request")
        ctx = tr.wire()
        assert ctx["id"] == tr.trace_id
        assert ctx["parent"] == tr.root.span_id
        child = tracing.adopt(ctx, worker="w0")
        assert child is not None
        assert child.trace_id == tr.trace_id
        assert child.remote_parent == tr.root.span_id

    @pytest.mark.parametrize("bad", [
        None, "just-a-string", 42, {}, {"id": 7}, {"parent": "p"}])
    def test_adopt_malformed_degrades_to_untraced(self, bad):
        assert tracing.adopt(bad) is None

    def test_ambient_nests_and_is_thread_local(self):
        tracing.enable()
        tr = tracing.new_trace("request")
        seen = {}

        def other_thread():
            seen["other"] = tracing.ambient()

        with tracing.active(tr, tr.root):
            inner = tr.begin("router.attempt")
            with tracing.active(tr, inner):
                assert tracing.ambient() == (tr, inner)
                t = threading.Thread(target=other_thread)
                t.start()
                t.join()
            assert tracing.ambient() == (tr, tr.root)
        assert tracing.ambient() is None
        assert seen["other"] is None    # context never leaks threads

    def test_note_lands_inside_the_ambient_span(self):
        tracing.enable()
        tr = tracing.new_trace("request")
        sp = tr.begin("dispatch")
        with tracing.active(tr, sp):
            tracing.note("fault injected: serving.replica.0")
        sp.end()
        d = [s for s in tr.export_spans() if s["name"] == "dispatch"][0]
        assert "fault injected" in d["notes"][0][1]

    def test_batch_span_links_waits_and_fans_out(self):
        tracing.enable()
        traces = [tracing.new_trace("request") for _ in range(3)]
        waits = [t.begin("batch.wait") for t in traces]
        bsp = tracing.begin_batch(
            list(zip(traces, waits)), wait_tags={"bucket": 4},
            replica="rep0")
        assert bsp is not None
        assert bsp.tags["batch"] == 3
        # every wait span ended at dispatch start, carrying a flow id
        # that terminates at the batch span
        assert sorted(bsp.flows_in) == sorted(
            w.flow_out for w in waits)
        tracing.end_batch(bsp, outcome="ok")
        for t in traces:
            t.finish("ok")
        # the shared dispatch span is copied into EVERY sibling trace
        # (self-contained dumps), keeping the owning trace's id
        for t in traces:
            ds = [s for s in t.export_spans() if s["name"] == "dispatch"]
            assert len(ds) == 1
            assert ds[0]["span_id"] == bsp.span_id
            assert ds[0]["trace_id"] == traces[0].trace_id

    def test_chrome_export_flows_and_dedup(self):
        tracing.enable()
        traces = [tracing.new_trace("request") for _ in range(2)]
        waits = [t.begin("batch.wait") for t in traces]
        bsp = tracing.begin_batch(list(zip(traces, waits)))
        tracing.end_batch(bsp)
        for t in traces:
            t.finish("ok")
        evs = tracing.chrome_trace_events()
        xs = [e for e in evs if e["ph"] == "X"]
        # the fanned-out dispatch span appears ONCE despite living in
        # two trace records
        assert sum(1 for e in xs if e["name"] == "dispatch") == 1
        starts = [e for e in evs if e["ph"] == "s"]
        finishes = [e for e in evs if e["ph"] == "f"]
        assert {e["id"] for e in starts} == {e["id"] for e in finishes}
        assert len(starts) == 2         # one flow per co-batched wait


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_rings_are_bounded(self):
        rec = tracing.FlightRecorder(trace_capacity=4, event_capacity=3)
        for i in range(10):
            rec.record_trace({"trace_id": f"t{i}", "spans": []})
            rec.record_event("shed", seq=i)
        assert [t["trace_id"] for t in rec.traces()] == \
            ["t6", "t7", "t8", "t9"]
        assert [e["seq"] for e in rec.events()] == [7, 8, 9]
        assert rec.n_traces == 10 and rec.n_events == 10

    def test_dump_jsonl_round_trips(self):
        tracing.enable()
        tracing.record_event("breaker", replica="rep0",
                             from_state="closed", to_state="open")
        tr = tracing.new_trace("request")
        tr.finish("ok")
        lines = [json.loads(x) for x in
                 tracing.dump_jsonl().splitlines()]
        evs = [x for x in lines if "event" in x]
        trs = [x for x in lines if "trace_id" in x and "spans" in x]
        assert evs[0]["event"] == "breaker"
        assert evs[0]["to_state"] == "open"
        assert trs[0]["trace_id"] == tr.trace_id

    def test_dump_writes_through_atomic_write(self, tmp_path):
        tracing.enable()
        tr = tracing.new_trace("request")
        tr.finish("ok")
        path = str(tmp_path / "flight.jsonl")
        tracing.dump(path)
        with open(path) as f:
            lines = [json.loads(x) for x in f if x.strip()]
        assert any(x.get("trace_id") == tr.trace_id for x in lines)
        assert not [p for p in os.listdir(tmp_path)
                    if p != "flight.jsonl"]     # no temp litter

    def test_maybe_dump_weaves_pid_and_records_itself(
            self, tmp_path, monkeypatch):
        base = str(tmp_path / "traces.jsonl")
        monkeypatch.setenv("MXNET_TRACING_OUT", base)
        assert tracing.maybe_dump("test") is None   # disabled: no-op
        tracing.enable()
        tr = tracing.new_trace("request")
        tr.finish("ok")
        path = tracing.maybe_dump("breaker_open")
        assert path == str(tmp_path / f"traces.{os.getpid()}.jsonl")
        with open(path) as f:
            lines = [json.loads(x) for x in f if x.strip()]
        dumps = [x for x in lines if x.get("event") == "dump"]
        assert dumps and dumps[0]["reason"] == "breaker_open"

    def test_maybe_dump_without_env_is_none(self):
        tracing.enable()
        assert tracing.dump_path() is None
        assert tracing.maybe_dump("test") is None


# ---------------------------------------------------------------------------
# exemplars through the Prometheus text codec
# ---------------------------------------------------------------------------

class TestExemplars:
    def _scrape_with_exemplar(self):
        telemetry.record_serving_request(0.012, outcome="ok",
                                         trace_id="00ab00cd00ef0001")
        telemetry.record_serving_request(0.013, outcome="ok")
        return telemetry.prom_text()

    def test_exemplar_on_the_latency_bucket(self):
        telemetry.enable()
        try:
            telemetry.reset()
            text = self._scrape_with_exemplar()
        finally:
            telemetry.disable()
            telemetry.reset()
        ex_lines = [ln for ln in text.splitlines() if " # {" in ln]
        assert ex_lines, "no exemplar line in prom_text"
        assert any('trace_id="00ab00cd00ef0001"' in ln
                   and "_bucket" in ln for ln in ex_lines)

    def test_parse_emit_parse_is_lossless(self):
        telemetry.enable()
        try:
            telemetry.reset()
            text = self._scrape_with_exemplar()
        finally:
            telemetry.disable()
            telemetry.reset()
        p1 = telemetry.parse_prom_text(text)
        p2 = telemetry.parse_prom_text(telemetry.emit_prom_text(p1))
        assert p1 == p2
        exs = [s.get("exemplar")
               for fam in p1.values() for s in fam["samples"]
               if s.get("exemplar")]
        assert exs and exs[0]["labels"] == {
            "trace_id": "00ab00cd00ef0001"}

    def test_prom_value_ignores_exemplars(self):
        # the autoscaler's scrape path must read the same totals
        # whether or not requests were traced
        telemetry.enable()
        try:
            telemetry.reset()
            text = self._scrape_with_exemplar()
        finally:
            telemetry.disable()
            telemetry.reset()
        parsed = telemetry.parse_prom_text(text)
        fam = parsed["mxnet_serving_request_seconds"]
        cnt = [s for s in fam["samples"]
               if s["name"].endswith("_count")]
        assert cnt and cnt[0]["value"] == 2.0
        buckets = [s for s in fam["samples"]
                   if s["name"].endswith("_bucket")
                   and s.get("exemplar")]
        assert buckets and isinstance(buckets[0]["value"], float)
        # the scrape-fed controller reads counters from this same text
        assert telemetry.prom_value(
            parsed, "mxnet_serving_requests_total",
            {"outcome": "ok"}) == 2.0


# ---------------------------------------------------------------------------
# in-process end to end: ingress-less router path + exporter endpoints
# ---------------------------------------------------------------------------

def _traffic(n, dim=8):
    return [np.random.RandomState(300 + i).randn(dim).astype(np.float32)
            for i in range(n)]


class TestEndToEnd:
    def test_router_request_yields_one_connected_trace(self):
        tracing.enable()
        telemetry.enable()
        srv = serving.Server(
            worker_factory.tiny_net(), batch_buckets=(2, 4),
            shape_buckets=[(8,)], slo_ms=200, name="tr_rep0")
        router = serving.Router([srv], slo_ms=200).start()
        try:
            telemetry.reset()
            futs = [router.submit(x) for x in _traffic(4)]
            for f in futs:
                f.result(timeout=60)
            recs = tracing.recorder().traces()
            assert len(recs) == 4
            for rec in recs:
                assert rec["status"] == "ok"
                names = {s["name"] for s in rec["spans"]}
                assert {"request", "router.queue", "router.attempt",
                        "batch.wait", "dispatch"} <= names
                # the attempt chain shares the trace id (the batch
                # dispatch span may carry a co-batched sibling's)
                for s in rec["spans"]:
                    if s["name"] == "router.attempt":
                        assert s["trace_id"] == rec["trace_id"]
                        assert s["tags"]["outcome"] == "ok"
                        assert s["tags"]["replica"] == "tr_rep0"
            # the traced requests put exemplars on the router histogram
            assert 'trace_id="' in telemetry.prom_text()
        finally:
            router.stop(timeout=30)
            telemetry.disable()
            telemetry.reset()

    def test_untraced_router_request_allocates_no_trace(self):
        srv = serving.Server(
            worker_factory.tiny_net(), batch_buckets=(2, 4),
            shape_buckets=[(8,)], slo_ms=200, name="off_rep0")
        router = serving.Router([srv], slo_ms=200).start()
        try:
            router.submit(_traffic(1)[0]).result(timeout=60)
            assert tracing.recorder().traces() == []
            assert tracing.recorder().events() == []
        finally:
            router.stop(timeout=30)

    def test_exporter_varz_and_traces_under_concurrent_scrapes(self):
        tracing.enable()
        telemetry.enable()
        exporter = telemetry.start_exporter()
        try:
            telemetry.reset()
            telemetry.record_serving_request(
                0.01, trace_id="00aa00bb00cc0001")
            tr = tracing.new_trace("request")
            tr.finish("ok")
            base = exporter.url.rsplit("/metrics", 1)[0]
            results, errors = [], []

            def scrape(path, n=8):
                try:
                    for _ in range(n):
                        with urllib.request.urlopen(
                                base + path, timeout=10) as r:
                            results.append(
                                (path, r.status,
                                 r.read().decode("utf-8")))
                except Exception as e:  # noqa: BLE001 - reraised below
                    errors.append((path, e))

            threads = [threading.Thread(target=scrape, args=(p,))
                       for p in ("/metrics", "/varz", "/traces",
                                 "/metrics", "/varz", "/traces")]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errors, errors
            assert all(st == 200 for _, st, _ in results)
            by = {}
            for path, _st, body in results:
                by.setdefault(path, []).append(body)
            assert any('trace_id="00aa00bb00cc0001"' in b
                       for b in by["/metrics"])
            varz = json.loads(by["/varz"][0])
            assert "mxnet_serving_request_seconds" in varz["metrics"]
            got = [json.loads(ln) for ln in
                   by["/traces"][0].splitlines() if ln.strip()]
            assert any(x.get("trace_id") == tr.trace_id for x in got)
        finally:
            exporter.stop()
            telemetry.disable()
            telemetry.reset()


# ---------------------------------------------------------------------------
# tools/latency_report.py: per-stage decomposition from a dump
# ---------------------------------------------------------------------------

class TestLatencyReport:
    def _report_mod(self):
        sys.path.insert(0, os.path.join(
            os.path.dirname(__file__), os.pardir, "tools"))
        try:
            import latency_report
        finally:
            sys.path.pop(0)
        return latency_report

    def test_stage_split_from_traces_alone(self, tmp_path):
        lr = self._report_mod()
        tracing.enable()
        for i in range(8):
            tr = tracing.new_trace("request")
            for name, dur in (("ingress.decode", 100),
                              ("router.queue", 400),
                              ("batch.wait", 1600),
                              ("dispatch", 800),
                              ("wire.return", 200),
                              ("ingress.reply", 100)):
                tr.add_raw(name, ts=tracing.now_us(), dur=dur)
            tr.finish("ok")
        tracing.record_event("failover", reason="replica_error")
        path = str(tmp_path / "dump.jsonl")
        tracing.dump(path)

        traces, events = lr.load_traces([path])
        assert len(traces) == 8 and len(events) == 1
        rep = lr.report(traces, events)
        assert rep["traces"] == 8
        assert rep["statuses"] == {"ok": 8}
        assert rep["events"] == {"failover": 1}
        # the overhead rollup
        assert rep["serving_ingress_overhead_framing_ms"] == \
            pytest.approx(0.2)
        assert rep["serving_ingress_overhead_socket_ms"] == \
            pytest.approx(0.2)
        assert rep["serving_ingress_overhead_scheduling_ms"] == \
            pytest.approx(2.0)
        stages = {r["stage"]: r for r in rep["stages"]}
        assert stages["batch.wait"]["n"] == 8
        assert stages["batch.wait"]["p50_ms"] == pytest.approx(1.6)

    def test_failover_retries_are_summed_per_request(self, tmp_path):
        lr = self._report_mod()
        tracing.enable()
        tr = tracing.new_trace("request")
        tr.add_raw("router.attempt", ts=tracing.now_us(), dur=1000)
        tr.add_raw("router.attempt", ts=tracing.now_us(), dur=3000)
        tr.finish("ok")
        path = str(tmp_path / "dump.jsonl")
        tracing.dump(path)
        traces, events = lr.load_traces([path])
        stages = lr.stage_latencies(traces)
        assert stages["router.attempt"] == [4.0]  # the request paid both

    def test_round_records_are_rolled_up_per_round_not_per_request(
            self, tmp_path, capsys):
        """A decode round's record lives in the trace of the stream
        that led it: no stage of that request, a rollup of its own."""
        lr = self._report_mod()
        tracing.enable()
        lead, other = (tracing.new_trace("generate") for _ in range(2))
        phases = (("wait", 100), ("sched", 300), ("build", 200),
                  ("launch", 400), ("fetch", 2500), ("emit", 500))
        at = tracing.now_us()
        for n in range(10):
            us = 8000 if n == 9 else 4000   # one slow round: its fetch
            parent = lead.add_raw(
                "decode.round", ts=at, dur=us, round=n + 1, streams=2,
                cap=2, outcome="error" if n == 9 else "ok")
            for tr in (lead, other):
                tr.add_raw("decode.step", ts=at + 650, dur=us - 700,
                           round=n + 1)
            for phase, dur in phases:
                dur += us - 4000 if phase == "fetch" else 0
                tags = {"callback_us": 100} if phase == "emit" else {}
                lead.add_raw("round." + phase, ts=at, dur=dur,
                             parent=parent, **tags)
                at += dur
        lead.finish("ok")
        other.finish("ok")
        path = str(tmp_path / "dump.jsonl")
        tracing.dump(path)
        traces, events = lr.load_traces([path, path])   # read twice
        rep = lr.report(traces, events)
        stages = {r["stage"] for r in rep["stages"]}
        assert "decode.step" in stages
        assert not {s for s in stages if s.startswith(("round.",
                                                       "decode.round"))}
        roll = rep["round_rollup"]
        assert (roll["rounds"], roll["errors"], roll["streams_p50"]) == \
            (10, 1, 2.0)
        assert roll["round_p50_ms"] == pytest.approx(4.0)
        assert roll["round_p99_ms"] == pytest.approx(8.0)
        assert roll["phases"]["fetch"] == {"p50_ms": 2.5, "p99_ms": 6.5}
        assert roll["phases"]["emit"] == {"p50_ms": 0.5, "p99_ms": 0.5}
        assert list(roll["phases"]) == ["wait", "sched", "build",
                                        "launch", "fetch", "emit"]
        assert roll["emit_callback_share"] == pytest.approx(0.2)
        lr._print_table(rep)
        assert "round rollup (10 decode round(s)" in capsys.readouterr().out
        # a dump without such records has no such section
        assert "round_rollup" not in lr.report(
            [{"trace_id": "t", "status": "ok",
              "spans": [{"name": "dispatch", "dur": 500}]}], [])

    def test_bad_lines_are_skipped_not_fatal(self, tmp_path):
        lr = self._report_mod()
        path = tmp_path / "torn.jsonl"
        path.write_text(
            '{"trace_id": "t1", "status": "ok", "spans": '
            '[{"name": "dispatch", "dur": 500}]}\n'
            "{torn line from a crash dum\n")
        traces, events = lr.load_traces([str(path)])
        assert len(traces) == 1 and events == []
