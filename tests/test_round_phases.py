"""The record a decode round leaves of its host turn (``Server`` with
tracing or telemetry on): one ``decode.round`` span whose six ``round.*``
children tile it, the ``round`` tag that joins every stream's
``decode.step`` span to it, ``mxnet_serving_round_phase_seconds_total``,
and nothing at all (no clock reading) with both off. On the CPU, the tiny
decoders behind a real ``Server``."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fault, serving, telemetry, tracing
from mxnet_tpu.base import MXNetError

pytestmark = pytest.mark.serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
for path in (ROOT, FIXTURES):
    if path not in sys.path:
        sys.path.insert(0, path)

import worker_factory  # noqa: E402  (the fixtures dir is the point)

PHASES = ("wait", "sched", "build", "launch", "fetch", "emit")
PROMPTS = [np.array(p, np.int32) for p in
           ([3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8, 1], [9, 9, 6])]
_NET = {}


def llama():
    if "net" not in _NET:
        _NET["net"] = worker_factory.tiny_llama(seed=7)
    return _NET["net"]


def make_server(net=None, **kw):
    args = dict(batch_buckets=(1, 2, 4), shape_buckets=[(8,)],
                slo_ms=60000.0, dtype="int32", warmup=False,
                decode_pages=96, page_size=4, len_buckets=(8, 16))
    args.update(kw)
    return serving.Server(net if net is not None else llama(), **args)


@pytest.fixture(autouse=True)
def clean_observers():
    yield
    fault.clear()
    tracing.reset()
    telemetry.disable()
    telemetry.reset()


def generate(srv, prompts, max_new, traced=True, on_token=None):
    """Submit ``prompts`` (each under a trace of the caller's, as the
    benchmark does), wait for all; (token lists or the error, spans)."""
    traces, handles = [], []
    for i, p in enumerate(prompts):
        n = max_new[i] if isinstance(max_new, (list, tuple)) else max_new
        if traced:
            traces.append(tracing.new_trace("test.request"))
            with tracing.active(traces[-1]):
                handles.append(srv.submit_generate(p, n, on_token=on_token))
        else:
            handles.append(srv.submit_generate(p, n, on_token=on_token))
    outs = []
    for h in handles:
        try:
            outs.append(h.result(timeout=120.0).tolist())
        except MXNetError as e:
            outs.append(e)
    spans = {}
    for tr in traces:       # a batch span is copied into every trace
        for s in tr.export_spans():
            spans[s["span_id"]] = s
    return outs, list(spans.values())


def rounds_of(spans):
    """{round number: (the ``decode.round`` span, its children in phase
    order, its streams' ``decode.step`` spans)}."""
    parents = {s["tags"]["round"]: s for s in spans
               if s["name"] == "decode.round"}
    assert len(parents) == sum(s["name"] == "decode.round" for s in spans)
    out = {}
    for n, parent in parents.items():
        kids = [s for s in spans if s.get("parent_id") == parent["span_id"]]
        assert sorted(k["name"] for k in kids) == \
            sorted("round." + p for p in PHASES)
        kids.sort(key=lambda k: PHASES.index(k["name"][len("round."):]))
        steps = [s for s in spans if s["name"] == "decode.step"
                 and s["tags"].get("round") == n]
        out[n] = (parent, kids, steps)
    return out


def assert_tiled(parent, kids):
    at = parent["ts"]
    for k in kids:
        assert k["ts"] == at, (k["name"], k["ts"], at)
        at += k["dur"]
    assert at == parent["ts"] + parent["dur"]
    assert sum(k["dur"] for k in kids) == parent["dur"]


def test_every_round_leaves_one_record_whose_phases_tile_it():
    tracing.enable()
    with make_server() as srv:
        outs, spans = generate(srv, PROMPTS, [9, 5, 7])
    assert [len(o) for o in outs] == [9, 5, 7]
    rounds = rounds_of(spans)
    # every decode.step span belongs to a recorded round, and the rounds
    # are numbered as the server made them
    tagged = {s["tags"]["round"] for s in spans if s["name"] == "decode.step"}
    assert tagged == set(rounds)
    assert sorted(rounds) == list(range(min(rounds), max(rounds) + 1))
    assert sum(len(steps) for _, _, steps in rounds.values()) == \
        sum(len(o) - 1 for o in outs)       # a first token is prefill's
    last_end = None
    for n in sorted(rounds):
        parent, kids, steps = rounds[n]
        assert_tiled(parent, kids)
        tags = parent["tags"]
        assert tags["streams"] == len(steps) >= 1
        assert tags["cap"] == srv.grid.batch_bucket(tags["streams"])
        assert (tags["outcome"], tags["model"], tags["replica"]) == \
            ("ok", "default", srv.name)
        assert kids[1]["tags"]["prefills"] >= 0
        assert 0 <= kids[5]["tags"]["callback_us"] <= kids[5]["dur"]
        # the scheduler thread's time is tiled ACROSS rounds too
        if last_end is not None:
            assert parent["ts"] == last_end
        last_end = parent["ts"] + parent["dur"]
        # each stream's span starts in build and ends in emit
        for s in steps:
            assert kids[2]["ts"] <= s["ts"] <= kids[3]["ts"]
            assert kids[5]["ts"] <= s["ts"] + s["dur"] <= last_end
    assert sum(r[1][1]["tags"]["prefills"] for r in rounds.values()) >= 1


def test_a_tick_of_more_streams_than_the_grid_holds_leaves_a_record_a_batch():
    tracing.enable()
    queued = threading.Event()
    with make_server(batch_buckets=(1, 2)) as srv:
        # hold the scheduler in the first prefill's emit until all three
        # are queued, so that they decode together: rounds of 2 + 1
        handles, traces = [], []
        for p in PROMPTS:
            traces.append(tracing.new_trace("test.request"))
            with tracing.active(traces[-1]):
                handles.append(srv.submit_generate(
                    p, 6, on_token=lambda i, t: queued.wait(30.0)))
        queued.set()
        assert [len(h.result(timeout=120.0)) for h in handles] == [6, 6, 6]
    spans = [s for tr in traces for s in tr.export_spans()]
    rounds = rounds_of(spans)
    widths = [rounds[n][0]["tags"]["streams"] for n in sorted(rounds)]
    assert 2 in widths and 1 in widths
    firsts = [n for n in sorted(rounds) if n + 1 in rounds
              and rounds[n][0]["tags"]["streams"] == 2
              and rounds[n + 1][0]["tags"]["streams"] == 1]
    assert firsts
    for n in firsts:
        (first, _, _), (second, kids2, _) = rounds[n], rounds[n + 1]
        assert_tiled(second, kids2)
        # the second batch of the tick: no wait of its own, and no
        # prefill was dispatched between the two
        assert second["ts"] == first["ts"] + first["dur"]
        assert kids2[0]["dur"] == 0 and kids2[1]["tags"]["prefills"] == 0


def _tiny_glm():
    from benchmarks.builders import glm_moe_dsa as builder

    if "glm" not in _NET:
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               "tiny_glm_dsa.json")) as f:
            _NET["glm"] = builder.build_net(json.load(f), 3, ctx=mx.cpu())[0]
    return _NET["glm"]


def test_a_chunked_prefill_ticks_prefill_spans_lie_inside_round_sched():
    rs = np.random.RandomState(11)
    short = rs.randint(1, 256, (12,)).astype(np.int32)
    long_ = rs.randint(1, 256, (61,)).astype(np.int32)
    tracing.enable()
    srv = make_server(_tiny_glm(), batch_buckets=(1, 4), decode_pages=65,
                      page_size=8, max_generate_tokens=96,
                      max_prefill_tokens=16, defrag_threshold=None)
    with srv:
        tr_a, tr_b = (tracing.new_trace("test.request") for _ in range(2))
        with tracing.active(tr_a):
            a = srv.submit_generate(short, 40)
        a.next_token(0, timeout=120)
        with tracing.active(tr_b):      # prefilled while `a` decodes
            b = srv.submit_generate(long_, 6)
        b.result(timeout=120)
        a.result(timeout=120)
    spans = tr_a.export_spans() + tr_b.export_spans()
    chunks = [s for s in spans if s["name"] == "prefill"
              and "chunks" in s.get("tags", {})]
    assert len(chunks) == 4             # 61 tokens: 3 x 16 and a tail
    scheds = [k for r in rounds_of(spans).values() for k in r[1]
              if k["name"] == "round.sched"]
    for c in chunks:
        inside = [k for k in scheds if k["ts"] <= c["ts"]
                  and c["ts"] + c["dur"] <= k["ts"] + k["dur"]]
        assert len(inside) == 1 and inside[0]["tags"]["prefills"] >= 1
    assert sum(k["tags"]["prefills"] for k in scheds) >= len(chunks)


def test_a_dispatch_that_raises_ends_the_record_with_an_error(monkeypatch):
    monkeypatch.setenv("MXNET_COMM_RETRY_DELAY", "0.001")
    tracing.enable()

    def on_token(i, _token):
        if i == 3:      # on the scheduler thread: the NEXT dispatch fails
            fault.install("serving.dispatch=every:1")

    with make_server() as srv:
        outs, spans = generate(srv, PROMPTS[:2], 12, on_token=on_token)
        fault.clear()
        stats = srv.stats()
    assert all(isinstance(o, MXNetError) for o in outs)
    assert stats["generates_active"] == 0 and stats["kvcache"]["used"] == 0
    rounds = rounds_of(spans)
    failed = [n for n in rounds
              if rounds[n][0]["tags"]["outcome"] == "error"]
    assert failed == [max(rounds)]
    parent, kids, steps = rounds[failed[0]]
    assert_tiled(parent, kids)
    # it stopped in launch: nothing was fetched, nothing emitted
    assert kids[4]["dur"] == 0 and kids[5]["dur"] <= kids[3]["dur"]
    # no span was left open: both streams' decode.step spans ended (an
    # open span is in no trace) and say what happened
    assert len(steps) == parent["tags"]["streams"] == 2
    assert {s["tags"]["outcome"] for s in steps} == {"error"}
    for n in set(rounds) - set(failed):
        assert {s["tags"]["outcome"] for s in rounds[n][2]} == {"ok"}


def test_a_stream_with_a_trace_of_the_servers_keeps_its_last_round():
    """A request submitted under no trace gets one of the server's own,
    sealed when the request ends, which is INSIDE its last round's emit:
    the seal waits for that round's record."""
    tracing.enable()
    with make_server() as srv:
        out, _ = generate(srv, PROMPTS[:1], 5, traced=False)
    assert len(out[0]) == 5
    records = [r for r in tracing.recorder().traces()
               if any(s["name"] == "decode.step" for s in r["spans"])]
    assert len(records) == 1 and records[0]["status"] == "ok"
    rounds = rounds_of(records[0]["spans"])
    assert len(rounds) == 4
    for parent, kids, steps in rounds.values():
        assert_tiled(parent, kids)
        assert len(steps) == 1


@pytest.mark.parametrize("observer", ["tracing", "telemetry", "both"])
def test_tokens_do_not_depend_on_who_watches(observer):
    with make_server() as srv:
        want, _ = generate(srv, PROMPTS, [9, 5, 7], traced=False)
    if observer in ("tracing", "both"):
        tracing.enable()
    if observer in ("telemetry", "both"):
        telemetry.enable()
    with make_server() as srv:
        got, _ = generate(srv, PROMPTS, [9, 5, 7],
                          traced=observer != "telemetry")
    assert got == want


def _scheduler_clock_reads(monkeypatch):
    """Count ``time.time_ns`` calls made on a scheduler thread."""
    reads = []
    real = time.time_ns

    def counting():
        if threading.current_thread().name.startswith("server_"):
            reads.append(1)
        return real()

    monkeypatch.setattr(time, "time_ns", counting)
    return reads


def test_unwatched_rounds_read_no_clock_and_change_no_stats(monkeypatch):
    reads = _scheduler_clock_reads(monkeypatch)
    with make_server() as srv:
        assert srv._thread.name.startswith("server_")
        plain, _ = generate(srv, PROMPTS, [9, 5, 7], traced=False)
        stats_plain = srv.stats()
        assert srv._round_clock is None and srv._round_end_ns is None
        assert srv._tenants["default"].engine.run_done_ns is None
    assert reads == []
    telemetry.enable()
    tracing.enable()
    with make_server() as srv:
        watched, _ = generate(srv, PROMPTS, [9, 5, 7])
        stats_watched = srv.stats()
    assert len(reads) > 7 * 8          # seven boundaries a round
    assert watched == plain
    for stats in (stats_plain, stats_watched):
        stats.pop("running")
    assert stats_watched == stats_plain


def test_phase_seconds_add_up_to_the_wall_time_of_the_rounds():
    telemetry.enable()
    ticks = []
    with make_server() as srv:
        tick = srv._decode_tick

        def timed_tick():
            t0 = time.perf_counter()
            try:
                return tick()
            finally:
                ticks.append((t0, time.perf_counter()))

        srv._decode_tick = timed_tick
        out, _ = generate(srv, PROMPTS[:1], 40, traced=False)
    assert len(out[0]) == 40
    samples = {s["labels"]["phase"]: s["value"] for s in
               telemetry.snapshot()["metrics"][
                   "mxnet_serving_round_phase_seconds_total"]["samples"]}
    assert sorted(samples) == sorted(PHASES)
    assert all(v >= 0.0 for v in samples.values())
    # the first tick admits, prefills and runs round 1; every later tick
    # runs one round; between ticks the thread is in the next one's wait
    wall = ticks[-1][1] - ticks[0][0]
    assert sum(samples.values()) == pytest.approx(wall, rel=0.05)
    steps = telemetry.snapshot()["metrics"][
        "mxnet_serving_decode_steps_total"]["samples"][0]["value"]
    assert steps == 39
    # no stream had a trace: the counter is the whole record
    assert not tracing.recorder().traces()
