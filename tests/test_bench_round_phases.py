"""The six ``host_turn_*`` readers (``benchmarks/lib/round_phases.py``) on
synthetic device events and spans with a known idle per phase, what they
read where there is nothing to read, that the sweep is linear, and their
place in ``BENCHMARK.json``."""
import importlib
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import round_phases, trace_reduce  # noqa: E402

METRICS = ("host_turn_ms_per_round", "host_turn_emit_ms_per_round",
           "host_turn_sched_ms_per_round", "host_turn_build_ms_per_round",
           "host_turn_launch_ms_per_round", "host_turn_fetch_ms_per_round")
SERVING = ["mistral7b_chat_open", "mistral7b_rag_closed",
           "longcat_flash_decode_c256", "glm5_dsa_longctx_c8",
           "phi4flash_reason_c32"]
OFFSET_NS = -1_700_000_000_000_000_000 + 12_345     # epoch -> profiler clock
US = 1000
# one round on the scheduler thread, us: wait 100, sched 400 (a prefill
# span over its middle 200), build 300, launch 200, fetch 2600, emit 400
PHASE_US = (("wait", 100), ("sched", 400), ("build", 300), ("launch", 200),
            ("fetch", 2600), ("emit", 400))
ROUND_US = sum(us for _, us in PHASE_US)
# the device runs from 50 us into launch to 2400 us into fetch: idle are
# wait, sched, build, 50 of launch, 200 of fetch and emit
BUSY_FROM, BUSY_TO = 100 + 400 + 300 + 50, 100 + 400 + 300 + 200 + 2400
IDLE_US = {"emit": 400.0, "sched": 100.0 + 400.0 - 200.0, "build": 300.0,
           "launch": 50.0, "fetch": 200.0}
PREFILL_US = 200.0


def read(name, inputs):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(inputs)


def round_spans(n, t0_us, prefill=True):
    """Round ``n`` starting at epoch microsecond ``t0_us``: the parent,
    its six children and the prefill span inside ``round.sched``."""
    parent = {"name": "decode.round", "span_id": f"r{n}", "ts": t0_us,
              "dur": ROUND_US, "trace_id": "t",
              "tags": {"round": n, "streams": 3}}
    spans, at = [parent], t0_us
    for phase, us in PHASE_US:
        spans.append({"name": "round." + phase, "span_id": f"r{n}.{phase}",
                      "parent_id": f"r{n}", "ts": at, "dur": us,
                      "trace_id": "t"})
        at += us
    if prefill:
        spans.append({"name": "prefill", "span_id": f"p{n}",
                      "ts": t0_us + 200, "dur": 200, "trace_id": "t"})
    spans += [{"name": "decode.step", "span_id": f"s{n}.{i}",
               "ts": t0_us + 520, "dur": 3400, "trace_id": "t",
               "tags": {"round": n}} for i in range(3)]
    return spans


def make_inputs(rounds=4, lead_us=0.0, tail_us=0.0, prefill=True):
    """``rounds`` back-to-back rounds; the device's first operation starts
    ``lead_us`` before round 0 begins, its last ends ``tail_us`` after the
    last round ends (negative: inside it)."""
    t0_us = 1_700_000_000_000_000        # epoch us of round 0
    # in whole numbers: epoch nanoseconds are past what a float holds
    to_ns = lambda us: int(us * 1000) + OFFSET_NS  # noqa: E731
    events, spans = [], []
    for n in range(rounds):
        start = t0_us + n * ROUND_US
        spans += round_spans(n, start, prefill)
        # two operations back to back with a seam of 2 ns between them
        mid = (BUSY_FROM + BUSY_TO) // 2
        events.append(trace_reduce.Event(
            f"fusion.{n}", to_ns(start + BUSY_FROM),
            (mid - BUSY_FROM) * US - 2))
        events.append(trace_reduce.Event(
            f"custom-call.{n}", to_ns(start + mid), (BUSY_TO - mid) * US))
    events.insert(0, trace_reduce.Event(
        "copy.0", to_ns(t0_us - lead_us) - 10, 10))
    events.append(trace_reduce.Event(
        "copy.1", to_ns(t0_us + rounds * ROUND_US + tail_us), 10))
    events.sort(key=lambda e: e.start_ns)
    return {"trace": trace_reduce.Trace(devices={0: events}),
            "trace_clock_offset_ns": OFFSET_NS, "spans": spans,
            "cell": {"name": "mistral7b_chat_open", "chips": 1}}


def test_known_idle_per_phase_reads_exactly():
    inputs = make_inputs(rounds=4)
    got = {m: read(m, inputs) for m in METRICS}
    for phase, us in IDLE_US.items():
        assert got[f"host_turn_{phase}_ms_per_round"] == \
            pytest.approx(us / 1e3, abs=1e-6), phase
    total = (sum(IDLE_US.values()) + PREFILL_US) / 1e3
    assert got["host_turn_ms_per_round"] == pytest.approx(total, abs=1e-6)
    split = round_phases.split(inputs)
    assert split["rounds"] == 4
    assert split["round_ms"] == pytest.approx(ROUND_US / 1e3)
    assert split["prefill_ms"] == pytest.approx(4 * PREFILL_US / 1e3)
    assert split["unattributed_ms"] == pytest.approx(0.0, abs=1e-6)
    # the five phases, the prefills' idle and the rest ARE the host turn
    assert sum(split["phase_ms"].values()) + split["prefill_ms"] \
        + split["unattributed_ms"] == pytest.approx(split["idle_ms"])
    # idle share of the slice = host turn over the mean round
    idle_pct = importlib.import_module(
        "benchmarks.layer_metrics.device_idle_pct_serve").read(inputs)
    assert 100.0 * got["host_turn_ms_per_round"] / split["round_ms"] == \
        pytest.approx(idle_pct, abs=0.01)
    # computed once a run, then shared
    assert inputs["_round_phases"] is split


@pytest.mark.parametrize("edge,lead_us,tail_us", [
    ("first", -1.0, 0.0), ("last", 0.0, -1.0), ("both", -900.0, -3999.0)])
def test_a_round_cut_by_an_edge_of_the_slice_is_not_counted(edge, lead_us,
                                                            tail_us):
    inputs = make_inputs(rounds=5, lead_us=lead_us, tail_us=tail_us)
    split = round_phases.split(inputs)
    assert split["rounds"] == (3 if edge == "both" else 4)
    # the whole rounds read as if nothing had been cut
    for phase, us in IDLE_US.items():
        assert read(f"host_turn_{phase}_ms_per_round", inputs) == \
            pytest.approx(us / 1e3, abs=1e-6), phase


def test_a_gap_under_a_prefill_span_is_not_scheds():
    with_, without = make_inputs(prefill=True), make_inputs(prefill=False)
    assert read("host_turn_sched_ms_per_round", without) - \
        read("host_turn_sched_ms_per_round", with_) == \
        pytest.approx(PREFILL_US / 1e3, abs=1e-6)
    assert read("host_turn_ms_per_round", with_) == \
        pytest.approx(read("host_turn_ms_per_round", without))
    # a prefill-only tick lies in the next round's WAIT: as much its own
    inputs = make_inputs(prefill=False)
    first = next(s for s in inputs["spans"] if s["span_id"] == "r1.wait")
    inputs["spans"].append({"name": "prefill", "span_id": "px",
                            "ts": first["ts"] + 10, "dur": 80,
                            "trace_id": "u"})
    assert read("host_turn_sched_ms_per_round", inputs) == pytest.approx(
        (500.0 - 80.0 / 4) / 1e3, abs=1e-6)


def test_duplicated_span_dicts_count_once():
    once, twice = make_inputs(), make_inputs()
    # a round's record copied into a second trace, and exported again
    twice["spans"] = twice["spans"] + [dict(s, trace_id="other")
                                       for s in twice["spans"]]
    assert [read(m, twice) for m in METRICS] == \
        [read(m, once) for m in METRICS]


@pytest.mark.parametrize("case", ["no-trace", "no-device-events",
                                  "no-offset", "no-round-spans",
                                  "training-cell", "no-whole-round"])
def test_reports_nothing_where_there_is_nothing_to_read(case):
    inputs = make_inputs()
    if case == "no-trace":
        inputs["trace"] = None
    elif case == "no-device-events":
        inputs["trace"] = trace_reduce.Trace()
    elif case == "no-offset":
        inputs["trace_clock_offset_ns"] = None
    elif case == "no-round-spans":      # the parent commit's spans
        inputs["spans"] = [s for s in inputs["spans"]
                           if s["name"] in ("prefill", "decode.step")]
    elif case == "training-cell":       # hands back neither key
        del inputs["spans"], inputs["trace_clock_offset_ns"]
    else:
        inputs = make_inputs(rounds=1, lead_us=-5.0)
    assert [read(m, inputs) for m in METRICS] == [None] * 6


def test_the_sweep_is_linear():
    """100,000 gaps against 2,000 rounds in well under two seconds."""
    inputs = make_inputs(rounds=2000)
    first = inputs["trace"].devices[0][0].end_ns
    # fifty operations of 10 ns, 2 us apart, in every round's build: gaps
    # of 1.99 us, long enough to be looked at
    extra = [trace_reduce.Event(
        "noise", first + (n * ROUND_US + 500 + 2 * i) * US, 10)
        for n in range(2000) for i in range(50)]
    events = sorted(inputs["trace"].devices[0] + extra,
                    key=lambda e: e.start_ns)
    inputs["trace"] = trace_reduce.Trace(devices={0: events})
    assert len(trace_reduce.idle_gaps(events)) > 100_000
    t0 = time.perf_counter()
    split = round_phases.split(inputs)
    assert time.perf_counter() - t0 < 2.0
    assert split["rounds"] == 2000
    assert split["unattributed_ms"] == pytest.approx(0.0, abs=1e-3)
    assert split["phase_ms"]["build"] / 2000 == pytest.approx(
        (300.0 - 50 * 0.01) / 1e3, abs=1e-6)


def test_manifest_entries_are_the_last_six():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = manifest["per_layer"]
    names = [m["name"] for m in per_layer]
    # the last six when they were accepted (PR 38); later PRs append
    # their entries after them and their serving cells to these lists
    assert tuple(names[56:62]) == METRICS and len(names) >= 62
    for m in per_layer[56:62]:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "server",
                     "moves": "tpot_p50_ms", "workloads": m["workloads"]}
        assert m["workloads"][:len(SERVING)] == SERVING
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
    # every earlier entry is where it was accepted
    assert names.index("mla_attn_roofline") == 33
    assert names.index("ssm_ms_per_round") == 45     # the first of Phi's
    assert names.index("bias_gelu_ms_per_step") == 55
    tpot = next(m for m in manifest["end_to_end"]
                if m["name"] == "tpot_p50_ms")
    assert tpot["workloads"][:len(SERVING)] == SERVING
    # the names the harness hands to its gap labelling stay those two
    with open(os.path.join(ROOT, "benchmarks", "lib", "serve_loop.py")) as f:
        assert 'if s["name"] in ("prefill", "decode.step")' in f.read()
