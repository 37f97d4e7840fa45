"""``trace_reduce.label_gaps`` as one sorted sweep against the loop it
replaced (every gap against every span that starts before its end), which
is kept here as the oracle: the same labels, the same sums bit for bit and
the same order, on hand-made and seeded inputs full of ties; its cost at a
chat-sized input; ``summary`` keeping two programs' ``fusion.4`` apart;
and ``ProfileSlice.at`` counting a slice's length from the trace's start.
"""
import random
import time

import pytest

from benchmarks.lib import harness
from benchmarks.lib import trace_reduce as tr


def label_gaps_every_pair(gaps, host):
    """``label_gaps`` as it was up to PR 42, to the letter."""
    sums = {}
    for gs, ge in gaps:
        best, best_cover, best_dur = "unattributed", 0.0, 0.0
        for h in host:
            if h.start_ns >= ge:
                break
            cover = min(ge, h.end_ns) - max(gs, h.start_ns)
            if cover <= 0:
                continue
            if cover > best_cover or (cover == best_cover
                                      and h.dur_ns < best_dur):
                best, best_cover, best_dur = h.name, cover, h.dur_ns
        label = best[len(tr.HOST_PREFIX):] \
            if best.startswith(tr.HOST_PREFIX) else best
        sums[label] = sums.get(label, 0.0) + (ge - gs)
    return sorted(sums.items(), key=lambda kv: -kv[1])


def _host(*spans):
    """(name, start, duration) -> events sorted by start, as
    ``Trace.host`` is; equal starts keep the order given."""
    return sorted((tr.Event(n, s, d) for n, s, d in spans),
                  key=lambda e: e.start_ns)


def _same(gaps, host):
    got = tr.label_gaps(gaps, host)
    want = label_gaps_every_pair(gaps, host)
    assert got == want
    # equal floats, not close ones: a sum in another order would differ
    assert [repr(v) for _, v in got] == [repr(v) for _, v in want]
    return got


HAND = {
    "no_gaps": ([], [("bench:a", 0, 10)]),
    "no_spans": ([(0, 10), (20, 25)], []),
    "neither": ([], []),
    "nested_inner_covers_less": (
        [(10, 30)], [("bench:outer", 0, 100), ("bench:inner", 12, 5)]),
    "nested_equal_cover_shorter_wins": (
        [(10, 20)], [("bench:outer", 0, 100), ("bench:inner", 5, 30)]),
    "equal_cover_equal_length_earlier_wins": (
        [(10, 20)], [("bench:first", 0, 40), ("bench:second", 5, 40),
                     ("bench:third", 5, 40)]),
    "equal_starts": (
        [(0, 8)], [("bench:long", 0, 50), ("bench:short", 0, 4),
                   ("bench:mid", 0, 8)]),
    "zero_length_gap": (
        [(5, 5), (7, 9)], [("bench:a", 0, 10)]),
    "zero_length_span": (
        [(0, 10)], [("bench:point", 5, 0), ("bench:a", 8, 1)]),
    "span_starts_at_gap_end": (
        [(0, 10)], [("bench:late", 10, 5)]),
    "span_ends_at_gap_start": (
        [(10, 20)], [("bench:early", 0, 10), ("bench:a", 19, 5)]),
    "a_round_of_ten_near_equal_spans_under_a_prefill": (
        [(100, 140), (10, 12), (60, 61)],
        [("bench:prefill", 0, 130)] + [("bench:decode.step", 50 + i, 80)
                                       for i in range(10)]),
    "gaps_longest_first_and_overlapping": (
        [(0, 50), (40, 45), (10, 20), (10, 20)],
        [("bench:a", 5, 10), ("bench:b", 12, 30), ("plain", 41, 2)]),
    "a_long_span_outlives_many_short_ones": (
        [(i, i + 1.5) for i in range(0, 60, 3)],
        [("bench:long", 1, 1000)] + [("bench:s%d" % (i % 4), i, 2)
                                     for i in range(0, 60, 2)]),
    "float_sums_that_depend_on_their_order": (
        [(0.1, 0.4), (1e9, 1e9 + 0.3), (0.7, 0.8), (3.3, 1e7 + 0.1)],
        [("bench:a", 0, 2e9)]),
}


@pytest.mark.parametrize("case", sorted(HAND))
def test_sweep_equals_every_pair_by_hand(case):
    gaps, spans = HAND[case]
    _same(gaps, _host(*spans))


def test_what_the_ties_come_to():
    gaps, spans = HAND["nested_equal_cover_shorter_wins"]
    assert _same(gaps, _host(*spans)) == [("inner", 10)]
    gaps, spans = HAND["equal_cover_equal_length_earlier_wins"]
    assert _same(gaps, _host(*spans)) == [("first", 10)]
    gaps, spans = HAND["span_starts_at_gap_end"]
    assert _same(gaps, _host(*spans)) == [("unattributed", 10)]
    gaps, spans = HAND["zero_length_gap"]
    assert _same(gaps, _host(*spans)) == [("a", 2), ("unattributed", 0)]


def _seeded(seed):
    """Gaps and spans on a coarse grid, so that equal covers, equal
    lengths, equal starts and touching ends are the rule; every few seeds
    the gaps are disjoint and longest first, as ``idle_gaps`` gives them."""
    rng = random.Random(seed)
    grid = rng.choice((8, 20, 60, 400))
    names = ["bench:decode.step", "bench:prefill", "bench:round.launch",
             "other"][:rng.randint(1, 4)]
    spans = []
    for _ in range(rng.choice((0, 1, 3, 12, 40, 120))):
        start = rng.randint(0, grid)
        dur = rng.choice((0, 1, 2, 3, rng.randint(0, grid),
                          rng.randint(0, 3 * grid)))
        if rng.random() < 0.2:
            start, dur = start + rng.random(), dur + rng.random()
        spans.append((rng.choice(names), start, dur))
    gaps = []
    for _ in range(rng.choice((0, 1, 5, 30, 90))):
        start = rng.randint(0, grid)
        length = rng.choice((0, 1, 1, 2, rng.randint(0, grid // 2)))
        if rng.random() < 0.2:
            start, length = start + rng.random(), length + rng.random()
        gaps.append((start, start + length))
    if seed % 3 == 0:
        gaps = sorted(tr.union(gaps), key=lambda g: g[0] - g[1])
    return gaps, _host(*spans)


@pytest.mark.parametrize("seed", range(120))
def test_sweep_equals_every_pair_seeded(seed):
    _same(*_seeded(seed))


def test_a_slice_of_idle_gaps_on_a_recorded_shape():
    """Device events and host spans as a serving slice has them: the gaps
    come from ``idle_gaps`` (longest first, most of them a nanosecond or
    two between back-to-back operations), ten near-equal ``decode.step``
    spans a round, recorded from long before the slice."""
    rng = random.Random(43)
    events, t = [], 1_000_000.0
    for _ in range(4000):
        t += rng.choice((1.0, 2.0, 1.0, 900.0, 25_000.0))
        dur = rng.choice((300.0, 4_000.0, 90_000.0))
        events.append(tr.Event("fusion.%d" % rng.randint(0, 9), t, dur))
        t += dur
    spans, s = [], 0.0
    while s < t:
        spans += [("bench:decode.step", s + rng.randint(0, 40), 2_000_000.0
                   + rng.randint(0, 3)) for _ in range(10)]
        if rng.random() < 0.1:
            spans.append(("bench:prefill", s + 500_000.0, 3_000_000.0))
        s += 2_100_000.0
    gaps = tr.idle_gaps(events)
    assert len(gaps) > 3000
    got = _same(gaps, _host(*spans))
    assert got[0][0] == "decode.step"


def test_cost_follows_the_overlaps_not_the_pairs():
    """100,000 gaps and 10,000 spans, ten to a round: a billion pairs,
    about a million overlaps. The loop it replaced takes minutes here."""
    rng = random.Random(7)
    spans = _host(*[("bench:decode.step", 5_000.0 * (i // 10) + i % 10,
                     5_000.0 + rng.randint(0, 5)) for i in range(10_000)])
    t0 = spans[len(spans) // 2].start_ns
    gaps = [(t0 + 20.0 * i, t0 + 20.0 * i + rng.choice((1.0, 2.0, 9.0)))
            for i in range(100_000)]
    gaps.sort(key=lambda g: g[0] - g[1])
    began = time.perf_counter()
    got = tr.label_gaps(gaps, spans)
    took = time.perf_counter() - began
    assert took < 5.0, f"label_gaps took {took:.1f} s"
    assert [n for n, _ in got] == ["decode.step"]
    assert got[0][1] == sum(e - s for s, e in gaps)
    few = gaps[:40] + gaps[-40:]
    assert tr.label_gaps(few, spans) == label_gaps_every_pair(few, spans)


def test_summary_keeps_two_programs_operations_apart():
    """``fusion.4`` of the head program and ``fusion.4`` of a layer
    program are two rows, each under its own shapes."""
    head = "%fusion.4 = f32[8,19360]{1,0} fusion(bf16[19360,6144]{1,0} %w)"
    layer = "%fusion.4 = bf16[8,6144]{1,0} fusion(bf16[8,6144]{1,0} %x)"
    events = [tr.Event("fusion.4", 0.0, 50.0, head),
              tr.Event("fusion.4", 60.0, 30.0, layer),
              tr.Event("fusion.4", 100.0, 50.0, head),
              tr.Event("copy.1", 160.0, 10.0, "%copy.1 = bf16[8]{0} copy()")]
    assert tr.by_name(events) == {
        "%fusion.4 = f32[8,19360] fusion(bf16[19360,6144] %w)": 100.0,
        "%fusion.4 = bf16[8,6144] fusion(bf16[8,6144] %x)": 30.0,
        "%copy.1 = bf16[8] copy()": 10.0}
    s = tr.summary(tr.Trace(devices={0: events}))
    assert s["device_ops"] == [
        ["%fusion.4 = f32[8,19360] fusion(bf16[19360,6144] %w)", 100 / 1e9],
        ["%fusion.4 = bf16[8,6144] fusion(bf16[8,6144] %x)", 30 / 1e9],
        ["%copy.1 = bf16[8] copy()", 10 / 1e9]]
    ops = tr.describe(tr.Trace(devices={0: events}))["devices"]["0"]["ops"]
    assert [(name, ms, count) for name, ms, count, _ in ops] == [
        ("fusion.4", 100 / 1e6, 2), ("fusion.4", 30 / 1e6, 1),
        ("copy.1", 10 / 1e6, 1)]


class _Clock:
    """``time`` for ``harness``: sleeping moves the clock and nothing
    waits."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def sleep(self, seconds):
        assert seconds >= 0
        self.now += seconds


class _Slice(harness.ProfileSlice):
    """No profiler: ``start`` comes back ``late`` seconds after it was
    called, and both write down the clock."""

    def __init__(self, clock, late):
        super().__init__("unused")
        self.clock, self.late, self.calls = clock, late, []

    def start(self):
        self.calls.append(("start called", self.clock.now))
        self.clock.now += self.late
        self.started = True
        self.calls.append(("started", self.clock.now))

    def stop(self):
        self.calls.append(("stopped", self.clock.now))
        self.stopped = True


@pytest.mark.parametrize("late", [0.0, 0.3, 11.0])
def test_a_slice_is_as_long_as_asked_however_late_the_trace_starts(
        monkeypatch, late):
    clock = _Clock()
    monkeypatch.setattr(harness, "time", clock)
    prof = _Slice(clock, late)
    prof.at(125.0, 129.0)                   # 4 s, 25 s from now
    prof.join(timeout=10.0)
    assert prof.calls == [("start called", 125.0),
                          ("started", 125.0 + late),
                          ("stopped", 129.0 + late)]


def test_a_slice_asked_for_in_the_past_starts_at_once(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(harness, "time", clock)
    prof = _Slice(clock, 0.5)
    prof.at(90.0, 90.5)
    prof.join(timeout=10.0)
    assert prof.calls == [("start called", 100.0), ("started", 100.5),
                          ("stopped", 101.0)]
