"""From a profiler trace (.xplane.pb) to numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A
device plane is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
per executed HLO operation (start and duration in ns). Host planes hold
the ``jax.profiler.TraceAnnotation`` spans the benchmark puts around its
own calls (names starting ``bench:``), on the same clock.

All interval arithmetic is on closed-open ``(start_ns, end_ns)`` pairs.
"""
from __future__ import annotations

import functools
import glob
import heapq
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"
# a collective as XLA names its HLO operations
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")

# a Pallas (Mosaic) kernel in the HLO text of a device operation
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'

Interval = Tuple[float, float]


@dataclass
class Event:
    """``name``: the HLO instruction's name (``fusion.20``) or a host
    annotation's; ``long_name``: the whole HLO text the profiler prints for
    a device operation (empty for host events)."""
    name: str
    start_ns: float
    dur_ns: float
    long_name: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """Device operations per chip and the benchmark's host annotations."""
    devices: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)
    lines: Dict[str, List[str]] = field(default_factory=dict)


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _device_event(e) -> Event:
    """The profiler names a device operation by its HLO text,
    ``%fusion.20 = f32[...] fusion(...)``."""
    text = e.name
    head = text.split(" = ", 1)[0] if " = " in text else text
    return Event(head.lstrip("%"), e.start_ns, e.duration_ns, text)


_LAYOUT = re.compile(r"\{[^{}]*\}")


@functools.lru_cache(maxsize=8192)
def strip_layouts(text: str) -> str:
    """HLO text without the ``{1,0:T(8,128)}`` layout of every shape.
    Memoised: a slice holds 100,000 events of a few thousand texts."""
    for _ in range(3):                      # braces nest (attributes)
        text = _LAYOUT.sub("", text)
    return text


def short_label(event: Event, width: int = 160) -> str:
    """An operation's HLO text without layouts, cut to ``width``."""
    return strip_layouts(event.long_name or event.name)[:width]


def from_profile_data(data) -> Trace:
    trace = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        names = []
        for line in plane.lines:
            names.append(line.name)
            if m and line.name == OPS_LINE:
                trace.devices[int(m.group(1))] = sorted(
                    (_device_event(e) for e in line.events),
                    key=lambda e: e.start_ns)
            elif not m:
                trace.host.extend(
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events if e.name.startswith(HOST_PREFIX))
        trace.lines[plane.name] = names
    trace.host.sort(key=lambda e: e.start_ns)
    return trace


def load(path: str) -> Trace:
    """``path``: an .xplane.pb file, or the directory handed to
    ``jax.profiler.start_trace``."""
    import jax

    if os.path.isdir(path):
        found = find_xplane(path)
        if found is None:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found
    return from_profile_data(jax.profiler.ProfileData.from_file(path))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], t0: float, t1: float
         ) -> List[Interval]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of union ``a`` that union ``b`` does not cover."""
    out: List[Interval] = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def intervals_of(events: Iterable[Event]) -> List[Interval]:
    return [(e.start_ns, e.end_ns) for e in events]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def span_of(events: Sequence[Event]) -> Optional[Interval]:
    """First start to last end."""
    if not events:
        return None
    return (min(e.start_ns for e in events), max(e.end_ns for e in events))


def busy_ns(events: Sequence[Event], window: Optional[Interval] = None
            ) -> float:
    """Time in which at least one operation ran."""
    iv = union(intervals_of(events))
    if window is not None:
        iv = clip(iv, *window)
    return total(iv)


def idle_gaps(events: Sequence[Event], window: Optional[Interval] = None
              ) -> List[Interval]:
    """The intervals of ``window`` (default: first start to last end) in
    which no operation ran, longest first."""
    window = window or span_of(events)
    if window is None:
        return []
    gaps = subtract([window], intervals_of(events))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def by_name(events: Iterable[Event]) -> Dict[str, float]:
    """Summed duration (ns) per operation as it is printed
    (``short_label``: the HLO name with its shapes), so that ``fusion.4``
    of one program and ``fusion.4`` of another are two rows."""
    out: Dict[str, float] = {}
    for e in events:
        key = short_label(e)
        out[key] = out.get(key, 0.0) + e.dur_ns
    return out


def matching(events: Iterable[Event], pattern: str) -> List[Event]:
    """Events whose name or long name matches ``pattern``."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name) or rx.search(e.long_name)]


def collective_ns(events: Sequence[Event]) -> Tuple[float, float]:
    """(time in collective operations, the part of it during which no
    other operation ran on that device)."""
    coll = [e for e in events if COLLECTIVE.search(e.name)]
    rest = [e for e in events if not COLLECTIVE.search(e.name)]
    c_iv = union(intervals_of(coll))
    return total(c_iv), total(subtract(c_iv, intervals_of(rest)))


def label_gaps(gaps: Sequence[Interval], host: Sequence[Event]
               ) -> List[Tuple[str, float]]:
    """Name each idle gap by the benchmark annotation that covers most of
    it (the shorter one on equal cover, then the earlier in ``host``);
    ``unattributed`` where none covers any of it. ``host`` is sorted by
    start, as ``Trace.host`` is. Returns (label, ns) summed per label in
    the order the gaps were given, largest first.

    One sweep over the gaps in order of start: a span enters the active
    set once it starts before a gap's end and leaves for good once it ends
    at or before a gap's start, so a gap is compared only with the spans
    that can overlap it and not with every span recorded before it."""
    spans = sorted(host, key=lambda h: h.start_ns)       # stable
    starts = [h.start_ns for h in spans]
    ends = [h.end_ns for h in spans]
    durs = [h.dur_ns for h in spans]
    picked: List[int] = [-1] * len(gaps)
    active: List[Tuple[float, int]] = []                 # heap of (end, i)
    nxt = 0
    for g in sorted(range(len(gaps)), key=lambda k: gaps[k][0]):
        gs, ge = gaps[g]
        while nxt < len(spans) and starts[nxt] < ge:
            heapq.heappush(active, (ends[nxt], nxt))
            nxt += 1
        while active and active[0][0] <= gs:
            heapq.heappop(active)
        best = None                # (-cover, duration, place in host)
        for end, i in active:
            cover = min(ge, end) - max(gs, starts[i])
            if cover > 0:
                key = (-cover, durs[i], i)
                if best is None or key < best:
                    best = key
        if best is not None:
            picked[g] = best[2]
    sums: Dict[str, float] = {}
    for (gs, ge), i in zip(gaps, picked):
        label = spans[i].name if i >= 0 else "unattributed"
        if label.startswith(HOST_PREFIX):
            label = label[len(HOST_PREFIX):]
        sums[label] = sums.get(label, 0.0) + (ge - gs)
    return sorted(sums.items(), key=lambda kv: -kv[1])


def summary(trace: Trace, top: int = 10) -> dict:
    """What the result line carries: busy and window seconds averaged
    over the chips used, the device operations that took most time (on
    the lowest-numbered chip) and the idle gaps by host annotation."""
    used = {d: ev for d, ev in trace.devices.items() if ev}
    if not used:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": []}
    busy = sum(busy_ns(ev) for ev in used.values()) / len(used)
    window = sum(total([span_of(ev)]) for ev in used.values()) / len(used)
    first = used[min(used)]
    ops = sorted(by_name(first).items(), key=lambda kv: -kv[1])[:top]
    gaps = label_gaps(idle_gaps(first), trace.host)[:top]
    return {"busy_s": busy / 1e9, "window_s": window / 1e9,
            "device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in gaps]}


def describe(trace: Trace, top: int = 60) -> dict:
    """For a person: the planes and lines the file holds and, per chip,
    the operations that took most time, summed by their long names."""
    out = {"lines": trace.lines, "host_events": len(trace.host),
           "devices": {}}
    for d, events in sorted(trace.devices.items()):
        sums: Dict[str, list] = {}
        kernels: Dict[str, list] = {}
        for e in events:
            row = sums.setdefault(short_label(e, 300), [0.0, 0, e.name])
            row[0] += e.dur_ns
            row[1] += 1
            if CUSTOM_CALL in e.long_name:
                k = kernels.setdefault(re.sub(r"[.\d]+$", "", e.name),
                                       [0.0, 0])
                k[0] += e.dur_ns
                k[1] += 1
        out["devices"][str(d)] = {
            "events": len(events), "busy_ms": busy_ns(events) / 1e6,
            "span_ms": total([span_of(events)]) / 1e6 if events else 0.0,
            "custom_calls": {n: [ns / 1e6, c]
                             for n, (ns, c) in sorted(kernels.items())},
            "ops": [[n, ns / 1e6, c, ln] for ln, (ns, c, n) in sorted(
                sums.items(), key=lambda kv: -kv[1][0])[:top]]}
    return out
