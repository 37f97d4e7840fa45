"""The device's idle time inside the traced slice's decode rounds, split
by what the server's scheduler thread was doing: the program's
``decode.round`` spans and their six ``round.*`` children (``wait``,
``sched``, ``build``, ``launch``, ``fetch``, ``emit``: they tile the
round) placed on the profiler's clock and intersected with the idle gaps
of the lowest-numbered chip. Inside ``wait`` and ``sched`` a gap that a
``prefill`` span covers is the prefill's, not theirs (self time: a span
less its children).

Only rounds that lie wholly inside the device's window count, so the
slice's edges cut nothing. One sweep over the sorted gaps and the sorted
phases: linear in both. Computed once per run and kept in ``inputs``
for the six ``host_turn_*`` readers. Where the program records no such
spans (a checkout before them), or there is no trace or no clock offset,
there is nothing to read.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.lib import readers, trace_reduce

PHASES = ("wait", "sched", "build", "launch", "fetch", "emit")
ROUND, CHILD, PREFILL = "decode.round", "round.", "prefill"
# a shorter gap is the seam between two back-to-back operations
MIN_GAP_NS = 1000.0
_KEY = "_round_phases"

Piece = Tuple[float, float, str]


def _overlaps(gaps: Sequence[trace_reduce.Interval],
              spans: Sequence[Piece]) -> List[Piece]:
    """The parts of ``gaps`` under ``spans`` as (start, end, label);
    both sorted by start, each disjoint in itself."""
    out: List[Piece] = []
    i = 0
    for gs, ge in gaps:
        while i < len(spans) and spans[i][1] <= gs:
            i += 1
        j = i
        while j < len(spans) and spans[j][0] < ge:
            s, e = max(gs, spans[j][0]), min(ge, spans[j][1])
            if e > s:
                out.append((s, e, spans[j][2]))
            j += 1
    return out


def _total(pieces: Sequence[Piece]) -> float:
    return sum(e - s for s, e, _ in pieces)


def reduce(events, spans, offset_ns: float) -> Optional[dict]:
    """``events``: one chip's device operations; ``spans``: the program's
    span dicts (``ts`` / ``dur`` in epoch microseconds; duplicates by
    ``span_id`` count once); ``offset_ns``: profiler clock less epoch
    clock. Milliseconds of idle summed over the whole rounds of the
    slice, or None where it holds none."""
    window = trace_reduce.span_of(events)
    seen = set()
    rounds: Dict[str, Piece] = {}
    children: List[Tuple[str, Piece]] = []
    prefills: List[trace_reduce.Interval] = []
    for s in spans:
        name = s["name"]
        if not (name == ROUND or name == PREFILL
                or name.startswith(CHILD)) or s["span_id"] in seen:
            continue
        seen.add(s["span_id"])
        # in whole numbers: epoch nanoseconds are past what a float holds
        start = int(s["ts"]) * 1000 + int(offset_ns)
        end = start + int(s["dur"]) * 1000
        if name == PREFILL:
            prefills.append((start, end))
        elif name == ROUND:
            if window[0] <= start and end <= window[1]:
                rounds[s["span_id"]] = (start, end, ROUND)
        elif name[len(CHILD):] in PHASES:
            children.append((s.get("parent_id"),
                             (start, end, name[len(CHILD):])))
    if not rounds:
        return None
    gaps = sorted(g for g in trace_reduce.idle_gaps(events)
                  if g[1] - g[0] >= MIN_GAP_NS)
    whole = sorted(rounds.values())
    in_rounds = _total(_overlaps(gaps, whole))
    by_phase = _overlaps(gaps, sorted(
        piece for parent, piece in children if parent in rounds))
    # what a prefill span covers of the idle under wait and sched
    waiting = [(s, e) for s, e, phase in by_phase
               if phase in ("wait", "sched")]
    under_prefill = _total(_overlaps(
        waiting, [(s, e, PREFILL) for s, e in trace_reduce.union(prefills)]))
    idle = dict.fromkeys(PHASES, 0.0)
    for s, e, phase in by_phase:
        idle[phase] += e - s
    idle["sched"] += idle.pop("wait") - under_prefill
    return {"rounds": len(whole),
            "round_ms": _total(whole) / 1e6 / len(whole),
            "idle_ms": in_rounds / 1e6,
            "prefill_ms": under_prefill / 1e6,
            "unattributed_ms": (in_rounds - _total(by_phase)) / 1e6,
            "phase_ms": {p: ns / 1e6 for p, ns in idle.items()}}


def split(inputs: dict) -> Optional[dict]:
    """:func:`reduce` of what a serving driver hands back, computed once
    per run."""
    if _KEY not in inputs:
        events = readers.first_device(inputs)
        offset = inputs.get("trace_clock_offset_ns")
        inputs[_KEY] = None if events is None or offset is None \
            else reduce(events, inputs.get("spans", ()), offset)
    return inputs[_KEY]


def host_turn_ms_per_round(inputs: dict, phase: Optional[str] = None
                           ) -> Optional[float]:
    """Device-idle milliseconds per whole round of the slice: inside
    ``phase`` (``sched`` holds ``wait`` too, less what ``prefill`` spans
    cover), or with None inside the rounds altogether."""
    got = split(inputs)
    if got is None:
        return None
    ms = got["idle_ms"] if phase is None else got["phase_ms"][phase]
    return ms / got["rounds"]
