"""Device operations of a profiler trace WITH the name the program gave
them. ``jax.profiler.ProfileData`` shows an operation's HLO text but not
the ``tf_op`` statistic of its event metadata, which is where the XLA
``op_name`` lands: ``jit(longcat_decode)/.../moe.experts/...``, the jitted
function and the ``jax.named_scope`` path. This reads the ``.xplane.pb``
wire format directly (``XSpace`` / ``XPlane`` / ``XLine`` / ``XEvent`` /
``XEventMetadata`` / ``XStat`` of tsl's ``xplane.proto``; field numbers in
the code) — no protobuf package is needed for seven message types.

The same file's host planes hold the ``moe.picks:`` annotations the
LongCat engine writes after every forward (:func:`pick_marks`): the expert
layers' pick counts of the slice's OWN rounds, where the program's
counters span ramp, window and drain.

Readers call :func:`first_chip` on what a driver hands back; where the
trace holds no such names or marks the readers report nothing.
"""
from __future__ import annotations

import functools
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclass
class ScopedOp:
    op_name: str        # the tf_op statistic: jit(fn)/scope/.../primitive
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is its bytes, a varint an int, fixed widths are skipped over."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield num, wire, value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _stat(buf: bytes) -> Tuple[int, object]:
    """XStat: metadata_id = 1; str_value = 5, ref_value = 7 (an id into
    the plane's stat metadata, whose name is the string)."""
    meta, value = 0, None
    for num, _, v in _fields(buf):
        if num == 1:
            meta = v
        elif num == 5:
            value = v.decode("utf-8", "replace")
        elif num == 7:
            value = ("ref", v)
    return meta, value


def _plane(buf: bytes) -> Optional[dict]:
    """XPlane: name = 2, lines = 3, event_metadata = 4 (map),
    stat_metadata = 5 (map). Device planes only."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for num, _, v in _fields(buf):
        if num == 2:
            name = v.decode()
            if not DEVICE_PLANE.match(name):
                return None
        elif num == 3:
            lines.append(v)
        elif num == 4:
            key, value = _map_entry(v)
            event_meta[key] = value
        elif num == 5:
            key, value = _map_entry(v)
            # XStatMetadata: id = 1, name = 2
            stat_names[key] = next(
                (x.decode() for n, _, x in _fields(value) if n == 2), "")
    if not DEVICE_PLANE.match(name):
        return None
    return {"name": name, "lines": lines, "event_meta": event_meta,
            "stat_names": stat_names}


def _events(line: bytes) -> Tuple[str, int, List[Tuple[int, int, int]]]:
    """XLine: name = 2, timestamp_ns = 3, events = 4; XEvent:
    metadata_id = 1, offset_ps = 2, duration_ps = 3. Only the two lines
    that are read are taken apart."""
    name = next((v.decode() for num, _, v in _fields(line) if num == 2), "")
    t0, events = 0, []
    if name not in (OPS_LINE, MODULES_LINE):
        return name, t0, events
    for num, _, v in _fields(line):
        if num == 3:
            t0 = v
        elif num == 4:
            meta = offset = dur = 0
            for n, _, x in _fields(v):
                if n == 1:
                    meta = x
                elif n == 2:
                    offset = x
                elif n == 3:
                    dur = x
            events.append((meta, offset, dur))
    return name, t0, events


@functools.lru_cache(maxsize=2)
def read_xplane(path: str) -> Dict[int, dict]:
    """Per chip: ``ops`` (every event of the ``XLA Ops`` line as a
    :class:`ScopedOp`; ``op_name`` empty where the metadata has no
    ``tf_op``) and ``modules`` (the ``XLA Modules`` line: one event per
    execution of a compiled program, named ``jit_<fn>(<id>)``)."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[int, dict] = {}
    for num, _, v in _fields(space):
        if num != 1:                        # XSpace.planes = 1
            continue
        plane = _plane(v)
        if plane is None:
            continue
        stat_names = plane["stat_names"]
        tf_op_ids = {k for k, n in stat_names.items() if n == "tf_op"}
        names: Dict[int, Tuple[str, str]] = {}
        for key, meta in plane["event_meta"].items():
            # XEventMetadata: name = 2, stats = 5
            text, op_name = "", ""
            for n, _, x in _fields(meta):
                if n == 2:
                    text = x.decode("utf-8", "replace")
                elif n == 5:
                    sid, value = _stat(x)
                    if sid in tf_op_ids and value is not None:
                        op_name = (stat_names.get(value[1], "")
                                   if isinstance(value, tuple) else value)
            names[key] = (text, op_name)
        chip = {"ops": [], "modules": []}
        for line in plane["lines"]:
            lname, t0, events = _events(line)
            if lname not in (OPS_LINE, MODULES_LINE):
                continue
            for meta, offset, dur in events:
                text, op_name = names.get(meta, ("", ""))
                op = ScopedOp(op_name if lname == OPS_LINE else text,
                              t0 + offset / 1e3, dur / 1e3)
                chip["ops" if lname == OPS_LINE else "modules"].append(op)
        out[int(DEVICE_PLANE.match(plane["name"]).group(1))] = chip
    return out


PICKS_MARK = "moe.picks:"


@functools.lru_cache(maxsize=2)
def pick_marks(path: str) -> List[dict]:
    """One dict per ``moe.picks:<phase>:<held>:<zero>:<absent>:<touched>
    :<layers>`` host annotation of the trace, in the file's order."""
    import jax

    marks = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PICKS_MARK):
                    phase, *counts = e.name[len(PICKS_MARK):].split(":")
                    if len(counts) == 5 and all(c.isdigit() for c in counts):
                        marks.append(dict(zip(
                            ("held", "zero", "absent", "touched", "layers"),
                            map(int, counts)), phase=phase))
    return marks


def find_profile(inputs: dict) -> Optional[str]:
    """The traced slice's ``.xplane.pb``: run.py writes a cell's profile
    under ``.cache/bench_out/<cell>-trace1/profile``."""
    from benchmarks.lib import trace_reduce

    if inputs.get("trace") is None:
        return None
    return trace_reduce.find_xplane(os.path.join(
        ROOT, ".cache", "bench_out", f"{inputs['cell']['name']}-trace1",
        "profile"))


def first_chip(inputs: dict) -> Optional[dict]:
    """``ops`` and ``modules`` of the lowest-numbered chip and the
    trace's ``marks`` (:func:`pick_marks`); a test hands them in under
    ``scoped`` directly."""
    if "scoped" in inputs:
        return inputs["scoped"]
    path = find_profile(inputs)
    if path is None:
        return None
    chips = read_xplane(path)
    if not chips:
        return None
    return dict(chips[min(chips)], marks=pick_marks(path))


def scope_ns(ops: List[ScopedOp], program: str, scope: str) -> float:
    """Device time (union of intervals, so that a loop and the operations
    of its body are not counted twice) of the operations of the jitted
    ``program`` whose name has ``scope`` as one element of its path (a
    prefix of one: ``moe.`` matches ``moe.router`` and ``moe.experts``)."""
    from benchmarks.lib import trace_reduce

    head = f"jit({program})/"
    rx = re.compile(r"(^|/)" + re.escape(scope))
    return trace_reduce.total(trace_reduce.union(
        (o.start_ns, o.end_ns) for o in ops
        if o.op_name.startswith(head) and rx.search(o.op_name)))


def runs_of(modules: List[ScopedOp], program: str) -> int:
    """Executions of the jitted ``program`` in the slice."""
    return sum(1 for m in modules
               if m.op_name.startswith(f"jit_{program}("))


def decode_rounds(chip: dict, config: dict) -> float:
    """Decode rounds in the slice: the engine runs ONE double-layer
    program, ``longcat_decode``, once per layer per round."""
    return runs_of(chip["modules"], "longcat_decode") / config["num_layers"]


def decode_scope_ms_per_round(inputs: dict, scope: str):
    """Device time under ``scope`` of the ``longcat_decode`` program per
    decode round of the traced slice; None where the trace has no such
    names (a CPU trace, a program without the scopes)."""
    chip = first_chip(inputs)
    if not chip:
        return None
    rounds = decode_rounds(chip, inputs["config"])
    ns = scope_ns(chip["ops"], "longcat_decode", scope)
    return ns / 1e6 / rounds if rounds and ns > 0 else None


def decode_picks(inputs: dict) -> Optional[dict]:
    """The pick counts of the slice's decode forwards, summed (``held``,
    ``zero``, ``absent``, ``touched``, and ``layers``: the expert-layer
    executions they cover); None where the trace has no such marks."""
    chip = first_chip(inputs)
    marks = [m for m in (chip or {}).get("marks", ())
             if m["phase"] == "decode"]
    if not marks or not sum(m["layers"] for m in marks):
        return None
    return {k: sum(m[k] for m in marks)
            for k in ("held", "zero", "absent", "touched", "layers")}
