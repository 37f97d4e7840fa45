"""What every driver shares: the run's inputs, the result it hands back,
program counters, the compile watch and the profiler slice."""
from __future__ import annotations

import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


@dataclass
class Run:
    """One invocation of run.py, as the drivers see it."""
    cell: dict                # the workloads entry
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list             # the jax devices this cell uses
    peaks: Optional[dict]     # None only in a rehearsal
    builder: Any
    reference: Any
    out_dir: str
    t0: float                 # perf_counter() at process start
    watch: Any = None         # CompileWatch, listening since before the build

    def log(self, what: str) -> None:
        print(f"[bench +{time.perf_counter() - self.t0:7.1f}s] {what}",
              file=sys.stderr, flush=True)


@dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    # what the per-layer readers read: spans, counters, trace, sizes
    layer: Dict[str, Any] = field(default_factory=dict)
    # printed on an earlier line, never in the result line
    notes: Dict[str, Any] = field(default_factory=dict)


def program_counters() -> dict:
    """Flat copy of the program's telemetry: ``{name: [(labels, value)]}``
    for counters and gauges, ``(labels, sum, count)`` for histograms."""
    from mxnet_tpu import telemetry

    out = {}
    for name, fam in telemetry.snapshot()["metrics"].items():
        rows = []
        for s in fam["samples"]:
            if "value" in s:
                rows.append((s["labels"], s["value"]))
            else:
                rows.append((s["labels"], s["sum"], s["count"]))
        out[name] = rows
    return out


def counter_sum(counters: dict, name: str, **labels) -> float:
    return sum(row[1] for row in counters.get(name, ())
               if len(row) == 2
               and all(row[0].get(k) == v for k, v in labels.items()))


def histogram_totals(counters: dict, name: str) -> tuple:
    rows = [r for r in counters.get(name, ()) if len(r) == 3]
    return sum(r[1] for r in rows), sum(r[2] for r in rows)


class CompileWatch:
    """Counts XLA backend compiles (persistent-cache loads included: a
    new executable was needed either way) through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.count, "compile_s": self.seconds,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


def peak_memory_bytes(devices) -> int:
    """Peak bytes on the fullest chip, from ``device.memory_stats()``; 0
    where the backend does not report it (the CPU in a rehearsal). On this
    runtime ``peak_bytes_in_use`` counts the buffers the process holds
    (weights, state, cache arena, inputs) and ``peak_bytes_reserved`` the
    largest block a running program reserved for its temporaries, apart
    from them; the chip has to hold both."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


CLOCK_MARK = "bench:clock:"


class ProfileSlice:
    """A profiler trace of a short steady slice. ``start`` and ``stop`` may
    run on a helper thread (``at``) so that a load generator is not held
    up by them. An annotation named after the epoch clock is written at
    the start, so host spans taken on ``time.time_ns`` can be placed on
    the profiler's clock."""

    def __init__(self, out_dir: str):
        self.dir = os.path.join(out_dir, "profile")
        self.started = False
        self.stopped = False
        # profiler clock (ns) = epoch clock (ns) + this
        self.clock_offset_ns: Optional[int] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host python frames: large, unread
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = True
        with jax.profiler.TraceAnnotation(f"{CLOCK_MARK}{time.time_ns()}"):
            pass

    def stop(self) -> None:
        import jax

        if self.started and not self.stopped:
            jax.profiler.stop_trace()
            self.stopped = True

    def at(self, t_start: float, t_stop: float) -> None:
        """Start at perf_counter() ``t_start`` and stop ``t_stop -
        t_start`` after the trace has started, on a helper thread;
        ``join`` waits for it. The length counts from the start and not to
        an absolute time: a ``start_trace`` that comes back late moves the
        slice, it does not empty it."""
        def body():
            time.sleep(max(0.0, t_start - time.perf_counter()))
            self.start()
            time.sleep(max(0.0, t_stop - t_start))
            self.stop()

        self._thread = threading.Thread(target=body, name="bench-profiler",
                                        daemon=True)
        self._thread.start()

    def join(self, timeout: float = 120.0) -> None:
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise RuntimeError("the profiler thread did not finish")
            self._thread = None

    def load(self, spans: Optional[List[dict]] = None):
        """The reduced trace, with the program's host spans (``ts`` and
        ``dur`` in epoch microseconds) added as host events."""
        from benchmarks.lib import trace_reduce

        if not self.stopped:
            return None
        trace = trace_reduce.load(self.dir)
        mark = next((e for e in trace.host
                     if e.name.startswith(CLOCK_MARK)), None)
        trace.host = [e for e in trace.host
                      if not e.name.startswith(CLOCK_MARK)]
        if mark is not None:
            self.clock_offset_ns = int(
                mark.start_ns - int(mark.name[len(CLOCK_MARK):]))
        if mark is not None and spans:
            offset = self.clock_offset_ns
            trace.host.extend(
                trace_reduce.Event(trace_reduce.HOST_PREFIX + s["name"],
                                   s["ts"] * 1e3 + offset, s["dur"] * 1e3)
                for s in spans)
            trace.host.sort(key=lambda e: e.start_ns)
        return trace
