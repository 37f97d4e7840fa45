"""Arithmetic the per-layer readers share. A reader takes the dict a
driver hands back (``Result.layer``, plus ``config``, ``traffic``,
``cell``, ``peaks``) and returns a number, or None where it finds nothing
to read — the harness then leaves the metric out of the line."""
from __future__ import annotations

import importlib
import re
from typing import List, Optional

from benchmarks.lib import harness, stats, trace_reduce



def span_ms(inputs: dict, name: str) -> List[float]:
    return [s["dur"] / 1e3 for s in inputs.get("spans", ())
            if s["name"] == name]


def span_p50_ms(inputs: dict, name: str) -> Optional[float]:
    return stats.median(span_ms(inputs, name))


def counter_delta(inputs: dict, name: str, **labels) -> float:
    return (harness.counter_sum(inputs["counters_after"], name, **labels)
            - harness.counter_sum(inputs["counters_before"], name, **labels))


def first_device(inputs: dict) -> Optional[list]:
    trace = inputs.get("trace")
    if trace is None or not trace.devices:
        return None
    events = trace.devices[min(trace.devices)]
    return events or None


def pallas_events(inputs: dict, pattern: Optional[str] = None) -> list:
    """The Pallas custom calls on the lowest-numbered chip; with
    ``pattern`` (a kernel's, from benchmarks/kernels/), those whose HLO
    text, layouts stripped, matches it."""
    events = [e for e in first_device(inputs) or []
              if trace_reduce.CUSTOM_CALL in e.long_name]
    if pattern:
        rx = re.compile(pattern)
        events = [e for e in events
                  if rx.search(trace_reduce.strip_layouts(e.long_name))]
    return events


def device_idle_pct(inputs: dict) -> Optional[float]:
    events = first_device(inputs)
    if not events:
        return None
    window = trace_reduce.span_of(events)
    busy = trace_reduce.busy_ns(events)
    return 100.0 * (1.0 - busy / (window[1] - window[0]))


def peak_hbm_gb(inputs: dict) -> Optional[float]:
    peak = inputs.get("peak_bytes")
    return peak / 1e9 if peak else None


def compiles_in_window(inputs: dict) -> Optional[float]:
    jit_miss = counter_delta(inputs, "mxnet_jit_cache_total", result="miss")
    return float(inputs["compiles"]["compiles"] + jit_miss)


def pallas_sites(inputs: dict) -> Optional[float]:
    """Kernel routings the program has counted by the end of the run (one
    per routed call site per trace of a program)."""
    return float(harness.counter_sum(inputs["counters_after"],
                                     "mxnet_pallas_dispatch_total"))


def kernel(name: str):
    return importlib.import_module(f"benchmarks.kernels.{name}")


def decode_rounds_in_trace(inputs: dict) -> int:
    """Decode rounds the traced slice holds: the paged kernel runs once
    per layer per round."""
    k = kernel("paged_attention")
    n = len(pallas_events(inputs, k.PATTERN))
    return n // inputs["config"]["num_hidden_layers"]


def roofline_pct(flops: float, nbytes: float, kernel_s: float, peaks: dict
                 ) -> Optional[float]:
    if kernel_s <= 0:
        return None
    floor = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])
    return 100.0 * floor / kernel_s
