"""Summed device time of Pallas custom calls per training step."""
from benchmarks.lib import readers


def read(inputs):
    if readers.first_device(inputs) is None or not inputs.get("trace_steps"):
        return None
    ns = sum(e.dur_ns for e in readers.pallas_events(inputs))
    return ns / 1e6 / inputs["trace_steps"]
