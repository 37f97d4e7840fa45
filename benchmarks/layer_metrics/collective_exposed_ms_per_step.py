"""The part of the collective time during which no other operation ran
on that chip, per training step."""
from benchmarks.lib import readers, trace_reduce


def read(inputs):
    events = readers.first_device(inputs)
    if not events or not inputs.get("trace_steps"):
        return None
    _, exposed = trace_reduce.collective_ns(events)
    return exposed / 1e6 / inputs["trace_steps"]
