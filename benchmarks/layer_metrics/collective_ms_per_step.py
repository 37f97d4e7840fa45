"""Time in collective operations (all-reduce and kin) per training step,
on the lowest-numbered chip."""
from benchmarks.lib import readers, trace_reduce


def read(inputs):
    events = readers.first_device(inputs)
    if not events or not inputs.get("trace_steps"):
        return None
    total, _ = trace_reduce.collective_ns(events)
    return total / 1e6 / inputs["trace_steps"]
