"""Device busy time per training step in the traced slice."""
from benchmarks.lib import readers, trace_reduce


def read(inputs):
    events = readers.first_device(inputs)
    if not events or not inputs.get("trace_steps"):
        return None
    return trace_reduce.busy_ns(events) / 1e6 / inputs["trace_steps"]
