"""The encode programs' share of the device's busy time in the traced
slice: how much of the chip the vision stage takes beside prefill chunks
and decode rounds."""
from benchmarks.lib import (dots_vlm_scopes, readers, trace_reduce,
                            xplane_scopes)


def read(inputs):
    events = readers.first_device(inputs)
    chip = xplane_scopes.first_chip(inputs)
    runs = dots_vlm_scopes.encode_runs(chip) if chip else []
    if not events or not runs:
        return None
    busy = trace_reduce.busy_ns(events)
    return 100.0 * sum(m.dur_ns for m in runs) / busy if busy else None
