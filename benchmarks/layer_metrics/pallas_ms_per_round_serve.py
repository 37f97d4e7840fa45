"""Summed device time of Pallas custom calls per decode round of the
traced slice (prefill's fused norms are in the sum)."""
from benchmarks.lib import readers


def read(inputs):
    if readers.first_device(inputs) is None:
        return None
    rounds = readers.decode_rounds_in_trace(inputs)
    if not rounds:
        return None
    return sum(e.dur_ns for e in readers.pallas_events(inputs)) / 1e6 / rounds
