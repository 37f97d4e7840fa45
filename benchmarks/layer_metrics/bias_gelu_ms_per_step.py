"""Summed device time of the bias + GELU Pallas custom calls (forward
and backward sites) per training step."""
from benchmarks.lib import readers


def read(inputs):
    if readers.first_device(inputs) is None or not inputs.get("trace_steps"):
        return None
    ns = sum(e.dur_ns
             for e in readers.pallas_events(inputs, r"fused_bias_gelu"))
    return ns / 1e6 / inputs["trace_steps"]
