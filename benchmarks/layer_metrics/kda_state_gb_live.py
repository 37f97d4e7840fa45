"""The most recurrent state the live streams held at once in the run (the
``mxnet_state_bytes_live_peak`` gauge the engine keeps: streams holding a
slot x a slot's bytes, 6 KDA layers' float32 states and convolution
tails), in GB. Nothing where the program has no such gauge."""
from benchmarks.lib import harness


def read(inputs):
    name = "mxnet_state_bytes_live_peak"
    if name not in inputs.get("counters_after", {}):
        return None
    return harness.counter_sum(inputs["counters_after"], name) / 1e9
