"""State slots handed to admitted streams a second of the window
(``mxnet_state_slot_allocs_total``, counted by
``serving/kvcache.py::StateSlots``): how fast a closed loop of short
answers turns its slots over. Nothing where the program has no such
counter."""
from benchmarks.lib import readers


def read(inputs):
    name = "mxnet_state_slot_allocs_total"
    if name not in inputs.get("counters_after", {}):
        return None
    return readers.counter_delta(inputs, name) / inputs["window_s"]
