"""Streams per decode dispatch, from the program's
``mxnet_serving_decode_batch_width`` histogram over the window."""
from benchmarks.lib import harness


def read(inputs):
    name = "mxnet_serving_decode_batch_width"
    s1, n1 = harness.histogram_totals(inputs["counters_after"], name)
    s0, n0 = harness.histogram_totals(inputs["counters_before"], name)
    return (s1 - s0) / (n1 - n0) if n1 > n0 else None
