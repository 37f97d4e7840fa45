"""The server's ``decode.step`` span: one whole decode round, host and
device, seen once per stream per round."""
from benchmarks.lib import readers


def read(inputs):
    return readers.span_p50_ms(inputs, "decode.step")
