"""The server's ``prefill`` span: one prefill dispatch, host and device."""
from benchmarks.lib import readers


def read(inputs):
    return readers.span_p50_ms(inputs, "prefill")
