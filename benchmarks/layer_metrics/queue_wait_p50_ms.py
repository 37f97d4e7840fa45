"""The server's ``gen.queue`` span: submit to the start of prefill."""
from benchmarks.lib import readers


def read(inputs):
    return readers.span_p50_ms(inputs, "gen.queue")
