"""Rows a prefill ran through the cross-decoder (the full-attention
layer's attention, the gated memory units, the cross-attention layers,
the head: a prompt's LAST token only) over rows it ran through the
self-decoder (every prompt token), whole run, from the engine's
``mxnet_prefill_rows_total{part}`` counter. Under 0.1 at these prompts;
100 means the skip is off."""
from benchmarks.lib import readers


def read(inputs):
    rows = readers.counter_delta(inputs, "mxnet_prefill_rows_total",
                                 part="self")
    cross = readers.counter_delta(inputs, "mxnet_prefill_rows_total",
                                  part="cross")
    return 100.0 * cross / rows if rows else None
