"""Padding patches the patch-count buckets added, as a share of all the
patches the tower ran over in the window
(``mxnet_vision_patches_padded_total`` / live + padded). The padding
costs its linear share only: attention is bounded by the live count."""
from benchmarks.lib import readers


def read(inputs):
    live = readers.counter_delta(inputs, "mxnet_vision_patches_total")
    padded = readers.counter_delta(inputs,
                                   "mxnet_vision_patches_padded_total")
    return 100.0 * padded / (live + padded) if live else None
