"""Cached keys attended to over cached keys scored, in the decode
forwards of the whole run (the engine's ``mxnet_dsa_keys_*_total``
counters, phase ``decode``): ``index_topk`` over a stream's length. 100
means the mechanism is off (every stream shorter than ``index_topk``)."""
from benchmarks.lib import readers


def read(inputs):
    scored = readers.counter_delta(inputs, "mxnet_dsa_keys_scored_total",
                                   phase="decode")
    picked = readers.counter_delta(inputs, "mxnet_dsa_keys_selected_total",
                                   phase="decode")
    return 100.0 * picked / scored if scored else None
