"""The delta-rule state-update kernel's share of its roofline: the bytes
of the live streams' states, each read once and written once a KDA layer
(``benchmarks/kernels/kda_state_update.py``: the same count whatever
implements the update), over the kernel's device time in the traced
slice, as a share of the HBM peak. A kernel run is one layer of one
round; its streams are those of the slice's rounds (the ``decode.step``
spans' ``round`` tags), averaged. Nothing where the program runs no such
kernel."""
from benchmarks.lib import falcon_h1_scopes, readers


def read(inputs):
    if not inputs.get("peaks") or "layer_kinds" not in inputs["config"]:
        return None
    k = readers.kernel("kda_state_update")
    events = readers.pallas_events(inputs, k.PATTERN)
    rounds = falcon_h1_scopes.slice_rounds(inputs)
    ns = sum(e.dur_ns for e in events)
    if ns <= 0 or not rounds:
        return None
    updates = len(events) * sum(rounds.values()) / len(rounds)
    shapes = k.shapes(inputs["config"], inputs["traffic"], 1)
    return readers.roofline_pct(k.flops(shapes, updates),
                                k.bytes_moved(shapes, updates), ns / 1e9,
                                inputs["peaks"])
