"""Live streams of a decode round of the traced slice: every real token
of a decode forward makes ``moe_topk`` picks in each expert layer, so the
slice's picks (the engine's ``moe.picks:`` annotations) over ``moe_topk``
and the expert-layer executions are the tokens of a round.
``decode_batch_mean`` reads the same width from a histogram that spans
ramp, window and drain."""
from benchmarks.lib import xplane_scopes


def read(inputs):
    picks = xplane_scopes.decode_picks(inputs)
    if not picks:
        return None
    return (picks["held"] + picks["zero"] + picks["absent"]) / \
        inputs["config"]["moe_topk"] / picks["layers"]
