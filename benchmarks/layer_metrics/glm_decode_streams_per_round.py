"""Live streams of a decode round of the traced slice: every real token
of a decode forward makes ``num_experts_per_tok`` picks in each expert
layer, so the slice's picks (the engine's ``moe.picks:`` annotations)
over ``num_experts_per_tok`` and the expert-layer executions are the
tokens of a round. ``decode_streams_per_round`` reads LongCat's key."""
from benchmarks.lib import xplane_scopes


def read(inputs):
    picks = xplane_scopes.decode_picks(inputs)
    k = inputs["config"].get("num_experts_per_tok")
    if not picks or not k:
        return None
    return (picks["held"] + picks["zero"] + picks["absent"]) / k \
        / picks["layers"]
