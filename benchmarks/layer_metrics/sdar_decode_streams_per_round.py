"""Live streams of a block round of the traced slice: every real
position of a block forward makes ``num_experts_per_tok`` picks in each
expert layer, so the slice's picks over that, the expert-layer executions
and the block's 4 positions are the streams of a round."""
from benchmarks.lib import sdar_scopes


def read(inputs):
    picks = sdar_scopes.round_picks(inputs)
    if not picks:
        return None
    c = inputs["config"]
    return picks["held"] / c["num_experts_per_tok"] / picks["layers"] \
        / c["block_length"]
