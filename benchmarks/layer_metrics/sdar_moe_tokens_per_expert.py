"""Positions an expert sees in one block round of the traced slice: the
picks of the slice's block forwards (the engine's ``moe.picks:``
annotations) per expert-layer execution, over the 128 experts, all held:
32 at 128 streams x 4 positions x 8 picks."""
from benchmarks.lib import sdar_scopes


def read(inputs):
    picks = sdar_scopes.round_picks(inputs)
    if not picks:
        return None
    return picks["held"] / picks["layers"] / inputs["config"]["num_experts"]
