"""Tokens a held expert sees in one decode round of the traced slice: the
picks that went to held experts in the slice's decode forwards (the
engine's ``moe.picks:`` annotations) per expert-layer execution, over the
64 held experts: 4 at 256 streams x 8 picks x 64 / 512."""
from benchmarks.lib import ling_scopes


def read(inputs):
    picks = ling_scopes.round_picks(inputs)
    if not picks:
        return None
    return picks["held"] / picks["layers"] / inputs["config"]["num_experts"]
