"""Tokens a held expert sees in one decode round of the traced slice:
picks that went to held experts per expert-layer execution of the slice's
decode forwards (the engine's ``moe.picks:`` annotations), over the
experts held. How near the deployment's expert load the cell runs."""
from benchmarks.lib import xplane_scopes


def read(inputs):
    picks = xplane_scopes.decode_picks(inputs)
    if not picks:
        return None
    return picks["held"] / picks["layers"] / \
        inputs["config"]["n_routed_experts"]
