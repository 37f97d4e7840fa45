"""Device time under the ``h1.mlp`` scope (norm, gate / up and down
products of the SwiGLU MLP) per decode round of the traced slice: every
layer."""
from benchmarks.lib import falcon_h1_scopes


def read(inputs):
    return falcon_h1_scopes.decode_scope_ms_per_round(inputs, "h1.mlp")
