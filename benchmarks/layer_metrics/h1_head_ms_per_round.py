"""Device time under the ``h1.head`` scope (final norm, the untied
261,120-row head and the greedy pick) per decode round of the traced
slice: one run of the head program."""
from benchmarks.lib import falcon_h1_scopes


def read(inputs):
    return falcon_h1_scopes.decode_scope_ms_per_round(inputs, "h1.head")
