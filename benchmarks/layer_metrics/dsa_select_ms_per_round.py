"""Device time under the ``dsa.select`` scope (the masks and the exact
top-``index_topk`` threshold over every cached token of a stream) per
decode round of the traced slice."""
from benchmarks.lib import glm_dsa_scopes


def read(inputs):
    return glm_dsa_scopes.decode_scope_ms_per_round(inputs, "dsa.select")
