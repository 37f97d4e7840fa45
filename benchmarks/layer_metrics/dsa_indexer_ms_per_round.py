"""Device time under the ``dsa.indexer`` scope of the GLM-5 decode layer
programs (index queries, keys and head weights, the gather of a stream's
cached index keys and the scores of every cached token) per decode round
of the traced slice."""
from benchmarks.lib import glm_dsa_scopes


def read(inputs):
    return glm_dsa_scopes.decode_scope_ms_per_round(inputs, "dsa.indexer")
