"""Device time under the ``moe.*`` scopes (router, held experts, shared
expert) of the GLM-5 decode layer programs per decode round of the traced
slice; ``moe_ms_per_round`` reads LongCat's program."""
from benchmarks.lib import glm_dsa_scopes


def read(inputs):
    return glm_dsa_scopes.decode_scope_ms_per_round(inputs, "moe.")
