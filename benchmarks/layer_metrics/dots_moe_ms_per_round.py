"""Device time under the ``moe.*`` scopes (group-limited router, held
experts, shared expert) of the dots.vlm1 decode layer programs per decode
round of the traced slice."""
from benchmarks.lib import dots_vlm_scopes


def read(inputs):
    return dots_vlm_scopes.decode_scope_ms_per_round(inputs, "moe.")
