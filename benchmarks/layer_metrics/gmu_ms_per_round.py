"""Device time under the ``gmu`` scope (seven gated memory units: two
matrices and the gate on the middle layer's memory) per decode round of
the traced slice."""
from benchmarks.lib import phi4flash_scopes


def read(inputs):
    return phi4flash_scopes.decode_scope_ms_per_round(inputs, "gmu")
