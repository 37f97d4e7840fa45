"""Device time under the ``moe.*`` scopes (router, held experts, zero
experts) of the ``longcat_decode`` program, per decode round of the
traced slice."""
from benchmarks.lib import xplane_scopes


def read(inputs):
    return xplane_scopes.decode_scope_ms_per_round(inputs, "moe.")
