"""Device time under the ``moe.*`` scopes (router, the pairs' ordering,
all 128 experts' grouped SwiGLU) per block round of the traced slice:
every layer."""
from benchmarks.lib import sdar_scopes


def read(inputs):
    return sdar_scopes.scope_ms_per_round(inputs, "moe.")
