"""Device time under the ``moe.`` scopes (router, the 64 held experts'
sorted passes, the shared expert) per decode round of the traced slice:
every expert layer."""
from benchmarks.lib import ling_scopes


def read(inputs):
    return ling_scopes.scope_ms(inputs, "decode", "moe.")
