"""Device time under the ``kda.update`` scope (the delta rule's decode
step on the slots' float32 matrix states, in place) per decode round of
the traced slice: every KDA layer."""
from benchmarks.lib import ling_scopes


def read(inputs):
    return ling_scopes.scope_ms(inputs, "decode", "kda.update")
