"""Device time under the ``kda.chunk`` scope (the chunk form of the delta
rule from the slot's state: the pair products, the unit-triangular
inverse and the loop that carries the state) per prefill dispatch of the
traced slice: every KDA layer. Nothing where the slice holds no
prefill."""
from benchmarks.lib import ling_scopes


def read(inputs):
    return ling_scopes.scope_ms(inputs, "prefill", "kda.chunk")
