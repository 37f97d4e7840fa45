"""Device time under the ``kda.proj`` and ``kda.out`` scopes (a KDA
mixer's q / k / v, decay, write-strength and gate projections, the three
causal convolutions, and on the way out the per-head norm, the gate and
``W_o``) per decode round of the traced slice: every KDA layer."""
from benchmarks.lib import ling_scopes


def read(inputs):
    return ling_scopes.scope_ms(inputs, "decode", "kda.proj", "kda.out")
