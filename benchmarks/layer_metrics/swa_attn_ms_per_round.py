"""Device time under the ``swa.attend`` scope (a window layer's ring
write, its read of every stream's ring through the paged kernel, the
differential combination and the out-projection) per decode round of the
traced slice: eight layers."""
from benchmarks.lib import phi4flash_scopes


def read(inputs):
    return phi4flash_scopes.decode_scope_ms_per_round(inputs, "swa.attend")
