"""Device time under the ``yoco.attend`` scope (the full-attention layer's
and the seven cross-attention layers' reads of the ONE shared K/V cache
through the page table, the differential combination and the
out-projection) per decode round of the traced slice."""
from benchmarks.lib import phi4flash_scopes


def read(inputs):
    return phi4flash_scopes.decode_scope_ms_per_round(inputs, "yoco.attend")
