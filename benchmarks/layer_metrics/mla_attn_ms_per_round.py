"""Device time under the ``mla.decode`` scope (the absorbed latent
attention through the page table, all sublayers) per decode round of the
traced slice."""
from benchmarks.lib import xplane_scopes


def read(inputs):
    return xplane_scopes.decode_scope_ms_per_round(inputs, "mla.decode")
