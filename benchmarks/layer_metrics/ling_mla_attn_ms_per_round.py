"""Device time under the ``mla.attend`` scope (the absorbed attention
over the ONE latent layer's pages: the query and output folds and the
paged latent kernel) per decode round of the traced slice."""
from benchmarks.lib import ling_scopes


def read(inputs):
    return ling_scopes.scope_ms(inputs, "decode", "mla.attend")
