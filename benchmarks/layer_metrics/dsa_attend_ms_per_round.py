"""Device time under the ``dsa.attend`` scope (the gather of the selected
latent rows and the absorbed attention over them) per decode round of the
traced slice."""
from benchmarks.lib import glm_dsa_scopes


def read(inputs):
    return glm_dsa_scopes.decode_scope_ms_per_round(inputs, "dsa.attend")
