"""Device time under the ``ssd.scan`` scope (a layer's depthwise
convolution, the SSD state update on the slots' float32 matrix states
and the gated group norm) per decode round of the traced slice: every
layer."""
from benchmarks.lib import falcon_h1_scopes


def read(inputs):
    return falcon_h1_scopes.decode_scope_ms_per_round(inputs, "ssd.scan")
