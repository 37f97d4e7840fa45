"""Device time under the ``ssm.`` scopes (``ssm.proj``: a Mamba layer's
in / x / dt / out projections and its convolution; ``ssm.scan``: the
selective scan's step on the slots' float32 states) per decode round of
the traced slice: nine layers."""
from benchmarks.lib import phi4flash_scopes


def read(inputs):
    return phi4flash_scopes.decode_scope_ms_per_round(inputs, "ssm.")
