"""Device time under the ``h1.attn`` scope (rotary, the keys' and
values' page writes and the paged GQA read of a layer's own pages) per
decode round of the traced slice: every layer."""
from benchmarks.lib import falcon_h1_scopes


def read(inputs):
    return falcon_h1_scopes.decode_scope_ms_per_round(inputs, "h1.attn")
