"""Device time under the ``ssd.proj`` and ``h1.proj`` scopes (the two
mixers' matrix products: the SSD mixer's in / out projections, attention's
q, k, v and o) per decode round of the traced slice: every layer. With the
``ssd.scan``, ``h1.attn``, ``h1.mlp`` and ``h1.head`` times it is a round's
device time."""
from benchmarks.lib import falcon_h1_scopes


def read(inputs):
    return falcon_h1_scopes.decode_scope_ms_per_round(inputs, "ssd.proj",
                                                      "h1.proj")
