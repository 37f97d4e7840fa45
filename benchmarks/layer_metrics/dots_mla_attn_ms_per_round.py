"""Device time under the ``mla.*`` scopes (projections, the arena write
and the absorbed attention through the paged latent kernel at 128 query
heads) of the dots.vlm1 decode layer programs per decode round of the
traced slice; ``mla_attn_ms_per_round`` reads LongCat's program."""
from benchmarks.lib import dots_vlm_scopes


def read(inputs):
    return dots_vlm_scopes.decode_scope_ms_per_round(inputs, "mla.")
