"""Device time under the ``sdar.attn`` scope (the q / k / v / o
projections, the per-head norms, rotary, the block's page writes and the
paged GQA read with the block's 4 positions folded into the head group)
per block round of the traced slice: every layer."""
from benchmarks.lib import sdar_scopes


def read(inputs):
    return sdar_scopes.scope_ms_per_round(inputs, "sdar.attn")
