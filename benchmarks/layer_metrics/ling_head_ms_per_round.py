"""Device time of the head program (final norm, the 19,648-row slice of
the head and the greedy pick) per decode round of the traced slice: one
run of ``ling_head``."""
from benchmarks.lib import ling_scopes


def read(inputs):
    return ling_scopes.head_ms_per_run(inputs)
