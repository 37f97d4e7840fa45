"""Device-idle time inside ``round.build`` (tokens, lengths and the page
table from Python lists, the per-stream ``decode.step`` spans begun) per
whole decode round of the traced slice."""
from benchmarks.lib import round_phases


def read(inputs):
    return round_phases.host_turn_ms_per_round(inputs, "build")
