"""Device-idle time inside ``round.fetch`` (bubbles between programs
already queued, and the tail from the last program's end to the ids'
arrival on the host) per whole decode round of the traced slice."""
from benchmarks.lib import round_phases


def read(inputs):
    return round_phases.host_turn_ms_per_round(inputs, "fetch")
