"""Device-idle time inside ``round.launch`` (the device waits for the
round's first program, or between programs for the next dispatch) per
whole decode round of the traced slice."""
from benchmarks.lib import round_phases


def read(inputs):
    return round_phases.host_turn_ms_per_round(inputs, "launch")
