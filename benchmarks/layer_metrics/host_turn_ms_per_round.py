"""Device-idle time inside the traced slice's whole decode rounds, per
round: every phase of the server's ``decode.round`` record, the idle
under ``prefill`` spans and any unattributed rest included."""
from benchmarks.lib import round_phases


def read(inputs):
    return round_phases.host_turn_ms_per_round(inputs)
