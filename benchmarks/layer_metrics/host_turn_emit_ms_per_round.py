"""Device-idle time inside ``round.emit`` (ids to Python, the streams'
spans ended, per-token telemetry, the callers' ``on_token`` and future
callbacks) per whole decode round of the traced slice."""
from benchmarks.lib import round_phases


def read(inputs):
    return round_phases.host_turn_ms_per_round(inputs, "emit")
