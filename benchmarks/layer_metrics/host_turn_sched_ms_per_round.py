"""Device-idle time inside ``round.wait`` and ``round.sched`` (the
scheduler between rounds: heartbeat, admission, deadlines, the tenant
split), less what ``prefill`` spans cover, per whole decode round of the
traced slice."""
from benchmarks.lib import round_phases


def read(inputs):
    return round_phases.host_turn_ms_per_round(inputs, "sched")
