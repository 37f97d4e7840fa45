"""How late the load generator sent a request, against when it was due."""
from benchmarks.lib import stats


def read(inputs):
    return stats.percentile(inputs.get("late_ms", ()), 99.0)
