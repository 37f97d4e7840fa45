"""Every gap between two consecutive tokens of every request."""
from benchmarks.lib import stats


def read(inputs):
    return stats.percentile(inputs.get("gap_ms", ()), 99.0)
