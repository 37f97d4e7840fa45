"""First token's arrival minus the time the request was due, over the
requests of the window; a failed request sits at +inf."""
from benchmarks.lib import stats


def read(inputs):
    return stats.percentile(inputs.get("ttft_ms", ()), 95.0)
