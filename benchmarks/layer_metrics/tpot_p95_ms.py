"""Per request (last - first token time) / (tokens - 1), over the
requests of the window; a failed request sits at +inf."""
from benchmarks.lib import stats


def read(inputs):
    return stats.percentile(inputs.get("tpot_ms", ()), 95.0)
