"""``ttft_p95_ms`` for a cell that does not report ``served_tokens_s``
(the metric that entry moves): in the saturated 256-caller loop the first
token waits behind the prefill dispatches that ``max_prefill_tokens``
bounds, and each of those stalls every live stream for its length, so the
bound trades this tail against ``tpot_p50_ms``."""
from benchmarks.layer_metrics.ttft_p95_ms import read  # noqa: F401
