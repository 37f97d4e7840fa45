"""``ttft_p95_ms`` as a metric that moves ``tpot_p50_ms``: in the
8-caller loop a request's first token waits for its images' encodes (one
a tick, behind other callers' images) and its prefill chunks; the decode
rounds of the streams that already answer pay for each a tick at a
time."""
from benchmarks.layer_metrics.ttft_p95_ms import read  # noqa: F401
