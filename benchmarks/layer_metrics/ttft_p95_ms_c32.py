"""``ttft_p95_ms`` for a cell that does not report ``served_tokens_s``
(the metric that entry moves): in the 32-caller loop the first token
waits for the chunks of every prompt admitted before it, one chunk a
tick, so it is the ramp's length, which the decode rounds of the streams
that already answer pay for a chunk at a time."""
from benchmarks.layer_metrics.ttft_p95_ms import read  # noqa: F401
