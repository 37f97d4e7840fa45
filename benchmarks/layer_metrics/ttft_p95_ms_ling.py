"""``ttft_p95_ms`` for a cell that does not report ``served_tokens_s``
(the metric that entry moves): in the 256-caller loop a first token waits
for the prefill chunks admitted before it (one chunk of up to 2,048
tokens a tick, the longest waiting prompt first) and for the decode
rounds between them, so the tail is the length of the ramp."""
from benchmarks.layer_metrics.ttft_p95_ms import read  # noqa: F401
