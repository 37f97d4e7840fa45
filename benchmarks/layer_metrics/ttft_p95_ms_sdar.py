"""``ttft_p95_ms`` for a cell that does not report ``served_tokens_s``
(the metric that entry moves): in the 128-caller loop a first token waits
for the prefills admitted before it, for its own (which makes no token)
and for the first denoising step of its first block."""
from benchmarks.layer_metrics.ttft_p95_ms import read  # noqa: F401
