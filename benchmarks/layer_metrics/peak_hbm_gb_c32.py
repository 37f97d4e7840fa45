"""``peak_hbm_gb_serve`` for a cell that does not report
``served_tokens_s`` (the metric that entry moves): weights, the ONE K/V
arena pair, the state slots and a prefill chunk's temporaries on one
chip; what is left decides how many streams of this depth a chip
holds."""
from benchmarks.lib.readers import peak_hbm_gb as read  # noqa: F401
