"""``peak_hbm_gb_serve`` for a cell that does not report
``served_tokens_s`` (the metric that entry moves): weights, every layer's
K and V arenas, 129 state slots and a prefill's temporaries on one
chip."""
from benchmarks.lib.readers import peak_hbm_gb as read  # noqa: F401
