"""``peak_hbm_gb_serve`` for a cell that does not report
``served_tokens_s`` (the metric that entry moves): weights with every
expert of six layers, every layer's K and V arenas, the block's logits
and a prefill's temporaries on one chip."""
from benchmarks.lib.readers import peak_hbm_gb as read  # noqa: F401
