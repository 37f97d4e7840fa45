"""``peak_hbm_gb_serve`` for a cell that does not report
``served_tokens_s`` (the metric that entry moves): weights, ONE latent
layer's pages, 257 state slots of six KDA layers and a prefill chunk's
temporaries on one chip."""
from benchmarks.lib.readers import peak_hbm_gb as read  # noqa: F401
