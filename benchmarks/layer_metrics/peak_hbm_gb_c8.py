"""``peak_hbm_gb_serve`` for a cell that does not report
``served_tokens_s`` (the metric that entry moves): weights, the latent and
index arenas and a prefill chunk's temporaries (a stream's expanded keys
and values, a query block's scores) on one chip; what is left decides how
many long streams a chip holds."""
from benchmarks.lib.readers import peak_hbm_gb as read  # noqa: F401
