"""``peak_hbm_gb_serve`` for a cell that does not report
``served_tokens_s`` (the metric that entry moves): weights, the latent
arena and the largest bounded prefill's temporaries on one chip; what is
left decides how many streams a round can hold, and so ``tpot_p50_ms``
at a given load."""
from benchmarks.lib.readers import peak_hbm_gb as read  # noqa: F401
