"""``compiles_in_window_serve`` for the dots.vlm1 cell (that entry moves
``tpot_p50_ms``, which the cell does not report): backend compiles and
jit-cache misses inside the window; the warm-up reaches every patch
bucket, prefill signature and decode bucket, so 0."""
from benchmarks.layer_metrics.compiles_in_window_serve import read  # noqa: F401
