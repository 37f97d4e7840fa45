"""``device_idle_pct_serve`` for the dots.vlm1 cell (that entry moves
``tpot_p50_ms``, which the cell does not report): the device's idle share
of the traced slice; an encode keeps it busy for 0.09-0.62 s a
dispatch."""
from benchmarks.layer_metrics.device_idle_pct_serve import read  # noqa: F401
