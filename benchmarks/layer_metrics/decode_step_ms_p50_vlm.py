"""``decode_step_ms_p50`` for the dots.vlm1 cell (that entry moves
``tpot_p50_ms``, which the cell does not report): a decode round of the
7-8 live streams, host and device."""
from benchmarks.layer_metrics.decode_step_ms_p50 import read  # noqa: F401
