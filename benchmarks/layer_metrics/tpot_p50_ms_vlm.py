"""``tpot_p50_ms`` as a READING of the dots.vlm1 cell, which does not
report it end to end: its six seeded runs spread 5.2% (a median over ~80
requests of means over 32-128 token gaps that each hold 0-3 encodes of
other callers' images; PERF.md section 6, PR 42), over the 4% a cell's
metric is admitted at. What a later change that interleaves encodes with
decode rounds would move."""
from benchmarks.lib import stats


def read(inputs):
    return stats.percentile(inputs.get("tpot_ms", ()), 50.0)
