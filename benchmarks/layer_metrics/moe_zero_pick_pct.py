"""Share of the window's expert picks (prefill and decode) that went to
zero-compute experts. A reading, not a good in itself: 0 means the
mechanism is off."""
from benchmarks.lib import readers


def read(inputs):
    picks = {to: readers.counter_delta(inputs, "mxnet_moe_picks_total",
                                       to=to)
             for to in ("held", "zero", "absent")}
    total = sum(picks.values())
    return 100.0 * picks["zero"] / total if total else None
