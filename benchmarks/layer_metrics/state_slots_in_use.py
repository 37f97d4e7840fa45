"""The most per-stream state slots (scan states, convolution tails,
window rings: ``serving/kvcache.py::StateSlots``) held at once in the
run, the scratch slot not counted: a stream holds its slot from the start
of its first ``prefill`` span (which carries the ``slot`` tag) to the end
of its last span, and the peak of that count over the run is read from
the spans. (The ``mxnet_state_slots_in_use`` gauge is 0 again when the
run is reduced.) Nothing where no ``prefill`` span names a slot."""


def read(inputs):
    held = {}
    for s in inputs.get("spans", ()):
        if s["name"] not in ("prefill", "decode.step"):
            continue
        lo, hi = held.get(s.get("trace_id"), (None, None))
        if s["name"] == "prefill" and "slot" in s.get("tags", ()):
            lo = s["ts"] if lo is None else min(lo, s["ts"])
        end = s["ts"] + s["dur"]
        held[s.get("trace_id")] = (lo, end if hi is None else max(hi, end))
    edges = [(t, step) for lo, hi in held.values() if lo is not None
             for t, step in ((lo, 1), (hi, -1))]
    if not edges:
        return None
    peak = now = 0
    for _, step in sorted(edges):       # an end sorts before a start
        now += step
        peak = max(peak, now)
    return float(peak)
