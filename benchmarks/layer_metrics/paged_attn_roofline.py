"""The paged decode kernel's share of its roofline: live-cache bytes of
the decode rounds in the traced slice over the kernel's device time."""
from benchmarks.lib import readers, trace_reduce


def read(inputs):
    events = readers.first_device(inputs)
    offset = inputs.get("trace_clock_offset_ns")
    if not events or offset is None:
        return None
    k = readers.kernel("paged_attention")
    ns = sum(e.dur_ns for e in readers.pallas_events(inputs, k.PATTERN))
    # every decode.step span that starts inside the slice is one stream in
    # one round, attending to its prompt plus the tokens made so far
    lo, hi = trace_reduce.span_of(events)
    prompt = inputs["trace_prompt_len"]
    context = 0
    for s in inputs["spans"]:
        if s["name"] == "decode.step" \
                and lo <= s["ts"] * 1e3 + offset <= hi:
            context += prompt[s["trace_id"]] + s["tags"]["token"] + 1
    if not context or ns <= 0:
        return None
    shapes = k.shapes(inputs["config"], inputs["traffic"], 1)
    return readers.roofline_pct(k.flops(shapes, context),
                                k.bytes_moved(shapes, context), ns / 1e9,
                                inputs["peaks"])
