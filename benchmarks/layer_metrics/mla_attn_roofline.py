"""The paged latent-attention kernel's share of its roofline: FLOPs and
bytes of the live cached rows of the decode rounds in the traced slice
over the kernel's device time. Live rows are counted as
``paged_attn_roofline`` counts them, from the slice's own ``decode.step``
spans (one stream in one round each: its prompt plus the tokens made so
far). Nothing where the program runs no such kernel."""
from benchmarks.lib import readers, trace_reduce


def read(inputs):
    events = readers.first_device(inputs)
    offset = inputs.get("trace_clock_offset_ns")
    if not events or offset is None or not inputs.get("peaks"):
        return None
    k = readers.kernel("mla_paged_attention")
    ns = sum(e.dur_ns for e in readers.pallas_events(inputs, k.PATTERN))
    if ns <= 0:
        return None
    lo, hi = trace_reduce.span_of(events)
    prompt = inputs["trace_prompt_len"]
    context = stream_rounds = 0
    for s in inputs["spans"]:
        if s["name"] == "decode.step" \
                and lo <= s["ts"] * 1e3 + offset <= hi:
            context += prompt[s["trace_id"]] + s["tags"]["token"] + 1
            stream_rounds += 1
    if not context:
        return None
    shapes = k.shapes(inputs["config"], inputs["traffic"], 1)
    return readers.roofline_pct(
        k.flops(shapes, context),
        k.bytes_moved(shapes, context, stream_rounds), ns / 1e9,
        inputs["peaks"])
