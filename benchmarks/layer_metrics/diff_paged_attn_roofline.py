"""The paged differential-attention kernel's share of its roofline:
FLOPs and bytes of the live cached rows of the decode rounds in the
traced slice (rings capped at the window, the shared cache whole, per
reading layer) over the kernel's device time. Live rows are counted from
the slice's own ``decode.step`` spans, as ``mla_attn_roofline`` counts
them, each by the share of it that lies inside the slice (the slice is a
few rounds long). Nothing where the program runs no such kernel."""
from benchmarks.lib import phi4flash_scopes, readers


def read(inputs):
    if not inputs.get("peaks"):
        return None
    k = readers.kernel("diff_paged_attention")
    ns = sum(e.dur_ns for e in readers.pallas_events(inputs, k.PATTERN))
    steps = phi4flash_scopes.slice_decode_steps(inputs)
    if ns <= 0 or not steps:
        return None
    # (live tokens, the share of the stream's round inside the slice)
    contexts = [(s["context"], s["weight"]) for s in steps]
    shapes = k.shapes(inputs["config"], inputs["traffic"], 1)
    return readers.roofline_pct(k.flops(shapes, contexts),
                                k.bytes_moved(shapes, contexts), ns / 1e9,
                                inputs["peaks"])
