"""Flash attention's share of its roofline: the least time the chip could
take for the step's attention (operations and bytes from shapes) over
the kernel's device time in the trace."""
from benchmarks.lib import readers


def read(inputs):
    if readers.first_device(inputs) is None or not inputs.get("trace_steps"):
        return None
    k = readers.kernel("flash_attention")
    s = k.shapes(inputs["config"], inputs["traffic"], inputs["cell"]["chips"])
    ns = sum(e.dur_ns for e in readers.pallas_events(inputs, k.PATTERN))
    return readers.roofline_pct(k.flops(s), k.bytes_moved(s),
                                ns / 1e9 / inputs["trace_steps"],
                                inputs["peaks"])
