"""The key-length-bounded flash forward's share of its roofline:
operations and bytes of the LIVE patches of the images whose encode runs
lie whole in the traced slice (benchmarks/kernels/vit_flash_attention.py)
over the device time of the kernel's calls inside those runs. Nothing
where the program runs no such kernel."""
from benchmarks.lib import dots_vlm_scopes, readers


def read(inputs):
    if readers.first_device(inputs) is None or not inputs.get("peaks") \
            or "vision_config" not in inputs["config"]:
        return None
    k = readers.kernel("vit_flash_attention")
    whole = dots_vlm_scopes.whole_encode_runs(inputs)
    spans = [(run.start_ns, run.end_ns) for run, _ in whole]
    ns = sum(e.dur_ns for e in readers.pallas_events(inputs, k.PATTERN)
             if any(a <= e.start_ns <= b for a, b in spans))
    if ns <= 0:
        return None
    s = k.shapes(inputs["config"], inputs["traffic"], 1)
    live = [n for _, n in whole]
    return readers.roofline_pct(k.flops(s, sum(n * n for n in live)),
                                k.bytes_moved(s, sum(live)), ns / 1e9,
                                inputs["peaks"])
