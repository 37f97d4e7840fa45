"""The tower's share of the chip's bf16 peak: FLOPs over the LIVE patches
of the images whose encode runs lie whole in the traced slice (a run's
count is the ``patches`` tag of the ``vision.encode`` span it started
under; a bucket's padding is not counted; a run the slice's edge cut is
left out) over those runs' device time."""
from benchmarks.lib import dots_vlm_scopes


def read(inputs):
    whole = dots_vlm_scopes.whole_encode_runs(inputs)
    if not whole or not inputs.get("peaks"):
        return None
    live = [n for _, n in whole]
    flops = dots_vlm_scopes.tower_flops(inputs["config"], sum(live),
                                        sum(n * n for n in live))
    seconds = sum(run.dur_ns for run, _ in whole) / 1e9
    return 100.0 * flops / seconds / inputs["peaks"]["bf16_flops"]
