"""The held experts' grouped SwiGLU against its roofline, decode rounds
only, counts and time from the same slice: time under ``moe.experts`` of
the ``longcat_decode`` runs in the traced slice, against max(FLOPs /
peak, bytes / peak) of as many expert-layer executions at the pairs and
touched experts per execution that the slice's own decode forwards
counted (the engine's ``moe.picks:`` annotations in the same trace)."""
from benchmarks.lib import readers, xplane_scopes


def read(inputs):
    chip = xplane_scopes.first_chip(inputs)
    picks = xplane_scopes.decode_picks(inputs)
    if not chip or not picks or not inputs.get("peaks"):
        return None
    layer_calls = xplane_scopes.runs_of(chip["modules"], "longcat_decode")
    ns = xplane_scopes.scope_ns(chip["ops"], "longcat_decode", "moe.experts")
    if not layer_calls or ns <= 0:
        return None
    pairs = picks["held"] / picks["layers"]
    touched = picks["touched"] / picks["layers"]
    k = readers.kernel("moe_experts")
    s = k.shapes(inputs["config"], inputs["traffic"], 1)
    return readers.roofline_pct(
        k.flops(s, pairs) * layer_calls,
        k.bytes_moved(s, pairs, touched) * layer_calls, ns / 1e9,
        inputs["peaks"])
