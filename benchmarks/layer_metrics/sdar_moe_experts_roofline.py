"""All 128 experts' grouped SwiGLU against its roofline, block rounds
only, counts and time from the same slice: time under ``moe.experts`` of
the ``sdar_block_layer`` runs in the traced slice, against max(FLOPs /
peak, bytes / peak) of as many expert-layer executions at the pairs and
touched experts per execution that the slice's own block forwards counted
(the engine's ``moe.picks:`` annotations), by
``benchmarks/kernels/moe_experts.py`` at this configuration's own widths
(6 x 2048 x 768 FLOP a pair)."""
from benchmarks.lib import readers, sdar_scopes, xplane_scopes


def read(inputs):
    chip = xplane_scopes.first_chip(inputs)
    picks = sdar_scopes.round_picks(inputs)
    if not chip or not picks or not inputs.get("peaks"):
        return None
    layer_calls = xplane_scopes.runs_of(chip["modules"], sdar_scopes.LAYER)
    ns = xplane_scopes.scope_ns(chip["ops"], sdar_scopes.LAYER,
                                "moe.experts")
    if not layer_calls or ns <= 0:
        return None
    pairs = picks["held"] / picks["layers"]
    touched = picks["touched"] / picks["layers"]
    c = inputs["config"]
    k = readers.kernel("moe_experts")
    s = {"hidden": c["hidden_size"],
         "expert_hidden": c["moe_intermediate_size"],
         "held": c["num_experts"]}
    return readers.roofline_pct(
        k.flops(s, pairs) * layer_calls,
        k.bytes_moved(s, pairs, touched) * layer_calls, ns / 1e9,
        inputs["peaks"])
