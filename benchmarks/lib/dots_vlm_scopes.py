"""What the dots.vlm1 cell's trace readers share: the tower's encode
programs (``dots_vit_encode_<bucket>``, one run an image), the language
model's decode layer programs (``dots_lm_decode_dense`` /
``dots_lm_decode_moe``, one run per layer of its kind per round) and the
server's ``vision.encode`` spans that lie in the traced slice. Where the
trace has no such programs or spans (a CPU trace, a checkout without the
model) the readers report nothing."""
from __future__ import annotations

from benchmarks.lib import glm_dsa_scopes, xplane_scopes

ENCODE = "dots_vit_encode_"
DECODE = ("dots_lm_decode_dense", "dots_lm_decode_moe")


def encode_runs(chip: dict) -> list:
    """The slice's executions of an encode program."""
    return [m for m in chip["modules"]
            if m.op_name.startswith("jit_" + ENCODE)]


def whole_encode_runs(inputs: dict, chip=None) -> list:
    """``(run, live patches)`` of the slice's encode runs that lie WHOLE
    in it: a run is matched to the server's ``vision.encode`` span it
    started under (the span lasts from the dispatch to the rows' arrival,
    on the profiler's clock; its ``patches`` tag is the image's live
    count), and a run the slice's edge cut, shorter than nine tenths of
    its span, is left out: its work would be counted whole over a part
    of its time."""
    chip = chip or xplane_scopes.first_chip(inputs)
    offset = inputs.get("trace_clock_offset_ns")
    if not chip or offset is None:
        return []
    spans = [s for s in inputs.get("spans", ())
             if s["name"] == "vision.encode"]
    whole = []
    for run in encode_runs(chip):
        under = [s for s in spans
                 if s["ts"] * 1e3 + offset <= run.start_ns
                 <= (s["ts"] + s["dur"]) * 1e3 + offset]
        if len(under) == 1 and run.dur_ns >= 0.9 * under[0]["dur"] * 1e3:
            whole.append((run, under[0]["tags"]["patches"]))
    return whole


def encode_ms_per_image(inputs: dict, scope=None):
    """Device time of the encode programs (under ``scope`` of them, where
    given) per image, over the runs that lie whole in the traced slice."""
    chip = xplane_scopes.first_chip(inputs)
    runs = [run for run, _ in whole_encode_runs(inputs, chip)]
    if not runs:
        return None
    if scope is None:
        ns = sum(m.dur_ns for m in runs)
    else:
        ns = scope_ns_in(chip["ops"], runs, scope)
    return ns / 1e6 / len(runs) if ns > 0 else None


def scope_ns_in(ops, runs, scope: str) -> float:
    """Device time of the encode programs' operations under ``scope``
    that start inside one of ``runs``."""
    from benchmarks.lib import trace_reduce

    spans = [(r.start_ns, r.end_ns) for r in runs]
    return trace_reduce.total(trace_reduce.union(
        (o.start_ns, o.end_ns) for o in ops
        if o.op_name.startswith(f"jit({ENCODE}") and f"/{scope}" in o.op_name
        and any(a <= o.start_ns <= b for a, b in spans)))


def tower_flops(config: dict, n_patches: float, n_squared: float) -> float:
    """Forward FLOPs of the tower over images of ``n_patches`` LIVE
    patches in all whose squares sum to ``n_squared``: every matrix a
    patch (the merger's a group of four) and the two attention products
    over an image's own patches."""
    vc = config["vision_config"]
    e, f, n = vc["embed_dim"], vc["intermediate_size"], vc["num_hidden_layers"]
    p = vc["num_channels"] * vc["patch_size"] ** 2
    per_patch = n * 2 * (4 * e * e + 3 * e * f) + 2 * e * p
    per_group = 2 * (4 * e * 4 * e + 4 * e * config["hidden_size"])
    return (per_patch * n_patches + per_group * n_patches / 4
            + n * 4.0 * e * n_squared)


def decode_scope_ms_per_round(inputs: dict, scope: str):
    """Device time under ``scope`` in both decode layer programs per
    decode round of the traced slice."""
    chip = xplane_scopes.first_chip(inputs)
    if not chip or "first_k_dense_replace" not in inputs["config"]:
        return None
    rounds = xplane_scopes.runs_of(chip["modules"], DECODE[1]) \
        / glm_dsa_scopes.expert_layers(inputs["config"])
    ns = sum(xplane_scopes.scope_ns(chip["ops"], p, scope) for p in DECODE)
    return ns / 1e6 / rounds if rounds and ns > 0 else None
