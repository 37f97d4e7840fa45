"""What the Falcon-H1 cell's trace readers share: the decode rounds of the
traced slice and the device time under the ``jax.named_scope``s of the
engine's programs (the model's own ``h1.*`` and, from
``ops/ssm.py::mamba2_forward``, the mixer's ``ssd.proj`` / ``ssd.scan``): ONE decode layer program, ``falcon_h1_decode_layer``
(a run a layer a round), and ``falcon_h1_head`` (a run a round, and one a
prefill that ends a prompt). Where the trace has no such programs (a CPU
trace, a checkout without the model) the readers report nothing."""
from __future__ import annotations

from benchmarks.lib import phi4flash_scopes, xplane_scopes

LAYER, HEAD = "falcon_h1_decode_layer", "falcon_h1_head"


def decode_rounds(chip: dict, config: dict) -> float:
    """Decode rounds in the slice, fractions of one counted."""
    return xplane_scopes.runs_of(chip["modules"], LAYER) \
        / config["num_hidden_layers"]


def decode_scope_ms_per_round(inputs: dict, *scopes: str):
    """Device time under ``scopes`` (each a prefix of one element of the
    operation's path; none lies inside another) per decode round of the
    traced slice: the layer program's over the rounds; the head program's
    a RUN (a prefill's last token in the slice runs it too and adds no
    round's worth). None where there is nothing to read."""
    chip = xplane_scopes.first_chip(inputs)
    if not chip or "mamba_d_ssm" not in inputs["config"]:
        return None
    rounds = decode_rounds(chip, inputs["config"])
    if not rounds:
        return None
    head_runs = xplane_scopes.runs_of(chip["modules"], HEAD)
    ms = 0.0
    for scope in scopes:
        ms += xplane_scopes.scope_ns(chip["ops"], LAYER, scope) / 1e6 / rounds
        if head_runs:
            ms += xplane_scopes.scope_ns(chip["ops"], HEAD, scope) / 1e6 \
                / head_runs
    return ms if ms > 0 else None


def slice_rounds(inputs: dict) -> dict:
    """The decode rounds that overlap the traced slice: ``{round:
    streams}`` from the slice's ``decode.step`` spans (one a stream a
    round, tagged with the server's round count)."""
    rounds: dict = {}
    for s in phi4flash_scopes.slice_decode_steps(inputs):
        r = s["tags"].get("round")
        rounds[r] = rounds.get(r, 0) + 1
    return rounds
