"""What the Ling cell's trace readers share: the decode rounds and the
prefill chunks of the traced slice and the device time under the
``jax.named_scope``s of the engine's programs (``kda.proj``,
``kda.update``, ``kda.chunk``, ``kda.out`` from
``ops/linear_attention.py::kda_forward``; ``mla.proj``, ``mla.attend``;
``moe.router``, ``moe.experts``, ``moe.shared``, ``ffn.dense``;
``h1.head``): a layer program a kind of layer and phase,
``ling_<decode|prefill>_layer_<kda|kda_dense|mla>`` (a run a layer of its
kind a dispatch), and ``ling_head`` (a run a round, and one a prefill that
ends a prompt). Where the trace has no such programs (a CPU trace, a
checkout without the model) the readers report nothing."""
from __future__ import annotations

from benchmarks.lib import xplane_scopes

KINDS = ("kda", "kda_dense", "mla")
HEAD = "ling_head"


def _dispatches(chip: dict, config: dict, phase: str) -> float:
    """Forwards of ``phase`` in the slice, fractions of one counted: the
    runs of the MLA layer program over the MLA layers a forward runs."""
    n_mla = list(config.get("layer_kinds", ())).count("mla")
    if not n_mla:
        return 0.0
    return xplane_scopes.runs_of(chip["modules"],
                                 f"ling_{phase}_layer_mla") / n_mla


def decode_rounds(chip: dict, config: dict) -> float:
    return _dispatches(chip, config, "decode")


def prefill_chunks(chip: dict, config: dict) -> float:
    return _dispatches(chip, config, "prefill")


def scope_ms(inputs: dict, phase: str, *scopes: str):
    """Device time under ``scopes`` (each a prefix of one element of the
    operation's path; none lies inside another) in the layer programs of
    ``phase`` per forward of that phase in the traced slice. None where
    there is nothing to read."""
    chip = xplane_scopes.first_chip(inputs)
    if not chip or "layer_kinds" not in inputs["config"]:
        return None
    n = _dispatches(chip, inputs["config"], phase)
    if not n:
        return None
    ns = sum(xplane_scopes.scope_ns(chip["ops"],
                                    f"ling_{phase}_layer_{kind}", scope)
             for scope in scopes for kind in KINDS)
    return ns / 1e6 / n if ns > 0 else None


def head_ms_per_run(inputs: dict):
    """Device time of the head program a RUN (a prefill's last token in
    the slice runs it too and adds no round's worth)."""
    chip = xplane_scopes.first_chip(inputs)
    if not chip or "layer_kinds" not in inputs["config"]:
        return None
    runs = xplane_scopes.runs_of(chip["modules"], HEAD)
    ns = xplane_scopes.scope_ns(chip["ops"], HEAD, "h1.head")
    return ns / 1e6 / runs if runs and ns > 0 else None


def round_picks(inputs: dict):
    """The pick counts of the slice's decode forwards, summed
    (``xplane_scopes.decode_picks``; the engine writes a forward's mark a
    forward late, which a sum over the slice does not see); None without
    the engine's decode program in the trace."""
    chip = xplane_scopes.first_chip(inputs)
    if not chip or "layer_kinds" not in inputs["config"] \
            or not decode_rounds(chip, inputs["config"]):
        return None
    return xplane_scopes.decode_picks(inputs)
