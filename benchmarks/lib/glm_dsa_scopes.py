"""What the GLM-5 cell's trace readers share: the decode rounds of the
traced slice and the device time under a ``jax.named_scope`` of the
engine's decode layer programs, ``glm_dsa_decode_dense`` and
``glm_dsa_decode_moe`` (one run per layer of its kind per round). Where
the trace has no such programs (a CPU trace, a checkout without the
model) the readers report nothing."""
from __future__ import annotations

from benchmarks.lib import xplane_scopes

PROGRAMS = ("glm_dsa_decode_dense", "glm_dsa_decode_moe")


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def decode_rounds(chip: dict, config: dict) -> float:
    """Decode rounds in the slice: the expert-layer program runs once per
    expert layer per round."""
    return xplane_scopes.runs_of(chip["modules"], PROGRAMS[1]) \
        / expert_layers(config)


def decode_scope_ms_per_round(inputs: dict, scope: str):
    """Device time under ``scope`` (a prefix of one element of the
    operation's path) in both decode layer programs, per decode round of
    the traced slice; None where there is nothing to read."""
    chip = xplane_scopes.first_chip(inputs)
    if not chip or "num_hidden_layers" not in inputs["config"]:
        return None
    rounds = decode_rounds(chip, inputs["config"])
    ns = sum(xplane_scopes.scope_ns(chip["ops"], p, scope)
             for p in PROGRAMS)
    return ns / 1e6 / rounds if rounds and ns > 0 else None


def chunk_spans(inputs: dict) -> list:
    """The server's ``prefill`` spans of chunked prompts (tags ``chunk``,
    ``chunks``, ``offset``)."""
    return [s for s in inputs.get("spans", ())
            if s["name"] == "prefill" and "chunks" in (s.get("tags") or {})]
