"""What the SDAR cell's trace readers share: the block rounds of the
traced slice and the device time under the ``jax.named_scope``s of the
engine's programs: ONE layer program, ``sdar_block_layer`` (a run a layer
a round; ``sdar.attn``, ``moe.router``, ``moe.experts``), and
``sdar_head`` (a run a round; ``sdar.head``, ``diffusion.pick``). A
prefill runs ``sdar_prefill_layer`` and no head, so nothing of it is
counted. Where the trace has no such programs (a CPU trace, a checkout
without the model) the readers report nothing."""
from __future__ import annotations

from benchmarks.lib import readers, xplane_scopes

LAYER, HEAD = "sdar_block_layer", "sdar_head"


def block_rounds(chip: dict, config: dict) -> float:
    """Block rounds in the slice, fractions of one counted."""
    return xplane_scopes.runs_of(chip["modules"], LAYER) \
        / config["num_hidden_layers"]


def scope_ms_per_round(inputs: dict, *scopes: str):
    """Device time under ``scopes`` (each a prefix of one element of the
    operation's path; none lies inside another) of the two programs per
    block round of the traced slice. None where there is nothing to
    read."""
    chip = xplane_scopes.first_chip(inputs)
    if not chip:
        return None
    rounds = block_rounds(chip, inputs["config"])
    if not rounds:
        return None
    ns = sum(xplane_scopes.scope_ns(chip["ops"], program, scope)
             for scope in scopes for program in (LAYER, HEAD))
    return ns / 1e6 / rounds if ns > 0 else None


def round_picks(inputs: dict):
    """The pick counts of the slice's block rounds, summed
    (``xplane_scopes.decode_picks``); None without the engine's layer
    program in the trace."""
    chip = xplane_scopes.first_chip(inputs)
    if not chip or not block_rounds(chip, inputs["config"]):
        return None
    return xplane_scopes.decode_picks(inputs)


def forwards(inputs: dict) -> tuple:
    """(denoising stream-forwards, commit stream-forwards, tokens
    unmasked) of the whole run, from the server's counters."""
    name = "mxnet_diffusion_block_forwards_total"
    return (readers.counter_delta(inputs, name, kind="denoise"),
            readers.counter_delta(inputs, name, kind="commit"),
            readers.counter_delta(inputs,
                                  "mxnet_diffusion_tokens_unmasked_total"))
