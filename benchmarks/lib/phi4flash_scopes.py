"""What the Phi-4-mini-flash cell's trace readers share: the decode
rounds of the traced slice and the device time under a
``jax.named_scope`` of the engine's decode programs,
``phi4flash_decode_<self|mid|full|cross>``. The slice is a few rounds
long (the harness's labelling is quadratic in its length), so nothing
here counts WHOLE rounds: rounds are the programs' runs over the runs a
round makes, a stream's round counts by the share of its ``decode.step``
span that lies inside the slice. Where the trace has no such programs (a
CPU trace, a checkout without the model) the readers report nothing."""
from __future__ import annotations

from benchmarks.lib import trace_reduce, xplane_scopes

# the decode programs and how often a round runs each; ``full`` and
# ``cross`` take one row a stream and also end a prefill (its last chunk's
# last token), ``self`` and ``mid`` are the decode phase's alone
def programs(config: dict) -> dict:
    pairs = config["num_hidden_layers"] // 4
    return {"phi4flash_decode_self": pairs, "phi4flash_decode_mid": 1,
            "phi4flash_decode_full": 1, "phi4flash_decode_cross": pairs - 1}


def decode_rounds(chip: dict, config: dict) -> float:
    """Decode rounds in the slice, fractions of one counted: the runs of
    the two programs that only a decode round runs, over the runs a
    round makes of them."""
    runs = programs(config)
    own = ("phi4flash_decode_self", "phi4flash_decode_mid")
    return (sum(xplane_scopes.runs_of(chip["modules"], p) for p in own)
            / sum(runs[p] for p in own))


def decode_scope_ms_per_round(inputs: dict, scope: str):
    """Device time under ``scope`` (a prefix of one element of the
    operation's path) in the decode programs, per decode round of the
    traced slice: per program its time under the scope a RUN, times the
    runs a round makes of it (so a prefill's last token in the slice,
    which runs ``full`` and ``cross`` too, adds no round's worth); None
    where there is nothing to read."""
    chip = xplane_scopes.first_chip(inputs)
    if not chip or "sliding_window" not in inputs["config"]:
        return None
    ms = 0.0
    for program, per_round in programs(inputs["config"]).items():
        runs = xplane_scopes.runs_of(chip["modules"], program)
        if runs:
            ms += (xplane_scopes.scope_ns(chip["ops"], program, scope)
                   / 1e6 / runs * per_round)
    return ms if ms > 0 else None


def slice_decode_steps(inputs: dict) -> list:
    """The ``decode.step`` spans (one stream in one round each) that
    overlap the traced slice, each with the stream's live tokens
    (``context``: its prompt plus the tokens made so far plus this one),
    the share of the span that lies inside the slice (``weight``) and
    that overlap in ns (``inside_ns``); ``slice_ns`` on each."""
    events = (inputs["trace"].devices[min(inputs["trace"].devices)]
              if inputs.get("trace") is not None
              and inputs["trace"].devices else None)
    offset = inputs.get("trace_clock_offset_ns")
    if not events or offset is None:
        return []
    lo, hi = trace_reduce.span_of(events)
    prompt = inputs["trace_prompt_len"]
    steps = []
    for s in inputs.get("spans", ()):
        if s["name"] != "decode.step" or s["dur"] <= 0:
            continue
        start = s["ts"] * 1e3 + offset
        inside = min(hi, start + s["dur"] * 1e3) - max(lo, start)
        if inside > 0:
            steps.append(dict(
                s, context=prompt[s["trace_id"]] + s["tags"]["token"] + 1,
                weight=inside / (s["dur"] * 1e3), inside_ns=inside,
                slice_ns=hi - lo))
    return steps
