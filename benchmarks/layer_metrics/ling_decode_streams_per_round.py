"""Live streams of a decode round of the traced slice: the time the
slice's ``decode.step`` spans (one a stream a round, back to back) spend
inside the slice over the slice's length, i.e. the streams inside a
decode step at an instant of it; under the round's width by the host's
turn and the prefill chunk between two rounds. Nothing without the
engine's decode program in the trace."""
from benchmarks.lib import ling_scopes, phi4flash_scopes, xplane_scopes


def read(inputs):
    chip = xplane_scopes.first_chip(inputs)
    steps = phi4flash_scopes.slice_decode_steps(inputs)
    if not chip or not steps \
            or not ling_scopes.decode_rounds(chip, inputs["config"]):
        return None
    return sum(s["inside_ns"] for s in steps) / steps[0]["slice_ns"]
