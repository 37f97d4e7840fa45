"""Device time under ``sdar.head`` (the final norm and the head over the
block's 4 positions a stream: 512 rows of 151,936 at full width) and
``diffusion.pick`` (the candidates, their confidences and which positions
to unmask) per block round of the traced slice."""
from benchmarks.lib import sdar_scopes


def read(inputs):
    return sdar_scopes.scope_ms_per_round(inputs, "sdar.head",
                                          "diffusion.pick")
