"""Device time of the vision tower's encode programs
(``dots_vit_encode_<bucket>``: 42 layers and the merger, one run an
image) per image encoded in the traced slice."""
from benchmarks.lib import dots_vlm_scopes


def read(inputs):
    return dots_vlm_scopes.encode_ms_per_image(inputs)
