"""Device time under the ``vit.attn`` scope of the encode programs (qkv
projection, 2-D rotary, the key-length-bounded flash forward, output
projection) per image encoded in the traced slice."""
from benchmarks.lib import dots_vlm_scopes


def read(inputs):
    return dots_vlm_scopes.encode_ms_per_image(inputs, "vit.attn")
