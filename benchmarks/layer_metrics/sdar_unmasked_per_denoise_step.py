"""Tokens a denoising step of one stream unmasks, whole run
(``mxnet_diffusion_tokens_unmasked_total`` over the ``denoise`` forwards):
1 under the static schedule, up to 4 where the confidence threshold
fires."""
from benchmarks.lib import sdar_scopes


def read(inputs):
    denoise, _, unmasked = sdar_scopes.forwards(inputs)
    return unmasked / denoise if denoise else None
