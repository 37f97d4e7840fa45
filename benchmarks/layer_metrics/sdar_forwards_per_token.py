"""Stream-forwards (denoising steps and commits) over the tokens they
unmasked, whole run (``mxnet_diffusion_block_forwards_total`` /
``mxnet_diffusion_tokens_unmasked_total``): what a token costs in
forwards. 1.25 under the static schedule of 4 steps and a commit a block
of 4; a request's last block is never committed and a first block opened
by the prompt's tail has fewer steps for its commit, which move it a
little; a threshold that fires, or a commit folded into the next block's
first step, would lower it."""
from benchmarks.lib import sdar_scopes


def read(inputs):
    denoise, commit, unmasked = sdar_scopes.forwards(inputs)
    return (denoise + commit) / unmasked if unmasked else None
