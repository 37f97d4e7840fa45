"""Generation by diffusion over blocks (SDAR, arXiv:2510.06303): what a
denoising step decides on the device.

A stream's current block is ``block`` positions, each a token id or the
mask id. A forward over the block gives every position logits for the
token AT that position; :func:`block_denoise_pick` then picks, among the
positions still masked, which to unmask and with which token, and hands
back the block's new state. The logits never leave the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

__all__ = ["block_denoise_pick", "block_confidence"]


def block_confidence(logits, mask_id):
    """``x0`` (.., block) int32, the largest logit of every position (a
    tie to the lowest id; the mask id is never a candidate), and ``c``
    (.., block) float32, its softmax probability over the ids but the
    mask's. The statistics are float32 whatever the logits are. Each
    reduction reads the logits as they lie: the mask id's column is left
    out of the argmax inside that one reduction and taken out of the
    sum afterwards, so no second array of the logits' size is made (0.3
    GB at 128 streams of the published vocabulary)."""
    v = logits.shape[-1]
    x = logits.astype(jnp.float32)
    x0 = jax.lax.argmax(
        jnp.where(jnp.arange(v, dtype=jnp.int32) == mask_id, -jnp.inf, x),
        x.ndim - 1, "int32")
    top = jnp.take_along_axis(x, x0[..., None], axis=-1)[..., 0]
    masks = x[..., mask_id]
    # shift by the largest logit of all, the mask id's among them
    shift = jnp.maximum(top, masks)
    total = jnp.sum(jnp.exp(x - shift[..., None]), axis=-1) \
        - jnp.exp(masks - shift)
    return x0, jnp.exp(top - shift) / total


@register("_contrib_block_denoise_pick", aliases=["block_denoise_pick"])
def block_denoise_pick(logits, state, quota, *, mask_id, threshold):
    """One denoising step's decision (``low_confidence_dynamic``).

    ``logits`` (B, block, V): position ``i``'s logits are for the token
    at position ``i``; ``state`` (B, block) int32: the block as the
    forward saw it, ``mask_id`` where still masked; ``quota`` (B,) int32:
    the fewest positions this step unmasks (the schedule's
    ``num_transfer_tokens[step]``; never more than are masked).

    Among a row's masked positions, those whose confidence ``c =
    softmax(logits)[argmax]`` is above ``threshold`` are unmasked if
    they are at least ``quota``; otherwise the ``quota`` of largest ``c``
    are (a tie to the lower position). An unmasked position keeps its
    token, so a row with nothing masked (a commit forward, a padding
    row) comes back as it went in. Returns the new state (B, block)
    int32: the only thing a round brings to the host."""
    with jax.named_scope("diffusion.pick"):
        masked = state == mask_id
        # over (rows, V): a (B, block, V) array of a narrow block is
        # tiled by the block and would be laid out again
        x0, c = (a.reshape(state.shape) for a in block_confidence(
            logits.reshape(-1, logits.shape[-1]), mask_id))
        c = jnp.where(masked, c, -jnp.inf)
        high = c > jnp.float32(threshold)
        # rank among the masked: how many have a larger c, or an equal
        # one at a lower position
        pos = jnp.arange(state.shape[-1], dtype=jnp.int32)
        ahead = (c[..., None, :] > c[..., :, None]) | (
            (c[..., None, :] == c[..., :, None])
            & (pos[None, :] < pos[:, None]))
        rank = jnp.sum(ahead & masked[..., None, :], axis=-1,
                       dtype=jnp.int32)
        quota = quota.astype(jnp.int32)[..., None]
        enough = jnp.sum(high, axis=-1, dtype=jnp.int32,
                         keepdims=True) >= quota
        take = masked & jnp.where(enough, high, rank < quota)
        return jnp.where(take, x0, state).astype(jnp.int32)
