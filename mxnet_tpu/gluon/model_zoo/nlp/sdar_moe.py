"""SDAR-MoE: a Qwen3-MoE-shaped decoder that generates by diffusion over
blocks (SDAR, "A Synergistic Diffusion-AutoRegression Paradigm for
Scalable Sequence Generation", arXiv:2510.06303; ``model_type``
``sdar_moe``).

Every layer is grouped-query attention with an RMSNorm over each query
and key head (Qwen3's ``q_norm`` / ``k_norm``), rotary over the whole
head, and a softmax-routed expert layer in the MLP's place: ``top_k`` of
``num_experts`` SwiGLU experts a token, their weights renormalised over
the picked (``norm_topk_prob``), no shared expert. ALL experts of a layer
live here (``ops/contrib.py::moe_routed_experts`` with ``first_held=0``).

What is not a causal decoder's: the attention mask is BLOCK-causal (a
position sees its own block of ``block_length`` positions, both ways,
and every block before it), and a logit at position ``i`` is for the
token AT position ``i``, whose input is the mask token's embedding while
it is unknown. Generation fills one block at a time: a denoising step
runs the block against the cache and unmasks the positions the model is
surest of (``ops/diffusion.py::block_denoise_pick``), and once nothing is
masked a commit forward writes the block's final keys and values into the
cache. :class:`SdarMoeDecodeEngine` is that step as ONE ``(batch,
block_length)`` signature a batch bucket, behind ``serving.Server``.
"""
from __future__ import annotations

import numpy as _np

from ....serving.engine import PagedDecodeEngine
from ...block import HybridBlock
from ... import nn
from .falcon_h1 import _mm, _norm
from .glm_moe_dsa import _scatter_rows
from .llama import RMSNorm
from .longcat_flash import PICKS_MARK, LongcatMoE, _dense, _embed, _named

__all__ = ["SdarAttention", "SdarMoE", "SdarMoeLayer", "SdarMoeModel",
           "SdarMoeDecodeEngine", "transfer_schedule", "sdar_moe_tiny"]


def transfer_schedule(block_length: int, denoising_steps: int) -> tuple:
    """The published ``get_num_transfer_tokens``: the fewest positions
    each of a block's denoising steps unmasks, ``block_length //
    denoising_steps`` and one more in the first ``block_length %
    denoising_steps`` steps."""
    base, rem = divmod(int(block_length), int(denoising_steps))
    return tuple(base + (i < rem) for i in range(int(denoising_steps)))


class SdarAttention(HybridBlock):
    """Grouped-query attention over whole sequences (no cache) under the
    block-causal mask: per-head RMSNorm of queries and keys, rotary over
    the whole head (half-split pairs), no bias."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope_theta,
                 eps, block_length, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._h, self._kv, self._d = num_heads, num_kv_heads, head_dim
        self._theta, self._block = float(rope_theta), int(block_length)
        with self.name_scope():
            self.q_proj = _dense(num_heads * head_dim, "q_")
            self.k_proj = _dense(num_kv_heads * head_dim, "k_")
            self.v_proj = _dense(num_kv_heads * head_dim, "v_")
            self.out_proj = _dense(units, "out_")
            self.q_norm = RMSNorm(head_dim, eps, prefix="qnorm_")
            self.k_norm = RMSNorm(head_dim, eps, prefix="knorm_")

    def hybrid_forward(self, F, x):
        b, l = x.shape[0], x.shape[1]
        q = self.q_norm(self.q_proj(x).reshape((b, l, self._h, self._d)))
        k = self.k_norm(self.k_proj(x).reshape((b, l, self._kv, self._d)))
        v = self.v_proj(x).reshape((b, l, self._kv, self._d))
        q = F._contrib_rope(q, theta=self._theta).transpose((0, 2, 1, 3))
        k = F._contrib_rope(k, theta=self._theta).transpose((0, 2, 1, 3))
        v = v.transpose((0, 2, 1, 3))
        rep = self._h // self._kv
        blk = F.floor(F.arange(l) / self._block)
        mask = F.broadcast_lesser_equal(blk.reshape((1, l)),
                                        blk.reshape((l, 1)))
        out = F._contrib_sdp_attention(
            q, F.repeat(k, repeats=rep, axis=1),
            F.repeat(v, repeats=rep, axis=1), mask.reshape((1, 1, l, l)))
        return self.out_proj(out.transpose((0, 2, 1, 3)).reshape(
            (b, l, self._h * self._d)))


class SdarMoE(LongcatMoE):
    """The whole routed expert layer: a softmax router over
    ``n_experts``, ``top_k`` a token, the picked weights renormalised to
    sum to one, every expert held, no zero-compute expert and a zero
    selection bias."""

    def __init__(self, units, hidden_size, n_experts, top_k, prefix=None,
                 params=None):
        super().__init__(units, hidden_size, n_experts, 0, top_k, 1.0,
                         prefix=prefix, params=params)
        self._cfg.update(score="softmax", renormalize=True)


class SdarMoeLayer(HybridBlock):
    def __init__(self, cfg, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        c = cfg
        with self.name_scope():
            self.norm1 = RMSNorm(c["units"], c["eps"], prefix="norm1_")
            self.attention = SdarAttention(
                c["units"], c["num_heads"], c["num_kv_heads"], c["head_dim"],
                c["rope_theta"], c["eps"], c["block_length"], prefix="attn_")
            self.norm2 = RMSNorm(c["units"], c["eps"], prefix="norm2_")
            self.moe = SdarMoE(c["units"], c["expert_hidden_size"],
                               c["n_experts"], c["top_k"], prefix="moe_")

    def hybrid_forward(self, F, x):
        x = x + self.attention(self.norm1(x))
        return x + self.moe(self.norm2(x))


class SdarMoeModel(HybridBlock):
    """Tokens (B, L) -> logits (B, L, vocab) under the block-causal mask;
    position ``i``'s logits are for the token at position ``i``.
    ``block_length``, ``denoising_steps``, ``confidence_threshold`` and
    ``mask_token_id`` are the generation loop's (the served path's): the
    sizes of the published ``block_diffusion_generate``."""

    def __init__(self, vocab_size=151936, num_layers=48, units=2048,
                 num_heads=32, num_kv_heads=4, head_dim=128,
                 expert_hidden_size=768, n_experts=128, top_k=8,
                 rope_theta=1e6, eps=1e-6, block_length=4,
                 denoising_steps=4, confidence_threshold=0.9,
                 mask_token_id=151669, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % num_kv_heads:
            raise ValueError("num_heads must divide into num_kv_heads")
        if not 0 <= mask_token_id < vocab_size:
            raise ValueError(f"mask_token_id {mask_token_id} is not one of "
                             f"the {vocab_size} ids")
        if not 1 <= denoising_steps <= block_length:
            raise ValueError("denoising_steps must lie in 1..block_length")
        # what the pure cache-aware forward needs beside the weights
        self._decode_cfg = {
            "vocab_size": int(vocab_size), "num_layers": int(num_layers),
            "units": int(units), "num_heads": int(num_heads),
            "num_kv_heads": int(num_kv_heads), "head_dim": int(head_dim),
            "expert_hidden_size": int(expert_hidden_size),
            "n_experts": int(n_experts), "top_k": int(top_k),
            "rope_theta": float(rope_theta), "eps": float(eps),
            "block_length": int(block_length),
            "denoising_steps": int(denoising_steps),
            "confidence_threshold": float(confidence_threshold),
            "mask_token_id": int(mask_token_id),
        }
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = []
            for i in range(num_layers):
                blk = SdarMoeLayer(self._decode_cfg, prefix=f"layer{i}_")
                self.blocks.append(blk)
                self.register_child(blk, f"layer{i}")
            self.norm = RMSNorm(units, eps, prefix="norm_")
            # the head is untied (tie_word_embeddings false)
            self.lm_head = _dense(vocab_size, "lm_head_")

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        for blk in self.blocks:
            x = blk(x)
        return self.lm_head(self.norm(x))

    def decode_engine(self, pool) -> "SdarMoeDecodeEngine":
        """The seam ``serving.Server`` asks for ``submit_generate``: a K
        and a V page arena a layer over ``pool``, on the device and in
        the dtype of the parameters."""
        return SdarMoeDecodeEngine.build(self, pool)


# ---------------------------------------------------------------------------
# serving: the cache-aware pure forward and its engine
# ---------------------------------------------------------------------------

# most (token, expert) pairs of one pass of the grouped matmuls: a block
# round's 128 streams x 4 positions x 8 picks go through in one
_PAIRS_PER_PASS = 4096


def _embed_rows(embed_w, tokens):
    """The residual stream starts, and stays, in float32."""
    import jax.numpy as jnp

    return _embed(embed_w, tokens).astype(jnp.float32)


def _layer_forward(x, p, k_arena, v_arena, positions, page_table, lengths,
                   *, cfg):
    """One layer, cache-aware and pure: ``x`` (B, L, U) float32 at
    ``positions`` (a prompt's whole blocks, or a row's trailing block);
    the layer's K and V arenas in and out, with the dispatch's keys and
    values written (a real position into its page, a padded one into the
    scratch page); and the expert layer's pick counts."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import paged_attention, rms_norm, rope_at
    from ....ops.contrib import moe_routed_experts

    b, l, _ = x.shape
    h, kv, d = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    ps, eps = cfg["page_size"], cfg["eps"]
    real = (positions >= 0) & (positions < lengths[:, None])
    with jax.named_scope("sdar.attn"):
        u = _norm(x, p["ln1"], eps)
        q = rms_norm(_mm(u, p["q"]).reshape(b, l, h, d), p["q_norm"],
                     eps=eps)
        k = rms_norm(_mm(u, p["k"]).reshape(b, l, kv, d), p["k_norm"],
                     eps=eps)
        v = _mm(u, p["v"]).reshape(b, l, kv, d)
        q = rope_at(q, positions, theta=cfg["rope_theta"])
        k = rope_at(k, positions, theta=cfg["rope_theta"])
        page_of = jnp.clip(positions // ps, 0, page_table.shape[1] - 1)
        page = jnp.where(
            real, jnp.take_along_axis(page_table, page_of, axis=1),
            0).reshape(-1)                              # padding -> scratch
        offset = jnp.mod(positions, ps).reshape(-1)
        dtype = k_arena.dtype
        k_arena = _scatter_rows(
            k_arena, k.reshape(b * l, kv * d).astype(dtype), page, offset)
        v_arena = _scatter_rows(
            v_arena, v.reshape(b * l, kv * d).astype(dtype), page, offset)
        # a token's heads side by side in one row: the kernel's own view
        # of an arena. A block step's queries are the row's trailing
        # block and go to the kernel folded into the head group; a
        # prefill's take the gather under the block mask
        att = paged_attention(
            q.astype(dtype).transpose(0, 2, 1, 3),
            k_arena[..., :kv * d].reshape(-1, kv, d),
            v_arena[..., :kv * d].reshape(-1, kv, d),
            page_table, lengths, q_positions=positions, page_size=ps,
            block=cfg["block_length"]).transpose(0, 2, 1, 3)
        x = x + _mm(att.reshape(b, l, h * d), p["o"])
    u = _norm(x, p["ln2"], eps).astype(p["router"].dtype)
    pairs = b * l * cfg["top_k"]
    y, counts = moe_routed_experts(
        u.reshape(b * l, -1), p["router"], p["router_bias"], p["gate_up"],
        p["down"], real.reshape(-1), first_held=0,
        n_routed=cfg["n_experts"], top_k=cfg["top_k"], score="softmax",
        renormalize=True,
        rows_per_pass=min(-(-pairs // 128) * 128, _PAIRS_PER_PASS))
    return (x + y.reshape(b, l, -1).astype(jnp.float32), k_arena, v_arena,
            counts)


def _head_pick(x, norm_w, head_w, state, quota, *, eps, mask_id, threshold):
    """The block's new state (B, block) int32 and the float32 logits it
    was picked from, (B x block, V): the head over every position of the
    block, then the denoising step's decision on the device. The logits
    leave the program as the rows the matrix product makes them: handed
    on as (B, block, V) they would be laid out again, a second 0.3 GB at
    128 streams."""
    import jax

    from ....ops.diffusion import block_denoise_pick

    b, l, u = x.shape
    with jax.named_scope("sdar.head"):
        logits = _mm(_norm(x, norm_w, eps).reshape(b * l, u), head_w)
    return block_denoise_pick(logits.reshape(b, l, -1), state, quota,
                              mask_id=mask_id, threshold=threshold), logits


class SdarMoeDecodeEngine(PagedDecodeEngine):
    """The decode engine over one :class:`SdarMoeModel`: per layer a key
    arena and a value arena (``arenas[2 * li]``, ``arenas[2 * li + 1]``;
    each ``(pages, page, kv_heads * head_dim)``, a token's heads side by
    side in one lane-dense row, read by the paged GQA kernel as they lie
    and scattered in place).

    A block step (:meth:`decode_block`) is the embedding lookup, ONE layer
    program run once per layer (``sdar_block_layer``: every layer is the
    same, so a signature compiles one layer whatever the depth) and
    ``sdar_head``, the head over the block's positions with the pick. A
    prefill is the embedding and the layer program (``sdar_prefill_layer``)
    alone: it writes the prompt's whole blocks into the cache under the
    block-causal mask and makes no token, so no head runs and the ids it
    returns are zeros nobody reads. After every forward ``last_counts``
    holds the expert layers' picks per layer as device arrays; with
    telemetry on they are read back and recorded, as LongCat's."""

    family = "sdar_moe"
    last_counts = ()

    def last_logits(self):
        """The (B, block_length, vocab) float32 logits of the last block
        step."""
        logits = super().last_logits()
        return logits.reshape(-1, self.block_length, logits.shape[-1])

    def __init__(self, model, pool):
        c = model._decode_cfg
        self.mask_id = int(c["mask_token_id"])
        self.transfer = transfer_schedule(c["block_length"],
                                          c["denoising_steps"])
        super().__init__(model, pool)

    def _extract(self, model, w):
        def layer(blk):
            a, m = blk.attention, blk.moe
            return {
                "ln1": w(blk.norm1.weight), "ln2": w(blk.norm2.weight),
                "q": w(a.q_proj.weight), "k": w(a.k_proj.weight),
                "v": w(a.v_proj.weight), "o": w(a.out_proj.weight),
                "q_norm": w(a.q_norm.weight), "k_norm": w(a.k_norm.weight),
                "router": w(m.router_weight),
                "router_bias": w(m.router_bias),
                "gate_up": w(m.gate_up_weight), "down": w(m.down_weight)}

        return (w(model.embed.weight),
                tuple(layer(blk) for blk in model.blocks),
                w(model.norm.weight), w(model.lm_head.weight))

    def _make_arenas(self, pool):
        from ....serving.kvcache import make_latent_arena

        cfg = self.cfg
        cfg["page_size"] = pool.page_size
        return list(make_latent_arena(
            2 * cfg["num_layers"], pool,
            cfg["num_kv_heads"] * cfg["head_dim"], self.dtype,
            device=self._device))

    def _run(self, b, l, w_pages, tokens, positions, page_table, lengths,
             quota=None):
        import jax

        from .... import telemetry

        phase = "prefill" if quota is None else "block"
        sig = (b, l, w_pages)
        embed_w, layers, norm_w, head_w = self._params
        cfg = self.cfg
        tokens, positions, page_table, lengths = jax.device_put(
            (tokens, positions, page_table, lengths), self._device)
        x = self._fn("embed", *sig, lambda: (_named(
            _embed_rows, "sdar_embed"), ()))(embed_w, tokens)
        layer = self._fn(phase + "_layer", *sig, lambda: (_named(
            _layer_forward, f"sdar_{phase}_layer", cfg=cfg), (2, 3)))
        counts = []
        for li, lp in enumerate(layers):
            x, self.arenas[2 * li], self.arenas[2 * li + 1], c = layer(
                x, lp, self.arenas[2 * li], self.arenas[2 * li + 1],
                positions, page_table, lengths)
            counts.append(c)
        self.last_counts = tuple(counts)
        if quota is None:
            # a prefill fills the cache and makes no token
            out = _np.zeros((b,), _np.int32), None
        else:
            out = self._fn("head", *sig, lambda: (_named(
                _head_pick, "sdar_head", eps=cfg["eps"],
                mask_id=self.mask_id,
                threshold=cfg["confidence_threshold"]), ()))(
                    x, norm_w, head_w, tokens, quota)
        if telemetry._state.enabled:
            held, zero, absent, touched = (
                int(v) for v in _np.sum(_np.asarray(counts), axis=0))
            kind = "prefill" if quota is None else "decode"
            telemetry.record_moe_picks(held, zero, absent, touched,
                                       len(layers), phase=kind)
            # the same counts as a host event of a running profiler
            # trace, so that a traced slice carries its own rounds' picks
            with jax.profiler.TraceAnnotation(
                    f"{PICKS_MARK}{kind}:{held}:{zero}:{absent}:{touched}"
                    f":{len(layers)}"):
                pass
        return out


def sdar_moe_tiny(**kwargs):
    """Test-sized config of the same kinds: two layers, 4 / 2 heads of 8,
    8 experts of width 16 with 3 a token, blocks of 4 in 4 steps."""
    cfg = dict(vocab_size=128, num_layers=2, units=32, num_heads=4,
               num_kv_heads=2, head_dim=8, expert_hidden_size=16,
               n_experts=8, top_k=3, rope_theta=1e4, block_length=4,
               denoising_steps=4, confidence_threshold=0.9,
               mask_token_id=127)
    cfg.update(kwargs)
    return SdarMoeModel(**cfg)
