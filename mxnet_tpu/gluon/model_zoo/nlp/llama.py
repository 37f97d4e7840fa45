"""Llama-style decoder-only LM — the stretch config (BASELINE.json config[4]).

No reference counterpart (the reference pre-dates Llama; SURVEY.md §5.7
flags long-context as a new capability). TPU-first design choices:
* RMSNorm in f32, output in compute dtype;
* RoPE computed in-graph from positions (no host tables, no recompiles
  across sequence lengths within a bucket);
* grouped-query attention (n_kv_heads < n_heads) through the same
  `_contrib_sdp_attention` seam (kv heads broadcast to q heads);
* SwiGLU FFN as two fused matmuls (gate+up projected together);
* Megatron TP rules + sequence-axis sharding hooks for ring attention.
"""
from __future__ import annotations

import functools
import math

from ....serving.engine import PagedDecodeEngine, greedy_pick
from ...block import HybridBlock
from ... import nn
from ...parameter import Parameter

__all__ = ["RMSNorm", "LlamaAttention", "LlamaMLP", "LlamaBlock",
           "LlamaModel", "LlamaDecodeEngine", "llama_tiny", "llama_3_8b",
           "llama_sharding_rules", "LlamaModelPP", "llama_tiny_pp",
           "llama_pp_sharding_rules"]


class RMSNorm(HybridBlock):
    """f32-statistics RMSNorm. Under ``MXNET_PALLAS_FUSED=1`` the
    ``_contrib_rms_norm`` op routes to the fused Pallas kernel
    (pallas_kernels/fused_layers.py, RMS mode) on TPU — every Llama
    block adopts the fused layer path through this seam."""

    def __init__(self, units, eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._eps = eps
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(units,),
                                          init="ones")

    def hybrid_forward(self, F, x, weight):
        return F._contrib_rms_norm(x, weight, eps=self._eps)


class LlamaAttention(HybridBlock):
    def __init__(self, units, num_heads, num_kv_heads=None, rope_theta=10000.0,
                 ring_axis=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        num_kv_heads = num_kv_heads or num_heads
        if num_heads % num_kv_heads:
            raise ValueError("num_heads must be divisible by num_kv_heads")
        self._units = units
        self._h = num_heads
        self._kv = num_kv_heads
        self._d = units // num_heads
        self._theta = rope_theta
        self._ring_axis = ring_axis  # sequence-parallel ring attention
        with self.name_scope():
            self.q_proj = nn.Dense(units, flatten=False, use_bias=False,
                                   prefix="q_")
            self.kv_proj = nn.Dense(2 * self._kv * self._d, flatten=False,
                                    use_bias=False, prefix="kv_")
            self.out_proj = nn.Dense(units, flatten=False, use_bias=False,
                                     prefix="out_")

    def hybrid_forward(self, F, x):
        b, l = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape((b, l, self._h, self._d))
        kv = self.kv_proj(x).reshape((b, l, 2 * self._kv, self._d))
        k, v = F.split(kv, num_outputs=2, axis=2)
        q = F._contrib_rope(q, theta=self._theta)
        k = F._contrib_rope(k, theta=self._theta)
        # (B, L, H, D) -> (B, H, L, D); kv heads repeat up to q heads (GQA)
        q = q.transpose((0, 2, 1, 3))
        k = k.transpose((0, 2, 1, 3))
        v = v.transpose((0, 2, 1, 3))
        if self._kv != self._h:
            rep = self._h // self._kv
            k = F.repeat(k, repeats=rep, axis=1)
            v = F.repeat(v, repeats=rep, axis=1)
        out = F._contrib_sdp_attention(q, k, v, causal=True,
                                       ring_axis=self._ring_axis)
        out = out.transpose((0, 2, 1, 3)).reshape((b, l, self._units))
        return self.out_proj(out)


class LlamaMLP(HybridBlock):
    """SwiGLU: gate and up projected in ONE matmul, then silu(gate)*up."""

    def __init__(self, units, hidden_size, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._hidden = hidden_size
        with self.name_scope():
            self.gate_up = nn.Dense(2 * hidden_size, flatten=False,
                                    use_bias=False, prefix="gateup_")
            self.down = nn.Dense(units, flatten=False, use_bias=False,
                                 prefix="down_")

    def hybrid_forward(self, F, x):
        gu = self.gate_up(x)
        gate, up = F.split(gu, num_outputs=2, axis=-1)
        return self.down(F.Activation(gate, act_type="silu") * up)


class LlamaBlock(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, num_kv_heads=None,
                 rope_theta=10000.0, eps=1e-6, ring_axis=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps, prefix="attnnorm_")
            self.attention = LlamaAttention(units, num_heads, num_kv_heads,
                                            rope_theta, ring_axis=ring_axis,
                                            prefix="attn_")
            self.mlp_norm = RMSNorm(units, eps, prefix="mlpnorm_")
            self.mlp = LlamaMLP(units, hidden_size, prefix="mlp_")

    def hybrid_forward(self, F, x):
        x = x + self.attention(self.attn_norm(x))
        return x + self.mlp(self.mlp_norm(x))


def _best_ce_chunk(vocab, target=8192):
    """Largest divisor of ``vocab`` <= target (the fused-CE tile size that
    keeps the bias-free path reachable — e.g. 8016 for Llama-3's 128256).
    A vocab <= target is its own (single) chunk. Only when every divisor
    is degenerate (< target/4, e.g. a large near-prime vocab) fall back to
    ``target`` and accept the padded path."""
    if vocab <= target:
        return vocab
    for c in range(target, 0, -1):
        if vocab % c == 0:  # c=1 always divides, so this always returns
            return c if c >= target // 4 else target


class LlamaModel(HybridBlock):
    """Decoder-only causal LM; returns (B, L, vocab) logits."""

    def __init__(self, vocab_size=128256, num_layers=32, units=4096,
                 hidden_size=14336, num_heads=32, num_kv_heads=8,
                 rope_theta=500000.0, eps=1e-5, tie_weights=False,
                 ring_axis=None, remat=False, fused_ce=False,
                 ce_chunk=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        # architecture record for the paged decode engine (serving):
        # everything the pure decode forward needs that the blocks
        # otherwise keep in closed-over layer attributes
        num_kv = num_kv_heads or num_heads
        self._decode_cfg = {
            "vocab_size": int(vocab_size), "num_layers": int(num_layers),
            "units": int(units), "num_heads": int(num_heads),
            "num_kv_heads": int(num_kv),
            "head_dim": int(units // num_heads),
            "rope_theta": float(rope_theta), "eps": float(eps),
        }
        # per-block gradient rematerialization (jax.checkpoint) inside
        # compiled train steps — pretrain-scale memory policy. ``remat``
        # may be a bool (True = save-nothing "full" policy) or a policy
        # name accepted by gluon.block.remat_call ("full" | "dots");
        # normalized here to policy-name-or-None
        self._remat = remat if isinstance(remat, str) else \
            ("full" if remat else None)
        # fused projection+CE head (ops/fused_loss.py): forward takes
        # (tokens, labels) and returns per-token loss; the (B, L, vocab)
        # logits never materialize — at pretrain vocab sizes they are
        # the largest intermediate of the step
        self._fused_ce = bool(fused_ce)
        # chunk must DIVIDE vocab for the bias-free fast path of
        # softmax_ce_head (a non-divisor falls back to padding + a
        # synthetic zero bias whose vocab-sized cotangent the fast path
        # exists to avoid — round-3 advisor finding). Default: largest
        # divisor of vocab <= 8192, e.g. 8016 for the Llama-3 128256.
        if ce_chunk and vocab_size % int(ce_chunk):
            # warn, don't raise: the default itself may legitimately pick
            # a non-divisor for near-prime vocabs (padded fallback is the
            # only option there) — but an accidental non-divisor when good
            # divisors exist deserves a loud signal
            import warnings

            best = _best_ce_chunk(vocab_size)
            warnings.warn(
                f"ce_chunk={ce_chunk} does not divide vocab_size="
                f"{vocab_size}: the fused CE head takes the padded "
                "fallback with a vocab-sized synthetic-bias cotangent"
                + (f"; a dividing chunk exists ({best})"
                   if vocab_size % best == 0 else ""),
                stacklevel=3)
        self._ce_chunk = int(ce_chunk) if ce_chunk else \
            _best_ce_chunk(vocab_size)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = []
            for i in range(num_layers):
                blk = LlamaBlock(units, hidden_size, num_heads, num_kv_heads,
                                 rope_theta, eps, ring_axis=ring_axis,
                                 prefix=f"layer{i}_")
                self.blocks.append(blk)
                self.register_child(blk, f"layer{i}")
            self.norm = RMSNorm(units, eps, prefix="norm_")
            # explicit in_units: in fused-CE mode the Dense's own
            # forward never runs, so the weight must not be deferred
            if tie_weights:
                self.lm_head = nn.Dense(vocab_size, in_units=units,
                                        flatten=False, use_bias=False,
                                        params=self.embed.params,
                                        prefix="embed_")
            else:
                self.lm_head = nn.Dense(vocab_size, in_units=units,
                                        flatten=False, use_bias=False,
                                        prefix="lm_head_")

    def hybrid_forward(self, F, tokens, labels=None):
        from ...block import remat_call

        x = self.embed(tokens)
        for blk in self.blocks:
            x = remat_call(blk, x, policy=self._remat) if self._remat \
                else blk(x)
        h = self.norm(x)
        if self._fused_ce:
            if labels is None:
                raise ValueError(
                    "LlamaModel(fused_ce=True) takes (tokens, labels) and "
                    "returns the per-token loss")
            w = self.lm_head.weight.data(tokens.context)
            return F._contrib_softmax_ce_head(h, w, None, labels,
                                              chunk=self._ce_chunk)
        return self.lm_head(h)

    def decode_engine(self, pool) -> "LlamaDecodeEngine":
        """Build the paged-KV decode engine for serving (the seam
        ``serving.Server`` asks for to enable ``submit_generate``).
        ``pool``: a :class:`mxnet_tpu.serving.kvcache.PagePool`. The
        engine lives where the parameters live, in their dtype."""
        return LlamaDecodeEngine.build(self, pool)


class LlamaModelPP(HybridBlock):
    """Llama with the layer trunk pipelined over the mesh's ``pp`` axis.

    ``num_layers = n_stages * layers_per_stage``; the trunk is ONE
    :class:`~mxnet_tpu.parallel.Pipelined` block whose stage-stacked
    parameters shard over ``pp`` while embed/norm/head stay GSPMD-managed
    (replicated over ``pp``, shardable over ``tp``/``dp`` as usual).
    Off-mesh it computes the identical function sequentially.
    """

    def __init__(self, vocab_size=256, n_stages=4, layers_per_stage=1,
                 units=64, hidden_size=128, num_heads=4, num_kv_heads=None,
                 rope_theta=10000.0, eps=1e-6, n_microbatches=None,
                 remat=False, ring_axis=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        from ....parallel.pipeline import Pipelined

        if isinstance(remat, str):
            # Pipelined's remat is jax.checkpoint over the stage scan with
            # the default policy only; a policy string would be silently
            # bool()-coerced to full remat — reject instead of lying
            raise ValueError(
                "LlamaModelPP supports remat=True/False only (the "
                "pipelined trunk's checkpoint has no policy plumbing); "
                f"got remat={remat!r}")
        self._units = units
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.trunk = Pipelined(
                lambda: LlamaBlock(units, hidden_size, num_heads,
                                   num_kv_heads, rope_theta, eps,
                                   ring_axis=ring_axis, prefix="stage_"),
                n_stages=n_stages, layers_per_stage=layers_per_stage,
                n_microbatches=n_microbatches, remat=remat,
                prefix="trunk_")
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    use_bias=False, prefix="lm_head_")

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        x = self.trunk(x)
        return self.lm_head(self.norm(x))


def llama_tiny_pp(n_stages=4, **kwargs):
    """Test-sized pipelined config (CI / dry-run)."""
    cfg = dict(vocab_size=256, n_stages=n_stages, layers_per_stage=1,
               units=64, hidden_size=128, num_heads=4, num_kv_heads=2,
               rope_theta=10000.0)
    cfg.update(kwargs)
    return LlamaModelPP(**cfg)


def llama_pp_sharding_rules(pp_axis="pp", tp_axis="tp"):
    """PP stage axis on the stacked trunk params, composed with the
    Megatron TP splits (shifted by the (stage, layer) lead dims) and the
    usual vocab-parallel embed/head."""
    from ....parallel import ShardingRules
    from ....parallel.pipeline import pipeline_sharding_rules
    from jax.sharding import PartitionSpec as P

    rules = ShardingRules([
        (r"(embed|lm_head)_weight$", P(tp_axis, None)),
    ])
    rules.extend(pipeline_sharding_rules(pp_axis, extra=[
        (r"pp_.*(q|kv|gateup)_weight$", (tp_axis,)),
        (r"pp_.*(out|down)_weight$", (None, tp_axis)),
    ]))
    return rules


def llama_sharding_rules(tp_axis="tp"):
    """Megatron TP: q/kv/gate-up column-parallel, out/down row-parallel,
    embedding + lm_head vocab-parallel."""
    from ....parallel import ShardingRules
    from jax.sharding import PartitionSpec as P

    return ShardingRules([
        (r"(q|kv|gateup)_weight$", P(tp_axis, None)),
        (r"(out|down)_weight$", P(None, tp_axis)),
        (r"(embed|lm_head)_weight$", P(tp_axis, None)),
    ])


# ---------------------------------------------------------------------------
# paged-KV decode engine (serving)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _fresh_attention(platform, routing):
    """Causal attention of a dispatch's own rows, ``(B, L, H, D)`` queries
    over ``(B, L, KV, D)`` keys and values, as ``(B, H, L, D)``: ONE
    jitted function a platform and routing state (what every executable
    cache is keyed by: the gate inside reads both at trace time), so the
    layers of a program, whose shapes are the same, trace and lower the
    kernel once and call it, where inline each layer would trace and
    lower its own copy (~0.7 s a 16-layer program on the chip's host)."""
    import jax
    import jax.numpy as jnp

    from ....ops.attention import sdp_attention

    def attend(q, k, v):
        # (B, L, H, D) -> (B, H, L, D); kv heads repeat up to q heads (GQA)
        rep = q.shape[2] // k.shape[2]
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        return sdp_attention(None, q.transpose(0, 2, 1, 3), k, v,
                             causal=True)

    return jax.jit(attend)


def _paged_forward(params, tokens, positions, page_table, lengths,
                   *arenas, cfg, page_size, fresh=False):
    """Pure cache-aware forward: embeds ``tokens`` (B, L) at absolute
    ``positions`` (B, L), scatters each layer's K/V into the paged
    arenas, attends through the page table, and returns the greedy
    token id and the logits of the LAST valid input position per row
    plus the updated arenas.

    ``fresh`` (static): the caller has seen that every row's positions
    are ``arange(L)``, a prefill that starts at position 0. The keys and
    values a query may see are then the dispatch's own rows, so a layer
    still scatters them into its arenas (the decode rounds that follow
    read them there) but attends over the ``k`` / ``v`` it has just
    computed with the causal mask, through
    :func:`~mxnet_tpu.ops.attention.sdp_attention` (the Pallas flash
    forward on the TPU for whole 128-blocks, the dense causal reference
    elsewhere): nothing is gathered through the page table and no
    ``(B, heads, L, table slots)`` score matrix is made. A real query at
    position p < ``lengths`` sees keys 0..p, all real; padding queries
    compute finite values that ``last`` never reads.

    ``arenas``: per layer a key array and a value array (``arenas[2 *
    li]``, ``arenas[2 * li + 1]``), each ``(pages, page, kv_heads *
    head_dim)``: a token's heads side by side in one lane-dense row, so
    a layer scatters into its own array in place and the attention
    reads it as it lies, viewed ``(slots, kv_heads, head_dim)``.

    One function serves both phases — prefill is (B, len-bucket),
    decode is (B, 1) — so both compile through the same cache site and
    the decode step is ONE executable per batch bucket; a prefill from
    position 0 is the same function with ``fresh`` bound. Positions at or
    beyond a row's ``lengths`` (bucket padding, whole-row batch
    padding) scatter into the reserved scratch page 0 and are masked
    out of every attention read — bit-transparent padding, extended to
    the cache.
    """
    import jax
    import jax.numpy as jnp

    from ....base import current_execution_platform
    from ....compiler.keys import routing_knobs
    from ....ops.attention import paged_attention, rms_norm, rope_at
    from .glm_moe_dsa import _scatter_rows

    embed_w, layer_params, norm_w, head_w = params
    n_heads = cfg["num_heads"]
    n_kv = cfg["num_kv_heads"]
    d = cfg["head_dim"]
    theta = cfg["rope_theta"]
    eps = cfg["eps"]
    ps = int(page_size)
    b, l = tokens.shape
    w_pages = page_table.shape[1]

    x = jnp.take(embed_w, tokens, axis=0)               # (B, L, U)
    real = positions < lengths[:, None]
    page_of = jnp.clip(positions // ps, 0, w_pages - 1)
    page = jnp.where(real, jnp.take_along_axis(page_table, page_of, axis=1),
                     0).reshape(-1)                     # padding -> scratch
    offset = (positions % ps).reshape(-1)
    written = []

    for (anw, qw, kvw, ow, mnw, guw, dw), k_arena, v_arena in zip(
            layer_params, arenas[::2], arenas[1::2]):
        h = rms_norm(x, anw, eps=eps)
        q = (h @ qw.T).reshape(b, l, n_heads, d)
        kv = (h @ kvw.T).reshape(b, l, 2 * n_kv, d)
        k, v = kv[:, :, :n_kv], kv[:, :, n_kv:]
        q = rope_at(q, positions, theta=theta)
        k = rope_at(k, positions, theta=theta)
        k_arena = _scatter_rows(
            k_arena, k.reshape(b * l, n_kv * d), page, offset)
        v_arena = _scatter_rows(
            v_arena, v.reshape(b * l, n_kv * d), page, offset)
        written += [k_arena, v_arena]
        if fresh:
            att = _fresh_attention(current_execution_platform(),
                                   routing_knobs())(q, k, v)
        else:
            # a row is padded to whole lane tiles where kv * d is not one
            att = paged_attention(
                q.transpose(0, 2, 1, 3),
                k_arena[..., :n_kv * d].reshape(-1, n_kv, d),
                v_arena[..., :n_kv * d].reshape(-1, n_kv, d),
                page_table, lengths, q_positions=positions, page_size=ps)
        att = att.transpose(0, 2, 1, 3).reshape(b, l, n_heads * d)
        x = x + att @ ow.T
        hm = rms_norm(x, mnw, eps=eps)
        gate, up = jnp.split(hm @ guw.T, 2, axis=-1)
        x = x + (jax.nn.silu(gate) * up) @ dw.T

    hfin = rms_norm(x, norm_w, eps=eps)
    # logits of the last REAL input row: axis index lengths-1-positions[:,0]
    # (prefill: lengths-1; decode L=1: always 0). Whatever L, this is a
    # (B, U) @ (U, V) contraction — the same lowering for both phases.
    last = jnp.clip(lengths - 1 - positions[:, 0], 0, l - 1)
    h_last = jnp.take_along_axis(
        hfin, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = h_last @ head_w.T
    return greedy_pick(logits), logits, *written


class LlamaDecodeEngine(PagedDecodeEngine):
    """:func:`_paged_forward` over one :class:`LlamaModel`: ONE program
    per signature runs the whole stack, over a key and a value array a
    layer (``arenas[2 * li]``, ``arenas[2 * li + 1]``), all donated.

    Which program a forward of more than one position takes is read off
    its ``positions``: ``arange(L)`` in every row (every :meth:`prefill`
    of this engine, which declares no ``chunked_prefill``) takes the
    ``fresh`` program, whose layers attend over the dispatch's own keys
    and values with the causal flash forward; a forward at an offset
    takes the program that gathers through the page table, as every
    decode step takes the one that walks the live pages. Each such
    dispatch counts in ``mxnet_serving_prefill_dispatch_total{path}``
    (``fresh`` / ``gather``)."""

    family = "llama"

    def _extract(self, model, w):
        return (
            w(model.embed.weight),
            tuple((w(blk.attn_norm.weight), w(blk.attention.q_proj.weight),
                   w(blk.attention.kv_proj.weight),
                   w(blk.attention.out_proj.weight),
                   w(blk.mlp_norm.weight), w(blk.mlp.gate_up.weight),
                   w(blk.mlp.down.weight))
                  for blk in model.blocks),
            w(model.norm.weight), w(model.lm_head.weight))

    def _make_arenas(self, pool):
        from ....serving.kvcache import make_latent_arena

        return make_latent_arena(
            2 * self.cfg["num_layers"], pool,
            self.cfg["num_kv_heads"] * self.cfg["head_dim"], self.dtype,
            device=self._device)

    def _run(self, b, l, w_pages, tokens, positions, page_table, lengths):
        import numpy as np

        from .... import telemetry

        fresh = l > 1 and bool((positions == np.arange(l)).all())
        if l > 1:
            telemetry.record_prefill_dispatch("fresh" if fresh else "gather")
        fn = self._fn("fresh" if fresh else None, b, l, w_pages, lambda: (
            functools.partial(_paged_forward, cfg=self.cfg,
                              page_size=self.page_size, fresh=fresh),
            tuple(range(5, 5 + len(self.arenas)))))
        ids, logits, *self.arenas = fn(self._params, tokens, positions,
                                       page_table, lengths, *self.arenas)
        return ids, logits


def llama_tiny(**kwargs):
    """Test-sized config (CI / dry-run)."""
    cfg = dict(vocab_size=256, num_layers=2, units=64, hidden_size=128,
               num_heads=4, num_kv_heads=2, rope_theta=10000.0)
    cfg.update(kwargs)
    return LlamaModel(**cfg)


def llama_3_8b(**kwargs):
    """Llama-3-8B shapes (BASELINE.json stretch config)."""
    cfg = dict(vocab_size=128256, num_layers=32, units=4096,
               hidden_size=14336, num_heads=32, num_kv_heads=8,
               rope_theta=500000.0)
    cfg.update(kwargs)
    return LlamaModel(**cfg)
