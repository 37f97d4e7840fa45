"""The decode engine behind ``Server.submit_generate``: what every paged
decoder shares, once. A served decoder is a :class:`PagedDecodeEngine`
subclass in its model file, next to the pure functions it runs;
``model.decode_engine(pool)``, the seam ``Server`` asks for, is
``return <ItsEngine>.build(self, pool)``.
"""
from __future__ import annotations

import time

import numpy as np

__all__ = ["PagedDecodeEngine", "greedy_pick"]

_DECODE_SITE = "serving_decode"


def greedy_pick(logits):
    """How a forward's last program picks the next tokens: the (B,)
    int32 ids of the largest logit per row, a tie to the lowest index as
    ``np.argmax`` gives it, taken in the logits' own dtype."""
    from jax import lax

    # int32 indices whatever jax_enable_x64 says: the chip emulates s64
    return lax.argmax(logits, logits.ndim - 1, "int32")


class PagedDecodeEngine:
    """Cache-aware generation engine over one model and one
    :class:`~mxnet_tpu.serving.kvcache.PagePool`.

    Owns the per-replica cache ``arenas`` (a list of device arrays whose
    pages the pool hands out) and dispatches the subclass's programs
    through the compiler service's ``serving_decode`` cache site: one
    executable per (batch-bucket, len-bucket) prefill signature, ONE
    ``(batch, block_length)`` executable per batch bucket for every
    decode step (``(batch, 1)`` for every engine that makes a token a
    stream a step) — zero steady-state retraces
    (``mxnet_jit_cache_total{cache="serving_decode"}`` is the marker).

    **The block step.** An engine whose model generates by diffusion
    over blocks declares ``block_length`` > 1 (it comes from the model's
    ``_decode_cfg`` alone; 1 for every other engine) with ``mask_id`` and
    ``transfer``, the fewest positions each denoising step of a block
    unmasks. A decode round then steps a stream's current BLOCK
    (:meth:`decode_block`): ``(batch, block_length)`` token ids, the mask
    id where a position is still masked, in; the block's new state out,
    ``4 * block_length`` bytes a row. The last program picks WHICH
    positions to unmask as well as their tokens
    (:func:`~mxnet_tpu.ops.diffusion.block_denoise_pick`); a commit (a
    block with nothing masked, whose keys and values become the
    cache's) is the same program. A prefill of such an engine writes the
    prompt's whole blocks into the cache and makes no token.

    A subclass sets ``family`` (the first element of the cache key's
    identity) and defines ``_extract``, ``_make_arenas`` (arrays whose
    axis 0 is the pool's pages: that is what
    :func:`~mxnet_tpu.serving.kvcache.apply_defrag` moves) and
    ``_run``. One whose forward attends THROUGH the cache at any
    ``positions`` (not only to the rows of the dispatch itself) sets
    ``chunked_prefill``: the server then prefills a prompt longer than
    its largest length bucket a chunk at a time (:meth:`prefill` with
    ``offsets``), and refuses such a prompt for every other engine.

    **The slot seam.** An engine whose streams carry state that is not
    pages of tokens (a scan's state, a convolution's tail, a window's
    ring) sets ``state_slots``: the pool then has to carry a
    :class:`~mxnet_tpu.serving.kvcache.StateSlots` (``pool.state_slots``)
    that sizes the engine's slot arrays, the server takes a slot with a
    stream's pages and frees it with them, and every :meth:`prefill` /
    :meth:`decode_step` / :meth:`forward` carries ``slots`` (B,) int32
    (0, the scratch slot, for a padding row), which ``_run`` gets as a
    keyword. A row whose first position is 0 starts a stream: the engine
    takes its state as zeros, whatever the slot held. The seam also
    carries ``final`` (B,) bool: the rows whose chunk ends their prompt.
    An engine may compute a next token for those rows only (the ids of
    the others are never read). An engine without ``state_slots`` is
    called exactly as before: its ``_run`` has no such keywords.

    **The embeddings seam.** An engine whose model takes, at some
    positions, rows that are not the embedding of a token id (the output
    of a vision tower at an image's placeholder ids) sets
    ``takes_embeds``: :meth:`prefill` / :meth:`forward` then accept
    ``embeds`` (B, R, units), a device array of such rows per batch row,
    and ``embed_rows`` (B, L) int32: the row of ``embeds[b]`` that
    position ``l`` of row ``b`` takes, or -1 for the embedding of its
    token id. ``_run`` gets both as keywords, and only when they are
    given: a forward over ids alone is the call it has always been. Such
    an engine may also declare ``vision``, the encode stage that makes
    the rows (``encode``, ``new_buffer``, ``bucket_of``:
    :class:`~mxnet_tpu.gluon.model_zoo.vision.navit.NavitEncodeEngine`),
    which the server runs before a request's first prefill chunk.

    Not thread-safe by design: exactly one scheduler thread drives it
    (the :class:`~mxnet_tpu.serving.server.Server` contract).
    """

    family: str
    chunked_prefill = False
    state_slots = False
    takes_embeds = False
    vision = None
    block_length = 1

    def __init__(self, model, pool):
        self.cfg = dict(model._decode_cfg)
        self.block_length = int(self.cfg.get("block_length", 1))
        self.pool = pool
        self.page_size = pool.page_size
        if self.state_slots and pool.state_slots is None:
            from ..base import MXNetError
            raise MXNetError(
                f"{type(self).__name__} keeps per-stream state slots: "
                "build its pool with PagePool(..., n_state_slots=)")
        # weights, cache and compute share the model's own dtype and
        # device: a bf16 net on tpu(0) decodes in bf16 on tpu(0)
        embed = model.embed.weight.data().data
        self.dtype = str(embed.dtype)
        self._device = next(iter(embed.devices()))
        self._ident = (self.family, tuple(sorted(self.cfg.items())),
                       self.dtype)
        self.arenas = list(self._make_arenas(pool))
        self._logits = None
        # time.time_ns() when the last forward had dispatched its
        # programs, read only while tracing or telemetry is on: where a
        # decode round's launch ends and its fetch begins
        # (`Server._decode_batch`)
        self.run_done_ns = None
        self.refresh_params(model)

    @classmethod
    def build(cls, model, pool):
        """The engine of ``model`` over ``pool``; a model whose
        parameter shapes are still deferred is run once first."""
        from ..gluon.parameter import DeferredInitializationError
        try:
            return cls(model, pool)
        except DeferredInitializationError:
            from .. import nd
            # materialize shapes — on the parameters' own context, or
            # the probe would compute (and place them) somewhere else
            ctx = model.embed.weight.list_ctx()[0]
            model(nd.zeros((1, 2), dtype="int32", ctx=ctx))
            return cls(model, pool)

    # -- what a model says ----------------------------------------------
    def _extract(self, model, w):
        """The weight pytree the programs take; ``w(parameter)`` is its
        array in the engine's dtype."""
        raise NotImplementedError

    def _make_arenas(self, pool):
        """The cache arrays, committed to ``self._device``."""
        raise NotImplementedError

    def _run(self, b, l, w_pages, tokens, positions, page_table, lengths):
        """Dispatch the (b, l) forward over int32 host arrays, advance
        ``self.arenas`` and return the (b,) ids that :func:`greedy_pick`
        takes from the (b, vocab) logits in the forward's last program,
        and those logits, both on the device. An engine with
        ``state_slots`` also takes ``slots`` (b,) int32 and ``final``
        (b,) bool as keywords."""
        raise NotImplementedError

    # -- weights ----------------------------------------------------------
    def refresh_params(self, model) -> None:
        """(Re)extract the weight arrays — called at build and after a
        model swap once no in-flight generate still needs the old
        weights (a request's whole completion runs on ONE version)."""
        import jax.numpy as jnp

        def w(p):
            return jnp.asarray(p.data().data, dtype=self.dtype)

        self._params = self._extract(model, w)

    # -- dispatch ---------------------------------------------------------
    def _fn(self, part, b, l, w_pages, build):
        """The jitted program ``part`` of the (b, l) forward, from the
        ``serving_decode`` cache site (``part`` is None for a model whose
        forward is one program). On a miss ``build()`` gives the function
        to jit and the arguments it may donate."""
        import jax

        from ..compiler import service as _csvc
        from ..compiler import signature

        cache = _csvc.shared_cache(_DECODE_SITE)
        platform = self._device.platform
        key = signature(
            _DECODE_SITE,
            self._ident if part is None else self._ident + (part,),
            avals=((b, l), (b, w_pages), self.dtype),
            attrs=(self.page_size,), platform=platform)
        fn = cache.lookup(key)
        if fn is not cache.MISS:
            return fn
        fn, donate = build()
        # CPU XLA does not honor donation (it would warn per call);
        # elsewhere the arenas are donated so the scatter updates alias
        jit_kw = ({"donate_argnums": donate}
                  if donate and platform != "cpu" else {})
        fn = jax.jit(fn, **jit_kw)
        cache.insert(key, fn)
        return fn

    def forward(self, tokens, positions, page_table, lengths, slots=None,
                final=None, embeds=None, embed_rows=None, quota=None):
        """Run one cache-aware forward; numpy in, the greedy next token
        ids (B,) int32 out: the pick is made on the device and only the
        ids cross to the host. The (B, vocab) logits stay on the device
        until the next forward (:meth:`last_logits`); the arenas advance
        in place (functionally). ``slots`` and ``final``: the slot seam
        (an engine with ``state_slots``; ``final`` None: every row);
        ``embeds`` and ``embed_rows``: the embeddings seam. ``quota``
        (B,) int32: the block step (:meth:`decode_block`), which returns
        the blocks' new state (B, block_length) in the ids' place."""
        from .. import telemetry, tracing
        from ..base import execution_platform

        tokens = np.asarray(tokens, dtype=np.int32)
        b, l = tokens.shape
        seam = {}
        if self.state_slots:
            if slots is None:
                raise ValueError(f"{type(self).__name__} needs the rows' "
                                 "state slots (slots=)")
            seam = {"slots": np.asarray(slots, dtype=np.int32),
                    "final": np.ones((b,), bool) if final is None
                    else np.asarray(final, dtype=bool)}
        if embeds is not None:
            if not self.takes_embeds:
                raise NotImplementedError(
                    f"{type(self).__name__} takes token ids only: its "
                    "model has no rows of embeddings to be handed")
            seam.update(embeds=embeds,
                        embed_rows=np.asarray(embed_rows, dtype=np.int32))
        if quota is not None:
            seam["quota"] = np.asarray(quota, dtype=np.int32)
        # the last forward's logits go before this one's are made
        self._logits = None
        # host int32 arrays ride along to wherever the committed weights
        # and arenas are; kernel routing follows that device, not the
        # process default
        with execution_platform(self._device.platform):
            ids, self._logits = self._run(
                b, l, np.shape(page_table)[1], tokens,
                np.asarray(positions, dtype=np.int32),
                np.asarray(page_table, dtype=np.int32),
                np.asarray(lengths, dtype=np.int32), **seam)
        if telemetry._state.enabled or tracing._state.enabled:
            self.run_done_ns = time.time_ns()
        ids = np.asarray(ids)
        if telemetry._state.enabled:
            telemetry.record_host_fetch(
                ids.nbytes,
                "decode" if l == 1 or quota is not None else "prefill")
        return ids

    def last_logits(self):
        """The (B, vocab) logits of the last forward, fetched to the
        host, in the dtype its program made them: what the ids were
        picked from. For oracles and checks; serving never asks."""
        return np.asarray(self._logits)

    def prefill(self, tokens, lengths, page_table, offsets=None,
                slots=None, final=None, embeds=None, embed_rows=None):
        """Prefill (B, len-bucket) prompts; ``lengths`` are the real
        prompt lengths. Returns the next token id per row. With
        ``offsets`` (B,) (an engine with ``chunked_prefill``) row ``i``
        is the chunk of its prompt that starts at ``offsets[i]``, the
        chunks before it are in the cache, and ``lengths[i]`` counts the
        prompt up to this chunk's last real token. ``slots``, ``final``,
        ``embeds``, ``embed_rows``: as :meth:`forward`."""
        b, l = np.shape(tokens)
        positions = np.broadcast_to(np.arange(l, dtype=np.int32), (b, l))
        if offsets is not None:
            if not self.chunked_prefill:
                raise NotImplementedError(
                    f"{type(self).__name__} attends to a dispatch's own "
                    "rows only: it cannot prefill a chunk at an offset")
            positions = positions + np.asarray(offsets,
                                               np.int32).reshape(b, 1)
        return self.forward(tokens, positions, page_table, lengths, slots,
                            final, embeds, embed_rows)

    def decode_step(self, tokens, lengths, page_table, slots=None):
        """One continuous-batching decode step: ``tokens`` (B,) are the
        rows' newest tokens, already counted in ``lengths``. ONE
        (B, 1)-shaped signature regardless of how deep each row is.
        Returns the next token id per row."""
        tokens = np.asarray(tokens, dtype=np.int32).reshape(-1, 1)
        positions = (np.asarray(lengths, dtype=np.int32) - 1).reshape(-1, 1)
        return self.forward(tokens, positions, page_table, lengths, slots)

    def decode_block(self, tokens, lengths, page_table, quota):
        """One round of an engine with ``block_length`` > 1: ``tokens``
        (B, block_length) are the rows' current blocks (the mask id
        where still masked), at the rows' trailing positions, already
        counted in ``lengths``; ``quota`` (B,) the fewest positions this
        step unmasks in each row (0: a commit or a padding row). The
        forward writes the block's keys and values at its own positions
        (a later step of the block, and its commit, overwrite them).
        ONE (B, block_length) signature whatever step each row is at.
        Returns the blocks' new state (B, block_length) int32."""
        tokens = np.asarray(tokens, dtype=np.int32)
        bk = tokens.shape[1]
        positions = (np.asarray(lengths, dtype=np.int32)[:, None] - bk
                     + np.arange(bk, dtype=np.int32)[None, :])
        return self.forward(tokens, positions, page_table, lengths,
                            quota=quota)

    def forward_full(self, tokens, chunk=None):
        """No-cache full-recompute oracle: run the whole (B, L) prefix
        through scratch pages and return the next-token logits. Frees
        its pages before returning — the O(n²) baseline path. With
        ``chunk`` (an engine with ``chunked_prefill``) a prefix longer
        than ``chunk`` goes through ``chunk`` tokens at a time, as the
        server feeds a prompt longer than its largest bucket. An engine
        with ``state_slots`` takes a scratch slot a row beside the pages
        and frees it with them."""
        tokens = np.asarray(tokens, dtype=np.int32)
        b, l = tokens.shape
        owners = [object() for _ in range(b)]
        table = np.zeros((b, self.pool.pages_for(l)), dtype=np.int32)
        state = self.pool.state_slots if self.state_slots else None
        try:
            for i, o in enumerate(owners):
                table[i] = self.pool.alloc(o, l)
            slots = None if state is None else np.asarray(
                [state.alloc(o) for o in owners], np.int32)
            step = l if chunk is None or l <= chunk else chunk
            for off in range(0, l, step):
                n = min(step, l - off)
                part = np.zeros((b, step), dtype=np.int32)
                part[:, :n] = tokens[:, off:off + n]
                self.prefill(part, np.full((b,), off + n, dtype=np.int32),
                             table, np.full((b,), off, dtype=np.int32)
                             if off else None, slots,
                             None if slots is None
                             else np.full((b,), off + n == l))
            return self.last_logits()
        finally:
            for o in owners:
                self.pool.free(o)
                if state is not None:
                    state.free(o)

    def apply_defrag(self, moves) -> None:
        """Replay :meth:`PagePool.defrag` page moves onto this engine's
        arenas — called by the serving scheduler between decode steps,
        BEFORE any dispatch reads the renumbered page tables. In a
        multi-tenant server every engine replays the SAME global
        permutation (the pool's accounting is shared), so a page another
        tenant owns moves its (garbage, for this engine) slots too —
        harmless, and it keeps every arena consistent with the one page
        numbering."""
        from .kvcache import apply_defrag

        self.arenas = [apply_defrag(a, moves) for a in self.arenas]
