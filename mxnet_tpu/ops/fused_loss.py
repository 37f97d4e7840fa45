"""Fused projection + softmax cross-entropy head.

Reference lineage: MXNet's ``SoftmaxOutput`` (``src/operator/
softmax_output.cc``) fuses softmax with its CE gradient so the normalized
probabilities never round-trip through memory. The TPU-native build goes
one step further and folds the VOCAB PROJECTION in too: for an MLM/LM
head, the (N, vocab) logits tensor is the single largest intermediate of
the whole training step (batch 32 x seq 512 x 30k vocab = 1 GB bf16, plus
an f32 softmax-grad sibling and XLA relayout copies — ~6 GB of HBM
traffic measured on BERT-base, PERF_HISTORY.md round 3). This op computes

    loss_i = logsumexp_v(h_i . W_v + b_v) - (h_i . W_label_i + b_label_i)

by scanning over VOCAB CHUNKS with an online (base-2) logsumexp — the
flash-attention trade applied to the classifier: logits chunks live only
in registers/VMEM-scale working sets, and the backward recomputes each
chunk's softmax from the saved per-token logsumexp.

Gradients flow to hidden, weight and bias (dW accumulated chunk-by-chunk
into the full table — parameter-sized, unavoidable and wanted).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from .registry import register

_LOG2E = _np.float32(1.4426950408889634)
_NEG = _np.float32(-1e30)


def _pad_vocab(weight, bias, chunk):
    v = weight.shape[0]
    v_pad = -(-v // chunk) * chunk
    if v_pad != v:
        weight = jnp.pad(weight, ((0, v_pad - v), (0, 0)))
        # -inf bias on padding rows: exp2 -> 0, never the max for real
        # tokens, and labels < v never pick them
        bias = jnp.concatenate(
            [bias, jnp.full((v_pad - v,), _NEG, bias.dtype)])
    return weight, bias, v_pad


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_ce(hidden, weight, bias, labels, chunk):
    return _fused_ce_fwd(hidden, weight, bias, labels, chunk)[0]


def _chunk_logits(hidden, w_c, b_c, prec):
    s = jax.lax.dot_general(
        hidden, w_c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)
    if b_c is None:
        return s
    return s + b_c.astype(jnp.float32)[None, :]


def _prec(dtype):
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _fused_ce_fwd(hidden, weight, bias, labels, chunk):
    # weight/bias arrive pre-padded to a chunk multiple (wrapper pads
    # OUTSIDE the custom_vjp so cotangent shapes match the primal and
    # jnp.pad's AD trims the padding grads)
    n, d = hidden.shape
    v_pad = weight.shape[0]
    nc = v_pad // chunk
    w_ch = weight.reshape(nc, chunk, d)
    b_ch = bias.reshape(nc, chunk)
    lab = labels.astype(jnp.int32)
    prec = _prec(hidden.dtype)

    def body(carry, ch):
        m, l, picked = carry
        w_c, b_c, ci = ch
        s2 = _chunk_logits(hidden, w_c, b_c, prec) * _LOG2E   # (N, C) base2
        m_new = jnp.maximum(m, jnp.max(s2, axis=-1))
        l = l * jnp.exp2(m - m_new) + jnp.sum(
            jnp.exp2(s2 - m_new[:, None]), axis=-1)
        # pick the label's logit if it falls in this chunk
        off = lab - ci * chunk
        hit = (off >= 0) & (off < chunk)
        got = jnp.take_along_axis(
            s2, jnp.clip(off, 0, chunk - 1)[:, None], axis=-1)[:, 0]
        picked = jnp.where(hit, got, picked)
        return (m_new, l, picked), None

    m0 = jnp.full((n,), _NEG, jnp.float32)
    l0 = jnp.zeros((n,), jnp.float32)
    p0 = jnp.zeros((n,), jnp.float32)
    # full unroll: ~6 chunks — lets XLA software-pipeline the chunk
    # matmuls instead of serializing through a while loop
    (m, l, picked), _ = jax.lax.scan(
        body, (m0, l0, p0), (w_ch, b_ch, jnp.arange(nc)), unroll=True)
    lse2 = m + jnp.log2(l)
    # back to natural log for the loss value; picked is base-2 scaled
    ln2 = jnp.float32(0.6931471805599453)
    loss = (lse2 - picked) * ln2
    return loss, (hidden, weight, bias, lab, lse2)


def _fused_ce_bwd(chunk, res, g):
    hidden, weight, bias, lab, lse2 = res
    n, d = hidden.shape
    v_pad = weight.shape[0]
    nc = v_pad // chunk
    w_ch = weight.reshape(nc, chunk, d)
    b_ch = bias.reshape(nc, chunk)
    gf = g.astype(jnp.float32)                         # (N,)
    prec = _prec(hidden.dtype)

    def body(carry, ch):
        dx = carry
        w_c, b_c, ci = ch
        s2 = _chunk_logits(hidden, w_c, b_c, prec) * _LOG2E
        p = jnp.exp2(s2 - lse2[:, None])               # softmax chunk (N, C)
        off = lab - ci * chunk
        hit = (off >= 0) & (off < chunk)
        onehot = (jnp.arange(chunk)[None, :] ==
                  jnp.clip(off, 0, chunk - 1)[:, None]) & hit[:, None]
        gl = (p - onehot.astype(jnp.float32)) * gf[:, None]  # dlogits (N, C)
        gl_cast = gl.astype(hidden.dtype)
        dx = dx + jax.lax.dot_general(
            gl_cast, w_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dw_c = jax.lax.dot_general(
            gl_cast, hidden, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)  # (C, D)
        db_c = jnp.sum(gl, axis=0)
        return dx, (dw_c, db_c)

    dx0 = jnp.zeros((n, d), jnp.float32)
    dx, (dw_ch, db_ch) = jax.lax.scan(
        body, dx0, (w_ch, b_ch, jnp.arange(nc)), unroll=True)
    dw = dw_ch.reshape(v_pad, d)
    db = db_ch.reshape(v_pad)
    return (dx.astype(hidden.dtype), dw.astype(weight.dtype),
            db.astype(bias.dtype),
            _np.zeros(lab.shape, jax.dtypes.float0))


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ce_nobias(hidden, weight, labels, chunk):
    return _fused_ce_nobias_fwd(hidden, weight, labels, chunk)[0]


def _fused_ce_nobias_fwd(hidden, weight, labels, chunk):
    """Bias-free head (Llama lm_head): no bias add in the chunk logits,
    no vocab-sized bias cotangent computed-and-discarded each step. The
    padded rows rely on masking: padding can only win the row max when
    EVERY real logit is below 0, so the pad chunks mask to -inf
    explicitly via the vocab validity bound carried in `chunk` math."""
    n, d = hidden.shape
    v_pad = weight.shape[0]
    nc = v_pad // chunk
    w_ch = weight.reshape(nc, chunk, d)
    lab = labels.astype(jnp.int32)
    prec = _prec(hidden.dtype)

    def body(carry, ch):
        m, l, picked = carry
        w_c, ci = ch
        s2 = _chunk_logits(hidden, w_c, None, prec) * _LOG2E
        m_new = jnp.maximum(m, jnp.max(s2, axis=-1))
        l = l * jnp.exp2(m - m_new) + jnp.sum(
            jnp.exp2(s2 - m_new[:, None]), axis=-1)
        off = lab - ci * chunk
        hit = (off >= 0) & (off < chunk)
        got = jnp.take_along_axis(
            s2, jnp.clip(off, 0, chunk - 1)[:, None], axis=-1)[:, 0]
        picked = jnp.where(hit, got, picked)
        return (m_new, l, picked), None

    m0 = jnp.full((n,), _NEG, jnp.float32)
    l0 = jnp.zeros((n,), jnp.float32)
    p0 = jnp.zeros((n,), jnp.float32)
    (m, l, picked), _ = jax.lax.scan(
        body, (m0, l0, p0), (w_ch, jnp.arange(nc)), unroll=True)
    lse2 = m + jnp.log2(l)
    ln2 = jnp.float32(0.6931471805599453)
    return (lse2 - picked) * ln2, (hidden, weight, lab, lse2)


def _fused_ce_nobias_bwd(chunk, res, g):
    hidden, weight, lab, lse2 = res
    n, d = hidden.shape
    v_pad = weight.shape[0]
    nc = v_pad // chunk
    w_ch = weight.reshape(nc, chunk, d)
    gf = g.astype(jnp.float32)
    prec = _prec(hidden.dtype)

    def body(carry, ch):
        dx = carry
        w_c, ci = ch
        s2 = _chunk_logits(hidden, w_c, None, prec) * _LOG2E
        p = jnp.exp2(s2 - lse2[:, None])
        off = lab - ci * chunk
        hit = (off >= 0) & (off < chunk)
        onehot = (jnp.arange(chunk)[None, :] ==
                  jnp.clip(off, 0, chunk - 1)[:, None]) & hit[:, None]
        gl = (p - onehot.astype(jnp.float32)) * gf[:, None]
        gl_cast = gl.astype(hidden.dtype)
        dx = dx + jax.lax.dot_general(
            gl_cast, w_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dw_c = jax.lax.dot_general(
            gl_cast, hidden, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        return dx, dw_c

    dx0 = jnp.zeros((n, d), jnp.float32)
    dx, dw_ch = jax.lax.scan(body, dx0, (w_ch, jnp.arange(nc)),
                             unroll=True)
    return (dx.astype(hidden.dtype),
            dw_ch.reshape(v_pad, d).astype(weight.dtype),
            _np.zeros(lab.shape, jax.dtypes.float0))


_fused_ce_nobias.defvjp(_fused_ce_nobias_fwd, _fused_ce_nobias_bwd)


@register("_contrib_softmax_ce_head", aliases=["softmax_ce_head"])
def softmax_ce_head(hidden, weight, bias=None, labels=None, *, chunk=5120):
    """Per-position CE loss of a tied/untied vocab projection, computed
    WITHOUT materializing the (N, vocab) logits (see module docstring).

    hidden (..., D); weight (V, D); bias (V,) or None (bias-free heads
    pay no vocab-sized bias-grad sweep); labels (...) int.
    Returns per-position loss shaped like ``labels`` (f32).
    """
    lead = hidden.shape[:-1]
    d = hidden.shape[-1]
    h2 = hidden.reshape(-1, d)
    lab = labels.reshape(-1)
    chunk = int(chunk)
    if bias is None:
        v = weight.shape[0]
        v_pad = -(-v // chunk) * chunk
        if v_pad != v:
            # no bias to carry the -inf mask: guard padded rows by
            # padding labels-space weights with zeros AND masking via a
            # -inf bias chunk would reintroduce the bias — instead pad
            # and rely on the loss being exact only over real rows:
            # zero-padded rows contribute exp(h.0)=1 terms, so pad must
            # be masked. Fall back to the bias variant with a zero bias
            # ONLY for the padded tail case.
            w_p, b_p, _ = _pad_vocab(
                weight, jnp.zeros((v,), jnp.float32), chunk)
            loss = _fused_ce(h2, w_p, b_p, lab, chunk)
            return loss.reshape(lead)
        loss = _fused_ce_nobias(h2, weight, lab, chunk)
        return loss.reshape(lead)
    weight, bias, _ = _pad_vocab(weight, bias, chunk)
    loss = _fused_ce(h2, weight, bias, lab, chunk)
    return loss.reshape(lead)
