"""Contrib operators.

Reference: ``src/operator/contrib/`` — ``transformer.cc`` (interleaved
attention matmuls used by GluonNLP BERT), ``gelu`` (via LeakyReLU gelu),
``adamw.cc`` (in optimizer_op.py here), ``index_copy.cc``, ``roi_align.cc``.

The fused attention ops are implemented as single jit-able compositions;
on TPU the flash-attention Pallas kernel in ``mxnet_tpu/ops/attention.py``
supersedes them for long sequences (SURVEY.md §5.7).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register


@register("_contrib_div_sqrt_dim", aliases=["div_sqrt_dim"])
def div_sqrt_dim(data):
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], dtype=data.dtype))


@register("_contrib_gelu")
def gelu_op(data):
    return jax.nn.gelu(data, approximate=False)


@register("_contrib_interleaved_matmul_selfatt_qk")
def interleaved_matmul_selfatt_qk(queries_keys_values, *, heads=1):
    """reference: src/operator/contrib/transformer.cc ::
    InterleavedMatMulSelfAttQK — input (seq, batch, 3*proj) with q/k/v
    interleaved per head; output (batch*heads, seq, seq) of scaled q·kᵀ."""
    seq, batch, _ = queries_keys_values.shape
    x = queries_keys_values.reshape(seq, batch, heads, 3, -1)
    q = x[:, :, :, 0]  # (seq, batch, heads, head_dim)
    k = x[:, :, :, 1]
    head_dim = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, dtype=q.dtype))
    qk = jnp.einsum("sbhd,tbhd->bhst", q * scale, k)
    return qk.reshape(batch * heads, seq, seq)


@register("_contrib_interleaved_matmul_selfatt_valatt")
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, *, heads=1):
    seq, batch, _ = queries_keys_values.shape
    x = queries_keys_values.reshape(seq, batch, heads, 3, -1)
    v = x[:, :, :, 2]  # (seq, batch, heads, head_dim)
    att = attention.reshape(batch, heads, seq, seq)
    out = jnp.einsum("bhst,tbhd->sbhd", att, v)
    return out.reshape(seq, batch, -1)


@register("_contrib_interleaved_matmul_encdec_qk")
def interleaved_matmul_encdec_qk(queries, keys_values, *, heads=1):
    qseq, batch, _ = queries.shape
    kseq = keys_values.shape[0]
    q = queries.reshape(qseq, batch, heads, -1)
    kv = keys_values.reshape(kseq, batch, heads, 2, -1)
    k = kv[:, :, :, 0]
    head_dim = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, dtype=q.dtype))
    qk = jnp.einsum("sbhd,tbhd->bhst", q * scale, k)
    return qk.reshape(batch * heads, qseq, kseq)


@register("_contrib_interleaved_matmul_encdec_valatt")
def interleaved_matmul_encdec_valatt(keys_values, attention, *, heads=1):
    kseq, batch, _ = keys_values.shape
    kv = keys_values.reshape(kseq, batch, heads, 2, -1)
    v = kv[:, :, :, 1]
    qseq = attention.shape[1]
    att = attention.reshape(batch, heads, qseq, kseq)
    out = jnp.einsum("bhst,tbhd->sbhd", att, v)
    return out.reshape(qseq, batch, -1)


@register("_contrib_index_copy", aliases=["index_copy"])
def index_copy(old_tensor, index_vector, new_tensor):
    # reference: src/operator/contrib/index_copy.cc — rows of old_tensor
    # at index_vector replaced by rows of new_tensor
    return old_tensor.at[index_vector.astype(jnp.int32)].set(
        new_tensor.astype(old_tensor.dtype))


@register("_contrib_index_array", aliases=["index_array"])
def index_array(data, *, axes=None):
    shape = data.shape
    axes_ = tuple(axes) if axes else tuple(range(len(shape)))
    grids = jnp.meshgrid(*[jnp.arange(shape[a]) for a in range(len(shape))], indexing="ij")
    sel = jnp.stack([grids[a] for a in axes_], axis=-1)
    return sel.astype(jnp.int64)


@register("_contrib_ROIAlign", aliases=["ROIAlign"])
def roi_align(data, rois, *, pooled_size=(7, 7), spatial_scale=1.0,
              sample_ratio=-1, position_sensitive=False, aligned=False):
    """reference: src/operator/contrib/roi_align.cc — bilinear ROI pooling.
    Vectorized gather-based implementation (jit-friendly, static shapes)."""
    n, c, h, w = data.shape
    num_rois = rois.shape[0]
    ph, pw = pooled_size
    sratio = sample_ratio if sample_ratio > 0 else 2
    offset = 0.5 if aligned else 0.0
    batch_idx = rois[:, 0].astype(jnp.int32)
    x1 = rois[:, 1] * spatial_scale - offset
    y1 = rois[:, 2] * spatial_scale - offset
    x2 = rois[:, 3] * spatial_scale - offset
    y2 = rois[:, 4] * spatial_scale - offset
    roi_w = jnp.maximum(x2 - x1, 1.0 if not aligned else 1e-6)
    roi_h = jnp.maximum(y2 - y1, 1.0 if not aligned else 1e-6)
    bin_w = roi_w / pw
    bin_h = roi_h / ph
    # sample grid: (num_rois, ph, pw, sratio, sratio)
    iy = (jnp.arange(sratio) + 0.5) / sratio
    ix = (jnp.arange(sratio) + 0.5) / sratio
    py = jnp.arange(ph)
    px = jnp.arange(pw)
    ys = y1[:, None, None] + (py[None, :, None] + iy[None, None, :]) * bin_h[:, None, None]
    xs = x1[:, None, None] + (px[None, :, None] + ix[None, None, :]) * bin_w[:, None, None]

    def bilinear(img, yy, xx):
        # img: (c, h, w); yy/xx: (...,)
        y0 = jnp.clip(jnp.floor(yy), 0, h - 1)
        x0 = jnp.clip(jnp.floor(xx), 0, w - 1)
        y1_ = jnp.clip(y0 + 1, 0, h - 1)
        x1_ = jnp.clip(x0 + 1, 0, w - 1)
        wy1 = jnp.clip(yy - y0, 0.0, 1.0)
        wx1 = jnp.clip(xx - x0, 0.0, 1.0)
        y0i, x0i, y1i, x1i = (a.astype(jnp.int32) for a in (y0, x0, y1_, x1_))
        v00 = img[:, y0i, x0i]
        v01 = img[:, y0i, x1i]
        v10 = img[:, y1i, x0i]
        v11 = img[:, y1i, x1i]
        return (v00 * (1 - wy1) * (1 - wx1) + v01 * (1 - wy1) * wx1
                + v10 * wy1 * (1 - wx1) + v11 * wy1 * wx1)

    def per_roi(b, ys_r, xs_r):
        img = data[b]  # (c,h,w)
        yy = ys_r[:, None, :, None]  # (ph,1,sr,1)
        xx = xs_r[None, :, None, :]  # (1,pw,1,sr)
        yy = jnp.broadcast_to(yy, (ph, pw, sratio, sratio))
        xx = jnp.broadcast_to(xx, (ph, pw, sratio, sratio))
        vals = bilinear(img, yy, xx)  # (c, ph, pw, sr, sr)
        return jnp.mean(vals, axis=(-1, -2))

    out = jax.vmap(per_roi)(batch_idx, ys, xs)  # (num_rois, c, ph, pw)
    return out


@register("_contrib_quantize_v2")
def quantize_v2(data, *, out_type="int8", min_calib_range=None, max_calib_range=None):
    if min_calib_range is None:
        min_calib_range = float(-1.0)
        max_calib_range = float(1.0)
    scale = 127.0 / jnp.maximum(jnp.abs(min_calib_range), jnp.abs(max_calib_range))
    q = jnp.clip(jnp.round(data * scale), -127, 127).astype(jnp.int8)
    return q, jnp.asarray(min_calib_range, jnp.float32), jnp.asarray(max_calib_range, jnp.float32)


@register("_contrib_dequantize")
def dequantize(data, min_range, max_range, *, out_type="float32"):
    scale = jnp.maximum(jnp.abs(min_range), jnp.abs(max_range)) / 127.0
    return data.astype(jnp.float32) * scale


@register("_contrib_moe_dispatch_combine", aliases=["moe_dispatch_combine"])
def moe_dispatch_combine(tokens, probs, gate_up_weight, down_weight, *,
                         top_k=2, capacity=0):
    """GShard dense dispatch -> per-expert SwiGLU -> combine.

    tokens (N, U); probs (N, E) router softmax; gate_up (E, U, 2H);
    down (E, H, U). Top-k gates renormalized over the selected experts;
    per-expert capacity enforced by position-in-expert cumsum (overflow
    tokens get zero combine weight — GShard semantics). All dense einsums:
    under GSPMD with 'ep'-sharded weights these lower to token all-to-alls
    plus expert-local matmuls on the MXU.
    """
    if capacity < 1:
        raise ValueError(
            f"moe_dispatch_combine requires capacity >= 1, got {capacity} "
            "(capacity 0 would silently drop every token)")
    n, e = probs.shape
    # top-k selection per token
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)       # (N, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)             # renormalize
    # queue counting in int32: a low-precision cumsum (bf16 tokens under
    # AMP) stops incrementing past 256 and collides capacity slots
    sel_i = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)    # (N, K, E)

    # position of each (token, k) within its expert queue, k-major so a
    # token's higher-priority assignment claims capacity first
    flat_i = sel_i.transpose(1, 0, 2).reshape(top_k * n, e)   # (K*N, E)
    pos = jnp.cumsum(flat_i, axis=0) - flat_i                 # pre-count
    keep = pos < capacity
    flat_i = flat_i * keep
    flat_sel = flat_i.astype(tokens.dtype)
    pos_idx = jnp.sum(pos * flat_i, axis=-1)                  # (K*N,)
    cap_oh = jax.nn.one_hot(pos_idx, capacity, dtype=tokens.dtype)
    # dispatch tensor (N, K, E, C) -> fold K: (N, E, C)
    disp = (flat_sel[:, :, None] * cap_oh[:, None, :]).reshape(
        top_k, n, e, capacity)
    gates = gate_vals.transpose(1, 0)[:, :, None, None]       # (K, N, 1, 1)
    dispatch = disp.sum(0)                                    # (N, E, C)
    combine = (disp * gates).sum(0)                           # (N, E, C)

    expert_in = jnp.einsum("nec,nu->ecu", dispatch, tokens)   # (E, C, U)
    gu = jnp.einsum("ecu,euh->ech", expert_in, gate_up_weight)
    h = gu.shape[-1] // 2
    act = jax.nn.silu(gu[..., :h]) * gu[..., h:]
    expert_out = jnp.einsum("ech,ehu->ecu", act, down_weight)
    return jnp.einsum("nec,ecu->nu", combine, expert_out)


# rows of the grouped matmul's m tile (megablox) on the TPU
_GMM_ROWS = 128
_GMM_TILING = (_GMM_ROWS, 1024, 1024)


def _grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` (M, K), rows sorted by group; ``rhs`` (G, K, N);
    ``group_sizes`` (G,) int32. Row ``i`` of group ``g`` is multiplied by
    ``rhs[g]``; rows past ``sum(group_sizes)`` are NOT computed and hold
    anything. On the TPU this is the megablox grouped-matmul kernel that
    ships with jax (a grid over the (group, m tile) pairs that hold rows,
    so an expert's weights are read once per tile it touches);
    elsewhere ``lax.ragged_dot``."""
    from ..base import current_execution_platform
    from ..parallel.mesh import auto_partitioned

    if current_execution_platform(lhs) == "tpu" and not auto_partitioned() \
            and lhs.shape[0] % _GMM_ROWS == 0:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        from .. import telemetry

        telemetry.record_pallas_dispatch("grouped_matmul")
        # traced in 32-bit mode: under the package's global x64 the
        # kernel's tile count becomes an s64 grid bound, which XLA's TPU
        # pipeline cannot rewrite
        with jax.enable_x64(False):
            return gmm(lhs, rhs, group_sizes,
                       preferred_element_type=lhs.dtype, tiling=_GMM_TILING)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


@register("_contrib_moe_routed_experts", aliases=["moe_routed_experts"],
          num_outputs=2)
def moe_routed_experts(tokens, router_weight, router_bias, gate_up_weight,
                       down_weight, valid=None, *, first_held=0,
                       n_routed, n_zero=0, top_k=1, scale=1.0,
                       rows_per_pass=0, score="softmax",
                       renormalize=False, n_group=1, topk_group=1):
    """One chip's share of a routed expert layer, dropless.

    ``tokens`` (N, U); ``router_weight`` (n_routed + n_zero, U) in
    ``Dense`` layout: ONE router over every routed expert of the
    deployment and the ``n_zero`` identity ("zero-compute") experts
    behind them; ``router_bias`` (n_routed + n_zero,) the selection
    bias (it picks, it does not weigh); ``gate_up_weight`` (held, U, 2H)
    and ``down_weight`` (held, H, U): the SwiGLU experts
    ``first_held .. first_held + held - 1`` that live here; ``valid``
    (N,) bool, padding tokens route nowhere.

    ``p = softmax(W_r x)`` in float32 (``score="sigmoid"``: each output
    on its own, ``sigmoid(W_r x)``), the ``top_k`` largest of
    ``p + bias`` are picked, each weighs ``scale * p`` (``renormalize``:
    ``scale * p / sum of the picked p``). The result is the held
    experts' part plus the whole zero-expert part (identity: ``w * x``,
    which the token's own chip adds); what the absent experts would add
    is left out.

    ``n_group`` > 1: the pick is GROUP-LIMITED (DeepSeek-V3's
    ``noaux_tc``): the outputs lie in ``n_group`` equal groups, a group
    scores the sum of its 2 largest ``p + bias``, the ``topk_group`` best
    groups stay and the ``top_k`` are picked among their experts only.

    Held (token, expert) pairs are sorted by expert and multiplied
    ``rows_per_pass`` rows at a time (default: N rounded up to the
    kernel's tile, four times the mean load when 1/64 of the router's
    outputs live here) in as many passes as the routing needs, so no
    pair is ever dropped and the work follows the pairs routed here. No
    tensor is wider than the (N, experts) scores.

    Returns ``(out (N, U), counts (4,) int32)``: picks to held, to
    zero and to absent experts, and the held experts that got a token.
    """
    n = tokens.shape[0]
    n_held = gate_up_weight.shape[0]
    hidden = down_weight.shape[1]
    f32 = jnp.float32
    with jax.named_scope("moe.router"):
        logits = jnp.einsum("nu,eu->ne", tokens, router_weight,
                            preferred_element_type=f32)
        if score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
        elif score == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"moe_routed_experts: score {score!r} is "
                             "neither 'softmax' nor 'sigmoid'")
        choice = probs + router_bias.astype(f32)
        if n_group > 1:
            grouped = choice.reshape(n, n_group, -1)
            best2, _ = jax.lax.top_k(grouped, 2)
            _, keep = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
            kept = jnp.any(keep[:, :, None]
                           == jnp.arange(n_group)[None, None, :], axis=1)
            choice = jnp.where(kept[:, :, None], grouped,
                               -jnp.inf).reshape(n, -1)
        _, idx = jax.lax.top_k(choice, top_k)
        picked = jnp.take_along_axis(probs, idx, axis=-1)
        if renormalize:
            picked = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                               + f32(1e-20))
        weight = f32(scale) * picked
        if valid is not None:
            idx = jnp.where(valid[:, None], idx, -1)
        local = idx - first_held
        held = (local >= 0) & (local < n_held)
        zero = idx >= n_routed
        # the held pairs in expert order, by token within an expert:
        # each pair's row is its expert's first row plus its rank among
        # that expert's pairs (a running count; a sort of N x top_k keys
        # takes the TPU compiler 20 s and more per program)
        key = jnp.where(held, local, n_held).reshape(-1)
        mine = key[:, None] == jnp.arange(n_held)[None, :]   # (N*K, held)
        running = jnp.cumsum(mine, axis=0, dtype=jnp.int32)
        sizes = running[-1]
        ends = jnp.cumsum(sizes)
        total = ends[-1]
        row = jnp.sum(jnp.where(mine, running - 1 + (ends - sizes), 0),
                      axis=1)
        row = jnp.where(key < n_held, row, n * top_k)        # not held
        order = jnp.zeros((n * top_k,), jnp.int32).at[row].set(
            jnp.arange(n * top_k, dtype=jnp.int32), mode="drop",
            unique_indices=True)
        n_zero_picks = jnp.sum(zero, dtype=jnp.int32)
        n_real = (jnp.sum(valid, dtype=jnp.int32) if valid is not None
                  else jnp.int32(n))
        counts = jnp.stack([total, n_zero_picks,
                            n_real * top_k - total - n_zero_picks,
                            jnp.sum(sizes > 0, dtype=jnp.int32)])
    if n_zero:
        with jax.named_scope("moe.zero"):
            out = jnp.sum(jnp.where(zero, weight, 0.0), axis=-1,
                          keepdims=True) * tokens.astype(f32)
    else:
        out = jnp.zeros(tokens.shape, f32)
    rows = int(rows_per_pass) or -(-n // _GMM_ROWS) * _GMM_ROWS
    flat_w = weight.reshape(-1)

    def one_pass(carry):
        p, acc = carry
        start = p * rows
        pos = start + jnp.arange(rows, dtype=jnp.int32)
        pair = jnp.take(order, jnp.minimum(pos, n * top_k - 1))
        tok = pair // top_k
        live = pos < total
        # this pass's slice of every expert's rows
        g = jnp.clip(ends - start, 0, rows)
        g = g - jnp.concatenate([jnp.zeros((1,), g.dtype), g[:-1]])
        x = jnp.take(tokens, tok, axis=0)
        gu = _grouped_matmul(x, gate_up_weight, g)
        act = jax.nn.silu(gu[:, :hidden]) * gu[:, hidden:]
        y = _grouped_matmul(act, down_weight, g)
        w = jnp.where(live, jnp.take(flat_w, pair), 0.0)
        y = jnp.where(live[:, None], y.astype(f32), 0.0) * w[:, None]
        return p + 1, acc.at[tok].add(y)

    with jax.named_scope("moe.experts"):
        _, out = jax.lax.while_loop(lambda c: c[0] * rows < total,
                                    one_pass, (jnp.int32(0), out))
    return out.astype(tokens.dtype), counts


def _fake_quant_act(data, min_calib_range, max_calib_range):
    """Snap activations onto the symmetric int8 grid — calibrated range
    when given, dynamic (per-batch max) otherwise. Values stay exactly on
    the grid, so downstream f32 math reproduces integer arithmetic.
    Derived from _quantize_act_s8 so the oracle and the s8 MXU path snap
    identically by construction."""
    codes, s = _quantize_act_s8(data, min_calib_range, max_calib_range)
    return codes.astype(jnp.float32) / s


def _quantize_act_s8(data, min_calib_range, max_calib_range):
    """Integer-domain counterpart of _fake_quant_act: (int8 codes, scale)
    with ``codes = round(clip(x * 127/t)) ; x ~ codes / scale``."""
    if min_calib_range is None:
        t = jnp.max(jnp.abs(data)).astype(jnp.float32) + 1e-12  # dynamic
    else:
        t = jnp.maximum(jnp.float32(abs(float(min_calib_range))),
                        jnp.float32(abs(float(max_calib_range)))) + 1e-12
    s = 127.0 / t
    codes = jnp.clip(jnp.round(data.astype(jnp.float32) * s),
                     -127, 127).astype(jnp.int8)
    return codes, s


def _int8_mxu_enabled():
    """True when quantized ops should run REAL s8 x s8 -> s32 MXU math.

    The v5e MXU's int8 rate is ~2x bf16 (measured 2.7x in the identical
    chained-matmul harness, PERF_HISTORY.md round 3); off-TPU the fake-quant f32
    path stays the oracle. MXNET_INT8_MXU=0 forces the oracle everywhere.
    """
    import os

    from ..base import current_execution_platform

    if os.environ.get("MXNET_INT8_MXU", "1") == "0":
        return False
    return current_execution_platform() == "tpu"


@register("_contrib_quantized_dense")
def quantized_dense(data, weight_q, w_scale, bias=None, *, num_hidden,
                    no_bias=False, flatten=True,
                    min_calib_range=None, max_calib_range=None):
    """Int8-weight dense (reference capability: quantization.py::
    quantize_model int8 inference).

    On TPU the GEMM is REAL s8 x s8 -> s32 on the MXU (int8 runs ~2x the
    bf16 rate), rescaled by ``w_scale / act_scale`` per output channel.
    Elsewhere the fake-quant f32 path computes numerically identical
    results (both operand sets sit exactly on the int8 grid, and the f32
    MXU matmul reproduces the integer arithmetic up to f32 summation,
    which the shared tolerance tests pin).
    """
    from .registry import get_op

    if _int8_mxu_enabled():
        xq, s_x = _quantize_act_s8(data, min_calib_range, max_calib_range)
        if flatten and xq.ndim > 2:
            xq = xq.reshape(xq.shape[0], -1)
        acc = jax.lax.dot_general(
            xq, weight_q, (((xq.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)          # (..., num_hidden)
        out = acc.astype(jnp.float32) * (w_scale / s_x)
        if not (no_bias or bias is None):
            out = out + bias.astype(jnp.float32)
        return out  # f32, matching the oracle path's output dtype

    xq = _fake_quant_act(data, min_calib_range, max_calib_range)
    w = weight_q.astype(jnp.float32) * w_scale[:, None]
    return get_op("FullyConnected").fn(
        xq, w, bias, num_hidden=num_hidden,
        no_bias=no_bias or bias is None, flatten=flatten)


def _calib_t(min_calib, max_calib, who):
    """Symmetric int8 threshold from python-float calib bounds; both
    bounds required, loud error naming the op otherwise."""
    if min_calib is None or max_calib is None:
        raise ValueError(
            f"{who}: min and max calibration bounds are both required "
            "for the int8 grid")
    return max(abs(float(min_calib)), abs(float(max_calib))) + 1e-12


def _requant_out(out_f32, out_min_calib, out_max_calib):
    """Fused requantize of a layer's f32-scaled result onto the int8 grid
    of its calibrated OUTPUT range — elementwise, so XLA folds it into
    the conv/dense epilogue and the inter-layer tensor in HBM is int8.
    Returns (codes, -t, t)."""
    t = jnp.float32(_calib_t(out_min_calib, out_max_calib,
                             "quantized out_type='int8'"))
    codes = jnp.clip(jnp.round(out_f32 * (127.0 / t)),
                     -127, 127).astype(jnp.int8)
    return codes, jnp.float32(-t), jnp.float32(t)


@register("_contrib_quantized_conv")
def quantized_conv(data, weight_q, w_scale, bias=None, *, kernel,
                   num_filter, stride=None, pad=None, dilate=None,
                   num_group=1, no_bias=False, layout=None,
                   min_calib_range=None, max_calib_range=None,
                   out_type="float32", out_min_calib=None,
                   out_max_calib=None):
    """Int8-weight convolution; on TPU the conv itself runs s8 x s8 ->
    s32 (see quantized_dense), elsewhere fake-quant f32.

    ``out_type='int8'`` (requires ``out_min_calib``/``out_max_calib``)
    fuses the requantize: returns (int8 codes, min, max) so the next
    quantized op consumes codes directly — the int8-end-to-end trunk
    path (reference: quantized conv + requantize fusion). ``data`` may
    then itself be int8 codes with ``min/max_calib_range`` as their
    range."""
    from .registry import get_op

    if _int8_mxu_enabled():
        from .nn import _conv_dnums, _channel_axis, _tuplize

        nd = len(kernel)
        if data.dtype == jnp.int8:
            # already codes (previous layer's int8 output)
            xq = data
            s_x = 127.0 / jnp.float32(_calib_t(
                min_calib_range, max_calib_range, "quantized_conv"))
        else:
            xq, s_x = _quantize_act_s8(data, min_calib_range,
                                       max_calib_range)
        acc = jax.lax.conv_general_dilated(
            xq, weight_q,
            window_strides=_tuplize(stride or 1, nd),
            padding=[(p, p) for p in _tuplize(pad or 0, nd)],
            rhs_dilation=_tuplize(dilate or 1, nd),
            dimension_numbers=_conv_dnums(nd, layout),
            feature_group_count=num_group,
            preferred_element_type=jnp.int32)
        c_ax = _channel_axis(layout, acc.ndim)
        sshape = [1] * acc.ndim
        sshape[c_ax] = w_scale.shape[0]
        out = acc.astype(jnp.float32) * (w_scale.reshape(sshape) / s_x)
        if not (no_bias or bias is None):
            out = out + bias.astype(jnp.float32).reshape(sshape)
        if out_type == "int8":
            return _requant_out(out, out_min_calib, out_max_calib)
        return out  # f32, matching the oracle path's output dtype

    if data.dtype == jnp.int8:
        t_in = jnp.float32(_calib_t(min_calib_range, max_calib_range,
                                    "quantized_conv"))
        xq = data.astype(jnp.float32) * (t_in / 127.0)
    else:
        xq = _fake_quant_act(data, min_calib_range, max_calib_range)
    scale = w_scale.reshape((-1,) + (1,) * (weight_q.ndim - 1))
    w = weight_q.astype(jnp.float32) * scale
    out = get_op("Convolution").fn(
        xq, w, bias, kernel=kernel, num_filter=num_filter, stride=stride,
        pad=pad, dilate=dilate, num_group=num_group, layout=layout,
        no_bias=no_bias or bias is None)
    if out_type == "int8":
        return _requant_out(out.astype(jnp.float32), out_min_calib,
                            out_max_calib)
    return out


@register("_contrib_requantize", num_outputs=3)
def requantize(data, min_range, max_range, *, out_type="int8",
               min_calib_range=None, max_calib_range=None):
    """int32 accumulator -> int8 codes (reference:
    src/operator/quantization/requantize-inl.h). ``min_range``/
    ``max_range`` describe the real-valued span of the s32 input; the
    output grid uses the calibrated range when given, else the input's.
    Pure elementwise rescale — XLA fuses it into the producing matmul's
    epilogue, so no f32 tensor ever materializes in HBM."""
    if out_type != "int8":
        raise ValueError("requantize: only int8 output is supported")
    in_t = _q8_range(min_range, max_range)
    if min_calib_range is not None or max_calib_range is not None:
        t = jnp.float32(_calib_t(min_calib_range, max_calib_range,
                                 "requantize"))
    else:
        t = in_t
    # s32 codes represent x = codes * in_t / (2^31 - 1)
    scale = (in_t / jnp.float32(2147483647.0)) * (127.0 / t)
    codes = jnp.clip(jnp.round(data.astype(jnp.float32) * scale),
                     -127, 127).astype(jnp.int8)
    return codes, -t, t


def _q8_range(min_r, max_r):
    t = jnp.maximum(jnp.abs(jnp.asarray(min_r, jnp.float32)),
                    jnp.abs(jnp.asarray(max_r, jnp.float32)))
    return t + 1e-12


@register("_contrib_quantized_pooling", num_outputs=3)
def quantized_pooling(data, min_data, max_data, *, kernel=None, pool_type="max",
                      global_pool=False, stride=None, pad=None,
                      pooling_convention="valid", layout=None, count_include_pad=True):
    """Pooling on int8 codes (reference: src/operator/quantization/
    quantized_pooling.cc). Max pooling is exact on codes (monotonic);
    avg pooling accumulates in s32 and rounds back onto the SAME grid, so
    the (min, max) range passes through unchanged and the trunk stays
    int8 — no dequantize between a quantized conv and its pool."""
    from .registry import get_op

    pool = get_op("Pooling").fn
    if pool_type == "max":
        out = pool(data.astype(jnp.int32), kernel=kernel, pool_type="max",
                   global_pool=global_pool, stride=stride, pad=pad,
                   pooling_convention=pooling_convention, layout=layout,
                   count_include_pad=count_include_pad).astype(jnp.int8)
    elif pool_type == "avg":
        # f32 mean of codes, rounded back to the code grid (the codes are
        # small ints, so f32 holds them exactly; XLA fuses the chain)
        out = jnp.clip(jnp.round(pool(
            data.astype(jnp.float32), kernel=kernel, pool_type="avg",
            global_pool=global_pool, stride=stride, pad=pad,
            pooling_convention=pooling_convention, layout=layout,
            count_include_pad=count_include_pad)), -127, 127).astype(jnp.int8)
    else:
        raise ValueError(
            f"quantized_pooling: pool_type {pool_type!r} not supported "
            "(reference supports max/avg)")
    return out, min_data, max_data


@register("_contrib_quantized_concat", variadic=True, num_outputs=3)
def quantized_concat(*args, dim=1, num_args=None):
    """Concat int8 tensors (reference: src/operator/quantization/
    quantized_concat.cc). Inputs arrive as ``x0..xn-1, min0, max0, ...``;
    inputs whose ranges differ are REQUANTIZED onto the widest range
    (codes scale by t_i / t_out) so one grid covers the result."""
    n = num_args if num_args is not None else len(args) // 3
    data = args[:n]
    mins = args[n::2][:n]
    maxs = args[n + 1::2][:n]
    ts = [_q8_range(mn, mx) for mn, mx in zip(mins, maxs)]
    t_out = ts[0]
    for t in ts[1:]:
        t_out = jnp.maximum(t_out, t)
    parts = []
    for x, t in zip(data, ts):
        scale = t / t_out
        parts.append(jnp.clip(jnp.round(x.astype(jnp.float32) * scale),
                              -127, 127).astype(jnp.int8))
    return jnp.concatenate(parts, axis=dim), -t_out, t_out


@register("_contrib_quantized_elemwise_add", num_outputs=3)
def quantized_elemwise_add(lhs, rhs, min_lhs, max_lhs, min_rhs, max_rhs, *,
                           min_calib_range=None, max_calib_range=None):
    """Residual add on int8 codes (reference: src/operator/quantization/
    quantized_elemwise_add.cc — the op that keeps ResNet skip
    connections int8). Each side rescales onto the OUTPUT grid (the
    calibrated range when given, else the sum of the input ranges so the
    result cannot clip), accumulating in f32 inside the fused epilogue;
    only int8 codes cross HBM."""
    t_l = _q8_range(min_lhs, max_lhs)
    t_r = _q8_range(min_rhs, max_rhs)
    if min_calib_range is not None or max_calib_range is not None:
        t = jnp.float32(_calib_t(min_calib_range, max_calib_range,
                                 "quantized_elemwise_add"))
    else:
        t = t_l + t_r
    acc = (lhs.astype(jnp.float32) * (t_l / 127.0)
           + rhs.astype(jnp.float32) * (t_r / 127.0))
    codes = jnp.clip(jnp.round(acc * (127.0 / t)),
                     -127, 127).astype(jnp.int8)
    return codes, -t, t


@register("_contrib_quantized_flatten", num_outputs=3)
def quantized_flatten(data, min_data, max_data):
    """Flatten int8 codes; range passes through (reference:
    src/operator/quantization/quantized_flatten.cc)."""
    return data.reshape(data.shape[0], -1), min_data, max_data


@register("_contrib_quadratic", aliases=["quadratic"])
def quadratic(data, *, a=0.0, b=0.0, c=0.0):
    # reference: src/operator/contrib/quadratic_op.cc (the tutorial op)
    return a * data * data + b * data + c


@register("_contrib_allclose", aliases=["allclose_op"])
def allclose_op(a, b, *, rtol=1e-5, atol=1e-8, equal_nan=True):
    # reference: src/operator/contrib/allclose_op.cc — 1 if all close
    return jnp.all(jnp.isclose(a, b, rtol=rtol, atol=atol,
                               equal_nan=equal_nan)).astype(jnp.float32)


@register("_contrib_fft", aliases=["fft"])
def fft(data, *, compute_size=128):
    """reference: src/operator/contrib/fft.cc — FFT along the last axis,
    real input, output interleaves (real, imag) doubling the last dim."""
    f = jnp.fft.fft(data.astype(jnp.float32), axis=-1)
    return jnp.stack([f.real, f.imag], axis=-1).reshape(
        data.shape[:-1] + (2 * data.shape[-1],)).astype(jnp.float32)


@register("_contrib_ifft", aliases=["ifft"])
def ifft(data, *, compute_size=128):
    # inverse of _contrib_fft's interleaved layout; output is the real part
    n = data.shape[-1] // 2
    ri = data.astype(jnp.float32).reshape(data.shape[:-1] + (n, 2))
    comp = ri[..., 0] + 1j * ri[..., 1]
    # reference scales by n on the inverse path (no 1/n normalization)
    return jnp.fft.ifft(comp, axis=-1).real.astype(jnp.float32) * n


@register("_contrib_count_sketch", aliases=["count_sketch"])
def count_sketch(data, h, s, *, out_dim, processing_batch_size=32):
    """reference: src/operator/contrib/count_sketch.cc — random feature
    hashing: out[j] += s[i] * data[i] for h[i] == j (per row)."""
    hi = h.reshape(-1).astype(jnp.int32)
    si = s.reshape(-1).astype(data.dtype)
    vals = data * si[None, :]
    out = jnp.zeros(data.shape[:-1] + (int(out_dim),), dtype=data.dtype)
    return out.at[..., hi].add(vals)


@register("_contrib_AdaptiveAvgPooling2D", aliases=["AdaptiveAvgPooling2D"])
def adaptive_avg_pooling2d(data, *, output_size=()):
    """reference: src/operator/contrib/adaptive_avg_pooling.cc — NCHW
    average pooling onto a fixed output grid with floor/ceil bin edges."""
    if not output_size:
        oh = ow = 1
    elif isinstance(output_size, int):
        oh = ow = int(output_size)
    else:
        out = tuple(output_size)
        oh, ow = (out[0], out[0]) if len(out) == 1 else (out[0], out[1])
    n, c, h, w = data.shape
    x = data.astype(jnp.float32)

    def pool_axis(arr, axis, n_in, n_out):
        # bin edges are static python ints (shapes are static under jit)
        starts = [(i * n_in) // n_out for i in range(n_out)]
        ends = [-(-(i + 1) * n_in // n_out) for i in range(n_out)]
        pieces = []
        for st, en in zip(starts, ends):
            sl = [slice(None)] * arr.ndim
            sl[axis] = slice(st, en)
            pieces.append(arr[tuple(sl)].mean(axis=axis, keepdims=True))
        return jnp.concatenate(pieces, axis=axis)

    x = pool_axis(x, 2, h, oh)
    x = pool_axis(x, 3, w, ow)
    return x.astype(data.dtype)


@register("_contrib_bipartite_matching", aliases=["bipartite_matching"],
          num_outputs=2)
def bipartite_matching(data, *, is_ascend=False, threshold=0.0, topk=-1):
    """reference: src/operator/contrib/bounding_box.cc ::
    BipartiteMatching — greedy bipartite matching on a (..., N, M) score
    matrix: repeatedly take the globally best remaining pair. Returns
    (row_match, col_match): for each row the matched col (or -1), and for
    each col the matched row (or -1). Static-shape lax.fori_loop over
    min(N, M) rounds — compiler-friendly."""
    import jax.lax as lax

    scores = data.astype(jnp.float32)
    lead = scores.shape[:-2]  # arbitrary batch dims, flattened for vmap
    n, m = scores.shape[-2:]
    scores = scores.reshape((-1, n, m))
    b = scores.shape[0]
    sgn = 1.0 if not is_ascend else -1.0
    s0 = scores * sgn
    thr = threshold * sgn
    rounds = min(n, m) if topk < 0 else min(topk, n, m)

    def one(sc):
        def body(_, state):
            s, rmatch, cmatch = state
            flat = s.reshape(-1)
            idx = jnp.argmax(flat)
            val = flat[idx]
            r, c_ = idx // m, idx % m
            ok = val >= thr
            rmatch = jnp.where(ok, rmatch.at[r].set(c_.astype(jnp.float32)),
                               rmatch)
            cmatch = jnp.where(ok, cmatch.at[c_].set(r.astype(jnp.float32)),
                               cmatch)
            neg = jnp.float32(-jnp.inf)
            s = jnp.where(ok, s.at[r, :].set(neg).at[:, c_].set(neg), s)
            return s, rmatch, cmatch

        init = (sc, jnp.full((n,), -1.0, jnp.float32),
                jnp.full((m,), -1.0, jnp.float32))
        _, rmatch, cmatch = lax.fori_loop(0, rounds, body, init)
        return rmatch, cmatch

    rms, cms = jax.vmap(one)(s0)
    return rms.reshape(lead + (n,)), cms.reshape(lead + (m,))
