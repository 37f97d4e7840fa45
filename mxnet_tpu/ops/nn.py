"""Neural-network operators.

Reference: ``src/operator/nn/`` — ``convolution.cc``, ``fully_connected.cc``,
``batch_norm.cc``, ``layer_norm.cc``, ``pooling.cc``, ``activation.cc``,
``softmax.cc``, ``dropout.cc``, ``deconvolution.cc``; plus
``src/operator/softmax_output.cc``, ``leaky_relu.cc``, ``instance_norm.cc``,
``l2_normalization.cc``, ``embedding`` from ``indexing_op.cc``.

TPU mapping: Convolution/FullyConnected lower to ``lax.conv_general_dilated``
/ ``lax.dot_general`` which XLA tiles onto the MXU; elementwise epilogues
(bias, activation) fuse into the matmul automatically under jit.
"""
from __future__ import annotations

from functools import partial as _partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as _np

from .registry import attr, register

# ---------------------------------------------------------------------------
# dense / conv
# ---------------------------------------------------------------------------


@register("FullyConnected", aliases=["fully_connected"], attrs=[
    attr("num_hidden", int, "Number of output hidden units.", low=0),
    attr("no_bias", bool, "Whether to disable the bias term."),
    attr("flatten", bool,
         "Flatten trailing input dims into one (MXNet default) or apply "
         "the projection to the last axis only."),
])
def fully_connected(data, weight, bias=None, *, num_hidden=0, no_bias=False, flatten=True):
    # reference: src/operator/nn/fully_connected.cc :: FullyConnectedCompute
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = jnp.matmul(data, weight.T.astype(data.dtype))
    if not no_bias and bias is not None:
        out = out + bias.astype(out.dtype)
    return out


def _tuplize(v, n):
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    if len(v) == 1:
        return v * n
    return v


def _conv_dnums(nd, layout=None):
    # MXNet layouts: NCW/NCHW/NCDHW (default) or NWC/NHWC/NDHWC
    # (channels-last — the TPU-preferred internal layout; XLA then needs no
    # activation relayout around the conv, see SURVEY.md §7.2 "fusion
    # audit"). Weights stay OIHW-style in BOTH cases so checkpoints are
    # layout-independent; XLA relayouts the (small) filter, not the
    # activations.
    spatial = "DHW"[-nd:] if nd <= 3 else None
    lhs = ("N" + spatial + "C") if (layout and layout.endswith("C")) \
        else ("NC" + spatial)
    rhs = "OI" + spatial
    return jax.lax.conv_dimension_numbers(
        (1,) * (nd + 2), (1,) * (nd + 2), (lhs, rhs, lhs))


def _channel_axis(layout, ndim):
    return (ndim - 1) if (layout and layout.endswith("C")) else 1


@register("Convolution", aliases=["convolution"], attrs=[
    attr("kernel", tuple, "Spatial kernel size, e.g. (3, 3)."),
    attr("stride", tuple, "Strides per spatial dim (default 1).", low=1),
    attr("dilate", tuple, "Dilation per spatial dim (default 1).", low=1),
    attr("pad", tuple, "Zero padding per spatial dim.", low=0),
    attr("num_filter", int, "Number of output channels.", low=1),
    attr("num_group", int, "Grouped-convolution group count.", low=1),
    attr("no_bias", bool, "Whether to disable the bias term."),
    attr("layout", str, "Input/output layout; channels-last is the "
         "TPU-preferred internal layout.",
         choices=("NCW", "NCHW", "NCDHW", "NWC", "NHWC", "NDHWC")),
])
def convolution(data, weight, bias=None, *, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=1, num_group=1, no_bias=False,
                layout=None, workspace=1024, cudnn_tune=None, cudnn_off=False):
    # reference: src/operator/nn/convolution.cc :: ConvolutionCompute
    nd = len(kernel)
    stride = _tuplize(stride or 1, nd)
    dilate = _tuplize(dilate or 1, nd)
    pad = _tuplize(pad or 0, nd)
    dnums = _conv_dnums(nd, layout)
    out = _conv_core(data, weight.astype(data.dtype), stride,
                     [(p, p) for p in pad], dilate, dnums, num_group,
                     layout, kernel)
    out = out.astype(data.dtype)
    if not no_bias and bias is not None:
        bshape = [1] * out.ndim
        bshape[_channel_axis(layout, out.ndim)] = bias.shape[0]
        out = out + bias.astype(out.dtype).reshape(bshape)
    return out


def _conv_s2d(x, w, kernel):
    """Stride-2 large-kernel conv via space-to-depth re-indexing (exact).

    out[ho] = sum_a x[2*ho + a - pad] * W[a] splits by input parity r:
    a = 2*alpha + r + pad, so the same sum is a STRIDE-1 conv over the
    s2d-packed input (phase r becomes a channel) with ceil-halved taps.
    MXU win: contraction depth grows 4x (3->12 channels for the ResNet
    stem, where C=3 left the systolic array ~85% idle; PERF_HISTORY.md round 4)
    and the strided-dW backward formulation disappears — autodiff of this
    composite IS the transformed backward.
    """
    n, h, w_, c = x.shape
    o = w.shape[0]

    def geom(k):
        pad = (k - 1) // 2
        alpha_lo = min(-((pad + r) // 2) for r in (0, 1))
        alpha_hi = max((k - 1 - pad - r) // 2 for r in (0, 1))
        taps = alpha_hi - alpha_lo + 1
        lpad = -(2 * alpha_lo + pad)  # 0 or 1
        return pad, alpha_lo, alpha_hi, taps, lpad

    kh, kw = kernel
    _, alo_h, ahi_h, th, lh = geom(kh)
    _, alo_w, ahi_w, tw, lw = geom(kw)
    wp = jnp.pad(w, ((0, 0), (0, 0), (lh, 2 * th - kh - lh),
                     (lw, 2 * tw - kw - lw)))
    w2 = wp.reshape(o, c, th, 2, tw, 2).transpose(0, 3, 5, 1, 2, 4)
    w2 = w2.reshape(o, 4 * c, th, tw)
    x2 = x.reshape(n, h // 2, 2, w_ // 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    x2 = x2.reshape(n, h // 2, w_ // 2, 4 * c)
    dn = jax.lax.conv_dimension_numbers(
        x2.shape, w2.shape, ("NHWC", "OIHW", "NHWC"))
    return jax.lax.conv_general_dilated(
        x2, w2, (1, 1), [(-alo_h, ahi_h), (-alo_w, ahi_w)],
        dimension_numbers=dn)


@_partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv1x1_strided_dot(x, w, stride):
    """Stride-(sh,sw) 1x1 NHWC conv: strided slice + MXU dot.

    dX zero-interleaves the small cotangent matmul back onto the input
    grid by pad+reshape instead of XLA's lhs-dilated scatter-conv
    (~2.5x its bandwidth floor on the ResNet downsample shapes).
    """
    sh, sw = stride
    xs = x[:, ::sh, ::sw, :]
    w2 = w.reshape(w.shape[0], w.shape[1]).astype(x.dtype)
    out = jax.lax.dot_general(xs, w2, (((3,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def _conv1x1_strided_fwd(x, w, stride):
    return _conv1x1_strided_dot(x, w, stride), (x, w)


def _conv1x1_strided_bwd(stride, res, dy):
    x, w = res
    sh, sw = stride
    n, h, w_, c = x.shape
    w2 = w.reshape(w.shape[0], w.shape[1]).astype(dy.dtype)
    dxs = jax.lax.dot_general(dy, w2, (((3,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)
    # zero-interleave (N,Ho,Wo,C) -> (N,H,W,C): pad the phase dims
    dx = jnp.pad(dxs[:, :, None, :, None, :],
                 ((0, 0), (0, 0), (0, sh - 1), (0, 0), (0, sw - 1), (0, 0))
                 ).reshape(n, h, w_, c)
    xs = x[:, ::sh, ::sw, :]
    dw = jax.lax.dot_general(dy, xs, (((0, 1, 2), (0, 1, 2)), ((), ())),
                             preferred_element_type=jnp.float32)
    return dx, dw.reshape(w.shape).astype(w.dtype)


_conv1x1_strided_dot.defvjp(_conv1x1_strided_fwd, _conv1x1_strided_bwd)


@jax.custom_vjp
def _conv1x1_dot(x, w):
    """Stride-1 1x1 NHWC conv as a dot_general, with dot-formulated VJPs.

    x: (N, H, W, C), w: (O, C, 1, 1) [OIHW weight convention kept so
    checkpoints stay layout-independent]. Forward contracts C; dX and dW
    are the transposed contractions — all three run on the MXU as dots,
    bypassing XLA:TPU's conv-backward algorithm selection (measured ~40%
    of roofline on the same shapes inside ResNet-50; PERF_HISTORY.md round 4).
    f32 accumulation, output cast back to the input dtype.
    """
    # NO preferred_element_type=f32: the TPU MXU accumulates bf16 dots in
    # f32 natively and rounds on output, but an explicit f32 preferred
    # type SURVIVES XLA's dot->conv canonicalization — the round-5 HLO
    # byte audit found ~14 GB/step of f32[256,56,56,256]-class conv
    # outputs materialized in HBM (2x the bytes of the bf16 tensors the
    # 3x3 convs emit), with the .astype living in the consumer fusion
    w2 = w.reshape(w.shape[0], w.shape[1]).astype(x.dtype)
    out = jax.lax.dot_general(x, w2, (((3,), (1,)), ((), ())))
    return out.astype(x.dtype)


def _conv1x1_dot_fwd(x, w):
    return _conv1x1_dot(x, w), (x, w)


def _conv1x1_dot_bwd(res, dy):
    x, w = res
    w2 = w.reshape(w.shape[0], w.shape[1]).astype(dy.dtype)
    # dX[n,h,w,c] = sum_o dy[n,h,w,o] * W[o,c] — no preferred f32 (see
    # forward note: it would materialize f32 dX tensors after dot->conv
    # canonicalization)
    dx = jax.lax.dot_general(
        dy, w2, (((3,), (0,)), ((), ()))).astype(x.dtype)
    # dW[o,c] = sum_{n,h,w} dy[n,h,w,o] * x[n,h,w,c]
    dw = jax.lax.dot_general(
        dy, x, (((0, 1, 2), (0, 1, 2)), ((), ())),
        preferred_element_type=jnp.float32)
    return dx, dw.reshape(w.shape).astype(w.dtype)


_conv1x1_dot.defvjp(_conv1x1_dot_fwd, _conv1x1_dot_bwd)


def _conv_core(data, weight, stride, pads, dilate, dnums, groups, layout,
               kernel):
    """conv_general_dilated, with a custom dW backward on eligible shapes.

    XLA:TPU derives dW as a conv whose 'kernel' is the (large) dy tensor —
    measured at ~38% of roofline across ResNet-50's layers (PERF_HISTORY.md round
    3; VERDICT r3 #3). MXNET_TPU_CONV_DW=patches switches eligible convs
    (2-D, group-1, undilated, channels-last) to an explicit im2col dW:
    gather input patches (conv_general_dilated_patches), contract
    (N·Ho·Wo) x (C·kh·kw) against (N·Ho·Wo) x O in ONE MXU dot_general;
    dX keeps XLA's transposed-conv rule.

    Measured END-TO-END on ResNet-50 batch 256 (round 4): the patches
    formulation is 4x SLOWER (615 vs 2,324 img/s) — the materialized
    patch tensors (9x activation bytes for 3x3 convs) turn the step
    HBM-bound, and XLA cannot fuse the gather into the contraction. An
    isolated chained-scan microbenchmark said the opposite (vjp-dW 12-46x
    slower there), i.e. the scan context poisons XLA's conv-bwd algorithm
    choice; trust only in-model traces. Kept env-gated for experiments;
    default = XLA's own backward.
    """
    import os

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, window_strides=stride, padding=pads,
            rhs_dilation=dilate, dimension_numbers=dnums,
            feature_group_count=groups,
            # NOTE: no preferred_element_type=f32 — the TPU MXU
            # accumulates bf16 convs in f32 natively, and an explicit f32
            # output breaks the conv transpose (VJP) rule's dtype
            # agreement.
        )

    # ResNet-stem-shaped convs (large kernel, stride 2, <=4 input channels)
    # run the MXU at ~15% of roofline: contraction channels of 3 leave the
    # systolic array idle, and the strided dW formulation is worse still.
    # Space-to-depth is the exact re-indexing fix: s2d(2) the input
    # (C -> 4C), zero-pad the kernel to even taps, and the same arithmetic
    # becomes a stride-1 conv with 4x the contraction depth. Exact for
    # fwd AND both backward passes (it is a pure re-indexing, so autodiff
    # through the reshape/conv composite is the transformed backward).
    if (len(kernel) == 2 and tuple(stride) == (2, 2)
            and groups == 1 and all(d == 1 for d in dilate)
            and not isinstance(pads, str)
            and bool(layout) and layout.endswith("C")
            and data.ndim == 4 and data.shape[-1] <= 4
            and kernel[0] >= 5 and kernel[1] >= 5
            and all(tuple(p) == ((k - 1) // 2,) * 2
                    for p, k in zip(pads, kernel))
            and data.shape[1] % 2 == 0 and data.shape[2] % 2 == 0
            and os.environ.get("MXNET_TPU_CONV_S2D", "1") == "1"):
        return _conv_s2d(data, weight, kernel)

    # Strided 1x1 convs as strided SLICE + matmul, dX zero-interleaved by
    # pad+reshape instead of XLA's lhs-dilated scatter-conv. Measured
    # END-TO-END in ResNet-50 (round 4): a 4.5% REGRESSION (2,465 vs
    # 2,585 img/s) — the materialized slice/pad intermediates cost more
    # than the scatter-conv formulation they replace, mirroring the
    # round-4 patches-dW lesson that isolated-op roofline math loses to
    # XLA's fusion once the op sits inside a real step. Kept opt-in for
    # experiments.
    if (tuple(kernel) == (1, 1) and len(stride) == 2
            and max(stride) > 1 and groups == 1
            and all(d == 1 for d in dilate)
            and not isinstance(pads, str)
            and all(tuple(p) == (0, 0) for p in pads)
            and bool(layout) and layout.endswith("C")
            and data.ndim == 4
            and data.shape[1] % stride[0] == 0
            and data.shape[2] % stride[1] == 0
            and os.environ.get("MXNET_TPU_CONV1X1_STRIDED_DOT", "0") == "1"):
        return _conv1x1_strided_dot(data, weight, tuple(stride))

    # Stride-1 1x1 channels-last convs ARE matmuls: formulate fwd/dW/dX as
    # explicit dot_generals so XLA:TPU's matmul path (not its conv-backward
    # algorithm selection) runs them. Round-4 trace: the 1x1 dX/dW conv
    # formulations sat at ~40% of the matmul roofline inside the ResNet-50
    # step (PERF_HISTORY.md round 4, conv-attribution table); a dot never enters
    # conv algorithm selection at all.
    if (tuple(kernel) == (1, 1) and tuple(stride) == (1, 1)
            and groups == 1 and all(d == 1 for d in dilate)
            and not isinstance(pads, str)
            and all(tuple(p) == (0, 0) for p in pads)
            and bool(layout) and layout.endswith("C")
            and data.ndim == 4
            and os.environ.get("MXNET_TPU_CONV1X1_DOT", "1") == "1"):
        return _conv1x1_dot(data, weight)

    eligible = (len(kernel) == 2 and groups == 1
                and all(d == 1 for d in dilate)
                and bool(layout) and layout.endswith("C")
                and os.environ.get("MXNET_TPU_CONV_DW", "vjp")
                == "patches")
    if not eligible:
        return conv(data, weight)

    kh, kw = kernel

    @jax.custom_vjp
    def f(x, w):
        return conv(x, w)

    def f_fwd(x, w):
        return conv(x, w), (x, w)

    def f_bwd(res, dy):
        x, w = res
        _, pull_x = jax.vjp(lambda x_: conv(x_, w), x)
        (dx,) = pull_x(dy)
        # dW via im2col: patches (N,Ho,Wo, C*kh*kw) — feature order is
        # (C, kh, kw), per conv_general_dilated_patches — against
        # dy (N,Ho,Wo,O), contracted over all positions at once
        patches = jax.lax.conv_general_dilated_patches(
            x, (kh, kw), stride, pads,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        n, ho, wo, _ = patches.shape
        cin = x.shape[-1]
        dw = jax.lax.dot_general(
            patches.reshape(n * ho * wo, cin * kh * kw),
            dy.reshape(n * ho * wo, -1),
            (((0,), (0,)), ((), ())))
        # (C*kh*kw, O) -> (O, C, kh, kw) == the OIHW weight layout
        dw = dw.reshape(cin, kh, kw, -1).transpose(3, 0, 1, 2)
        return dx, dw.astype(w.dtype)

    f.defvjp(f_fwd, f_bwd)
    return f(data, weight)


@register("Deconvolution", aliases=["deconvolution"])
def deconvolution(data, weight, bias=None, *, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), num_filter=1, num_group=1, no_bias=True,
                  target_shape=(), layout=None, workspace=1024,
                  cudnn_tune=None, cudnn_off=False):
    # reference: src/operator/nn/deconvolution.cc — conv transpose.
    nd = len(kernel)
    stride = _tuplize(stride or 1, nd)
    dilate = _tuplize(dilate or 1, nd)
    pad = _tuplize(pad or 0, nd)
    adj = _tuplize(adj or 0, nd)
    spatial = "DHW"[-nd:]
    lhs = ("N" + spatial + "C") if (layout and layout.endswith("C")) \
        else ("NC" + spatial)
    dn = jax.lax.conv_dimension_numbers(
        data.shape, weight.shape, (lhs, "IO" + spatial, lhs)
    )
    # conv_transpose with MXNet padding semantics:
    # out = (in-1)*stride - 2*pad + dilate*(k-1) + 1 + adj
    padding = []
    for i in range(nd):
        k_eff = dilate[i] * (kernel[i] - 1) + 1
        lo = k_eff - 1 - pad[i]
        hi = k_eff - 1 - pad[i] + adj[i]
        padding.append((lo, hi))
    if num_group > 1:
        # lax.conv_transpose has no group support; the equivalent
        # lhs-dilated conv does. Deconv weight (I, O/g, k, k) becomes a
        # conv weight (O, I/g, k, k) by per-group channel transpose only.
        g = num_group
        i_ch = weight.shape[0]
        og = weight.shape[1]
        wt = weight.reshape((g, i_ch // g, og) + tuple(weight.shape[2:]))
        wt = jnp.swapaxes(wt, 1, 2).reshape((g * og, i_ch // g)
                                            + tuple(weight.shape[2:]))
        # NO spatial flip: matches lax.conv_transpose(transpose_kernel=
        # False), the convention the ungrouped path (and MXNet) uses
        dn2 = jax.lax.conv_dimension_numbers(
            data.shape, wt.shape, (lhs, "OI" + spatial, lhs))
        out = jax.lax.conv_general_dilated(
            data, wt.astype(data.dtype), window_strides=(1,) * nd,
            padding=padding, lhs_dilation=stride, rhs_dilation=dilate,
            dimension_numbers=dn2, feature_group_count=g)
    else:
        out = jax.lax.conv_transpose(
            data, weight.astype(data.dtype), strides=stride,
            padding=padding, rhs_dilation=dilate, dimension_numbers=dn,
            transpose_kernel=False,
        )
    out = out.astype(data.dtype)
    if not no_bias and bias is not None:
        bshape = [1] * out.ndim
        bshape[_channel_axis(layout, out.ndim)] = bias.shape[0]
        out = out + bias.astype(out.dtype).reshape(bshape)
    return out


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------


@register("Pooling", aliases=["pooling"], attrs=[
    attr("kernel", tuple, "Pooling window size."),
    attr("pool_type", str, "Pooling reduction.",
         choices=("max", "avg", "sum", "lp")),
    attr("stride", tuple, "Window strides (default 1).", low=1),
    attr("pad", tuple, "Zero padding per spatial dim.", low=0),
    attr("global_pool", bool, "Pool over the whole spatial extent."),
    attr("pooling_convention", str, "Output-size rounding rule.",
         choices=("valid", "full", "same")),
    attr("p_value", int, "p of the Lp pooling norm.", low=1),
    attr("layout", str, "Input layout.",
         choices=("NCW", "NCHW", "NCDHW", "NWC", "NHWC", "NDHWC")),
])
def pooling(data, *, kernel=(), pool_type="max", stride=(), pad=(),
            global_pool=False, pooling_convention="valid", count_include_pad=True,
            cudnn_off=False, p_value=2, layout=None):
    # reference: src/operator/nn/pooling.cc :: PoolingCompute
    # layout: channels-first (default) or channels-last ("NHWC"/"NWC"/
    # "NDHWC") — spatial window axes shift accordingly
    nd = data.ndim - 2
    channels_last = bool(layout) and layout.endswith("C")
    spatial0 = 1 if channels_last else 2
    if global_pool:
        ax = tuple(range(spatial0, spatial0 + nd))
        if pool_type == "max":
            return jnp.max(data, axis=ax, keepdims=True)
        if pool_type in ("avg", "sum"):
            r = jnp.mean if pool_type == "avg" else jnp.sum
            return r(data, axis=ax, keepdims=True)
        if pool_type == "lp":
            return jnp.power(
                jnp.sum(jnp.power(jnp.abs(data), p_value), axis=ax, keepdims=True),
                1.0 / p_value)
    kernel = _tuplize(kernel, nd)
    stride = _tuplize(stride or 1, nd)
    pad = _tuplize(pad or 0, nd)
    if channels_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride

    def pads_for(convention):
        spatial = []
        for i in range(nd):
            if convention == "same":
                # TF-style SAME: out = ceil(in / stride); symmetric split
                # with the extra cell at the end. Explicit pad is part of
                # the convention, not additive (reference pooling.cc
                # requires pad=0 with convention=same).
                size = data.shape[spatial0 + i]
                out = -(-size // stride[i])
                total = max((out - 1) * stride[i] + kernel[i] - size, 0)
                lo = total // 2
                hi = total - lo
            else:
                lo = hi = pad[i]
                if convention == "full":
                    # ceil instead of floor output size: extra hi padding
                    size = data.shape[spatial0 + i] + 2 * pad[i] - kernel[i]
                    rem = size % stride[i]
                    if rem != 0:
                        hi += stride[i] - rem
            spatial.append((lo, hi))
        if channels_last:
            return [(0, 0)] + spatial + [(0, 0)]
        return [(0, 0), (0, 0)] + spatial

    if pooling_convention == "same" and any(p != 0 for p in pad):
        raise ValueError(
            "Pooling: pooling_convention='same' requires pad=0 "
            "(reference: src/operator/nn/pooling.cc parameter check)")

    padding = pads_for(pooling_convention)
    if pool_type == "max":
        # fixed-width init scalar: a bare Python int promotes to i64
        # under jax_enable_x64 and reduce_window rejects the mismatch
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else _np.dtype(data.dtype).type(jnp.iinfo(data.dtype).min)
        return jax.lax.reduce_window(data, init, jax.lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        summed = jax.lax.reduce_window(data, 0.0, jax.lax.add, window, strides, padding)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return summed / denom
        ones = jnp.ones_like(data)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, padding)
        return summed / counts
    if pool_type == "lp":
        powed = jax.lax.reduce_window(
            jnp.power(jnp.abs(data), p_value), 0.0, jax.lax.add, window, strides, padding)
        return jnp.power(powed, 1.0 / p_value)
    raise ValueError(f"unknown pool_type {pool_type}")


@register("ROIPooling")
def roi_pooling(data, rois, *, pooled_size=(), spatial_scale=1.0):
    """reference: src/operator/roi_pooling.cc — quantized-bin max pooling.

    Bin i spans [floor(i*rh/ph), ceil((i+1)*rh/ph)) like the reference
    (bins may overlap by one row/col). Dense masked-max formulation:
    data-dependent bin edges become boolean masks over the feature map, a
    per-axis reduction each — no dynamic shapes, XLA-friendly.
    """
    ph, pw = pooled_size
    n, c, h_, w_ = data.shape

    def one(roi):
        b = roi[0].astype(jnp.int32)
        # reference round() is half-AWAY-from-zero; coords are >= 0 so
        # floor(x + 0.5) reproduces it (jnp.round is half-to-even)
        x1 = jnp.floor(roi[1] * spatial_scale + 0.5).astype(jnp.int32)
        y1 = jnp.floor(roi[2] * spatial_scale + 0.5).astype(jnp.int32)
        x2 = jnp.floor(roi[3] * spatial_scale + 0.5).astype(jnp.int32)
        y2 = jnp.floor(roi[4] * spatial_scale + 0.5).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1).astype(jnp.float32)
        rw = jnp.maximum(x2 - x1 + 1, 1).astype(jnp.float32)
        img = jnp.take(data, b, axis=0)  # (C, H, W)
        hs = jnp.arange(h_)[:, None]
        ws = jnp.arange(w_)[:, None]
        iy = jnp.arange(ph)[None]
        ix = jnp.arange(pw)[None]
        hstart = y1 + jnp.floor(iy * rh / ph).astype(jnp.int32)
        hend = y1 + jnp.ceil((iy + 1) * rh / ph).astype(jnp.int32)
        wstart = x1 + jnp.floor(ix * rw / pw).astype(jnp.int32)
        wend = x1 + jnp.ceil((ix + 1) * rw / pw).astype(jnp.int32)
        ymask = (hs >= hstart) & (hs < hend) & (hs >= 0) & (hs < h_)
        xmask = (ws >= wstart) & (ws < wend) & (ws >= 0) & (ws < w_)
        neg = jnp.array(-jnp.inf, dtype=jnp.float32)
        # reduce W first: (C, H, pw), then H: (C, ph, pw)
        tmp = jnp.max(jnp.where(xmask[None, None], img.astype(
            jnp.float32)[..., None], neg), axis=2)
        out = jnp.max(jnp.where(ymask[None, :, :, None],
                                tmp[:, :, None, :], neg), axis=1)
        return jnp.where(jnp.isfinite(out), out, 0.0).astype(data.dtype)

    return jax.vmap(one)(rois.astype(jnp.float32))


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


@register("BatchNorm", aliases=["batch_norm"], pass_training_flag=True,
          attrs=[
    attr("eps", float, "Numerical-stability epsilon added to variance.",
         low=0.0),
    attr("momentum", float, "Moving-average momentum.", low=0.0, high=1.0),
    attr("fix_gamma", bool, "Treat gamma as fixed at 1."),
    attr("use_global_stats", bool,
         "Normalize with moving stats even in training."),
    attr("axis", int, "Channel axis (1 = channels-first, -1 = last)."),
])
def batch_norm(data, gamma, beta, moving_mean, moving_var, *, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False,
               min_calib_range=None, max_calib_range=None, _training=False):
    """reference: src/operator/nn/batch_norm.cc :: BatchNormCompute.

    In training mode returns (out, batch_mean, batch_var) so the caller
    (gluon BatchNorm block / CachedOp aux-state threading) can update the
    moving statistics functionally — the TPU-native replacement for MXNet's
    in-place aux-state mutation. In inference mode returns just `out`
    (matching mx.nd.BatchNorm's single visible output).
    """
    axis = axis % data.ndim
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    use_batch_stats = _training and not use_global_stats
    if use_batch_stats:
        out, mean, var = _bn_train(axis, float(eps), data, g, beta)
        return out, mean, var
    reduce_axes = tuple(i for i in range(data.ndim) if i != axis)
    x32 = data.astype(jnp.float32)
    mean, var = moving_mean.astype(jnp.float32), moving_var.astype(jnp.float32)
    inv = jax.lax.rsqrt(var + eps)
    scale = g.astype(jnp.float32) * inv
    bias = beta.astype(jnp.float32) - mean * scale
    out = (x32 * scale.reshape(bshape) + bias.reshape(bshape)).astype(data.dtype)
    if output_mean_var:
        return out, mean, var
    return out


def _bn_stats(x, axis, eps):
    """Per-channel (mean, var, rsqrt(var+eps)).

    Half-precision inputs use one-traversal moments (E[x^2]-E[x]^2, both
    reduced in the same fused f32 loop): the f32 cancellation error,
    ~1e-7*(mean/std)^2 relative, is subdominant to the input's own bf16
    quantization until mean/std exceeds ~300. f32 inputs keep the exact
    centered two-pass (jnp.var) — they carry no quantization floor to
    hide behind, and the extra traversal only matters on the bf16 hot
    path.
    """
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=reduce_axes)
    if x.dtype == jnp.float32 or x.dtype == jnp.float64:
        var = jnp.var(x32, axis=reduce_axes)
    else:
        sq = jnp.mean(x32 * x32, axis=reduce_axes)
        var = jnp.maximum(sq - mean * mean, 0.0)
    return mean, var, jax.lax.rsqrt(var + eps)


@_partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bn_train(axis, eps, x, g, b):
    """Training-mode batch norm with a hand-derived backward.

    Autodiff through the statistics produces ~7 full-tensor reductions and
    a dozen f32 elementwise chains per layer (round-4 ResNet trace: the
    BN-backward arithmetic fused into the conv-dX fusions was the largest
    single cost bucket). The classic two-reduction backward needs only
    sum(dy) and sum(dy*xhat) — which are exactly dbeta and dgamma.

    The (mean, var) outputs are statistics for the moving-average update
    (MXNet aux states, reference: src/operator/nn/batch_norm.cc — aux
    outputs carry no gradient); their cotangents are ignored.
    """
    out, mean, var, _ = _bn_train_math(axis, eps, x, g, b)
    return out, mean, var


def _bn_train_math(axis, eps, x, g, b):
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    mean, var, inv = _bn_stats(x, axis, eps)
    scale = g.astype(jnp.float32) * inv
    bias = b.astype(jnp.float32) - mean * scale
    out = (x.astype(jnp.float32) * scale.reshape(bshape)
           + bias.reshape(bshape)).astype(x.dtype)
    return out, mean, var, inv


def _bn_train_fwd(axis, eps, x, g, b):
    out, mean, var, inv = _bn_train_math(axis, eps, x, g, b)
    return (out, mean, var), (x, g, b, mean, inv)


def _bn_train_bwd(axis, eps, res, cots):
    x, g, b, mean, inv = res
    dy = cots[0]  # stats cotangents (aux moving-average path) are zero
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    n = 1
    for i in reduce_axes:
        n *= x.shape[i]
    dy32 = dy.astype(jnp.float32)
    xhat = (x.astype(jnp.float32) - mean.reshape(bshape)) * inv.reshape(bshape)
    dbeta = jnp.sum(dy32, axis=reduce_axes)
    dgamma = jnp.sum(dy32 * xhat, axis=reduce_axes)
    g32 = g.astype(jnp.float32)
    dx = ((g32 * inv / n).reshape(bshape)
          * (n * dy32 - dbeta.reshape(bshape) - xhat * dgamma.reshape(bshape))
          ).astype(x.dtype)
    return dx, dgamma.astype(g.dtype), dbeta.astype(b.dtype)


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


def _fused_ln_routable(data, axis):
    """Whether a Pallas fused-layer kernel may take this call, and how
    (``fused_ln_supported``'s answer: 0 no, 1 yes, n on n batch shards):
    MXNET_PALLAS_FUSED=1, last-axis norm, TPU execution platform and the
    row/lane shape gate. Checked per call — the env knob is a live switch."""
    from ..pallas_kernels.fused_layers import (fused_layers_enabled,
                                               fused_ln_supported)

    if not fused_layers_enabled():
        return 0
    if axis not in (-1, data.ndim - 1):
        return 0
    return fused_ln_supported(data)


@register("LayerNorm", aliases=["layer_norm"])
def layer_norm(data, gamma, beta, *, axis=-1, eps=1e-5, output_mean_var=False):
    # reference: src/operator/nn/layer_norm.cc
    shards = 0 if output_mean_var else _fused_ln_routable(data, axis)
    if shards:
        # Pallas one-pass kernel (pallas_kernels/fused_layers.py): same
        # f32 statistics, custom_vjp backward recomputing xhat from the
        # saved (mean, rstd) rows instead of autodiff through the reductions
        from .. import telemetry
        from ..pallas_kernels.fused_layers import fused_layer_norm
        from ..parallel.mesh import over_batch_shards

        telemetry.record_pallas_dispatch("fused_layer_norm")
        kernel = over_batch_shards(fused_layer_norm, shards, 1)
        return kernel(data, gamma, beta, eps=eps)
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axis, keepdims=True)
    var = jnp.var(x32, axis=axis, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    out = (x32 - mean) * inv
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    out = out * gamma.astype(jnp.float32).reshape(bshape) + beta.astype(jnp.float32).reshape(bshape)
    out = out.astype(data.dtype)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)
    return out


@register("_contrib_fused_layer_norm", aliases=["fused_layer_norm"],
          needs_rng=True, pass_training_flag=True,
          rng_gate=lambda attrs: bool(attrs.get("dropout"))
          and bool(attrs.get("_training")), attrs=[
    attr("eps", float, "Normalization epsilon.", low=0.0),
    attr("dropout", float, "Drop rate applied to ``data`` (not the "
         "residual) before the add+norm.", low=0.0, high=1.0),
])
def fused_layer_norm_op(rng, data, gamma, beta, residual=None, *,
                        eps=1e-5, dropout=0.0, _training=False):
    """Fused ``LayerNorm(dropout(data) + residual)`` over the last axis
    — the post-LN transformer cell's add+norm collapsed into one op
    (reference capability: transformer.cc's fused residual epilogues).

    Routed to the Pallas one-pass kernel under ``MXNET_PALLAS_FUSED=1``
    + shape/platform gates; otherwise the eager jnp composition runs
    with the SAME stateless position-hash dropout mask, so both routes
    drop identical elements for a given op key (the flash-attention
    dropout contract), and so does the kernel on the shards of a
    data-parallel batch: each is told its first global row. Training-mode
    only dropout; the PRNG key is drawn only when it applies (rng_gate).
    """
    from ..pallas_kernels.fused_layers import (fused_layer_norm,
                                               fused_layer_norm_reference)

    p = float(dropout) if _training else 0.0
    seed = None
    if p > 0.0:
        from ..pallas_kernels.flash_attention import fold_key_seed

        seed = fold_key_seed(rng)
    shards = _fused_ln_routable(data, -1)
    if shards:
        from .. import telemetry
        from ..parallel.mesh import batch_shard_index, over_batch_shards

        def fused_layer_norm_rows(data, residual, gamma, beta, seed):
            # the hash's row ids are global: a shard's rows start at
            # (its place along the batch axes) x (the rows it holds)
            first_row = batch_shard_index() * (data.size // data.shape[-1])
            return fused_layer_norm(data, gamma, beta, residual, eps=eps,
                                    dropout=p, seed=seed,
                                    first_row=first_row)

        telemetry.record_pallas_dispatch("fused_layer_norm")
        return over_batch_shards(fused_layer_norm_rows, shards, 2)(
            data, residual, gamma, beta, seed)
    return fused_layer_norm_reference(data, gamma, beta, residual,
                                      eps=eps, dropout=p, seed=seed)


@register("_contrib_fused_bias_gelu", aliases=["fused_bias_gelu"])
def fused_bias_gelu_op(data, bias):
    """Fused ``gelu(data + bias)`` (exact erf form) — the Dense matmul
    epilogue. Bit-identical to the eager pair (bias add in the matmul
    dtype, then ``Activation(act_type='gelu')``); under
    ``MXNET_PALLAS_FUSED=1`` + gates it runs as one Pallas VMEM pass
    whose backward recomputes the activation derivative instead of
    saving erf/cdf intermediates."""
    from ..pallas_kernels.fused_layers import (fused_bias_gelu,
                                               fused_bias_gelu_reference)

    shards = _fused_ln_routable(data, -1)
    if shards:
        from .. import telemetry
        from ..parallel.mesh import over_batch_shards

        telemetry.record_pallas_dispatch("fused_bias_gelu")
        return over_batch_shards(fused_bias_gelu, shards, 1)(data, bias)
    return fused_bias_gelu_reference(data, bias)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, *, eps=1e-3):
    ax = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    out = (data - mean) * jax.lax.rsqrt(var + eps)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("GroupNorm")
def group_norm(data, gamma, beta, *, num_groups=1, eps=1e-5):
    n, c = data.shape[:2]
    rest = data.shape[2:]
    x = data.reshape((n, num_groups, c // num_groups) + rest)
    ax = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=ax, keepdims=True)
    var = jnp.var(x, axis=ax, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(bshape) + beta.reshape(bshape)


@register("LRN")
def lrn(data, *, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    # reference: src/operator/nn/lrn.cc — cross-channel local response norm
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = jnp.zeros_like(data)
    for i in range(nsize):
        acc = acc + padded[:, i : i + data.shape[1]]
    return data / jnp.power(knorm + alpha * acc / nsize, beta)


# ---------------------------------------------------------------------------
# activations / softmax
# ---------------------------------------------------------------------------


@register("Activation", aliases=["activation"])
def activation(data, *, act_type="relu"):
    # reference: src/operator/nn/activation.cc
    fns = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
        "silu": jax.nn.silu,
        "swish": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=False),
        "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
        "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
    }
    return fns[act_type](data)


@register("LeakyReLU")
def leaky_relu(data, gamma=None, *, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, _training=False):
    # reference: src/operator/leaky_relu.cc
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma
        if g.ndim < data.ndim and g.size > 1:
            g = g.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        scale, alpha = 1.0507009873554805, 1.6732632423543772
        return scale * jnp.where(data > 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, s * data)
    raise ValueError(act_type)


@register("softmax")
def softmax_op(data, length=None, *, axis=-1, temperature=None, dtype=None, use_length=False):
    # reference: src/operator/nn/softmax.cc
    x = data if temperature in (None, 1.0) else data / temperature
    if use_length and length is not None:
        pos = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        mask = pos.reshape(shape) < length.reshape(
            [x.shape[i] if i == 0 else 1 for i in range(x.ndim)])
        x = jnp.where(mask, x, -jnp.inf)
    out = jax.nn.softmax(x, axis=axis)
    if use_length and length is not None:
        out = jnp.where(jnp.isnan(out), 0.0, out)
    return out.astype(jnp.dtype(dtype)) if dtype else out


@register("log_softmax")
def log_softmax_op(data, *, axis=-1, temperature=None, dtype=None, use_length=False):
    x = data if temperature in (None, 1.0) else data / temperature
    out = jax.nn.log_softmax(x, axis=axis)
    return out.astype(jnp.dtype(dtype)) if dtype else out


@register("softmin")
def softmin(data, *, axis=-1, temperature=None, dtype=None):
    return softmax_op(-data, axis=axis, temperature=temperature, dtype=dtype)


@register("masked_softmax")
def masked_softmax(data, mask, *, axis=-1, temperature=1.0,
                   normalize=True):
    """Softmax over positions where ``mask`` is true; masked positions
    get probability 0 (reference: src/operator/nn/masked_softmax.cc —
    fully-masked rows produce zeros, not NaN)."""
    m = mask.astype(bool)
    x = data if temperature in (None, 1.0) else data / temperature
    if not normalize:
        # upstream normalize=False: plain exp on kept positions
        return jnp.where(m, jnp.exp(x), 0.0).astype(data.dtype)
    neg = jnp.finfo(jnp.float32).min
    out = jax.nn.softmax(jnp.where(m, x.astype(jnp.float32), neg),
                         axis=axis)
    # a fully-masked row softmaxes the uniform min -> uniform probs;
    # zero them like the reference kernel does
    out = jnp.where(m, out, 0.0)
    return out.astype(data.dtype)


@register("masked_log_softmax")
def masked_log_softmax(data, mask, *, axis=-1, temperature=1.0):
    """log of masked_softmax; masked positions are -inf (reference:
    masked_softmax.cc::MaskedSoftmaxGrad's paired log variant)."""
    m = mask.astype(bool)
    x = data if temperature in (None, 1.0) else data / temperature
    neg = jnp.finfo(jnp.float32).min
    out = jax.nn.log_softmax(jnp.where(m, x.astype(jnp.float32), neg),
                             axis=axis)
    out = jnp.where(m, out, -jnp.inf)
    return out.astype(data.dtype)


def _make_softmax_output(grad_scale, ignore_label, use_ignore, smooth_alpha,
                         normalization):
    """Fused softmax + cross-entropy-gradient head. The backward IGNORES the
    incoming gradient and emits (prob - one_hot(label)) * grad_scale,
    normalized per the `normalization` attr ('null' | 'batch' | 'valid') —
    reference: src/operator/softmax_output-inl.h :: SoftmaxOutputBackward."""

    @jax.custom_vjp
    def _so(data, label):
        return jax.nn.softmax(data, axis=-1)

    def fwd(data, label):
        prob = jax.nn.softmax(data, axis=-1)
        return prob, (prob, label)

    def bwd(res, g):
        prob, label = res
        n_class = prob.shape[-1]
        onehot = jax.nn.one_hot(label.astype(jnp.int32), n_class, dtype=prob.dtype)
        if smooth_alpha:
            onehot = onehot * (1 - smooth_alpha) + smooth_alpha / (n_class - 1) * (1 - onehot)
        grad = prob - onehot
        valid = None
        if use_ignore:
            mask = (label != ignore_label).astype(prob.dtype)
            grad = grad * mask[..., None]
            valid = jnp.maximum(jnp.sum(mask), 1.0)
        if normalization == "valid":
            denom = valid if valid is not None else float(_np_prod(prob.shape[:-1]))
            grad = grad / denom
        elif normalization == "batch":
            grad = grad / float(prob.shape[0])
        grad = grad * grad_scale
        lgrad = (jnp.zeros_like(label, dtype=jax.dtypes.float0)
                 if jnp.issubdtype(label.dtype, jnp.integer) else jnp.zeros_like(label))
        return grad, lgrad

    _so.defvjp(fwd, bwd)
    return _so


def _np_prod(shape):
    n = 1
    for d in shape:
        n *= d
    return n


@register("SoftmaxOutput", aliases=["Softmax"])
def softmax_output(data, label, *, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    _so = _make_softmax_output(grad_scale, ignore_label, use_ignore,
                               smooth_alpha, normalization)
    if multi_output:
        # (n, c, d1, ...) -> softmax over axis 1
        x = jnp.moveaxis(data, 1, -1)
        return jnp.moveaxis(_so(x, label), -1, 1)
    if data.ndim > 2 and not preserve_shape:
        flat = data.reshape(data.shape[0], -1)
        return _so(flat, label).reshape(data.shape)
    return _so(data, label)


@register("make_loss", aliases=["MakeLoss"])
def make_loss(data, *, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    # reference: src/operator/make_loss.cc — identity fwd, grad = grad_scale
    @jax.custom_vjp
    def _ml(x):
        return x

    def fwd(x):
        return x, x.shape

    def bwd(shape, g):
        return (jnp.full(shape, grad_scale, dtype=jnp.float32),)

    _ml.defvjp(fwd, bwd)
    return _ml(data)


@register("BlockGrad", aliases=["stop_gradient"])
def block_grad(data):
    return jax.lax.stop_gradient(data)


# ---------------------------------------------------------------------------
# embedding / dropout
# ---------------------------------------------------------------------------


@register("Embedding")
def embedding(data, weight, *, input_dim=0, output_dim=0, dtype="float32",
              sparse_grad=False, _sparse_uid=None):
    # reference: src/operator/tensor/indexing_op.cc :: EmbeddingOpForward
    idx = data.astype(jnp.int32)
    if sparse_grad and _sparse_uid is not None:
        from ..parallel.sparse_grad import sparse_grad_active

        if sparse_grad_active():
            # row-sparse gradient: the custom VJP logs (rows, dY) into
            # the active scope and the train step does a lazy row update
            # — the dense (vocab, dim) cotangent is never consumed
            return _sparse_lookup(weight, idx, _sparse_uid)
    return jnp.take(weight, idx, axis=0)


import functools as _functools

import numpy as _np_mod


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sparse_lookup(weight, idx, uid):
    return jnp.take(weight, idx, axis=0)


def _sparse_lookup_fwd(weight, idx, uid):
    return jnp.take(weight, idx, axis=0), (idx, weight)


def _sparse_lookup_bwd(uid, res, g):
    from ..parallel.sparse_grad import log_sparse_grad

    idx, weight = res
    log_sparse_grad(uid, idx, g)
    # symbolic-zero dense cotangent: dead unless the weight also feeds a
    # dense-grad op, which the sparse path forbids (see sparse_grad.py)
    return (jnp.zeros_like(weight),
            _np_mod.zeros(idx.shape, jax.dtypes.float0))


_sparse_lookup.defvjp(_sparse_lookup_fwd, _sparse_lookup_bwd)


@register("Dropout", aliases=["dropout"], needs_rng=True,
          pass_training_flag=True, attrs=[
    attr("p", float, "Fraction of units dropped.", low=0.0, high=1.0),
    attr("mode", str, "When to apply dropout.",
         choices=("training", "always")),
])
def dropout_op(rng, data, *, p=0.5, mode="training", axes=(), cudnn_off=False,
               _training=False):
    # reference: src/operator/nn/dropout.cc
    apply = _training or mode == "always"
    if not apply or p == 0.0:
        return data
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1
    keep = 1.0 - p
    import numpy as _np
    import os as _os

    thresh32 = _np.uint32(min(0xFFFF, int(round(keep * 65536.0))))
    if _os.environ.get("MXNET_TPU_HASH_DROPOUT", "0") == "1" or \
            _os.environ.get("MXNET_PALLAS_FUSED", "0") == "1":
        # MXNET_PALLAS_FUSED also selects the hash path: the fused layer
        # kernels generate THEIR dropout from this same position hash, so
        # one knob keeps every dropout site in the model on one stream
        # family (and the mask fuses into adjacent chains instead of
        # spilling RngBitGenerator bool traffic — the PERF_HISTORY.md batch-32
        # residue bucket the fused kernels target).
        # Stateless position-hash mask (round 5, VERDICT r4 #2 attempt):
        # pure elementwise integer code that XLA fuses into the adjacent
        # chains — zero extra HBM traffic, no RngBitGenerator custom
        # calls. MEASURED SLOWER end-to-end on TPU v5e (BERT-base: 255.6
        # vs 272.6 samples/s): the VPU has no native 32-bit integer
        # multiply, so the 3-multiply murmur finalizer costs more than
        # the hardware RNG kernels it replaces. Kept opt-in for
        # fusion-sensitive CPU paths and as the documented A/B; the flash
        # kernels still use this hash for ATTENTION-prob dropout, where
        # positional statelessness (fwd/bwd mask identity across kernel
        # orientations) has no generator-based alternative.
        from ..pallas_kernels.flash_attention import _hash_u16, fold_key_seed

        seed = fold_key_seed(rng)
        flat = jnp.zeros(tuple(shape), jnp.uint32)
        stride = 1
        for d in reversed(range(len(shape))):
            flat = flat + jax.lax.broadcasted_iota(
                jnp.uint32, tuple(shape), d) * _np.uint32(stride)
            stride *= shape[d]
        mask = _hash_u16(flat, seed) < thresh32
    else:
        # u16 threshold compare instead of jax.random.bernoulli's u32->f32
        # uniform: half the generated bits and no convert, at 2^-16
        # keep-rate granularity. The inverse-keep scale is a multiply
        # (divides don't strength-reduce for non-exact reciprocals).
        bits = jax.random.bits(rng, tuple(shape), dtype=jnp.uint16)
        mask = bits < thresh32.astype(_np.uint16)
    inv_keep = jnp.asarray(1.0 / keep, dtype=data.dtype)
    return jnp.where(mask, data * inv_keep, jnp.zeros_like(data))


# ---------------------------------------------------------------------------
# losses / misc heads
# ---------------------------------------------------------------------------


@register("LinearRegressionOutput")
def linear_regression_output(data, label, *, grad_scale=1.0):
    @jax.custom_vjp
    def _lr(x, y):
        return x

    def fwd(x, y):
        return x, (x, y)

    def bwd(res, g):
        x, y = res
        n = x.shape[0]
        return ((x - y) * grad_scale / 1.0, jnp.zeros_like(y))

    _lr.defvjp(fwd, bwd)
    return _lr(data, label.reshape(data.shape))


@register("MAERegressionOutput")
def mae_regression_output(data, label, *, grad_scale=1.0):
    @jax.custom_vjp
    def _mae(x, y):
        return x

    def fwd(x, y):
        return x, (x, y)

    def bwd(res, g):
        x, y = res
        return (jnp.sign(x - y) * grad_scale, jnp.zeros_like(y))

    _mae.defvjp(fwd, bwd)
    return _mae(data, label.reshape(data.shape))


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, *, grad_scale=1.0):
    @jax.custom_vjp
    def _log(x, y):
        return jax.nn.sigmoid(x)

    def fwd(x, y):
        out = jax.nn.sigmoid(x)
        return out, (out, y)

    def bwd(res, g):
        out, y = res
        return ((out - y) * grad_scale, jnp.zeros_like(y))

    _log.defvjp(fwd, bwd)
    return _log(data, label.reshape(data.shape))


@register("smooth_l1")
def smooth_l1(data, *, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(data) < 1.0 / s2,
                     0.5 * s2 * jnp.square(data),
                     jnp.abs(data) - 0.5 / s2)


@register("CTCLoss", aliases=["ctc_loss"])
def ctc_loss(data, label, data_lengths=None, label_lengths=None, *,
             use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    # reference: src/operator/nn/ctc_loss.cc.  Forward-backward in log space
    # via lax.scan over time — compiler-friendly control flow.
    # data: (seq, batch, alphabet) unnormalized; label: (batch, L) padded with
    # -1 (or 0 when blank_label='last').
    seq_len, batch, alphabet = data.shape
    logprob = jax.nn.log_softmax(data, axis=-1)
    L = label.shape[1]
    blank = 0 if blank_label == "first" else alphabet - 1
    lab = label.astype(jnp.int32)
    if blank_label == "first":
        valid = lab > 0 if not use_label_lengths else (
            jnp.arange(L)[None, :] < label_lengths.astype(jnp.int32)[:, None])
    else:
        valid = lab >= 0 if not use_label_lengths else (
            jnp.arange(L)[None, :] < label_lengths.astype(jnp.int32)[:, None])
    lab_len = jnp.sum(valid.astype(jnp.int32), axis=1)
    # extended label sequence with interleaved blanks: length 2L+1
    S = 2 * L + 1
    ext = jnp.full((batch, S), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(jnp.where(valid, lab, blank))
    ext_len = 2 * lab_len + 1
    neg_inf = -1e30

    def emit(t):
        # (batch, S) log p of emitting ext symbol at time t
        return jnp.take_along_axis(logprob[t], ext, axis=1)

    alpha0 = jnp.full((batch, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logprob[0, :, blank])
    alpha0 = alpha0.at[:, 1].set(jnp.take_along_axis(logprob[0], ext[:, 1:2], axis=1)[:, 0])

    same = jnp.pad(ext[:, 2:] == ext[:, :-2], ((0, 0), (2, 0)), constant_values=True)

    def step(alpha, t):
        a = alpha
        a1 = jnp.pad(alpha[:, :-1], ((0, 0), (1, 0)), constant_values=neg_inf)
        a2 = jnp.pad(alpha[:, :-2], ((0, 0), (2, 0)), constant_values=neg_inf)
        a2 = jnp.where(same, neg_inf, a2)
        new = jnp.logaddexp(jnp.logaddexp(a, a1), a2) + emit(t)
        if use_data_lengths and data_lengths is not None:
            live = (t < data_lengths.astype(jnp.int32))[:, None]
            new = jnp.where(live, new, alpha)
        return new, None

    alphaT, _ = jax.lax.scan(step, alpha0, jnp.arange(1, seq_len))
    idx_last = (ext_len - 1)[:, None]
    last2 = jnp.concatenate([
        jnp.take_along_axis(alphaT, idx_last, axis=1),
        jnp.take_along_axis(alphaT, jnp.maximum(idx_last - 1, 0), axis=1),
    ], axis=1)
    ll = jnp.logaddexp(last2[:, 0], last2[:, 1])
    return -ll


# ---------------------------------------------------------------------------
# upsampling / image-ish nn ops
# ---------------------------------------------------------------------------


@register("UpSampling", variadic=True)
def upsampling(*data, scale=1, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=512):
    x = data[0]
    if sample_type == "nearest":
        # reference upsampling.cc: EVERY input is upsampled to the common
        # output size data[0].shape * scale — inputs may have different
        # resolutions (FPN-style), each gets its own integer factor
        out_h, out_w = x.shape[2] * scale, x.shape[3] * scale
        ups = [jnp.repeat(jnp.repeat(d, out_h // d.shape[2], axis=2),
                          out_w // d.shape[3], axis=3)
               for d in data]
        if len(ups) == 1:
            return ups[0]
        if multi_input_mode == "sum":
            out = ups[0]
            for u in ups[1:]:
                out = out + u
            return out
        return jnp.concatenate(ups, axis=1)
    if sample_type == "bilinear":
        # reference upsampling.cc: bilinear mode IS a Deconvolution with a
        # caller-supplied (usually bilinear-initialized, learnable) kernel:
        # kernel=2*scale-scale%2, stride=scale, pad=ceil((scale-1)/2)
        if len(data) < 2:
            raise ValueError(
                "UpSampling(sample_type='bilinear') needs a weight input "
                "(reference: upsampling.cc bilinear = Deconvolution)")
        w = data[1]  # (C, 1, k, k): depthwise bilinear kernel, learnable
        k = 2 * scale - scale % 2
        p = scale // 2
        return deconvolution(
            x, w, None, kernel=(k, k), stride=(scale, scale), pad=(p, p),
            num_filter=x.shape[1], num_group=x.shape[1], no_bias=True)
    raise ValueError(f"UpSampling: unknown sample_type {sample_type!r}")


@register("_contrib_BilinearResize2D", aliases=["BilinearResize2D"])
def bilinear_resize_2d(data, *, height=0, width=0, scale_height=None,
                       scale_width=None, mode="size", align_corners=True):
    n, c, h, w = data.shape
    out_h = int(height or round(h * (scale_height or 1)))
    out_w = int(width or round(w * (scale_width or 1)))
    x = jnp.moveaxis(data, 1, -1)
    x = jax.image.resize(x, (n, out_h, out_w, c), method="bilinear")
    return jnp.moveaxis(x, -1, 1)


@register("GridGenerator")
def grid_generator(data, *, transform_type="affine", target_shape=(0, 0)):
    h, w = target_shape
    ys = jnp.linspace(-1.0, 1.0, h)
    xs = jnp.linspace(-1.0, 1.0, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()], axis=0)  # (3, h*w)
    theta = data.reshape(-1, 2, 3)
    out = jnp.einsum("nij,jk->nik", theta, base)  # (n, 2, h*w)
    return out.reshape(-1, 2, h, w)


@register("mish")
def mish(data):
    # reference: src/operator/nn/activation.cc act_type mish (also reachable
    # via Activation(act_type="mish"))
    return data * jnp.tanh(jax.nn.softplus(data))


@register("im2col", attrs=[
    attr("kernel", tuple, "Sliding window size."),
])
def im2col(data, *, kernel=(), stride=(), dilate=(), pad=()):
    """reference: src/operator/nn/im2col.h — unfold conv patches.

    data (N, C, H, W) -> (N, C*prod(kernel), prod(out_spatial)); the
    gather is conv_general_dilated_patches, which XLA lowers without
    materializing per-tap copies until the consumer needs them.
    """
    nd = len(kernel)
    stride = _tuplize(stride or 1, nd)
    dilate = _tuplize(dilate or 1, nd)
    pad = _tuplize(pad or 0, nd)
    spatial = "DHW"[-nd:]
    lhs = "NC" + spatial
    patches = jax.lax.conv_general_dilated_patches(
        data, tuple(kernel), stride, [(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=(lhs, "OI" + spatial, lhs))
    n = patches.shape[0]
    return patches.reshape(n, patches.shape[1], -1)


@register("col2im", attrs=[
    attr("kernel", tuple, "Sliding window size."),
])
def col2im(data, *, output_size=(), kernel=(), stride=(), dilate=(),
           pad=()):
    """reference: src/operator/nn/im2col.h col2im — scatter-add patches
    back. Implemented as the exact VJP of im2col (the two are adjoint by
    definition), so overlap accumulation is XLA's scatter fusion."""
    nd = len(kernel)
    n, ckk = data.shape[0], data.shape[1]
    c = ckk
    for k in tuple(kernel):
        c //= k
    x_shape = (n, c) + tuple(output_size)
    zero = jnp.zeros(x_shape, dtype=data.dtype)
    _, pull = jax.vjp(
        lambda x: im2col(x, kernel=kernel, stride=stride, dilate=dilate,
                         pad=pad), zero)
    (out,) = pull(data)
    return out


@register("Convolution_v1", aliases=["convolution_v1"])
def convolution_v1(data, weight, bias=None, **kwargs):
    # reference: src/operator/convolution_v1.cc — legacy alias with the
    # modern op's semantics
    return convolution(data, weight, bias, **kwargs)


@register("Pooling_v1", aliases=["pooling_v1"], attrs=[])
def pooling_v1(data, **kwargs):
    return pooling(data, **kwargs)


@register("Crop", eager_only=False)
def crop(*inputs, offset=(0, 0), h_w=(0, 0), center_crop=False,
         num_args=1):
    """reference: src/operator/crop.cc — crop data (NCHW) to h_w or to the
    second input's spatial size."""
    data = inputs[0]
    if len(inputs) > 1:
        th, tw = inputs[1].shape[2], inputs[1].shape[3]
    else:
        th, tw = h_w
        if th <= 0 or tw <= 0:
            raise ValueError(
                "Crop: h_w must be given (positive) when no crop_like "
                "input is passed (reference crop.cc parameter check)")
    h, w = data.shape[2], data.shape[3]
    if center_crop:
        oy, ox = (h - th) // 2, (w - tw) // 2
    else:
        oy, ox = offset
    return data[:, :, oy:oy + th, ox:ox + tw]


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    # reference: src/operator/loss_binary_op.cc — summed scalar CE over
    # the batch, labels are class indices
    lp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        lp, label.astype(jnp.int32).reshape(-1, 1), axis=-1)
    return -jnp.sum(picked)


@_partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _identity_kl_reg(data, sparseness_target, penalty):
    return data


def _identity_kl_fwd(data, sparseness_target, penalty):
    return data, data


def _identity_kl_bwd(sparseness_target, penalty, data, dy):
    rho = sparseness_target
    rho_hat = jnp.clip(jnp.mean(data.astype(jnp.float32), axis=0),
                       1e-6, 1 - 1e-6)
    kl_grad = penalty * (-rho / rho_hat + (1 - rho) / (1 - rho_hat))
    return (dy + kl_grad.astype(dy.dtype),)


_identity_kl_reg.defvjp(_identity_kl_fwd, _identity_kl_bwd)


@register("IdentityAttachKLSparseReg")
def identity_attach_kl_sparse_reg(data, *, sparseness_target=0.1,
                                  penalty=0.001, momentum=0.9):
    """reference: src/operator/identity_attach_KL_sparse_reg.cc —
    identity forward; backward adds the KL sparsity penalty gradient
    computed from the batch mean activation (the reference's moving
    average collapses to the batch mean in a pure-function graph)."""
    return _identity_kl_reg(data, float(sparseness_target), float(penalty))
