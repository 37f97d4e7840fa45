"""Convolution and pooling layers.

Reference: ``python/mxnet/gluon/nn/conv_layers.py`` — `_Conv` base,
Conv1D/2D/3D (+Transpose), Max/Avg pools 1/2/3D, Global pools,
ReflectionPad2D.

TPU layout note: MXNet's API default is channels-first (NCHW), but the
TPU conv emitters want channels-last (the lane dimension is the channel
dimension — NCHW convs compile with activation relayouts on both sides).
``conv_layout("NHWC")`` switches the *default* layout of every
conv/pool/BatchNorm block constructed inside the context, so a whole model
can be built channels-last with one line while weights stay OIHW
(checkpoints are layout-independent). See PERF_HISTORY.md round 3.
"""
from __future__ import annotations

import contextlib

from ...base import MXNetError
from ..block import HybridBlock

__all__ = ["Conv1D", "Conv2D", "Conv3D", "Conv1DTranspose", "Conv2DTranspose",
           "Conv3DTranspose", "MaxPool1D", "MaxPool2D", "MaxPool3D",
           "AvgPool1D", "AvgPool2D", "AvgPool3D", "GlobalMaxPool1D",
           "GlobalMaxPool2D", "GlobalMaxPool3D", "GlobalAvgPool1D",
           "GlobalAvgPool2D", "GlobalAvgPool3D", "ReflectionPad2D",
           "conv_layout", "current_conv_layout"]

_CHANNELS_LAST = {1: "NWC", 2: "NHWC", 3: "NDHWC"}
_CHANNELS_FIRST = {1: "NCW", 2: "NCHW", 3: "NCDHW"}
_layout_override = [None]  # "channels_last" | "channels_first" | None


class _DefaultLayout(str):
    """Signature-default layout marker: compares/prints as the plain
    string, but lets ``conv_layout`` distinguish "caller kept the
    default" from "caller explicitly asked for channels-first" — an
    explicit ``layout='NCHW'`` inside ``conv_layout('NHWC')`` is kept
    (round-3 advisor finding: it used to be silently flipped)."""


_NCW = _DefaultLayout("NCW")
_NCHW = _DefaultLayout("NCHW")
_NCDHW = _DefaultLayout("NCDHW")


@contextlib.contextmanager
def conv_layout(layout):
    """Build-time default-layout context: ``with conv_layout("NHWC"): ...``.

    Inside the context every conv/pool/BatchNorm block whose caller did not
    choose a non-default layout is constructed channels-last ("NCHW" etc.
    restores channels-first). Affects block CONSTRUCTION only — a built
    block's layout is fixed.
    """
    mode = "channels_last" if layout.endswith("C") else "channels_first"
    prev = _layout_override[0]
    _layout_override[0] = mode
    try:
        yield
    finally:
        _layout_override[0] = prev


def current_conv_layout(ndim=2):
    """The layout a conv/pool block built right now would default to."""
    if _layout_override[0] == "channels_last":
        return _CHANNELS_LAST[ndim]
    return _CHANNELS_FIRST[ndim]


def _resolve_layout(layout, ndim):
    """Apply the conv_layout override to a block's layout argument.

    The override only replaces SIGNATURE-DEFAULT layouts (the
    ``_DefaultLayout`` sentinels): any layout the caller passed
    explicitly — channels-first included — is kept.
    """
    if _layout_override[0] == "channels_last" \
            and isinstance(layout, _DefaultLayout):
        return _CHANNELS_LAST[ndim]
    return str(layout)


def _tup(val, n):
    if isinstance(val, int):
        return (val,) * n
    return tuple(val)


class _Conv(HybridBlock):
    def __init__(self, channels, kernel_size, strides, padding, dilation,
                 groups, layout, in_channels=0, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 op_name="Convolution", adj=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._channels = channels
        self._in_channels = in_channels
        layout = _resolve_layout(layout, len(kernel_size))
        self._layout = layout
        ndim = len(kernel_size)
        self._kwargs = {
            "kernel": kernel_size, "stride": strides, "dilate": dilation,
            "pad": padding, "num_filter": channels, "num_group": groups,
            "layout": layout,
        }
        self._op_name = op_name
        if adj is not None:
            self._kwargs["adj"] = adj
        with self.name_scope():
            if op_name == "Convolution":
                wshape = (channels, in_channels // groups if in_channels else 0) + tuple(kernel_size)
            else:  # Deconvolution: weight is (in, out//groups, *k)
                wshape = (in_channels, channels // groups) + tuple(kernel_size)
            self.weight = self.params.get(
                "weight", shape=wshape, init=weight_initializer,
                allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(channels,), init=bias_initializer)
            else:
                self.bias = None
            from .basic_layers import _make_activation

            self.act = _make_activation(activation, self)

    def _infer_param_shapes(self, x, *rest):
        in_c = x.shape[-1 if (self._layout or "").endswith("C") else 1]
        w = list(self.weight.shape)
        if self._op_name == "Convolution":
            w[1] = in_c // self._kwargs["num_group"]
        else:
            w[0] = in_c
        self.weight._finish_deferred_init(tuple(w))

    def hybrid_forward(self, F, x, weight, bias=None):
        op = getattr(F, self._op_name)
        out = op(x, weight, bias, no_bias=bias is None, **self._kwargs)
        if self.act is not None:
            out = self.act(out)
        return out

    def __repr__(self):
        return (f"{type(self).__name__}({self._channels}, "
                f"kernel_size={self._kwargs['kernel']}, "
                f"stride={self._kwargs['stride']})")


class Conv1D(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0, dilation=1,
                 groups=1, layout=_NCW, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        super().__init__(channels, _tup(kernel_size, 1), _tup(strides, 1),
                         _tup(padding, 1), _tup(dilation, 1), groups, layout,
                         in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv2D(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 dilation=(1, 1), groups=1, layout=_NCHW, activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        super().__init__(channels, _tup(kernel_size, 2), _tup(strides, 2),
                         _tup(padding, 2), _tup(dilation, 2), groups, layout,
                         in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv3D(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), dilation=(1, 1, 1), groups=1,
                 layout=_NCDHW, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        super().__init__(channels, _tup(kernel_size, 3), _tup(strides, 3),
                         _tup(padding, 3), _tup(dilation, 3), groups, layout,
                         in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv1DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, dilation=1, groups=1, layout=_NCW,
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        super().__init__(channels, _tup(kernel_size, 1), _tup(strides, 1),
                         _tup(padding, 1), _tup(dilation, 1), groups, layout,
                         in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="Deconvolution", adj=_tup(output_padding, 1),
                         **kwargs)


class Conv2DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 output_padding=(0, 0), dilation=(1, 1), groups=1,
                 layout=_NCHW, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        super().__init__(channels, _tup(kernel_size, 2), _tup(strides, 2),
                         _tup(padding, 2), _tup(dilation, 2), groups, layout,
                         in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="Deconvolution", adj=_tup(output_padding, 2),
                         **kwargs)


class Conv3DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), output_padding=(0, 0, 0),
                 dilation=(1, 1, 1), groups=1, layout=_NCDHW, activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        super().__init__(channels, _tup(kernel_size, 3), _tup(strides, 3),
                         _tup(padding, 3), _tup(dilation, 3), groups, layout,
                         in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="Deconvolution", adj=_tup(output_padding, 3),
                         **kwargs)


class _Pooling(HybridBlock):
    def __init__(self, pool_size, strides, padding, ceil_mode, global_pool,
                 pool_type, layout=None, count_include_pad=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if strides is None:
            strides = pool_size
        layout = _resolve_layout(layout, len(pool_size))
        self._kwargs = {
            "kernel": pool_size, "stride": strides, "pad": padding,
            "pool_type": pool_type, "global_pool": global_pool,
            "pooling_convention": "full" if ceil_mode else "valid",
            "layout": layout,
        }
        if count_include_pad is not None:
            self._kwargs["count_include_pad"] = count_include_pad

    def hybrid_forward(self, F, x):
        return F.Pooling(x, **self._kwargs)

    def __repr__(self):
        return (f"{type(self).__name__}(size={self._kwargs['kernel']}, "
                f"stride={self._kwargs['stride']}, "
                f"padding={self._kwargs['pad']})")


class MaxPool1D(_Pooling):
    def __init__(self, pool_size=2, strides=None, padding=0, layout=_NCW,
                 ceil_mode=False, **kwargs):
        super().__init__(_tup(pool_size, 1), _tup(strides, 1) if strides is not None else None,
                         _tup(padding, 1), ceil_mode, False, "max",
                         layout=layout, **kwargs)


class MaxPool2D(_Pooling):
    def __init__(self, pool_size=(2, 2), strides=None, padding=0,
                 layout=_NCHW, ceil_mode=False, **kwargs):
        super().__init__(_tup(pool_size, 2), _tup(strides, 2) if strides is not None else None,
                         _tup(padding, 2), ceil_mode, False, "max",
                         layout=layout, **kwargs)


class MaxPool3D(_Pooling):
    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 layout=_NCDHW, ceil_mode=False, **kwargs):
        super().__init__(_tup(pool_size, 3), _tup(strides, 3) if strides is not None else None,
                         _tup(padding, 3), ceil_mode, False, "max",
                         layout=layout, **kwargs)


class AvgPool1D(_Pooling):
    def __init__(self, pool_size=2, strides=None, padding=0, layout=_NCW,
                 ceil_mode=False, count_include_pad=True, **kwargs):
        super().__init__(_tup(pool_size, 1), _tup(strides, 1) if strides is not None else None,
                         _tup(padding, 1), ceil_mode, False, "avg",
                         layout=layout,
                         count_include_pad=count_include_pad, **kwargs)


class AvgPool2D(_Pooling):
    def __init__(self, pool_size=(2, 2), strides=None, padding=0,
                 layout=_NCHW, ceil_mode=False, count_include_pad=True,
                 **kwargs):
        super().__init__(_tup(pool_size, 2), _tup(strides, 2) if strides is not None else None,
                         _tup(padding, 2), ceil_mode, False, "avg",
                         layout=layout,
                         count_include_pad=count_include_pad, **kwargs)


class AvgPool3D(_Pooling):
    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 layout=_NCDHW, ceil_mode=False, count_include_pad=True,
                 **kwargs):
        super().__init__(_tup(pool_size, 3), _tup(strides, 3) if strides is not None else None,
                         _tup(padding, 3), ceil_mode, False, "avg",
                         layout=layout,
                         count_include_pad=count_include_pad, **kwargs)


class _GlobalPool(_Pooling):
    def __init__(self, ndim, pool_type, layout, **kwargs):
        super().__init__((1,) * ndim, (1,) * ndim, (0,) * ndim, False, True,
                         pool_type, layout=layout, **kwargs)


class GlobalMaxPool1D(_GlobalPool):
    def __init__(self, layout=_NCW, **kwargs):
        super().__init__(1, "max", layout, **kwargs)


class GlobalMaxPool2D(_GlobalPool):
    def __init__(self, layout=_NCHW, **kwargs):
        super().__init__(2, "max", layout, **kwargs)


class GlobalMaxPool3D(_GlobalPool):
    def __init__(self, layout=_NCDHW, **kwargs):
        super().__init__(3, "max", layout, **kwargs)


class GlobalAvgPool1D(_GlobalPool):
    def __init__(self, layout=_NCW, **kwargs):
        super().__init__(1, "avg", layout, **kwargs)


class GlobalAvgPool2D(_GlobalPool):
    def __init__(self, layout=_NCHW, **kwargs):
        super().__init__(2, "avg", layout, **kwargs)


class GlobalAvgPool3D(_GlobalPool):
    def __init__(self, layout=_NCDHW, **kwargs):
        super().__init__(3, "avg", layout, **kwargs)


class ReflectionPad2D(HybridBlock):
    def __init__(self, padding=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if isinstance(padding, int):
            padding = (0, 0, 0, 0, padding, padding, padding, padding)
        self._padding = tuple(padding)

    def hybrid_forward(self, F, x):
        return F.Pad(x, mode="reflect", pad_width=self._padding)
