"""Channels-last (NHWC) internal layout: ops, blocks, and zoo parity.

The TPU-preferred conv layout is channels-last; ``nn.conv_layout("NHWC")``
switches block construction defaults while weights stay OIHW so the same
checkpoint loads into either layout. These tests pin the numerical parity
NCHW <-> NHWC across conv/pool/BN and a small zoo model.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon import nn


def _rand(shape, seed=0):
    return mx.nd.array(np.random.RandomState(seed).uniform(-1, 1, shape).astype(np.float32))


def test_convolution_op_nhwc_matches_nchw():
    x = _rand((2, 4, 9, 9))
    w = _rand((8, 4, 3, 3), seed=1)
    b = _rand((8,), seed=2)
    y_ref = nd.Convolution(x, w, b, kernel=(3, 3), num_filter=8, stride=(2, 2),
                           pad=(1, 1))
    y_nhwc = nd.Convolution(x.transpose((0, 2, 3, 1)), w, b, kernel=(3, 3),
                            num_filter=8, stride=(2, 2), pad=(1, 1),
                            layout="NHWC")
    np.testing.assert_allclose(y_nhwc.transpose((0, 3, 1, 2)).asnumpy(),
                               y_ref.asnumpy(), rtol=1e-5, atol=1e-5)


def test_grouped_convolution_nhwc():
    x = _rand((2, 4, 8, 8))
    w = _rand((8, 2, 3, 3), seed=1)
    y_ref = nd.Convolution(x, w, None, kernel=(3, 3), num_filter=8,
                           num_group=2, pad=(1, 1), no_bias=True)
    y_nhwc = nd.Convolution(x.transpose((0, 2, 3, 1)), w, None, kernel=(3, 3),
                            num_filter=8, num_group=2, pad=(1, 1),
                            no_bias=True, layout="NHWC")
    np.testing.assert_allclose(y_nhwc.transpose((0, 3, 1, 2)).asnumpy(),
                               y_ref.asnumpy(), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pool_type", ["max", "avg"])
def test_pooling_op_nhwc(pool_type):
    x = _rand((2, 3, 9, 9))
    y_ref = nd.Pooling(x, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                       pool_type=pool_type)
    y_nhwc = nd.Pooling(x.transpose((0, 2, 3, 1)), kernel=(3, 3),
                        stride=(2, 2), pad=(1, 1), pool_type=pool_type,
                        layout="NHWC")
    np.testing.assert_allclose(y_nhwc.transpose((0, 3, 1, 2)).asnumpy(),
                               y_ref.asnumpy(), rtol=1e-5, atol=1e-5)


def test_global_pool_nhwc():
    x = _rand((2, 3, 5, 5))
    y_ref = nd.Pooling(x, global_pool=True, pool_type="avg", kernel=(1, 1))
    y_nhwc = nd.Pooling(x.transpose((0, 2, 3, 1)), global_pool=True,
                        pool_type="avg", kernel=(1, 1), layout="NHWC")
    np.testing.assert_allclose(y_nhwc.transpose((0, 3, 1, 2)).asnumpy(),
                               y_ref.asnumpy(), rtol=1e-6, atol=1e-6)


def test_deconvolution_nhwc_matches_nchw():
    x = _rand((2, 4, 5, 5))
    w = _rand((4, 6, 3, 3), seed=1)  # Deconvolution weight: (in, out, kh, kw)
    b = _rand((6,), seed=2)
    y_ref = nd.Deconvolution(x, w, b, kernel=(3, 3), num_filter=6,
                             stride=(2, 2), pad=(1, 1), no_bias=False)
    y_nhwc = nd.Deconvolution(x.transpose((0, 2, 3, 1)), w, b, kernel=(3, 3),
                              num_filter=6, stride=(2, 2), pad=(1, 1),
                              no_bias=False, layout="NHWC")
    np.testing.assert_allclose(y_nhwc.transpose((0, 3, 1, 2)).asnumpy(),
                               y_ref.asnumpy(), rtol=1e-5, atol=1e-5)


def test_conv_layout_context_defaults():
    with nn.conv_layout("NHWC"):
        conv = nn.Conv2D(4, 3, padding=1)
        pool = nn.MaxPool2D(2)
        bn = nn.BatchNorm()
    assert conv._layout == "NHWC"
    assert pool._kwargs["layout"] == "NHWC"
    assert bn._axis == -1
    # outside the context the defaults are unchanged
    assert nn.Conv2D(4, 3)._layout == "NCHW"
    assert nn.BatchNorm()._axis == 1
    # explicit channels-last outside any context still honored
    assert nn.Conv2D(4, 3, layout="NHWC")._layout == "NHWC"


def test_batchnorm_axis_last_matches_axis1():
    x = _rand((2, 6, 4, 4))
    bn1 = nn.BatchNorm(in_channels=6)
    bn2 = nn.BatchNorm(axis=-1, in_channels=6)
    bn1.initialize()
    bn2.initialize()
    with mx.autograd.record():
        y1 = bn1(x)
    with mx.autograd.record():
        y2 = bn2(x.transpose((0, 2, 3, 1)))
    np.testing.assert_allclose(y2.transpose((0, 3, 1, 2)).asnumpy(),
                               y1.asnumpy(), rtol=1e-5, atol=1e-5)


def test_resnet_nhwc_parity_and_train_step():
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1

    net1 = resnet18_v1(classes=10, thumbnail=True)
    net2 = resnet18_v1(classes=10, thumbnail=True, layout="NHWC")
    net1.initialize()
    net2.initialize()
    x = _rand((2, 3, 32, 32))
    y1 = net1(x)
    y2 = net2(x)  # settles deferred shapes
    # insertion order is structural (same build order in both nets); the
    # name counters differ across nets so sorted names would misalign
    p1, p2 = net1.collect_params(), net2.collect_params()
    for k1, k2 in zip(list(p1), list(p2)):
        p2[k2].set_data(p1[k1].data())
    y2 = net2(x)
    assert y2.shape == y1.shape == (2, 10)
    np.testing.assert_allclose(y2.asnumpy(), y1.asnumpy(), rtol=2e-4,
                               atol=2e-4)
    # gradients flow through the NHWC path
    from mxnet_tpu.gluon import loss as gloss

    lossfn = gloss.SoftmaxCrossEntropyLoss()
    label = mx.nd.array(np.array([1, 2], dtype=np.float32))
    with mx.autograd.record():
        out = lossfn(net2(x), label)
    out.backward()
    g = net2.collect_params()[list(p2)[0]].grad()
    assert float(np.abs(g.asnumpy()).sum()) > 0


def test_conv_layout_keeps_explicit_channels_first():
    """Round-3 advisor finding: an EXPLICIT layout='NCHW' (or BatchNorm
    axis=1) inside conv_layout('NHWC') must be kept, not flipped."""
    with nn.conv_layout("NHWC"):
        default_conv = nn.Conv2D(4, 3)
        explicit_conv = nn.Conv2D(4, 3, layout="NCHW")
        default_bn = nn.BatchNorm()
        explicit_bn = nn.BatchNorm(axis=1)
    assert default_conv._layout == "NHWC"
    assert explicit_conv._layout == "NCHW"
    assert default_bn._axis == -1
    assert explicit_bn._axis == 1
    # outside any context the defaults are channels-first
    assert nn.Conv2D(4, 3)._layout == "NCHW"
    assert nn.BatchNorm()._axis == 1


def test_pooling_convention_same():
    """pooling_convention='same' implements TF SAME: out = ceil(in/stride),
    avg excludes the implicit pad cells only via count_include_pad."""
    x = _rand((1, 1, 5, 5))
    out = nd.Pooling(x, kernel=(3, 3), stride=(2, 2), pool_type="max",
                     pooling_convention="same")
    assert out.shape == (1, 1, 3, 3)
    # oracle: manual pad to SAME then valid pooling
    xa = x.asnumpy()[0, 0]
    padded = np.full((7, 7), -np.inf, "float32")
    padded[1:6, 1:6] = xa
    want = np.stack([[padded[r:r + 3, c:c + 3].max()
                      for c in (0, 2, 4)] for r in (0, 2, 4)])
    np.testing.assert_allclose(out.asnumpy()[0, 0], want, rtol=1e-6)
    with pytest.raises(Exception, match="same"):
        nd.Pooling(x, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                   pooling_convention="same")


def test_conv_dw_patches_matches_vjp(monkeypatch):
    """MXNET_TPU_CONV_DW=patches (the im2col dW experiment path) must
    produce the same gradients as XLA's conv backward."""
    from mxnet_tpu import autograd

    rs = np.random.RandomState(0)
    x_np = rs.randn(2, 9, 9, 5).astype("float32")
    w_np = rs.randn(6, 5, 3, 3).astype("float32") * 0.1
    grads = {}
    for mode in ("vjp", "patches"):
        monkeypatch.setenv("MXNET_TPU_CONV_DW", mode)
        x, w = mx.nd.array(x_np), mx.nd.array(w_np)
        x.attach_grad(); w.attach_grad()
        with mx.autograd.record():
            y = nd.Convolution(x, w, kernel=(3, 3), stride=(2, 2),
                               pad=(1, 1), num_filter=6, no_bias=True,
                               layout="NHWC")
            ((y * y).sum()).backward()
        grads[mode] = (x.grad.asnumpy(), w.grad.asnumpy())
    np.testing.assert_allclose(grads["patches"][0], grads["vjp"][0],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(grads["patches"][1], grads["vjp"][1],
                               rtol=2e-3, atol=2e-3)


class TestHandDerivedVJPs:
    """Round-4 perf paths: hand-derived BN backward + 1x1-conv-as-dot.

    Both replace autodiff-derived backward graphs with closed-form VJPs
    (PERF_HISTORY.md round 4: the autodiff BN backward carried ~7 full-tensor
    reductions; 1x1 conv backward sat in XLA's conv algorithm selection).
    Gates: gradients must match the plain formulation to fp tolerance.
    """

    def _bn_ref(self, x, g, b, eps):
        import jax
        import jax.numpy as jnp
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.var(x, axis=(0, 1, 2))
        inv = jax.lax.rsqrt(var + eps)
        return (x - mean) * inv * g + b

    def test_bn_train_grads_match_autodiff(self):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ops import nn as opsnn
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(4, 5, 6, 7).astype(np.float32))
        g = jnp.asarray(rs.rand(7).astype(np.float32) + 0.5)
        b = jnp.asarray(rs.randn(7).astype(np.float32))
        eps = 1e-3
        dy = jnp.asarray(rs.randn(4, 5, 6, 7).astype(np.float32))
        o1, vjp1 = jax.vjp(lambda *a: self._bn_ref(*a, eps), x, g, b)
        o2, vjp2 = jax.vjp(lambda *a: opsnn._bn_train(3, eps, *a)[0], x, g, b)
        np.testing.assert_allclose(o1, o2, atol=1e-5)
        for got, want in zip(vjp2(dy), vjp1(dy)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-4)

    def test_bn_train_stats_outputs(self):
        import jax.numpy as jnp
        from mxnet_tpu.ops import nn as opsnn
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.randn(3, 4, 5, 6).astype(np.float32))
        g = jnp.ones((6,), np.float32)
        b = jnp.zeros((6,), np.float32)
        _, mean, var = opsnn._bn_train(3, 1e-3, x, g, b)
        np.testing.assert_allclose(mean, np.mean(np.asarray(x), axis=(0, 1, 2)),
                                   atol=1e-5)
        np.testing.assert_allclose(var, np.var(np.asarray(x), axis=(0, 1, 2)),
                                   atol=1e-4)

    def test_bn_channel_axis_1(self):
        """NCHW (axis=1) goes through the same custom-vjp path."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ops import nn as opsnn
        rs = np.random.RandomState(5)
        x = jnp.asarray(rs.randn(2, 5, 4, 4).astype(np.float32))
        g = jnp.asarray(rs.rand(5).astype(np.float32) + 0.5)
        b = jnp.asarray(rs.randn(5).astype(np.float32))

        def ref(x, g, b):
            import jax as _jax
            mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
            var = jnp.var(x, axis=(0, 2, 3), keepdims=True)
            inv = _jax.lax.rsqrt(var + 1e-3)
            return (x - mean) * inv * g.reshape(1, -1, 1, 1) \
                + b.reshape(1, -1, 1, 1)

        dy = jnp.asarray(rs.randn(2, 5, 4, 4).astype(np.float32))
        o1, vjp1 = jax.vjp(ref, x, g, b)
        o2, vjp2 = jax.vjp(lambda *a: opsnn._bn_train(1, 1e-3, *a)[0],
                           x, g, b)
        np.testing.assert_allclose(o1, o2, atol=1e-5)
        for got, want in zip(vjp2(dy), vjp1(dy)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-4)

    def test_conv1x1_dot_grads_match_conv(self):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ops import nn as opsnn
        rs = np.random.RandomState(6)
        x = jnp.asarray(rs.randn(2, 5, 6, 8).astype(np.float32))
        w = jnp.asarray(rs.randn(12, 8, 1, 1).astype(np.float32))

        def conv_ref(x, w):
            dn = jax.lax.conv_dimension_numbers(
                x.shape, w.shape, ("NHWC", "OIHW", "NHWC"))
            return jax.lax.conv_general_dilated(
                x, w, (1, 1), [(0, 0), (0, 0)], dimension_numbers=dn)

        o1, vjp1 = jax.vjp(conv_ref, x, w)
        o2, vjp2 = jax.vjp(opsnn._conv1x1_dot, x, w)
        np.testing.assert_allclose(o1, o2, atol=1e-4)
        dy = jnp.asarray(rs.randn(*o1.shape).astype(np.float32))
        for got, want, tol in zip(vjp2(dy), vjp1(dy), (1e-4, 1e-3)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=tol)

    def test_conv1x1_dot_used_by_convolution_op(self):
        """nd.Convolution on a stride-1 1x1 NHWC conv routes to the dot
        path and still matches the NCHW conv formulation."""
        x = _rand((2, 8, 6, 6))
        w = _rand((12, 8, 1, 1), seed=1)
        y_ref = nd.Convolution(x, w, None, kernel=(1, 1), num_filter=12,
                               no_bias=True)
        y_nhwc = nd.Convolution(x.transpose((0, 2, 3, 1)), w, None,
                                kernel=(1, 1), num_filter=12, no_bias=True,
                                layout="NHWC")
        np.testing.assert_allclose(y_nhwc.transpose((0, 3, 1, 2)).asnumpy(),
                                   y_ref.asnumpy(), rtol=1e-4, atol=1e-4)

    def test_conv_s2d_stem_matches_direct(self):
        """The ResNet-stem rewrite (stride-2 large-kernel conv as
        space-to-depth + stride-1 conv) is an exact re-indexing: fwd and
        both grads match the direct conv bitwise-close."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ops import nn as opsnn
        rs = np.random.RandomState(7)
        for k in (7, 5):
            pad = (k - 1) // 2
            x = jnp.asarray(rs.randn(2, 16, 16, 3).astype(np.float32))
            w = jnp.asarray(rs.randn(8, 3, k, k).astype(np.float32) * 0.1)
            dn = jax.lax.conv_dimension_numbers(
                x.shape, w.shape, ("NHWC", "OIHW", "NHWC"))

            def ref(x, w):
                return jax.lax.conv_general_dilated(
                    x, w, (2, 2), [(pad, pad)] * 2, dimension_numbers=dn)

            o1, vjp1 = jax.vjp(ref, x, w)
            o2, vjp2 = jax.vjp(
                lambda x, w: opsnn._conv_s2d(x, w, (k, k)), x, w)
            np.testing.assert_allclose(o1, o2, atol=1e-4)
            dy = jnp.asarray(rs.randn(*o1.shape).astype(np.float32))
            for got, want in zip(vjp2(dy), vjp1(dy)):
                np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                           atol=1e-3)

    def test_conv1x1_strided_dot_grads_match_conv(self):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ops import nn as opsnn
        rs = np.random.RandomState(8)
        x = jnp.asarray(rs.randn(2, 8, 8, 6).astype(np.float32))
        w = jnp.asarray(rs.randn(10, 6, 1, 1).astype(np.float32))
        dn = jax.lax.conv_dimension_numbers(
            x.shape, w.shape, ("NHWC", "OIHW", "NHWC"))

        def ref(x, w):
            return jax.lax.conv_general_dilated(
                x, w, (2, 2), [(0, 0), (0, 0)], dimension_numbers=dn)

        o1, vjp1 = jax.vjp(ref, x, w)
        o2, vjp2 = jax.vjp(
            lambda x, w: opsnn._conv1x1_strided_dot(x, w, (2, 2)), x, w)
        np.testing.assert_allclose(o1, o2, atol=1e-5)
        dy = jnp.asarray(rs.randn(*o1.shape).astype(np.float32))
        for got, want in zip(vjp2(dy), vjp1(dy)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-4)

    def test_stem_conv_op_s2d_parity(self, monkeypatch):
        """nd.Convolution with the exact ResNet stem geometry (7x7/s2/p3,
        3 channels, NHWC) routes through the s2d rewrite and matches the
        NCHW direct formulation. The route itself is asserted (a spy on
        _conv_s2d) so a dispatch-guard regression cannot silently fall
        back to the direct conv with a green test."""
        from mxnet_tpu.ops import nn as opsnn
        calls = []
        real = opsnn._conv_s2d
        monkeypatch.setattr(
            opsnn, "_conv_s2d",
            lambda x, w, k: calls.append(k) or real(x, w, k))
        x = _rand((2, 3, 16, 16))
        w = _rand((8, 3, 7, 7), seed=1)
        y_ref = nd.Convolution(x, w, None, kernel=(7, 7), num_filter=8,
                               stride=(2, 2), pad=(3, 3), no_bias=True)
        y_nhwc = nd.Convolution(x.transpose((0, 2, 3, 1)), w, None,
                                kernel=(7, 7), num_filter=8, stride=(2, 2),
                                pad=(3, 3), no_bias=True, layout="NHWC")
        assert calls == [(7, 7)], "stem conv did not route through s2d"
        np.testing.assert_allclose(y_nhwc.transpose((0, 3, 1, 2)).asnumpy(),
                                   y_ref.asnumpy(), rtol=1e-4, atol=1e-4)
