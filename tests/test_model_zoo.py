"""Vision model-zoo tests (reference strategy: tests/python/unittest/
test_gluon_model_zoo.py — build each family, forward a small batch)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision


def _forward(net, hw=64, classes=10, batch=2):
    net.initialize()
    x = mx.nd.random.uniform(shape=(batch, 3, hw, hw))
    y = net(x)
    assert y.shape == (batch, classes)
    assert np.isfinite(y.asnumpy()).all()


def test_resnet_thumbnail():
    net = vision.resnet18_v1(classes=10, thumbnail=True)
    _forward(net, hw=32)


def test_resnet_v2_thumbnail():
    net = vision.resnet18_v2(classes=10, thumbnail=True)
    _forward(net, hw=32)


def test_resnet_bottleneck():
    net = vision.resnet50_v1(classes=10, thumbnail=True)
    _forward(net, hw=32)


def test_mobilenet_v1():
    _forward(vision.mobilenet0_25(classes=10), hw=64)


@pytest.mark.slow   # ~16 s of per-op eager compiles; v1 keeps the family
def test_mobilenet_v2():
    _forward(vision.mobilenet_v2_0_25(classes=10), hw=64)


@pytest.mark.slow   # ~27 s of per-op eager compiles (hard-swish, SE blocks)
def test_mobilenet_v3():
    _forward(vision.mobilenet_v3_small(classes=10), hw=64)


def test_squeezenet():
    _forward(vision.squeezenet1_1(classes=10), hw=64)


def test_vgg():
    _forward(vision.vgg11(classes=10), hw=64)


@pytest.mark.slow   # ~12 s: 224x224 through 9216-wide dense layers
def test_alexnet():
    _forward(vision.alexnet(classes=10), hw=224, batch=1)


def test_densenet():
    # every dense layer has its own channel count, i.e. its own eager
    # compile: two layers a block cover the family's forward (the 121
    # config itself is constructed by test_get_model_registry)
    _forward(vision.DenseNet(64, 32, [2, 2, 2, 2], classes=10), hw=224,
             batch=1)


@pytest.mark.slow   # ~25 s of per-op eager compiles at 299x299
def test_inception():
    _forward(vision.inception_v3(classes=10), hw=299, batch=1)


def test_get_model_registry():
    net = vision.get_model("resnet18_v1", classes=10, thumbnail=True)
    _forward(net, hw=32)
    with pytest.raises(mx.MXNetError):
        vision.get_model("resnet999")
    # every registered name constructs without forward
    assert len(vision._models) >= 36


def test_zoo_hybridize_matches_eager():
    net = vision.resnet18_v1(classes=10, thumbnail=True)
    net.initialize()
    x = mx.nd.random.uniform(shape=(2, 3, 32, 32))
    y_eager = net(x).asnumpy()
    net.hybridize()
    y_jit = net(x).asnumpy()
    np.testing.assert_allclose(y_eager, y_jit, rtol=2e-5, atol=2e-5)


def test_zoo_save_load_roundtrip(tmp_path):
    net = vision.mobilenet_v2_0_25(classes=10)
    net.initialize()
    x = mx.nd.random.uniform(shape=(1, 3, 64, 64))
    y0 = net(x).asnumpy()
    f = str(tmp_path / "m.params")
    net.save_parameters(f)
    net2 = vision.mobilenet_v2_0_25(classes=10)
    net2.load_parameters(f)
    np.testing.assert_allclose(y0, net2(x).asnumpy(), rtol=1e-6, atol=1e-6)
