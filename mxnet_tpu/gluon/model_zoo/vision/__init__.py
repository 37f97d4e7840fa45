"""Gluon vision model zoo.

Parity surface: python/mxnet/gluon/model_zoo/vision/__init__.py::get_model —
resnet v1/v2 (18-152), vgg (11-19, +bn), alexnet, densenet (121-201),
squeezenet (1.0/1.1), inception-v3, mobilenet v1/v2 (4 multipliers each),
plus mobilenet-v3 small/large (GluonCV milestone capability).

``pretrained=True`` resolves weights through ``model_store`` (sha1-verified
cache; ``$MXNET_GLUON_REPO`` may be an ``http(s)://`` or ``file://`` repo,
so air-gapped hosts serve weights from a shared filesystem).
"""
from __future__ import annotations

from . import alexnet as _alexnet
from . import densenet as _densenet
from . import inception as _inception
from . import mobilenet as _mobilenet
from . import resnet as _resnet
from . import squeezenet as _squeezenet
from . import vgg as _vgg

from .alexnet import *  # noqa: F401,F403
from .densenet import *  # noqa: F401,F403
from .inception import *  # noqa: F401,F403
from .mobilenet import *  # noqa: F401,F403
from .resnet import *  # noqa: F401,F403
from .squeezenet import *  # noqa: F401,F403
from .vgg import *  # noqa: F401,F403
from .navit import (NavitTower, NavitEncodeEngine, navit_encode,  # noqa: F401
                    navit_tiny, patch_positions, patchify)
from .ssd import SSD, SSDMultiBoxLoss, get_ssd, ssd_toy  # noqa: F401

_models = {
    "ssd_toy": ssd_toy,
    "resnet18_v1": _resnet.resnet18_v1,
    "resnet34_v1": _resnet.resnet34_v1,
    "resnet50_v1": _resnet.resnet50_v1,
    "resnet101_v1": _resnet.resnet101_v1,
    "resnet152_v1": _resnet.resnet152_v1,
    "resnet18_v2": _resnet.resnet18_v2,
    "resnet34_v2": _resnet.resnet34_v2,
    "resnet50_v2": _resnet.resnet50_v2,
    "resnet101_v2": _resnet.resnet101_v2,
    "resnet152_v2": _resnet.resnet152_v2,
    "vgg11": _vgg.vgg11,
    "vgg13": _vgg.vgg13,
    "vgg16": _vgg.vgg16,
    "vgg19": _vgg.vgg19,
    "vgg11_bn": _vgg.vgg11_bn,
    "vgg13_bn": _vgg.vgg13_bn,
    "vgg16_bn": _vgg.vgg16_bn,
    "vgg19_bn": _vgg.vgg19_bn,
    "alexnet": _alexnet.alexnet,
    "densenet121": _densenet.densenet121,
    "densenet161": _densenet.densenet161,
    "densenet169": _densenet.densenet169,
    "densenet201": _densenet.densenet201,
    "squeezenet1.0": _squeezenet.squeezenet1_0,
    "squeezenet1.1": _squeezenet.squeezenet1_1,
    "inceptionv3": _inception.inception_v3,
    "mobilenet1.0": _mobilenet.mobilenet1_0,
    "mobilenet0.75": _mobilenet.mobilenet0_75,
    "mobilenet0.5": _mobilenet.mobilenet0_5,
    "mobilenet0.25": _mobilenet.mobilenet0_25,
    "mobilenetv2_1.0": _mobilenet.mobilenet_v2_1_0,
    "mobilenetv2_0.75": _mobilenet.mobilenet_v2_0_75,
    "mobilenetv2_0.5": _mobilenet.mobilenet_v2_0_5,
    "mobilenetv2_0.25": _mobilenet.mobilenet_v2_0_25,
    "mobilenetv3_large": _mobilenet.mobilenet_v3_large,
    "mobilenetv3_small": _mobilenet.mobilenet_v3_small,
}


def get_model(name, **kwargs):
    """Return a model by name (reference: vision/__init__.py::get_model)."""
    from ....base import MXNetError

    name = name.lower()
    if name not in _models:
        raise MXNetError(
            f"Model {name!r} is not supported. Available: {sorted(_models)}")
    return _models[name](**kwargs)
