"""SSD / multibox op tests (reference: tests for multibox_prior/
target/detection + example/ssd training behavior).

Oracles: hand-computed anchor geometry, encode→decode round-trip
(MultiBoxTarget's offsets fed through MultiBoxDetection must reproduce
the ground-truth box), and a trainable toy SSD that learns a fixed scene.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon import Trainer
from mxnet_tpu.gluon.model_zoo import vision


class TestMultiBoxPrior:
    def test_geometry(self):
        x = mx.nd.ones((1, 1, 2, 2))
        an = mx.nd.contrib.MultiBoxPrior(x, sizes=(0.5,), ratios=(1.0,))
        a = an.asnumpy()[0]
        assert a.shape == (4, 4)
        # first cell center (0.25, 0.25), size 0.5 -> [0, 0, 0.5, 0.5]
        onp.testing.assert_allclose(a[0], [0.0, 0.0, 0.5, 0.5], atol=1e-6)
        # last cell center (0.75, 0.75)
        onp.testing.assert_allclose(a[3], [0.5, 0.5, 1.0, 1.0], atol=1e-6)

    def test_anchor_count_and_clip(self):
        x = mx.nd.ones((1, 1, 3, 5))
        an = mx.nd.contrib.MultiBoxPrior(
            x, sizes=(0.9, 0.4), ratios=(1.0, 2.0, 0.5), clip=True)
        # A = 2 + 3 - 1 = 4
        assert an.shape == (1, 3 * 5 * 4, 4)
        a = an.asnumpy()
        assert a.min() >= 0.0 and a.max() <= 1.0


class TestTargetDetectRoundTrip:
    def test_encode_decode_recovers_gt(self):
        """Offsets computed by MultiBoxTarget, decoded by
        MultiBoxDetection with a perfect classifier, must reproduce the
        ground-truth box."""
        x = mx.nd.ones((1, 1, 4, 4))
        an = mx.nd.contrib.MultiBoxPrior(x, sizes=(0.4,),
                                         ratios=(1.0, 2.0))
        n = an.shape[1]
        gt = onp.array([[[1, 0.22, 0.31, 0.58, 0.66]]], "float32")
        cls_pred = mx.nd.zeros((1, 3, n))
        loc_t, loc_m, cls_t = mx.nd.contrib.MultiBoxTarget(
            an, mx.nd.array(gt), cls_pred)
        ct = cls_t.asnumpy()[0]
        assert (ct == 2).sum() >= 1          # class 1 -> target 2
        # perfect softmax probs: matched anchors say class 1
        probs = onp.zeros((1, 3, n), "float32")
        probs[0, 0, :] = 1.0                 # background everywhere
        matched = ct > 0
        probs[0, 0, matched] = 0.0
        probs[0, 2, matched] = 1.0
        det = mx.nd.contrib.MultiBoxDetection(
            mx.nd.array(probs), loc_t, an, threshold=0.5,
            nms_threshold=0.5).asnumpy()[0]
        kept = det[det[:, 0] >= 0]
        assert len(kept) >= 1
        onp.testing.assert_allclose(kept[0, 2:6], gt[0, 0, 1:5],
                                    atol=1e-3)
        assert kept[0, 0] == 1.0             # class id back to 0-based

    def test_hard_negative_mining(self):
        x = mx.nd.ones((1, 1, 4, 4))
        an = mx.nd.contrib.MultiBoxPrior(x, sizes=(0.4,), ratios=(1.0,))
        n = an.shape[1]
        gt = onp.array([[[0, 0.2, 0.2, 0.6, 0.6]]], "float32")
        rs = onp.random.RandomState(0)
        cls_pred = mx.nd.array(rs.randn(1, 2, n).astype("float32"))
        _lt, _lm, ct = mx.nd.contrib.MultiBoxTarget(
            an, mx.nd.array(gt), cls_pred, negative_mining_ratio=3.0)
        c = ct.asnumpy()[0]
        n_pos = (c > 0).sum()
        n_neg = (c == 0).sum()
        n_ign = (c == -1).sum()
        assert n_pos >= 1 and n_ign > 0
        assert n_neg <= 3 * n_pos + 1        # mined ratio respected


class TestSSDModel:
    def test_shapes_and_zoo(self):
        net = vision.get_model("ssd_toy", num_classes=3)
        net.initialize()
        x = mx.nd.ones((2, 3, 64, 64))
        an, cp, bp = net(x)
        assert an.shape[0] == 1 and an.shape[2] == 4
        assert cp.shape == (2, an.shape[1], 4)
        assert bp.shape == (2, an.shape[1] * 4)
        det = net.detect(x)
        assert det.shape == (2, an.shape[1], 6)

    @pytest.mark.slow   # ~60-110 s: 40 eager steps behind a 30 s
    def test_training_learns_fixed_scene(self):  # per-op compile of the net
        onp.random.seed(3)
        mx.random.seed(3)
        net = vision.ssd_toy(num_classes=2)
        net.initialize()
        loss_fn = vision.SSDMultiBoxLoss()
        # one fixed image with one box of class 0
        rs = onp.random.RandomState(4)
        img = rs.rand(1, 3, 32, 32).astype("float32")
        img[:, :, 8:24, 8:24] += 2.0          # bright square = the object
        x = mx.nd.array(img)
        label = mx.nd.array(onp.array(
            [[[0, 0.25, 0.25, 0.75, 0.75]]], "float32"))
        trainer = Trainer(net.collect_params(), "adam",
                          {"learning_rate": 5e-3})
        first = last = None
        for i in range(40):
            with autograd.record():
                anchors, cls_preds, box_preds = net(x)
                loc_t, loc_m, cls_t = net.targets(anchors, label,
                                                  cls_preds)
                loss = loss_fn(cls_preds, box_preds, cls_t, loc_t, loc_m)
            loss.backward()
            trainer.step(1)
            v = float(loss.asnumpy())
            first = first if first is not None else v
            last = v
        assert last < first * 0.5, (first, last)
        det = net.detect(x, threshold=0.3).asnumpy()[0]
        kept = det[det[:, 0] >= 0]
        assert len(kept) >= 1
        # best detection overlaps the ground truth decently
        bx = kept[0, 2:6]
        ix = max(0, min(bx[2], 0.75) - max(bx[0], 0.25)) * \
            max(0, min(bx[3], 0.75) - max(bx[1], 0.25))
        union = (bx[2] - bx[0]) * (bx[3] - bx[1]) + 0.25 - ix
        assert ix / union > 0.3, kept[0]


def test_two_gts_sharing_best_anchor_both_match():
    """Regression: iterative bipartite matching — two gt boxes whose
    best anchor coincides must BOTH get a positive anchor."""
    x = mx.nd.ones((1, 1, 2, 2))
    an = mx.nd.contrib.MultiBoxPrior(x, sizes=(0.5,), ratios=(1.0,))
    # both gts' best anchor is cell (0,0); the loser must fall back to
    # its next-best positively-overlapping anchor
    gt = onp.array([[[0, 0.02, 0.02, 0.48, 0.48],
                     [1, 0.10, 0.10, 0.60, 0.60]]], "float32")
    cp = mx.nd.zeros((1, 3, an.shape[1]))
    _lt, _lm, ct = mx.nd.contrib.MultiBoxTarget(an, mx.nd.array(gt), cp)
    c = ct.asnumpy()[0]
    assert (c == 1).sum() >= 1 and (c == 2).sum() >= 1, c


def test_prior_reference_order():
    """Anchor order: sizes with ratio[0] first, then ratios[1:] with
    size[0] — the reference emission order."""
    x = mx.nd.ones((1, 1, 1, 1))
    an = mx.nd.contrib.MultiBoxPrior(
        x, sizes=(0.4, 0.2), ratios=(1.0, 4.0)).asnumpy()[0]
    w = an[:, 2] - an[:, 0]
    h = an[:, 3] - an[:, 1]
    onp.testing.assert_allclose(w, [0.4, 0.2, 0.8], atol=1e-6)
    onp.testing.assert_allclose(h, [0.4, 0.2, 0.2], atol=1e-6)


def test_ssd_exports(tmp_path):
    net = vision.ssd_toy(num_classes=2)
    net.initialize()
    x = mx.nd.ones((2, 3, 32, 32))
    net(x)
    net.hybridize()
    net(x)
    prefix = str(tmp_path / "ssd")
    net.export(prefix)                      # symbolic trace must work
    sym = mx.sym.load(prefix + "-symbol.json")
    assert "MultiBoxPrior" in sym.tojson()


def test_svm_output_hinge_grad():
    """SVMOutput backward: hinge gradient w.r.t. scores, not identity."""
    from mxnet_tpu import autograd

    x = mx.nd.array(onp.array([[2.0, 1.5, -1.0]], "float32"))
    x.attach_grad()
    lab = mx.nd.array(onp.array([0.0], "float32"))
    with autograd.record():
        out = mx.nd.SVMOutput(x, lab, margin=1.0, use_linear=True,
                              regularization_coefficient=1.0)
    out.backward()
    g = x.grad.asnumpy()[0]
    # class 1 violates (2.0 - 1.5 < 1): +1 there, -1 at the label;
    # class 2 satisfies (2.0 - (-1.0) >= 1): 0
    onp.testing.assert_allclose(g, [-1.0, 1.0, 0.0], atol=1e-6)
