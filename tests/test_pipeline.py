"""Pipeline parallelism tests (SURVEY §2.4 PP row — new TPU capability).

Oracle = the sequential fallback: the GPipe schedule over the ``pp`` mesh
axis must compute the SAME function as applying the stacked layers in
order on one device — fwd and bwd — and must compose with the fused
sharded TrainStep (dp x pp, and dp x pp x tp).
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, parallel as par
from mxnet_tpu.gluon import loss as gloss, nn
from mxnet_tpu.gluon.model_zoo import nlp
from mxnet_tpu.parallel.pipeline import pipeline_apply


def _stacked_mlp(n_stages, l_per, d, seed=0):
    """Stage params for a toy residual-MLP layer: h + tanh(h @ W + b)."""
    rs = onp.random.RandomState(seed)
    w = jnp.asarray(rs.randn(n_stages, l_per, d, d) * 0.3, jnp.float32)
    b = jnp.asarray(rs.randn(n_stages, l_per, d) * 0.1, jnp.float32)
    return (w, b)


def _stage_fn(leaves, h, key):
    w, b = leaves
    return h + jnp.tanh(h @ w + b)


class TestPipelineApply:
    @pytest.mark.parametrize("n_stages,l_per,n_micro",
                             [(4, 1, 4), (4, 2, 8), (2, 3, 2), (8, 1, 4)])
    def test_matches_sequential(self, n_stages, l_per, n_micro):
        d, B = 16, 8
        stacked = _stacked_mlp(n_stages, l_per, d)
        rs = onp.random.RandomState(1)
        x = jnp.asarray(rs.randn(B, 6, d), jnp.float32)
        key = jax.random.PRNGKey(0)
        mesh = par.make_mesh({"pp": n_stages},
                             devices=jax.devices()[:n_stages])
        want = pipeline_apply(_stage_fn, stacked, x, key, mesh=None)
        got = jax.jit(lambda p, xx: pipeline_apply(
            _stage_fn, p, xx, key, mesh=mesh,
            n_microbatches=n_micro))(stacked, x)
        onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                    rtol=2e-5, atol=2e-5)

    def test_grads_match_sequential(self):
        n_stages, l_per, d, B = 4, 2, 12, 8
        stacked = _stacked_mlp(n_stages, l_per, d, seed=2)
        rs = onp.random.RandomState(3)
        x = jnp.asarray(rs.randn(B, 4, d), jnp.float32)
        key = jax.random.PRNGKey(0)
        mesh = par.make_mesh({"pp": n_stages},
                             devices=jax.devices()[:n_stages])

        def loss(params, xx, m):
            y = pipeline_apply(_stage_fn, params, xx, key, mesh=m,
                               n_microbatches=4)
            return (y ** 2).sum()

        # jitted: one compile each, not one per eager op of the schedule
        gw = jax.jit(jax.grad(loss), static_argnums=2)(stacked, x, None)
        gp = jax.jit(jax.grad(loss), static_argnums=2)(stacked, x, mesh)
        for a, b, nm in zip(gp, gw, "wb"):
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                        rtol=2e-4, atol=2e-4,
                                        err_msg=f"d{nm}")

    def test_remat_matches(self):
        n_stages, d = 4, 8
        stacked = _stacked_mlp(n_stages, 1, d, seed=4)
        x = jnp.asarray(onp.random.RandomState(5).randn(4, 3, d),
                        jnp.float32)
        key = jax.random.PRNGKey(0)
        mesh = par.make_mesh({"pp": n_stages},
                             devices=jax.devices()[:n_stages])

        def loss(params, remat):
            y = pipeline_apply(_stage_fn, params, x, key, mesh=mesh,
                               remat=remat)
            return (y ** 2).sum()

        g0 = jax.jit(jax.grad(loss), static_argnums=1)(stacked, False)
        g1 = jax.jit(jax.grad(loss), static_argnums=1)(stacked, True)
        for a, b in zip(g1, g0):
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                        rtol=2e-5, atol=2e-5)

    def test_bad_shapes_raise(self):
        stacked = _stacked_mlp(4, 1, 8)
        x = jnp.zeros((6, 8), jnp.float32)  # 6 not divisible by 4
        mesh = par.make_mesh({"pp": 4}, devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="divisible"):
            pipeline_apply(_stage_fn, stacked, x, jax.random.PRNGKey(0),
                           mesh=mesh, n_microbatches=4)
        mesh2 = par.make_mesh({"pp": 2}, devices=jax.devices()[:2])
        with pytest.raises(ValueError, match="stages"):
            pipeline_apply(_stage_fn, stacked, x, jax.random.PRNGKey(0),
                           mesh=mesh2)


class TestPipelinedBlock:
    def test_offmesh_forward_and_param_surface(self):
        net = nlp.llama_tiny_pp(n_stages=2, layers_per_stage=2)
        net.initialize()
        tokens = mx.nd.array(onp.random.RandomState(0).randint(
            0, 256, (4, 8)), dtype="int32")
        out = net(tokens)
        assert out.shape == (4, 8, 256)
        names = list(net.collect_params())
        stacked = [n for n in names if "pp_" in n]
        # 2 norms + 3 attn denses + 2 mlp denses per stage template
        assert len(stacked) == 7
        for n in stacked:
            p = net.collect_params()[n]
            assert tuple(p.shape[:2]) == (2, 2), n
        # template's own (donor) params are NOT in the trainable surface
        assert not any("stage_" in n and "pp_" not in n for n in names)

    def test_trainstep_pp_matches_offmesh_loss(self):
        """Same init → first-step loss identical on-mesh and off-mesh."""
        onp.random.seed(7)
        mx.random.seed(7)
        rs = onp.random.RandomState(11)
        tokens = rs.randint(0, 256, (8, 8)).astype("int32")
        labels = rs.randint(0, 256, (8, 8)).astype("int32")

        def build():
            mx.random.seed(42)  # initializer reproducibility contract (r5)
            net = nlp.llama_tiny_pp(n_stages=4, n_microbatches=4)
            net.initialize()
            return net

        class LMLoss(gloss.Loss):
            def __init__(self):
                super().__init__(weight=None, batch_axis=0)
                self._ce = gloss.SoftmaxCrossEntropyLoss()

            def hybrid_forward(self, F, pred, label):
                return self._ce(pred.reshape((-1, pred.shape[-1])),
                                label.reshape((-1,)))

        losses = []
        for mesh_axes in (None, {"dp": 2, "pp": 4}):
            net = build()
            mesh = par.make_mesh(mesh_axes) if mesh_axes else \
                par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
            rules = nlp.llama_pp_sharding_rules() if mesh_axes else None
            step = par.TrainStep(net, LMLoss(), "sgd", mesh=mesh,
                                 rules=rules, loss_only=True,
                                 optimizer_params={"learning_rate": 0.1})
            loss, _ = step(mx.nd.array(tokens, dtype="int32"),
                           mx.nd.array(labels, dtype="int32"))
            losses.append(float(loss.asnumpy()))
        assert abs(losses[0] - losses[1]) < 2e-4, losses

    def test_trainstep_pp_tp_dp_converges(self):
        onp.random.seed(13)
        mx.random.seed(13)
        net = nlp.llama_tiny_pp(n_stages=2, layers_per_stage=2,
                                n_microbatches=4)
        net.initialize()
        mesh = par.make_mesh({"dp": 2, "pp": 2, "tp": 2})

        class LMLoss(gloss.Loss):
            def __init__(self):
                super().__init__(weight=None, batch_axis=0)
                self._ce = gloss.SoftmaxCrossEntropyLoss()

            def hybrid_forward(self, F, pred, label):
                return self._ce(pred.reshape((-1, pred.shape[-1])),
                                label.reshape((-1,)))

        step = par.TrainStep(net, LMLoss(), "adam", mesh=mesh,
                             rules=nlp.llama_pp_sharding_rules(),
                             loss_only=True,
                             optimizer_params={"learning_rate": 3e-3})
        rs = onp.random.RandomState(17)
        tokens = mx.nd.array(rs.randint(0, 256, (8, 8)), dtype="int32")
        # memorize a fixed batch: loss must drop hard
        first = last = None
        for i in range(30):
            loss, _ = step(tokens, tokens)
            v = float(loss.asnumpy())
            if first is None:
                first = v
            last = v
        assert last < first * 0.6, (first, last)


def test_concrete_shape_template():
    """Regression: a stage template with fully concrete shapes (no
    deferred init) must still forward — the template donor params are
    initialized lazily from the stacked shapes."""
    from mxnet_tpu.gluon import nn

    class Res(nn.HybridSequential):
        pass

    def factory():
        blk = Res()
        blk.add(nn.Dense(8, in_units=8, flatten=False))
        return blk

    net = par.Pipelined(factory, n_stages=2)
    net.initialize()
    x = mx.nd.array(onp.random.RandomState(0).randn(4, 8).astype("float32"))
    y = net(x)
    assert y.shape == (4, 8)
    assert onp.isfinite(y.asnumpy()).all()


class Test1F1B:
    """pipeline_train_1f1b: the memory-bounded schedule (VERDICT #10).
    Gradients and loss must match the sequential reference exactly."""

    def _setup(self):
        rs = onp.random.RandomState(0)
        S, D, B = 4, 6, 8
        w = jnp.asarray(rs.randn(S, D, D) * 0.3, jnp.float32)
        b = jnp.asarray(rs.randn(S, D) * 0.1, jnp.float32)
        x = jnp.asarray(rs.randn(B, D), jnp.float32)
        y = jnp.asarray(rs.randn(B, D), jnp.float32)

        def stage_fn(leaves, h, key):
            wl, bl = leaves
            return jnp.tanh(h @ wl + bl)

        def loss_fn(h, lbl):
            return ((h - lbl) ** 2).mean()

        return stage_fn, loss_fn, (w, b), x, y

    def test_grads_match_sequential(self):
        import jax as _jax

        stage_fn, loss_fn, leaves, x, y = self._setup()
        key = _jax.random.PRNGKey(0)
        mesh = par.make_mesh({"pp": 4}, devices=jax.devices()[:4])
        loss_p, grads_p, dx_p = par.pipeline_train_1f1b(
            stage_fn, loss_fn, leaves, x, y, key, mesh=mesh,
            n_microbatches=4)
        # sequential reference (the same function's off-mesh path)
        loss_s, grads_s, dx_s = par.pipeline_train_1f1b(
            stage_fn, loss_fn, leaves, x, y, key, mesh=None)
        # per-micro mean losses average to the full-batch mean only when
        # microbatches are equal-sized (they are)
        assert float(loss_p) == pytest.approx(float(loss_s), rel=1e-5)
        for gp, gs in zip(grads_p, grads_s):
            onp.testing.assert_allclose(onp.asarray(gp), onp.asarray(gs),
                                        rtol=1e-4, atol=1e-5)
        onp.testing.assert_allclose(onp.asarray(dx_p), onp.asarray(dx_s),
                                    rtol=1e-4, atol=1e-5)

    def test_more_microbatches_than_stages(self):
        import jax as _jax

        stage_fn, loss_fn, leaves, x, y = self._setup()
        key = _jax.random.PRNGKey(1)
        mesh = par.make_mesh({"pp": 4}, devices=jax.devices()[:4])
        loss_p, grads_p, _ = par.pipeline_train_1f1b(
            stage_fn, loss_fn, leaves, x, y, key, mesh=mesh,
            n_microbatches=8)
        loss_s, grads_s, _ = par.pipeline_train_1f1b(
            stage_fn, loss_fn, leaves, x, y, key, mesh=None)
        assert float(loss_p) == pytest.approx(float(loss_s), rel=1e-5)
        for gp, gs in zip(grads_p, grads_s):
            onp.testing.assert_allclose(onp.asarray(gp), onp.asarray(gs),
                                        rtol=1e-4, atol=1e-5)

    def test_pipelined_block_flag(self):
        with pytest.raises(ValueError, match="schedule"):
            par.Pipelined(lambda: None, n_stages=4, schedule="zigzag")


class _ResLayer(mx.gluon.HybridBlock):
    """Shape-preserving residual stage for pipeline tests."""

    def __init__(self, d, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.fc = nn.Dense(d, flatten=False)

    def hybrid_forward(self, F, x):
        return x + F.tanh(self.fc(x))


class TestTrainStep1F1B:
    """VERDICT r3 #9: the SAME user code runs GPipe or 1F1B by flag —
    ``TrainStep(Pipelined(..., schedule=...), loss, opt)``. Gate: the two
    schedules produce matching losses and updated parameters."""

    D, B, T, S = 12, 8, 4, 4

    def _build_net(self, schedule):
        net = par.Pipelined(lambda: _ResLayer(self.D), n_stages=self.S,
                            layers_per_stage=1, n_microbatches=4,
                            schedule=schedule)
        net.initialize()
        return net

    def _batch(self):
        rs = onp.random.RandomState(11)
        x = mx.nd.array(rs.randn(self.B, self.T, self.D).astype("float32"))
        y = mx.nd.array(rs.randn(self.B, self.T, self.D).astype("float32"))
        return x, y

    def _run_one_step(self, schedule, x, y, donor=None):
        net = self._build_net(schedule)
        net(x)  # settle stacked shapes
        if donor is not None:
            for p_dst, p_src in zip(net.collect_params().values(),
                                    donor.collect_params().values()):
                p_dst.set_data(p_src.data())
        mesh = par.make_mesh({"pp": self.S},
                             devices=jax.devices()[:self.S])
        step = par.TrainStep(net, gloss.L2Loss(), "sgd", mesh=mesh,
                             rules=par.pipeline_sharding_rules(),
                             loss_only=True,
                             optimizer_params={"learning_rate": 0.2})
        loss, _ = step(x, y)
        return net, float(loss.asnumpy())

    def test_same_start_same_result(self):
        x, y = self._batch()
        donor = self._build_net("gpipe")
        donor(x)  # settle; donor is never stepped
        net_g, loss_g = self._run_one_step("gpipe", x, y, donor=donor)
        net_f, loss_f = self._run_one_step("1f1b", x, y, donor=donor)
        assert loss_f == pytest.approx(loss_g, rel=1e-4)
        for (k1, p1), (k2, p2) in zip(
                sorted(net_g._collect_params_with_prefix().items()),
                sorted(net_f._collect_params_with_prefix().items())):
            onp.testing.assert_allclose(
                p1.data().asnumpy(), p2.data().asnumpy(),
                rtol=2e-4, atol=2e-5, err_msg=f"{k1} vs {k2}")


class TestTrainStepRemat:
    """TrainStep(remat=...) — the policy knob threaded through
    parallel/step.py (ISSUE 7): any compiled step can trade recompute
    for memory. The loss trajectory is the un-rematerialized one to
    float32 rounding (see ``REMAT_RTOL``), and bit-identical between the
    policies and with or without input donation."""

    # The first loss is computed before any update, from one and the same
    # forward: equal bits. The backward of a rematerialized step runs on a
    # RECOMPUTED forward that XLA fuses, and so associates, differently
    # from the saved one, which moves a gradient in its last bits; after
    # k SGD steps the weights, and with them the loss, differ by a few
    # ulp. On this net (3 steps, losses in [0.25, 0.5)) "full" and "dots"
    # each differ from no remat by 0, 0 and 1 ulp (8.7e-8 relative) and
    # from each other by nothing. The bound is 8 ulp of float32.
    REMAT_RTOL = 8 * float(onp.finfo(onp.float32).eps)

    def _run(self, remat, steps=3, donate=False):
        mx.random.seed(0)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(32, in_units=16, flatten=False,
                             activation="gelu"))
            net.add(nn.Dense(8, flatten=False))
        net.initialize()
        net(mx.nd.zeros((1, 16)))
        rs = onp.random.RandomState(5)
        # definition order, NOT sorted-by-name: auto-prefix counters
        # advance across tests, and "dense10_" sorts before "dense9_"
        for p in net.collect_params().values():
            p.set_data(mx.nd.array(
                rs.randn(*p.shape).astype(onp.float32) * 0.1))
        step = par.TrainStep(net, gloss.L2Loss(), "sgd",
                             optimizer_params={"learning_rate": 0.05},
                             remat=remat, donate_inputs=donate)
        rs2 = onp.random.RandomState(1)
        losses = []
        for _ in range(steps):
            x = mx.nd.array(rs2.randn(4, 16).astype(onp.float32))
            y = mx.nd.array(rs2.randn(4, 8).astype(onp.float32))
            losses.append(float(step(x, y)[0].asnumpy()))
        return losses

    def test_policies_match_no_remat(self):
        base = self._run(None)
        full = self._run("full")
        assert self._run("dots") == full
        assert full[0] == base[0]
        onp.testing.assert_allclose(full, base, rtol=self.REMAT_RTOL, atol=0)

    def test_invalid_policy_raises_at_construction(self):
        net = nn.Dense(4, in_units=4)
        net.initialize()
        with pytest.raises(ValueError, match="remat policy"):
            par.TrainStep(net, gloss.L2Loss(), "sgd", remat="bogus")

    def test_remat_composes_with_donation(self):
        # fresh buffers per step: remat + donate_inputs train together,
        # and donation moves no bit of either trajectory
        assert self._run("full", donate=True) == self._run("full")
        assert self._run(None, donate=True) == self._run(None)


class TestDonateInputsShapeChange:
    """Regression (ISSUE 7 satellite): a donating TrainStep reused after
    a shape change must invalidate its cached lowering and refuse a
    donated-dead buffer with a clear error — never dispatch against it."""

    def _make(self):
        net = nn.Dense(8, in_units=16, flatten=False)
        net.initialize()
        return par.TrainStep(net, gloss.L2Loss(), "sgd",
                             optimizer_params={"learning_rate": 0.1},
                             donate_inputs=True)

    @staticmethod
    def _batch(rs, b):
        return (mx.nd.array(rs.randn(b, 16).astype(onp.float32)),
                mx.nd.array(rs.randn(b, 8).astype(onp.float32)))

    def test_fresh_buffers_across_shape_changes(self):
        step = self._make()
        rs = onp.random.RandomState(0)
        for b in (4, 6, 4, 6):
            x, y = self._batch(rs, b)
            loss, _ = step(x, y)
            assert onp.isfinite(loss.asnumpy()).all()

    def test_donated_reuse_raises_mxnet_error(self):
        from mxnet_tpu.base import MXNetError

        step = self._make()
        rs = onp.random.RandomState(0)
        xa, ya = self._batch(rs, 4)
        step(xa, ya)[0].asnumpy()          # donates xa/ya buffers
        xb, yb = self._batch(rs, 6)
        step(xb, yb)[0].asnumpy()          # shape change
        with pytest.raises(MXNetError, match="donated"):
            step(xa, ya)                   # dead buffers, clear error

    def test_shape_change_invalidates_cached_lowering(self):
        step = self._make()
        rs = onp.random.RandomState(0)
        step(*self._batch(rs, 4))[0].asnumpy()
        assert len(step._cache) == 1
        step(*self._batch(rs, 6))[0].asnumpy()
        # the shape-A lowering (donated-dead inputs) must be gone
        assert len(step._cache) == 1
