"""Fused Pallas layer-kernel tests (ISSUE 7 tentpole).

The kernels run in interpret mode on the CPU oracle (pattern:
test_pallas_kernels.py); on real TPU the same tests validate the
compiled kernels. Bit-/tolerance-identity contract: the fused
``fused_layer_norm`` / ``fused_rms_norm`` / ``fused_bias_gelu`` forward
AND grads must match the eager ops/nn.py path across the shape gates,
and the op-level routing (``MXNET_PALLAS_FUSED=1``) must be a pure
dispatch decision — identical math either way.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.pallas_kernels import fused_layers as fl

pytestmark = pytest.mark.pallas


def _rows(shape=(16, 256), seed=0, dtype="float32"):
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.randn(*shape).astype(dtype))


def _vec(d=256, seed=1):
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.randn(d).astype("float32"))


class TestFusedLayerNorm:
    def test_plain_matches_eager_layer_norm(self):
        """No residual/dropout: the kernel must match the eager
        ops/nn.py::layer_norm math (f32 stats, centered variance)."""
        from mxnet_tpu.ops.nn import layer_norm

        x, g, b = _rows(), _vec(seed=1), _vec(seed=2)
        out = fl.fused_layer_norm(x, g, b, interpret=True)
        ref = layer_norm(x, g, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("shape", [(16, 128), (8, 16, 256),
                                       (24, 768), (8, 1024)])
    def test_shapes_across_gates(self, shape):
        x = _rows(shape)
        g, b = _vec(shape[-1], 1), _vec(shape[-1], 2)
        res = _rows(shape, seed=5)
        out = fl.fused_layer_norm(x, g, b, res, interpret=True)
        ref = fl.fused_layer_norm_reference(x, g, b, res)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_residual_dropout_matches_reference(self):
        """The kernel's stateless hash mask must be BITWISE the
        reference's — same elements dropped, values then equal to
        tolerance."""
        x, res = _rows(), _rows(seed=3)
        g, b = _vec(seed=1), _vec(seed=2)
        seed = jnp.asarray(11, jnp.uint32)
        out = fl.fused_layer_norm(x, g, b, res, dropout=0.25, seed=seed,
                                  interpret=True)
        ref = fl.fused_layer_norm_reference(x, g, b, res, dropout=0.25,
                                            seed=seed)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match_reference(self):
        """Backward recomputes xhat from saved (mean, rstd) — dx/dres/
        dgamma/dbeta must match autodiff through the eager composition,
        with the dropout mask regenerated bit-identically."""
        x, res = _rows(), _rows(seed=3)
        g, b = _vec(seed=1), _vec(seed=2)
        seed = jnp.asarray(5, jnp.uint32)

        def lf(x, res, g, b):
            return jnp.sum(fl.fused_layer_norm(
                x, g, b, res, dropout=0.25, seed=seed,
                interpret=True) ** 2)

        def lr(x, res, g, b):
            return jnp.sum(fl.fused_layer_norm_reference(
                x, g, b, res, dropout=0.25, seed=seed) ** 2)

        gf = jax.grad(lf, argnums=(0, 1, 2, 3))(x, res, g, b)
        gr = jax.grad(lr, argnums=(0, 1, 2, 3))(x, res, g, b)
        for a, r, name in zip(gf, gr, ("dx", "dres", "dgamma", "dbeta")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=name)

    def test_gradients_no_dropout_no_residual(self):
        x, g, b = _rows(), _vec(seed=1), _vec(seed=2)

        def lf(x, g, b):
            return jnp.sum(fl.fused_layer_norm(x, g, b,
                                               interpret=True) ** 2)

        def lr(x, g, b):
            return jnp.sum(fl.fused_layer_norm_reference(x, g, b) ** 2)

        gf = jax.grad(lf, argnums=(0, 1, 2))(x, g, b)
        gr = jax.grad(lr, argnums=(0, 1, 2))(x, g, b)
        for a, r, name in zip(gf, gr, ("dx", "dgamma", "dbeta")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=name)

    def test_bf16_tolerance(self):
        x = _rows().astype(jnp.bfloat16)
        res = _rows(seed=3).astype(jnp.bfloat16)
        g, b = _vec(seed=1), _vec(seed=2)
        out = fl.fused_layer_norm(x, g, b, res, interpret=True)
        ref = fl.fused_layer_norm_reference(x, g, b, res)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=0.05, atol=0.05)

    def test_dropout_requires_seed(self):
        x, g, b = _rows(), _vec(seed=1), _vec(seed=2)
        with pytest.raises(ValueError, match="seed"):
            fl.fused_layer_norm(x, g, b, dropout=0.1, interpret=True)

    def test_shape_gate(self):
        """fused_ln_shape_supported: lane-aligned feature dim, 8-multiple
        rows, VMEM-resident D; fused_ln_supported additionally requires
        TPU execution (False on the CPU test platform)."""
        ok = jnp.zeros((16, 256))
        assert fl.fused_ln_shape_supported(ok)
        assert not fl.fused_ln_shape_supported(jnp.zeros((16, 100)))
        assert not fl.fused_ln_shape_supported(jnp.zeros((15, 256)))
        assert not fl.fused_ln_shape_supported(jnp.zeros((16, 16384)))
        assert not fl.fused_ln_shape_supported(jnp.zeros((256,)))
        # platform gate: no TPU in the CPU test process
        assert not fl.fused_ln_supported(ok)


class TestFusedRMSNorm:
    def test_matches_eager_rms_norm(self):
        from mxnet_tpu.ops.attention import rms_norm

        x, w = _rows(), _vec(seed=4)
        out = fl.fused_rms_norm(x, w, interpret=True)
        ref = rms_norm(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match(self):
        x, w = _rows(), _vec(seed=4)
        gf = jax.grad(lambda x, w: jnp.sum(
            fl.fused_rms_norm(x, w, interpret=True) ** 2),
            argnums=(0, 1))(x, w)
        gr = jax.grad(lambda x, w: jnp.sum(
            fl.fused_rms_norm_reference(x, w) ** 2), argnums=(0, 1))(x, w)
        for a, r, name in zip(gf, gr, ("dx", "dw")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=name)

    def test_mixed_dtype_promotes_like_eager(self):
        """bf16 activations with f32 norm weights: the eager path rounds
        xhat to bf16 then promotes by the weight multiply — the kernel
        must produce the same dtype AND the same rounding."""
        x = _rows((8, 256)).astype(jnp.bfloat16)
        w = _vec(256, 4)  # f32
        out = fl.fused_rms_norm(x, w, interpret=True)
        ref = fl.fused_rms_norm_reference(x, w)
        assert out.dtype == ref.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    def test_bf16_llama_shape(self):
        x = _rows((4, 8, 512)).astype(jnp.bfloat16)
        w = _vec(512, 4)
        out = fl.fused_rms_norm(x, w, interpret=True)
        ref = fl.fused_rms_norm_reference(x, w)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=0.05, atol=0.05)


class TestFusedBiasGelu:
    def test_matches_eager_dense_epilogue(self):
        """gelu(x + bias) must equal the unfused pair (bias add in the
        matmul dtype, then exact-erf Activation gelu)."""
        x, b = _rows(), _vec(seed=6)
        out = fl.fused_bias_gelu(x, b, interpret=True)
        ref = jax.nn.gelu(x + b.astype(x.dtype), approximate=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match(self):
        x, b = _rows(), _vec(seed=6)
        gf = jax.grad(lambda x, b: jnp.sum(
            fl.fused_bias_gelu(x, b, interpret=True) ** 2),
            argnums=(0, 1))(x, b)
        gr = jax.grad(lambda x, b: jnp.sum(
            fl.fused_bias_gelu_reference(x, b) ** 2), argnums=(0, 1))(x, b)
        for a, r, name in zip(gf, gr, ("dx", "dbias")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=name)

    def test_bf16(self):
        x = _rows((8, 16, 128)).astype(jnp.bfloat16)
        b = _vec(128, 6)
        out = fl.fused_bias_gelu(x, b, interpret=True)
        ref = fl.fused_bias_gelu_reference(x, b)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=0.05, atol=0.05)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("rows", [8, 24, 136, 1024])
    @pytest.mark.parametrize("d", [128, 384, 768, 3072])
    def test_strip_loop_edges(self, d, rows, dtype):
        """Forward and ``jax.grad`` against the reference where the strip
        loop has an edge: one piece a row (128), a piece that is not a
        power of two (384, 768), BERT's FFN width; rows of half a strip
        (8), of strips of 8 (24, 136: no multiple of 16) and of several
        blocks (1024)."""
        bf16 = dtype == "bfloat16"
        x = _rows((rows, d), seed=d + rows).astype(dtype)
        b = _vec(d, 6)

        def out_and_grads(f):
            """f(x, b) and the gradients of sum(f ** 2), one program."""
            def run(x, b):
                out, vjp = jax.vjp(f, x, b)
                return (out,) + vjp(2 * out)
            return jax.jit(run)(x, b)

        got = out_and_grads(
            lambda x, b: fl.fused_bias_gelu(x, b, interpret=True))
        want = out_and_grads(fl.fused_bias_gelu_reference)
        assert got[0].dtype == x.dtype
        # a bias gradient is a sum over rows: its error grows with them
        db_scale = max(1.0, float(np.abs(np.asarray(want[2])).max()))
        for a, r, name, tol, scale in zip(
                got, want, ("out", "dx", "dbias"), (1e-5, 2e-4, 2e-4),
                (1.0, 1.0, db_scale)):
            tol = 0.05 if bf16 else tol
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(r, np.float32),
                                       rtol=tol, atol=tol * scale,
                                       err_msg=name)

    def test_matches_whole_block_form(self):
        """The strip bodies against the whole-block expressions they
        replaced (the oracle lives here, not in the library): the same
        scalar chain an element, so float32 forward and ``dx`` agree to
        rounding. Bit equality is checked on the chip (PERF.md section
        6, PR 37): the CPU backend may contract a multiply-add
        differently in two programs."""
        x, b = _rows((136, 768), seed=3), _vec(768, 6).reshape(1, 768)
        dy = _rows((136, 768), seed=4)

        @jax.jit
        def whole_block(x, b, dy):
            u = x + b
            cdf = fl._HALF32 * (fl._ONE32 + fl._erf32(u * fl._INV_SQRT2))
            pdf = jnp.exp(-fl._HALF32 * u * u) * fl._INV_SQRT2PI
            dx = dy * (cdf + u * pdf)
            return u * cdf, dx, dx.sum(axis=0)

        out = fl._fused_bias_gelu_pallas(x, b, True)
        dx, db = fl._fused_bias_gelu_pallas(x, b, True, backward_dy=dy)
        for got, want, tol in zip((out, dx, db), whole_block(x, b, dy),
                                  (1e-6, 1e-6, 1e-5)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=tol, atol=tol)


class TestOpRouting:
    """The ops/nn.py + model-zoo seams: MXNET_PALLAS_FUSED toggles a pure
    dispatch decision. On the CPU platform the fused ops take the
    reference composition, so env on/off must be value-identical for
    dropout-free graphs."""

    def test_fused_ops_env_off_is_eager(self, monkeypatch):
        import mxnet_tpu as mx

        monkeypatch.delenv("MXNET_PALLAS_FUSED", raising=False)
        x = mx.nd.array(np.random.RandomState(0)
                        .randn(4, 256).astype(np.float32))
        g = mx.nd.array(np.ones(256, np.float32))
        b = mx.nd.array(np.zeros(256, np.float32))
        fused = mx.nd.fused_layer_norm(x, g, b)
        plain = mx.nd.LayerNorm(x, g, b)
        np.testing.assert_allclose(fused.asnumpy(), plain.asnumpy(),
                                   rtol=1e-6, atol=1e-6)

    def test_fused_layer_norm_op_residual(self, monkeypatch):
        import mxnet_tpu as mx

        monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")
        rs = np.random.RandomState(1)
        x = mx.nd.array(rs.randn(4, 256).astype(np.float32))
        res = mx.nd.array(rs.randn(4, 256).astype(np.float32))
        g = mx.nd.array(rs.randn(256).astype(np.float32))
        b = mx.nd.array(rs.randn(256).astype(np.float32))
        out = mx.nd.fused_layer_norm(x, g, b, res)
        ref = mx.nd.LayerNorm(x + res, g, b)
        np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(),
                                   rtol=1e-5, atol=1e-5)

    def test_fused_bias_gelu_op_matches_dense_pair(self, monkeypatch):
        import mxnet_tpu as mx

        monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")
        rs = np.random.RandomState(2)
        x = mx.nd.array(rs.randn(4, 128).astype(np.float32))
        b = mx.nd.array(rs.randn(128).astype(np.float32))
        out = mx.nd.fused_bias_gelu(x, b)
        ref = mx.nd.Activation(x + b, act_type="gelu")
        np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(),
                                   rtol=1e-6, atol=1e-6)

    def test_encoder_cell_fused_path_matches(self, monkeypatch):
        """TransformerEncoderCell (the BERT building block) with the
        fused add+norm + bias+gelu path vs the eager path — identical
        at dropout=0 (one forward+backward)."""
        import mxnet_tpu as mx
        from mxnet_tpu import autograd
        from mxnet_tpu.gluon.model_zoo.nlp.transformer import (
            TransformerEncoderCell)

        def run(env):
            if env:
                monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")
            else:
                monkeypatch.delenv("MXNET_PALLAS_FUSED", raising=False)
            mx.random.seed(0)
            cell = TransformerEncoderCell(64, 128, 4, dropout=0.0,
                                          activation="gelu")
            cell.initialize()
            x = mx.nd.array(np.random.RandomState(1)
                            .randn(2, 16, 64).astype(np.float32))
            cell(x)  # settle deferred shapes
            rs = np.random.RandomState(3)
            for name, p in sorted(cell.collect_params().items()):
                p.set_data(mx.nd.array(
                    rs.randn(*p.shape).astype(np.float32) * 0.05))
            x.attach_grad()
            with autograd.record():
                y = cell(x)
            y.backward()
            return y.asnumpy(), x.grad.asnumpy()

        y0, g0 = run(False)
        y1, g1 = run(True)
        np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(g1, g0, rtol=1e-5, atol=1e-5)

    def test_encoder_cell_fused_dropout_trains(self, monkeypatch):
        """Dropout > 0 through the fused op (hash mask, gated rng draw):
        forward+backward runs and produces finite grads."""
        import mxnet_tpu as mx
        from mxnet_tpu import autograd
        from mxnet_tpu.gluon.model_zoo.nlp.transformer import (
            TransformerEncoderCell)

        monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")
        mx.random.seed(0)
        cell = TransformerEncoderCell(64, 128, 4, dropout=0.1,
                                      activation="gelu")
        cell.initialize()
        x = mx.nd.array(np.random.RandomState(1)
                        .randn(2, 16, 64).astype(np.float32))
        x.attach_grad()
        with autograd.record():
            y = cell(x)
        y.backward()
        assert np.isfinite(y.asnumpy()).all()
        assert np.isfinite(x.grad.asnumpy()).all()

    def test_knob_toggle_invalidates_eager_op_cache(self, monkeypatch):
        """MXNET_PALLAS_FUSED keys the per-op executable cache (like
        `platform`): toggling it mid-process must re-trace, not replay
        the previously-routed body."""
        import mxnet_tpu as mx
        from mxnet_tpu import telemetry

        monkeypatch.delenv("MXNET_PALLAS_FUSED", raising=False)
        x = mx.nd.array(np.zeros((8, 256), np.float32))
        g = mx.nd.array(np.ones(256, np.float32))
        b = mx.nd.array(np.zeros(256, np.float32))
        # a unique attr value gives this test its own cache entries —
        # the per-op cache key is shape-independent, so sibling tests
        # would otherwise have pre-warmed both knob states
        eps = 1.2345e-5
        telemetry.enable()
        try:
            def counts():
                fam = telemetry.snapshot()["metrics"].get(
                    "mxnet_jit_cache_total")
                out = {(s["labels"]["cache"], s["labels"]["result"]):
                       s["value"] for s in (fam["samples"] if fam
                                            else ())}
                return (out.get(("eager_op", "hit"), 0),
                        out.get(("eager_op", "miss"), 0))

            mx.nd.LayerNorm(x, g, b, eps=eps)      # knob-off: miss
            _, m1 = counts()
            mx.nd.LayerNorm(x, g, b, eps=eps)      # warm replay: hit
            h2, m2 = counts()
            assert m2 == m1 and h2 >= 1
            monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")
            mx.nd.LayerNorm(x, g, b, eps=eps)      # knob flip: re-trace
            _, m3 = counts()
            assert m3 == m2 + 1
        finally:
            telemetry.disable()
            telemetry.reset()

    def test_pallas_dispatch_telemetry(self, monkeypatch):
        """mxnet_pallas_dispatch_total{kernel} counts kernel routings —
        zero here (CPU platform keeps the eager path), present as a
        family once a routing records."""
        import mxnet_tpu as mx
        from mxnet_tpu import telemetry

        monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")
        telemetry.enable()
        try:
            x = mx.nd.array(np.zeros((8, 256), np.float32))
            g = mx.nd.array(np.ones(256, np.float32))
            b = mx.nd.array(np.zeros(256, np.float32))
            mx.nd.fused_layer_norm(x, g, b)  # CPU -> eager, no dispatch
            fam = telemetry.snapshot()["metrics"].get(
                "mxnet_pallas_dispatch_total")
            counts = {s["labels"]["kernel"]: s["value"]
                      for s in (fam["samples"] if fam else ())}
            assert counts.get("fused_layer_norm", 0) == 0
            # record directly (the TPU-routing path's call)
            telemetry.record_pallas_dispatch("fused_layer_norm")
            fam = telemetry.snapshot()["metrics"][
                "mxnet_pallas_dispatch_total"]
            counts = {s["labels"]["kernel"]: s["value"]
                      for s in fam["samples"]}
            assert counts["fused_layer_norm"] == 1
        finally:
            telemetry.disable()
            telemetry.reset()
