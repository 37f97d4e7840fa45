"""NDArray semantics tests (reference: tests/python/unittest/test_ndarray.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx


def test_creation():
    x = mx.nd.zeros((2, 3))
    assert x.shape == (2, 3)
    assert x.dtype == np.float32
    assert (x.asnumpy() == 0).all()
    y = mx.nd.ones((4,), dtype="int32")
    assert y.dtype == np.int32
    z = mx.nd.full((2, 2), 3.5)
    assert (z.asnumpy() == 3.5).all()
    a = mx.nd.array([[1, 2], [3, 4]], dtype="float32")
    assert a.shape == (2, 2)
    assert a.size == 4
    assert a.ndim == 2


def test_from_numpy_default_dtype():
    # float64 numpy defaults to float32 NDArray, like MXNet
    a = mx.nd.array(np.array([1.0, 2.0]))
    assert a.dtype == np.float32
    b = mx.nd.array(np.array([1, 2], dtype=np.int64))
    assert b.dtype == np.int64


def test_arithmetic():
    x = mx.nd.array([[1, 2], [3, 4]])
    y = mx.nd.array([[10, 20], [30, 40]])
    assert np.allclose((x + y).asnumpy(), [[11, 22], [33, 44]])
    assert np.allclose((y - x).asnumpy(), [[9, 18], [27, 36]])
    assert np.allclose((x * y).asnumpy(), [[10, 40], [90, 160]])
    assert np.allclose((y / x).asnumpy(), [[10, 10], [10, 10]])
    assert np.allclose((x + 1).asnumpy(), [[2, 3], [4, 5]])
    assert np.allclose((2 * x).asnumpy(), [[2, 4], [6, 8]])
    assert np.allclose((1 - x).asnumpy(), [[0, -1], [-2, -3]])
    assert np.allclose((8 / x).asnumpy(), [[8, 4], [8 / 3, 2]])
    assert np.allclose((x ** 2).asnumpy(), [[1, 4], [9, 16]])
    assert np.allclose((-x).asnumpy(), [[-1, -2], [-3, -4]])


def test_inplace_arithmetic():
    x = mx.nd.ones((2, 2))
    x += 1
    assert (x.asnumpy() == 2).all()
    x *= 3
    assert (x.asnumpy() == 6).all()
    x /= 2
    assert (x.asnumpy() == 3).all()
    x -= 1
    assert (x.asnumpy() == 2).all()


def test_comparison_ops():
    x = mx.nd.array([1, 2, 3])
    y = mx.nd.array([3, 2, 1])
    assert np.allclose((x == y).asnumpy(), [0, 1, 0])
    assert np.allclose((x != y).asnumpy(), [1, 0, 1])
    assert np.allclose((x > y).asnumpy(), [0, 0, 1])
    assert np.allclose((x >= 2).asnumpy(), [0, 1, 1])
    assert np.allclose((x < y).asnumpy(), [1, 0, 0])


def test_indexing_read():
    x = mx.nd.array(np.arange(24).reshape(2, 3, 4))
    assert np.allclose(x[0].asnumpy(), np.arange(12).reshape(3, 4))
    assert np.allclose(x[1, 2].asnumpy(), [20, 21, 22, 23])
    assert np.allclose(x[0, 1, 2].asnumpy(), 6)
    assert np.allclose(x[:, 1].asnumpy(), [[4, 5, 6, 7], [16, 17, 18, 19]])
    assert np.allclose(x[0, :, 1:3].asnumpy(), [[1, 2], [5, 6], [9, 10]])


def test_setitem():
    x = mx.nd.zeros((3, 3))
    x[1] = 1
    assert np.allclose(x.asnumpy()[1], 1)
    x[0, 2] = 5
    assert x.asnumpy()[0, 2] == 5
    x[:] = 9
    assert (x.asnumpy() == 9).all()
    x[0:2, 0:2] = mx.nd.ones((2, 2)) * 7
    assert (x.asnumpy()[:2, :2] == 7).all()


def test_view_write_through():
    # MXNet: x[i:j] returns a view; writes propagate to the base array
    x = mx.nd.array(np.arange(6).reshape(2, 3))
    v = x[0]
    v[:] = -1
    assert np.allclose(x.asnumpy()[0], -1)
    # and base writes are visible through the view
    x[0, 1] = 42
    assert v.asnumpy()[1] == 42


def test_reshape_view():
    x = mx.nd.array(np.arange(6))
    r = x.reshape(2, 3)
    assert r.shape == (2, 3)
    r[0, 0] = 99
    assert x.asnumpy()[0] == 99
    # magic reshape values (reference: matrix_op.cc::ReshapeShape)
    y = mx.nd.zeros((2, 3, 4))
    assert mx.nd.Reshape(y, shape=(0, -1)).shape == (2, 12)
    assert mx.nd.Reshape(y, shape=(-2,)).shape == (2, 3, 4)
    assert mx.nd.Reshape(y, shape=(-3, 4)).shape == (6, 4)
    assert mx.nd.Reshape(y, shape=(-4, 1, 2, -2)).shape == (1, 2, 3, 4)


def test_astype_copy():
    x = mx.nd.array([1.5, 2.5])
    y = x.astype("int32")
    assert y.dtype == np.int32
    c = x.copy()
    c[0] = 100
    assert x.asnumpy()[0] == 1.5


def test_scalar_conversions():
    x = mx.nd.array([3.5])
    assert float(x) == 3.5
    assert x.asscalar() == 3.5
    with pytest.raises(Exception):
        mx.nd.ones((2,)).asscalar()


def test_wait_and_waitall():
    x = mx.nd.ones((10, 10))
    y = x * 2
    y.wait_to_read()
    mx.nd.waitall()
    assert (y.asnumpy() == 2).all()


def test_out_kwarg():
    x = mx.nd.array([1.0, 2.0])
    out = mx.nd.zeros((2,))
    mx.nd.sqrt(x, out=out)
    assert np.allclose(out.asnumpy(), np.sqrt([1.0, 2.0]))


def test_save_load_roundtrip(tmp_path):
    fname = str(tmp_path / "test.params")
    d = {"arg:w": mx.nd.random.normal(shape=(3, 4)),
         "aux:b": mx.nd.ones((5,), dtype="int32")}
    mx.nd.save(fname, d)
    back = mx.nd.load(fname)
    assert set(back) == set(d)
    for k in d:
        assert back[k].dtype == d[k].dtype
        assert np.allclose(back[k].asnumpy(), d[k].asnumpy())
    # list save
    mx.nd.save(fname, [d["arg:w"]])
    lst = mx.nd.load(fname)
    assert isinstance(lst, list) and len(lst) == 1


def test_save_load_bfloat16(tmp_path):
    import ml_dtypes

    fname = str(tmp_path / "bf16.params")
    x = mx.nd.array(np.array([1.0, 2.0, 3.0]), dtype="bfloat16")
    mx.nd.save(fname, {"x": x})
    back = mx.nd.load(fname)["x"]
    assert back.dtype == ml_dtypes.bfloat16
    assert np.allclose(back.asnumpy().astype(np.float32), [1, 2, 3])


def test_context_movement():
    x = mx.nd.ones((2, 2), ctx=mx.cpu(0))
    assert x.context == mx.cpu(0)
    y = x.as_in_context(mx.cpu(0))
    assert y is x
    z = x.copyto(mx.cpu(0))
    assert z is not x
    assert np.allclose(z.asnumpy(), x.asnumpy())


@pytest.mark.parametrize("make", [mx.tpu, mx.gpu], ids=["tpu", "gpu"])
def test_accelerator_context_needs_an_accelerator(make, monkeypatch):
    """tpu(i)/gpu(i) stand in on CPU devices only where JAX_PLATFORMS
    holds the process to the CPU (this suite); anywhere else a process
    without an accelerator raises, naming what jax found."""
    import jax

    assert make(0).jax_device().platform == "cpu"      # JAX_PLATFORMS=cpu
    monkeypatch.setattr(
        jax, "local_devices",
        lambda *a, **k: [d for d in jax.devices() if d.platform == "cpu"])
    for platforms in ("", "tpu,cpu"):
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        with pytest.raises(mx.MXNetError, match="no accelerator"):
            make(0).jax_device()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert make(0).jax_device().platform == "cpu"


def test_dlpack_interchange():
    import jax.numpy as jnp

    x = mx.nd.array([1.0, 2.0])
    j = jnp.from_dlpack(x)
    assert np.allclose(np.asarray(j), [1.0, 2.0])


def test_concat_split_stack():
    a = mx.nd.ones((2, 3))
    b = mx.nd.zeros((2, 3))
    c = mx.nd.concat(a, b, dim=0)
    assert c.shape == (4, 3)
    parts = mx.nd.split(c, num_outputs=2, axis=0)
    assert len(parts) == 2 and np.allclose(parts[0].asnumpy(), 1)
    s = mx.nd.stack(a, b, axis=0)
    assert s.shape == (2, 2, 3)


def test_broadcast_ops():
    x = mx.nd.ones((2, 1, 3))
    y = mx.nd.ones((1, 4, 3))
    assert mx.nd.broadcast_add(x, y).shape == (2, 4, 3)
    assert mx.nd.broadcast_to(mx.nd.ones((1, 3)), shape=(5, 3)).shape == (5, 3)
    # elemwise_add enforces strict shapes (reference semantics)
    with pytest.raises(Exception):
        mx.nd.elemwise_add(mx.nd.ones((2, 3)), mx.nd.ones((3,))).wait_to_read()


def test_take_pick_onehot():
    x = mx.nd.array(np.arange(12).reshape(3, 4))
    idx = mx.nd.array([0, 2], dtype="int32")
    assert np.allclose(mx.nd.take(x, idx).asnumpy(), [[0, 1, 2, 3], [8, 9, 10, 11]])
    picked = mx.nd.pick(x, mx.nd.array([1, 0, 3]), axis=1)
    assert np.allclose(picked.asnumpy(), [1, 4, 11])
    oh = mx.nd.one_hot(mx.nd.array([0, 2]), depth=3)
    assert np.allclose(oh.asnumpy(), [[1, 0, 0], [0, 0, 1]])


def test_reductions_match_numpy():
    a = np.random.randn(3, 4, 5).astype(np.float32)
    x = mx.nd.array(a)
    # atol for near-zero means/sums: f32 accumulation order differs
    # between XLA and numpy
    assert np.allclose(x.sum().asnumpy(), a.sum(), rtol=1e-5, atol=1e-5)
    assert np.allclose(mx.nd.sum(x, axis=1).asnumpy(), a.sum(axis=1),
                       rtol=1e-5, atol=1e-5)
    assert np.allclose(mx.nd.mean(x, axis=(0, 2)).asnumpy(),
                       a.mean(axis=(0, 2)), rtol=1e-5, atol=1e-5)
    assert np.allclose(mx.nd.max(x, axis=2, keepdims=True).asnumpy(),
                       a.max(axis=2, keepdims=True))
    assert np.allclose(mx.nd.norm(x).asnumpy(), np.linalg.norm(a.ravel()),
                       rtol=1e-5, atol=1e-6)
    # exclude semantics
    assert np.allclose(mx.nd.sum(x, axis=1, exclude=True).asnumpy(),
                       a.sum(axis=(0, 2)), rtol=1e-5, atol=1e-5)


def test_dot():
    a = np.random.randn(3, 4).astype(np.float32)
    b = np.random.randn(4, 5).astype(np.float32)
    assert np.allclose(mx.nd.dot(mx.nd.array(a), mx.nd.array(b)).asnumpy(),
                       a @ b, rtol=1e-4, atol=1e-5)
    # transpose flags
    assert np.allclose(
        mx.nd.dot(mx.nd.array(a), mx.nd.array(b.T), transpose_b=True).asnumpy(),
        a @ b, rtol=1e-4, atol=1e-5)
    # batch_dot
    x = np.random.randn(2, 3, 4).astype(np.float32)
    y = np.random.randn(2, 4, 5).astype(np.float32)
    assert np.allclose(mx.nd.batch_dot(mx.nd.array(x), mx.nd.array(y)).asnumpy(),
                       x @ y, rtol=1e-4, atol=1e-5)


def test_bfloat16_matmul():
    # TPU-first: bf16 is a first-class dtype
    x = mx.nd.ones((4, 4), dtype="bfloat16")
    y = mx.nd.dot(x, x)
    assert str(y.dtype) == "bfloat16"
    assert np.allclose(y.asnumpy().astype(np.float32), 4.0)


def test_attach_grad_detach():
    x = mx.nd.ones((2,))
    x.attach_grad()
    assert x.grad is not None and (x.grad.asnumpy() == 0).all()
    d = x.detach()
    assert getattr(d, "_grad_req") == "null"


def test_iter_len():
    x = mx.nd.array([[1, 2], [3, 4], [5, 6]])
    assert len(x) == 3
    rows = [r.asnumpy() for r in x]
    assert len(rows) == 3 and np.allclose(rows[2], [5, 6])


class TestLinalgTail:
    """Round-4 linalg long tail (reference: la_op.cc gelqf/syevd/potri/
    trmm/sumlogdiag/... kernels) vs the numpy oracle."""

    def _spd(self, n=4, seed=0):
        rs = np.random.RandomState(seed)
        m = rs.randn(n, n).astype("float32")
        return m @ m.T + n * np.eye(n, dtype="float32")

    def test_gelqf_reconstructs(self):
        rs = np.random.RandomState(1)
        a = rs.randn(3, 5).astype("float32")
        L, Q = mx.nd.linalg_gelqf(mx.nd.array(a))
        l, q = L.asnumpy(), Q.asnumpy()
        np.testing.assert_allclose(l @ q, a, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(q @ q.T, np.eye(3), rtol=1e-4,
                                    atol=1e-5)
        assert np.allclose(l, np.tril(l), atol=1e-5)

    def test_syevd_reconstructs(self):
        a = self._spd()
        U, L = mx.nd.linalg_syevd(mx.nd.array(a))
        u, lam = U.asnumpy(), L.asnumpy()
        np.testing.assert_allclose(u.T @ np.diag(lam) @ u, a, rtol=1e-3,
                                    atol=1e-3)
        np.testing.assert_allclose(u @ a @ u.T, np.diag(lam), rtol=1e-3,
                                    atol=1e-3)

    def test_potri_matches_inverse(self):
        a = self._spd(seed=2)
        chol = np.linalg.cholesky(a).astype("float32")
        got = mx.nd.linalg_potri(mx.nd.array(chol)).asnumpy()
        np.testing.assert_allclose(got, np.linalg.inv(a), rtol=1e-2,
                                    atol=1e-3)

    def test_trmm_sumlogdiag_diag_ops(self):
        rs = np.random.RandomState(3)
        a = np.tril(rs.randn(4, 4)).astype("float32")
        b = rs.randn(4, 4).astype("float32")
        np.testing.assert_allclose(
            mx.nd.linalg_trmm(mx.nd.array(a), mx.nd.array(b),
                              alpha=2.0).asnumpy(),
            2.0 * a @ b, rtol=1e-5)
        spd = self._spd(seed=4)
        chol = np.linalg.cholesky(spd).astype("float32")
        np.testing.assert_allclose(
            float(mx.nd.linalg_sumlogdiag(mx.nd.array(chol)).asnumpy()),
            np.log(np.diag(chol)).sum(), rtol=1e-5)
        np.testing.assert_allclose(
            mx.nd.linalg_extractdiag(mx.nd.array(b)).asnumpy(),
            np.diag(b), rtol=1e-6)
        v = rs.randn(4).astype("float32")
        np.testing.assert_allclose(
            mx.nd.linalg_makediag(mx.nd.array(v)).asnumpy(), np.diag(v),
            rtol=1e-6)

    def test_det_inverse_slogdet(self):
        a = self._spd(seed=5)
        np.testing.assert_allclose(
            float(mx.nd.linalg_det(mx.nd.array(a)).asnumpy()),
            np.linalg.det(a), rtol=1e-3)
        np.testing.assert_allclose(
            mx.nd.linalg_inverse(mx.nd.array(a)).asnumpy(),
            np.linalg.inv(a), rtol=1e-2, atol=1e-4)
        sign, logdet = mx.nd.linalg_slogdet(mx.nd.array(a))
        ws, wl = np.linalg.slogdet(a)
        assert float(sign.asnumpy()) == ws
        np.testing.assert_allclose(float(logdet.asnumpy()), wl, rtol=1e-4)


class TestFluentMethodSurface:
    """Round-4: the reference's fluent method forms (x.sin(), x.sort(),
    x.broadcast_to(...)) — one forwarding layer over the op registry."""

    def test_unary_fluent_match_free_functions(self):
        a = mx.nd.array([[4.0, 1.0], [2.0, 3.0]])
        np.testing.assert_allclose(a.sin().asnumpy(),
                                    np.sin(a.asnumpy()), rtol=1e-6)
        np.testing.assert_allclose(a.sort().asnumpy(),
                                    np.sort(a.asnumpy()), rtol=1e-6)
        np.testing.assert_allclose(a.floor().asnumpy(),
                                    np.floor(a.asnumpy()))
        np.testing.assert_allclose(a.rsqrt().asnumpy(),
                                    1 / np.sqrt(a.asnumpy()), rtol=1e-6)
        assert a.zeros_like().asnumpy().sum() == 0
        assert a.relu().shape == a.sigmoid().shape == (2, 2)

    def test_shape_fluent(self):
        assert mx.nd.ones((1, 2)).broadcast_to((3, 2)).shape == (3, 2)
        assert mx.nd.ones((1, 2)).broadcast_like(
            mx.nd.zeros((3, 2))).shape == (3, 2)
        assert mx.nd.ones((4, 4)).slice_like(
            mx.nd.zeros((2, 3))).shape == (2, 3)
        parts = mx.nd.ones((2, 4)).split(num_outputs=2, axis=1)
        assert len(parts) == 2 and parts[0].shape == (2, 2)
        a = mx.nd.array([[4.0, 1.0], [2.0, 3.0]])
        np.testing.assert_allclose(
            a.pick(mx.nd.array([0.0, 1.0])).asnumpy(), [4.0, 3.0])

    def test_fluent_grads_flow(self):
        from mxnet_tpu import autograd
        a = mx.nd.array([0.3, 0.7])
        a.attach_grad()
        with autograd.record():
            loss = a.sin().sum()
        loss.backward()
        np.testing.assert_allclose(a.grad.asnumpy(),
                                    np.cos(a.asnumpy()), rtol=1e-6)
