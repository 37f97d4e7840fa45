"""Ring attention tests — sequence/context parallelism over the mesh
(capability row: SURVEY §5.7 long context; Ring Attention construction).

Oracle = dense f32 attention on the full sequence; the ring must be
numerically exact (same online-softmax algebra), fwd and bwd, causal and
not, and must compose with the sharded TrainStep on a dp x sp mesh.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, parallel as par
from mxnet_tpu.ops.attention import _sdpa_reference


def _qkv(B=2, H=3, L=32, D=16, seed=0):
    rs = onp.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(B, H, L, D), jnp.float32)
                 for _ in range(3))


class TestRingExactness:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_fwd_bwd(self, causal):
        q, k, v = _qkv()
        mesh = par.make_mesh({"sp": 8}, devices=jax.devices()[:8])
        out = par.ring_attention(q, k, v, mesh=mesh, causal=causal)
        want = _sdpa_reference(q, k, v, None, 1.0 / 4.0, causal)
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(want),
                                    rtol=2e-5, atol=2e-5)

        def loss_ring(a, b, c):
            return (par.ring_attention(a, b, c, mesh=mesh,
                                       causal=causal) ** 2).sum()

        def loss_ref(a, b, c):
            return (_sdpa_reference(a, b, c, None, 1.0 / 4.0,
                                    causal) ** 2).sum()

        # jitted: one compile each, not one per eager op of the ring
        gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        gw = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b, nm in zip(gr, gw, "qkv"):
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                        rtol=2e-4, atol=2e-4,
                                        err_msg=f"d{nm}")

    def test_under_jit_with_sharded_inputs(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        q, k, v = _qkv(L=64)
        mesh = par.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        sh = NamedSharding(mesh, P(None, None, "sp", None))
        qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
        f = jax.jit(lambda a, b, c: par.ring_attention(
            a, b, c, mesh=mesh, causal=True))
        out = f(qs, ks, vs)
        want = _sdpa_reference(q, k, v, None, 1.0 / 4.0, True)
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(want),
                                    rtol=2e-5, atol=2e-5)
        # output keeps the sequence sharding (no implicit gather)
        assert out.sharding.spec == P(None, None, "sp", None)

    def test_single_device_axis_falls_back(self):
        q, k, v = _qkv()
        mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        out = par.ring_attention(q, k, v, mesh=mesh, axis="sp")
        want = _sdpa_reference(q, k, v, None, 1.0 / 4.0, False)
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(want),
                                    rtol=1e-5, atol=1e-6)


class TestRingInModel:
    def test_mha_cell_ring_vs_dense(self):
        """The same MultiHeadAttention weights must produce identical
        outputs with and without ring_axis under a dp x sp TrainStep."""
        from mxnet_tpu.gluon import loss as gloss
        from mxnet_tpu.gluon.model_zoo.nlp.attention import \
            MultiHeadAttention

        def build(ring):
            onp.random.seed(0)
            mx.random.seed(0)
            cell = MultiHeadAttention(units=16, num_heads=4, causal=True,
                                      ring_axis="sp" if ring else None)
            cell.initialize()
            return cell

        rs = onp.random.RandomState(1)
        x = mx.nd.array(rs.randn(4, 16, 16).astype(onp.float32))
        y = mx.nd.array(rs.randn(4, 16, 16).astype(onp.float32))

        losses = {}
        for ring in (False, True):
            cell = build(ring)
            mesh = par.make_mesh({"dp": 2, "sp": 4},
                                 devices=jax.devices()[:8])
            step = par.TrainStep(cell, gloss.L2Loss(), "sgd", mesh=mesh,
                                 seq_axis="sp",
                                 optimizer_params={"learning_rate": 0.1})
            l, _ = step(x, y)
            losses[ring] = float(l.asnumpy())
        assert losses[True] == pytest.approx(losses[False], rel=1e-5), \
            losses


class TestShardingPreservation:
    def test_no_allgather_over_other_axes(self):
        """Round-2 review finding: only the ring axis may be manual —
        dp/tp shardings must survive and no all-gather may appear."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = par.make_mesh({"dp": 2, "sp": 4}, devices=jax.devices()[:8])
        q = jnp.ones((4, 2, 32, 8), jnp.float32)
        qs = jax.device_put(q, NamedSharding(mesh,
                                             P("dp", None, "sp", None)))
        f = jax.jit(lambda a, b, c: par.ring_attention(
            a, b, c, mesh=mesh, causal=True))
        hlo = f.lower(qs, qs, qs).compile().as_text()
        assert "all-gather" not in hlo
        out = f(qs, qs, qs)
        assert out.sharding.spec == P("dp", None, "sp", None)

    def test_ring_axis_without_mesh_takes_normal_dispatch(self):
        """ring_axis on the op must fall through to flash/reference
        dispatch when no mesh is active (not pin the dense path)."""
        import mxnet_tpu as mxx

        q = mxx.nd.array(onp.random.RandomState(0)
                         .randn(1, 2, 16, 8).astype("float32"))
        out = mxx.nd.contrib.sdp_attention(q, q, q, causal=True,
                                           ring_axis="sp")
        want = _sdpa_reference(q.data, q.data, q.data, None,
                               1.0 / onp.sqrt(8), True)
        onp.testing.assert_allclose(out.asnumpy(), onp.asarray(want),
                                    rtol=1e-5, atol=1e-6)


class TestMemoryScaling:
    def test_no_full_L_residual_in_backward(self):
        """Round-3 upgrade (VERDICT #4): training through ring attention
        must keep O(L_local) residuals — the old implementation saved the
        rotating K/V scan carries, a stacked (n_ring, B, H, L_local, D)
        tensor = full L per device. Walk the gradient jaxpr (recursively,
        shard_map/scan bodies included) and assert no intermediate holds
        n_ring x the shard size."""
        mesh = par.make_mesh({"sp": 8}, devices=jax.devices()[:8])
        b, h, l, d = 1, 2, 256, 16
        n_ring = 8
        shard_elems = b * h * (l // n_ring) * d

        q = jnp.ones((b, h, l, d), jnp.float32)

        def loss(q, k, v):
            return par.ring_attention(q, k, v, mesh=mesh,
                                      causal=True).sum()

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)

        def as_jaxpr(val):
            # duck-typed: ClosedJaxpr has .jaxpr, Jaxpr has .eqns
            if hasattr(val, "jaxpr"):
                val = val.jaxpr
            return val if hasattr(val, "eqns") else None

        def subjaxprs(eqn):
            for val in eqn.params.values():
                items = val if isinstance(val, (tuple, list)) else (val,)
                for item in items:
                    sub = as_jaxpr(item)
                    if sub is not None:
                        yield sub

        def max_size(jx):
            worst = 0
            for eqn in jx.eqns:
                for sub in subjaxprs(eqn):
                    worst = max(worst, max_size(sub))
                for var in list(eqn.outvars) + list(eqn.invars):
                    aval = getattr(var, "aval", None)
                    if aval is None or not hasattr(aval, "size"):
                        continue
                    worst = max(worst, int(aval.size))
            return worst

        worst = max_size(jaxpr.jaxpr)
        # global arrays at the shard_map boundary are b*h*l*d = n *
        # shard; a stacked scan residual would be n * that again
        assert worst <= n_ring * shard_elems, \
            f"found {worst}-element intermediate (> {n_ring}x shard)"

    def test_8k_tokens_on_cpu_mesh(self):
        """Long-context smoke: 8192 tokens ring-sharded over 8 devices,
        forward AND backward, vs the dense oracle."""
        mesh = par.make_mesh({"sp": 8}, devices=jax.devices()[:8])
        rs = onp.random.RandomState(0)
        b, h, l, d = 1, 1, 8192, 64
        q = jnp.asarray(rs.randn(b, h, l, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, h, l, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, h, l, d), jnp.float32)

        def ring_loss(q, k, v):
            out = par.ring_attention(q, k, v, mesh=mesh, causal=True)
            return (out * out).sum()

        def dense_loss(q, k, v):
            out = _sdpa_reference(q, k, v, None, 1.0 / onp.sqrt(d), True)
            return (out * out).sum()

        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
        g_dense = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
        for gr, gd in zip(g_ring, g_dense):
            onp.testing.assert_allclose(onp.asarray(gr), onp.asarray(gd),
                                        rtol=2e-3, atol=2e-3)


    def test_kernel_path_matches_einsum_path(self, monkeypatch):
        """The Pallas-kernel per-pair path (used on TPU) must compute the
        same ring as the einsum path — exercised here via interpret mode."""
        import functools
        import importlib

        # the parallel package re-exports the ring_attention FUNCTION
        # under the same name; get the module itself
        ra = importlib.import_module(
            "mxnet_tpu.parallel.ring_attention")

        mesh = par.make_mesh({"sp": 4}, devices=jax.devices()[:4])
        rs = onp.random.RandomState(1)
        b, h, l, d = 1, 2, 512, 32
        q = jnp.asarray(rs.randn(b, h, l, d), jnp.float32)
        k = jnp.asarray(rs.randn(b, h, l, d), jnp.float32)
        v = jnp.asarray(rs.randn(b, h, l, d), jnp.float32)

        def loss(q, k, v):
            out = par.ring_attention(q, k, v, mesh=mesh, causal=True)
            return (out * out).sum()

        # a fresh jit per path: the monkeypatched pair functions below
        # must be traced, not replayed from the einsum trace
        g_einsum = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        orig_fwd, orig_bwd = ra._pair_fwd, ra._pair_bwd
        monkeypatch.setattr(ra, "_use_kernel", lambda *a: True)
        monkeypatch.setattr(
            ra, "_pair_fwd",
            functools.partial(orig_fwd, interpret=True))
        monkeypatch.setattr(
            ra, "_pair_bwd",
            functools.partial(orig_bwd, interpret=True))
        g_kernel = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        for ge, gk, nm in zip(g_einsum, g_kernel, "qkv"):
            onp.testing.assert_allclose(onp.asarray(gk), onp.asarray(ge),
                                        rtol=2e-4, atol=2e-4,
                                        err_msg=f"d{nm}")
