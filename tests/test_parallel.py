"""Tests for mxnet_tpu.parallel — mesh, sharding rules, fused TrainStep.

Runs on the virtual 8-device CPU mesh (root conftest forces
XLA_FLAGS=--xla_force_host_platform_device_count=8), the fake-cluster
strategy from SURVEY.md §4.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn, loss as gloss
from jax.sharding import PartitionSpec as P


def _mlp(units=64):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(units, activation="relu"))
        net.add(nn.Dense(10))
    net.initialize()
    return net


class TestMesh:
    def test_default_all_dp(self):
        mesh = par.make_mesh()
        assert mesh.shape["dp"] == 8

    def test_infer_axis(self):
        mesh = par.make_mesh({"dp": -1, "tp": 2})
        assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2

    def test_bad_sizes(self):
        with pytest.raises(MXNetError):
            par.make_mesh({"dp": 3})
        with pytest.raises(MXNetError):
            par.make_mesh({"dp": -1, "tp": -1})

    def test_use_mesh(self):
        mesh = par.make_mesh({"dp": 8})
        assert par.current_mesh() is None
        with par.use_mesh(mesh):
            assert par.current_mesh() is mesh
        assert par.current_mesh() is None


class TestShardingRules:
    def test_first_match_wins_and_fallback(self):
        rules = par.ShardingRules([(r"_weight$", P("tp", None))])
        mesh = par.make_mesh({"dp": 2, "tp": 4})
        assert par.spec_for_param("dense0_weight", (128, 16), rules, mesh) == P("tp", None)
        # 10 % 4 != 0 -> replicate instead of invalid sharding
        assert par.spec_for_param("dense1_weight", (10, 16), rules, mesh) == P()
        assert par.spec_for_param("dense0_bias", (128,), rules, mesh) == P()

    def test_shard_parameters(self):
        net = _mlp(128)
        net(mx.nd.array(np.zeros((2, 16), dtype="float32")))  # settle shapes
        mesh = par.make_mesh({"dp": 2, "tp": 4})
        w = [p for p in net.collect_params().values()
             if p.shape == (128, 16)][0]
        rules = par.ShardingRules([(w.name + "$", P("tp", None))])
        specs = par.shard_parameters(net.collect_params(), mesh, rules)
        assert w.data().data.sharding.spec == P("tp", None)
        assert specs[w.name] == P("tp", None)


class TestTrainStep:
    def test_dp_converges(self):
        np.random.seed(0)
        mx.random.seed(0)
        net = _mlp()
        mesh = par.make_mesh({"dp": 8})
        step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "adam",
                             mesh=mesh, optimizer_params={"learning_rate": 1e-2})
        x = mx.nd.array(np.random.randn(32, 20).astype("float32"))
        y = mx.nd.array(np.random.randint(0, 10, (32,)).astype("float32"))
        losses = [float(step(x, y)[0].asnumpy()) for _ in range(8)]
        assert losses[-1] < losses[0]

    def test_dp_matches_single_device(self):
        """DP over 8 devices must be numerically the single-device step."""
        def run(mesh_axes):
            np.random.seed(42)
            mx.random.seed(42)
            net = _mlp()
            import jax
            n = int(np.prod(list(mesh_axes.values())))
            mesh = par.make_mesh(mesh_axes, devices=jax.devices()[:n])
            step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                                 mesh=mesh,
                                 optimizer_params={"learning_rate": 0.5})
            x = mx.nd.array(np.random.RandomState(1).randn(16, 12).astype("float32"))
            y = mx.nd.array(np.random.RandomState(2).randint(0, 10, (16,)).astype("float32"))
            losses = [float(step(x, y)[0].asnumpy()) for _ in range(3)]
            return losses

        l_dp = run({"dp": 8})
        l_single = run({"dp": 1})
        np.testing.assert_allclose(l_dp, l_single, rtol=2e-5)

    def test_tp_converges_and_layout_stable(self):
        np.random.seed(0)
        net = _mlp(128)
        net(mx.nd.array(np.zeros((2, 20), dtype="float32")))  # settle shapes
        params = list(net.collect_params().values())
        w0 = [p for p in params if p.shape == (128, 20)][0]
        b0 = [p for p in params if p.shape == (128,)][0]
        w1 = [p for p in params if p.shape == (10, 128)][0]
        mesh = par.make_mesh({"dp": 2, "tp": 4})
        rules = par.ShardingRules([
            (w0.name + "$", P("tp", None)),
            (b0.name + "$", P("tp")),
            (w1.name + "$", P(None, "tp")),
        ])
        step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                             mesh=mesh, rules=rules,
                             optimizer_params={"learning_rate": 0.1,
                                               "momentum": 0.9})
        x = mx.nd.array(np.random.randn(16, 20).astype("float32"))
        y = mx.nd.array(np.random.randint(0, 10, (16,)).astype("float32"))
        losses = [float(step(x, y)[0].asnumpy()) for _ in range(6)]
        assert losses[-1] < losses[0]
        assert w0.data().data.sharding.spec == P("tp", None)

    def test_batchnorm_aux_updates(self):
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"))
            net.add(nn.BatchNorm())
            net.add(nn.Dense(4))
        net.initialize()
        mesh = par.make_mesh({"dp": 8})
        step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                             mesh=mesh, optimizer_params={"learning_rate": 0.1})
        x = mx.nd.array(np.random.randn(16, 8).astype("float32") * 3 + 1)
        y = mx.nd.array(np.random.randint(0, 4, (16,)).astype("float32"))
        step(x, y)  # settles deferred shapes and updates stats once
        bn = [p for p in net.collect_params().values()
              if p.name.endswith("running_mean")][0]
        before = bn.data().asnumpy().copy()
        step(x, y)
        after = bn.data().asnumpy()
        assert not np.allclose(before, after), "BN moving stats must update"

    def test_lr_schedule_stays_one_executable(self):
        from mxnet_tpu import lr_scheduler
        net = _mlp()
        mesh = par.make_mesh({"dp": 8})
        sched = lr_scheduler.FactorScheduler(step=2, factor=0.5, base_lr=0.1)
        step = par.TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "adam",
                             mesh=mesh,
                             optimizer_params={"learning_rate": 0.1,
                                               "lr_scheduler": sched})
        x = mx.nd.array(np.random.randn(8, 4).astype("float32"))
        y = mx.nd.array(np.random.randint(0, 10, (8,)).astype("float32"))
        for _ in range(5):
            step(x, y)
        # one shape key -> one compiled executable despite the schedule
        assert len(step._cache) == 1


# ---------------------------------------------------------------------------
# Pallas kernels over the batch shards of a data-parallel mesh
# (parallel/mesh.py: kernel_shards / over_batch_shards). The platform gate is
# opened by declaring the trace to be for a TPU; the kernels run interpreted.
# ---------------------------------------------------------------------------


def _dp_mesh(axes):
    import jax

    n = int(np.prod(list(axes.values())))
    return par.make_mesh(axes, devices=jax.devices()[:n])


def _rand(seed, *shape):
    import jax.numpy as jnp

    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype("float32"))


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """The three training kernels with ``interpret=True``, behind the ops'
    own call sites, and the fused-layer knob on. The list it gives holds
    (kernel, leading dimension of its first operand) of every call."""
    import functools

    from mxnet_tpu import pallas_kernels
    from mxnet_tpu.pallas_kernels import fused_layers as fl

    calls = []

    def interpreted(module, name):
        kernel = getattr(module, name)

        @functools.wraps(kernel)
        def call(x, *args, **kwargs):
            calls.append((name, x.shape[0]))
            return kernel(x, *args, interpret=True, **kwargs)

        monkeypatch.setattr(module, name, call)

    monkeypatch.setenv("MXNET_PALLAS_FUSED", "1")
    interpreted(pallas_kernels, "flash_attention")
    interpreted(fl, "fused_layer_norm")
    interpreted(fl, "fused_bias_gelu")
    return calls


def _flash_case():
    from mxnet_tpu.ops.attention import sdp_attention

    args = tuple(_rand(i, 8, 2, 128, 16) for i in range(3))
    return (lambda q, k, v: sdp_attention(None, q, k, v)), args, 3


def _layer_norm_case():
    import jax

    from mxnet_tpu.ops.nn import fused_layer_norm_op

    key = jax.random.PRNGKey(7)

    def op(x, res, gamma, beta):
        return fused_layer_norm_op(key, x, gamma, beta, res, dropout=0.1,
                                   _training=True)

    return op, (_rand(0, 8, 16, 256), _rand(1, 8, 16, 256), _rand(2, 256),
                _rand(3, 256)), 2


def _bias_gelu_case():
    from mxnet_tpu.ops.nn import fused_bias_gelu_op

    return fused_bias_gelu_op, (_rand(0, 8, 16, 256), _rand(1, 256)), 1


KERNEL_CASES = {"flash_attention": _flash_case,
                "fused_layer_norm": _layer_norm_case,
                "fused_bias_gelu": _bias_gelu_case}


def _out_and_grads(op, args, weight):
    import jax

    def scalar(*a):
        out = op(*a)
        return (out * weight).sum(), out

    grads, out = jax.grad(scalar, argnums=tuple(range(len(args))),
                          has_aux=True)(*args)
    return (out,) + grads


def _under_dp4(op, args, n_sharded, weight):
    """``op``'s output and gradients from ONE jitted program on a dp=4
    mesh, the batch operands sharded, traced as ``TrainStep`` traces."""
    import jax
    from jax.sharding import NamedSharding

    from mxnet_tpu.base import execution_platform

    mesh = _dp_mesh({"dp": 4})
    rows, rep = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    placed = tuple(jax.device_put(a, rows if i < n_sharded else rep)
                   for i, a in enumerate(args))
    fn = jax.jit(lambda *a: _out_and_grads(op, a, weight))
    with execution_platform("tpu"), par.use_mesh(mesh, batch_axes=("dp",)):
        text = str(jax.make_jaxpr(fn)(*placed))
        return fn(*placed), text


@pytest.mark.pallas
@pytest.mark.parametrize("kernel", list(KERNEL_CASES))
def test_kernel_over_batch_shards_matches_unsharded(kernel,
                                                    interpreted_kernels):
    """Under dp=4 each kernel runs on its shard's rows inside a
    ``shard_map`` and gives what the unsharded kernel and the reference
    give: the output, ``dx``, and the replicated operands' gradients
    (``dbias``, ``dgamma``, ``dbeta``: the sum over the shards). For
    ``fused_layer_norm`` the output equality IS the dropout statement: the
    four shards draw the mask the reference draws over the global array."""
    from mxnet_tpu.base import execution_platform

    op, args, n_sharded = KERNEL_CASES[kernel]()
    weight = _rand(9, *args[0].shape)
    reference = _out_and_grads(op, args, weight)       # CPU: gives way
    with execution_platform("tpu"):
        unsharded = _out_and_grads(op, args, weight)   # no mesh: the kernel
    sharded, text = _under_dp4(op, args, n_sharded, weight)
    assert "shard_map" in text and "pallas_call" in text
    for got, want_kernel, want_ref in zip(sharded, unsharded, reference):
        scale = float(np.abs(np.asarray(want_ref)).max())
        np.testing.assert_allclose(got, want_kernel, atol=2e-6 * scale)
        np.testing.assert_allclose(got, want_ref, atol=2e-5 * scale)


@pytest.mark.pallas
def test_layer_norm_dropout_over_shards_needs_the_row_offset(
        interpreted_kernels, monkeypatch):
    """The control of the dropout statement above: with every shard told
    that its rows start at 0, all four draw shard 0's mask and the output
    leaves the reference."""
    from mxnet_tpu.parallel import mesh as mesh_mod

    op, args, n_sharded = _layer_norm_case()
    weight = _rand(9, *args[0].shape)
    reference = _out_and_grads(op, args, weight)[0]
    monkeypatch.setattr(mesh_mod, "batch_shard_index", lambda: 0)
    (out, *_), _ = _under_dp4(op, args, n_sharded, weight)
    out = np.asarray(out)
    np.testing.assert_allclose(out[:2], reference[:2], atol=1e-4)
    assert np.abs(out[2:] - np.asarray(reference)[2:]).max() > 1.0


@pytest.mark.parametrize("axes,batch_axes,lead,want", [
    (None, (), 8, 1),
    ({"dp": 1}, ("dp",), 8, 1),
    ({"dp": 4}, ("dp",), 8, 4),
    ({"dp": 4}, ("dp",), 6, 0),              # a ragged batch
    ({"dp": 4}, (), 8, 0),                   # nobody named the batch axes
    ({"dp": 2, "tp": 2}, ("dp",), 8, 0),     # an axis the kernel cannot see
    ({"dp": 2, "sp": 2}, ("dp", "sp"), 8, 4),
    ({"dp": 2, "sp": 2}, ("dp", "sp"), 6, 0),
], ids=["no_mesh", "dp1", "dp4", "dp4_ragged", "dp4_unnamed", "dp2_tp2",
        "two_batch_axes", "two_batch_axes_ragged"])
def test_kernel_shards(axes, batch_axes, lead, want):
    from mxnet_tpu.parallel.mesh import kernel_shards

    if axes is None:
        assert kernel_shards(lead) == want
        return
    with par.use_mesh(_dp_mesh(axes), batch_axes=batch_axes):
        assert kernel_shards(lead) == want
        # a caller inside its own shard_map over part of the mesh
        held = tuple(axes)[:1]
        rest_is_one = all(s == 1 for a, s in axes.items() if a not in held)
        assert kernel_shards(lead, manual_axes=held) == int(rest_is_one)


@pytest.mark.pallas
@pytest.mark.parametrize("axes,batch,want", [
    (None, 8, 1), ({"dp": 1}, 8, 1), ({"dp": 4}, 8, 4), ({"dp": 4}, 6, 0),
    ({"dp": 2, "tp": 2}, 8, 0),
], ids=["no_mesh", "dp1", "dp4", "dp4_ragged", "dp2_tp2"])
def test_kernel_gates_answer_with_the_shards(axes, batch, want):
    """``flash_supported`` and ``fused_ln_supported`` pass on what
    ``kernel_shards`` says where platform and shape allow a kernel, and
    hold the shape gate to what ONE shard holds."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.pallas_kernels import flash_supported
    from mxnet_tpu.pallas_kernels.fused_layers import fused_ln_supported

    q = jax.ShapeDtypeStruct((batch, 2, 128, 16), jnp.float32)
    x = jax.ShapeDtypeStruct((batch, 16, 256), jnp.float32)
    few_rows = jax.ShapeDtypeStruct((batch, 2, 256), jnp.float32)
    mesh = contextlib.nullcontext() if axes is None else par.use_mesh(
        _dp_mesh(axes), batch_axes=("dp",))
    with mesh:
        assert flash_supported(q, q, q) == 0 == fused_ln_supported(x)  # CPU
        with execution_platform("tpu"):
            assert flash_supported(q, q, q) == want
            assert fused_ln_supported(x) == want
            # 8 x 2 rows tile into 8-row blocks whole, not as four shards
            assert fused_ln_supported(few_rows) == (want if want == 1 else 0)


@pytest.mark.pallas
@pytest.mark.parametrize("kernel", ["flash_attention", "fused_bias_gelu"])
@pytest.mark.parametrize("axes", [None, {"dp": 1}], ids=["no_mesh", "dp1"])
def test_one_chip_trace_holds_no_shard_map(kernel, axes,
                                           interpreted_kernels):
    """Where no axis has more than one device the gates answer "kernel"
    and the op's jaxpr is the one it always was: the kernel, unwrapped."""
    import contextlib

    import jax

    from mxnet_tpu.base import execution_platform

    op, args, _ = KERNEL_CASES[kernel]()
    mesh = contextlib.nullcontext() if axes is None else par.use_mesh(
        _dp_mesh(axes), batch_axes=("dp",))
    with execution_platform("tpu"):
        bare = str(jax.make_jaxpr(op)(*args))
        with mesh:
            text = str(jax.make_jaxpr(op)(*args))
    assert "pallas_call" in text and "shard_map" not in text
    assert text == bare


@pytest.mark.pallas
@pytest.mark.parametrize("p_drop,axes,routes", [
    (0.0, {"dp": 4}, "shard_map"), (0.1, {"dp": 4}, "reference"),
    (0.1, {"dp": 1}, "kernel"), (0.0, {"dp": 2, "tp": 2}, "reference"),
], ids=["dp4", "dp4_dropout", "dp1_dropout", "dp2_tp2"])
def test_sdp_attention_route_under_a_mesh(p_drop, axes, routes,
                                          interpreted_kernels):
    """Flash attention goes over the batch shards only without attention
    dropout: its in-kernel mask hashes shard-local (batch x head) ids."""
    import jax

    from mxnet_tpu.base import execution_platform
    from mxnet_tpu.ops.attention import sdp_attention

    _, (q, k, v), _ = _flash_case()
    key = jax.random.PRNGKey(0)

    def op(q, k, v):
        return sdp_attention(key, q, k, v, dropout=p_drop, _training=True)

    with execution_platform("tpu"), \
            par.use_mesh(_dp_mesh(axes), batch_axes=("dp",)):
        text = str(jax.make_jaxpr(op)(q, k, v))
    assert ("shard_map" in text) == (routes == "shard_map")
    assert ("pallas_call" in text) == (routes != "reference")


def test_routing_knobs_tell_the_meshes_apart():
    """A per-op cache entry traced with a kernel over dp=4's shards holds
    that mesh's ``shard_map``: the routing key must not hand it to a step
    on dp=2, on other devices, off the mesh, or where the gates give way."""
    import jax

    from mxnet_tpu.compiler import routing_knobs

    def knob(axes, batch_axes=("dp",), devices=None):
        n = int(np.prod(list(axes.values())))
        mesh = par.make_mesh(axes, devices=devices or jax.devices()[:n])
        with par.use_mesh(mesh, batch_axes=batch_axes):
            return routing_knobs()[2]

    off = routing_knobs()[2]
    assert off is False and knob({"dp": 1}) is False
    assert knob({"dp": 2, "tp": 2}) is True and knob({"dp": 4}, ()) is True
    dp4 = knob({"dp": 4})
    assert dp4 == knob({"dp": 4}) and hash(dp4) is not None
    assert len({dp4, knob({"dp": 2}), off, True,
                knob({"dp": 4}, devices=jax.devices()[4:])}) == 5


def test_train_step_traces_under_its_batch_axes():
    from mxnet_tpu.base import current_execution_platform
    from mxnet_tpu.parallel.mesh import current_batch_axes

    mesh = _dp_mesh({"dp": 4})
    step = par.TrainStep(_mlp(), gloss.SoftmaxCrossEntropyLoss(), "sgd",
                         mesh=mesh, batch_axis=("dp", "fsdp"))
    assert current_batch_axes() == ()
    with step.tracing():
        assert par.current_mesh() is mesh
        assert current_batch_axes() == ("dp",)
        assert current_execution_platform() == "cpu"
    assert par.current_mesh() is None and current_batch_axes() == ()


@pytest.mark.pallas
def test_dp_matches_single_device_with_the_kernels(interpreted_kernels,
                                                   monkeypatch):
    """``test_dp_matches_single_device`` for a step that holds the
    kernels: a post-LN transformer cell with dropout 0.1 trains to the
    same losses under ``TrainStep(mesh dp=4)``, each kernel on its shard's
    2 of the 8 rows and the LayerNorm dropout mask drawn over the global
    array, as under ``dp=1``, where the kernels take the batch whole."""
    from mxnet_tpu import base
    from mxnet_tpu.gluon.model_zoo.nlp.transformer import \
        TransformerEncoderCell

    monkeypatch.setattr(base, "current_execution_platform",
                        lambda sample=None: "tpu")

    def run(dp):
        np.random.seed(3)
        mx.random.seed(3)
        net = TransformerEncoderCell(128, 256, 2, dropout=0.1,
                                     activation="gelu")
        net.initialize()
        step = par.TrainStep(net, gloss.L2Loss(), "sgd",
                             mesh=_dp_mesh({"dp": dp}),
                             optimizer_params={"learning_rate": 0.05})
        x = mx.nd.array(np.asarray(_rand(1, 8, 128, 128)))
        y = mx.nd.array(np.asarray(_rand(2, 8, 128, 128)))
        del interpreted_kernels[:]
        losses = [float(step(x, y)[0].asnumpy()) for _ in range(3)]
        return losses, set(interpreted_kernels)

    kernels = ("flash_attention", "fused_layer_norm", "fused_bias_gelu")
    dp4, calls = run(4)
    # (k, 8) too: the shape probe that settles the parameters, off the mesh
    assert calls >= {(k, 2) for k in kernels}
    dp1, calls = run(1)
    # (what the per-op cache holds from that probe is not traced again)
    assert {lead for _, lead in calls} == {8}
    assert dp4[-1] < dp4[0]
    np.testing.assert_allclose(dp4, dp1, rtol=2e-5)
