"""Optimizer tests vs numpy reference implementations
(reference: tests/python/unittest/test_optimizer.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt
from mxnet_tpu import telemetry as telemetry_mod


def _setup(shape=(4, 3), seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(*shape).astype("float32")
    g = rng.randn(*shape).astype("float32")
    return w, g, mx.nd.array(w), mx.nd.array(g)


def test_sgd_matches_numpy():
    w, g, wnd, gnd = _setup()
    o = opt.create("sgd", learning_rate=0.1, wd=0.01, rescale_grad=1.0)
    state = o.create_state(0, wnd)
    o.update(0, wnd, gnd, state)
    expect = w - 0.1 * (g + 0.01 * w)
    assert np.allclose(wnd.asnumpy(), expect, rtol=1e-5)


def test_sgd_momentum_matches_numpy():
    w, g, wnd, gnd = _setup()
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9, wd=0.0)
    state = o.create_state(0, wnd)
    mom = np.zeros_like(w)
    for _ in range(3):
        o.update(0, wnd, gnd, state)
        mom = 0.9 * mom - 0.1 * g
        w = w + mom
    assert np.allclose(wnd.asnumpy(), w, rtol=1e-5)


def test_adam_matches_numpy():
    w, g, wnd, gnd = _setup()
    lr, b1, b2, eps = 0.01, 0.9, 0.999, 1e-8
    o = opt.create("adam", learning_rate=lr, beta1=b1, beta2=b2, epsilon=eps,
                   wd=0.0)
    state = o.create_state(0, wnd)
    m = np.zeros_like(w)
    v = np.zeros_like(w)
    for t in range(1, 4):
        o.update(0, wnd, gnd, state)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        lr_t = lr * np.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        w = w - lr_t * m / (np.sqrt(v) + eps)
    assert np.allclose(wnd.asnumpy(), w, rtol=1e-4, atol=1e-6)


def test_rmsprop_runs_and_descends():
    w, g, wnd, gnd = _setup()
    o = opt.create("rmsprop", learning_rate=0.01)
    state = o.create_state(0, wnd)
    before = np.abs(wnd.asnumpy()).sum()
    for _ in range(5):
        o.update(0, wnd, gnd, state)
    assert not np.allclose(wnd.asnumpy(), w)


@pytest.mark.parametrize("name", ["adagrad", "adadelta", "ftrl", "signum",
                                  "nag", "lamb", "adamw", "sgld", "dcasgd"])
def test_all_optimizers_update(name):
    w, g, wnd, gnd = _setup(seed=3)
    o = opt.create(name, **({"learning_rate": 0.05} if name != "adadelta" else {}))
    state = o.create_state_multi_precision(0, wnd)
    o.update_multi_precision(0, wnd, gnd, state)
    assert not np.allclose(wnd.asnumpy(), w), name
    assert np.all(np.isfinite(wnd.asnumpy())), name


def test_multi_precision_bf16():
    rng = np.random.RandomState(1)
    w = rng.randn(8, 8).astype("float32")
    wnd = mx.nd.array(w, dtype="bfloat16")
    gnd = mx.nd.array(rng.randn(8, 8), dtype="bfloat16")
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9, multi_precision=True)
    state = o.create_state_multi_precision(0, wnd)
    # master weight is fp32
    assert str(state[0].dtype) == "float32"
    o.update_multi_precision(0, wnd, gnd, state)
    assert str(wnd.dtype) == "bfloat16"


def test_updater_state_roundtrip():
    w, g, wnd, gnd = _setup()
    o = opt.create("adam", learning_rate=0.01)
    upd = opt.get_updater(o)
    upd(0, gnd, wnd)
    states = upd.get_states()
    upd2 = opt.get_updater(opt.create("adam", learning_rate=0.01))
    upd2.set_states(states)
    assert 0 in upd2.states
    m1 = upd.states[0][0].asnumpy()
    m2 = upd2.states[0][0].asnumpy()
    assert np.allclose(m1, m2)


def test_lr_scheduler_factor():
    from mxnet_tpu.lr_scheduler import FactorScheduler, CosineScheduler

    s = FactorScheduler(step=10, factor=0.5, base_lr=1.0)
    assert s(1) == 1.0
    assert s(11) == 0.5
    assert s(21) == 0.25
    c = CosineScheduler(max_update=100, base_lr=1.0, final_lr=0.0)
    assert np.isclose(c(0), 1.0)
    assert np.isclose(c(50), 0.5, atol=1e-6)
    assert np.isclose(c(100), 0.0)


def test_lr_scheduler_warmup():
    from mxnet_tpu.lr_scheduler import PolyScheduler

    s = PolyScheduler(max_update=100, base_lr=1.0, warmup_steps=10,
                      warmup_begin_lr=0.0)
    assert s(5) == 0.5
    assert s(10) == 1.0


def test_optimizer_with_scheduler():
    from mxnet_tpu.lr_scheduler import FactorScheduler

    o = opt.create("sgd", learning_rate=1.0,
                   lr_scheduler=FactorScheduler(step=1, factor=0.5, base_lr=1.0))
    w, g, wnd, gnd = _setup()
    state = o.create_state(0, wnd)
    o.update(0, wnd, gnd, state)
    assert o.learning_rate < 1.0 or o.num_update == 1


def test_lr_mult_wd_mult():
    o = opt.create("sgd", learning_rate=1.0)
    o.set_lr_mult({0: 0.1})
    assert np.isclose(o._get_lr(0), 0.1)
    assert np.isclose(o._get_lr(1), 1.0)


# ---------------------------------------------------------------------------
# fused multi-tensor sweep engine (optimizer/multi_tensor.py)
# ---------------------------------------------------------------------------


def _train_eager(fused, optname, okw, monkeypatch, steps=10,
                 dtype="float32", mp=False, grad_req=None,
                 mixed_dtypes=False, double_backward=False):
    """One eager Trainer run; returns (loss bytes, weight bytes, state
    bytes) for bit-comparison between engine-on and engine-off."""
    import jax

    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss

    monkeypatch.setenv("MXNET_FUSED_OPTIMIZER", "1" if fused else "0")
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=32), nn.Dense(8, in_units=16))
    net.initialize()
    rs = np.random.RandomState(7)
    params = list(net.collect_params().values())
    for i, p in enumerate(params):
        cast = dtype
        if mixed_dtypes and i % 2 == 1:
            cast = "bfloat16" if dtype == "float32" else "float32"
        p.set_data(mx.nd.array(
            rs.randn(*p.shape).astype(np.float32)).astype(cast))
        if grad_req is not None and i == 1:
            p.grad_req = grad_req
    kw = dict(okw)
    if mp:
        kw["multi_precision"] = True
    tr = gluon.Trainer(net.collect_params(), optname, kw)
    loss_fn = L2Loss()
    rs2 = np.random.RandomState(11)
    x = mx.nd.array(rs2.randn(8, 32).astype(np.float32)).astype(dtype)
    y = mx.nd.array(rs2.randn(8, 8).astype(np.float32)).astype(dtype)
    losses = []
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        if double_backward:
            # grad_req='add' accumulation: a second backward before the
            # step sums into the same grad buffers on both paths
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
        tr.step(8)
        losses.append(loss.asnumpy().tobytes())
    ws = [p.data().asnumpy().tobytes() for p in params]
    sts = []
    for upd in tr._updaters:
        for i in sorted(upd.states):
            for leaf in jax.tree_util.tree_leaves(
                    upd.states[i],
                    is_leaf=lambda z: z is None or hasattr(z, "asnumpy")):
                if leaf is not None:
                    sts.append(leaf.asnumpy().tobytes())
    return losses, ws, sts


class TestFusedSweepBitIdentity:
    """ISSUE 11 acceptance gate: the fused multi-tensor sweep is
    BIT-identical to the per-param reference (trained state over >= 10
    steps) for every fused family, multi-precision included."""

    @pytest.mark.parametrize("optname,okw,dtype,mp", [
        ("adam", {"learning_rate": 0.01}, "float32", False),
        ("adam", {"learning_rate": 0.01}, "bfloat16", True),
        ("sgd", {"learning_rate": 0.05, "momentum": 0.9}, "float32",
         False),
        ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
         "bfloat16", True),
        ("adamw", {"learning_rate": 0.01, "wd": 0.01}, "float32", False),
        ("lamb", {"learning_rate": 0.01, "wd": 0.01}, "float32", False),
        ("lamb", {"learning_rate": 0.01, "wd": 0.01, "lower_bound": 0.1,
                  "upper_bound": 10.0}, "float32", False),
        ("lamb", {"learning_rate": 0.01}, "bfloat16", True),
    ])
    def test_trainer_ten_steps_bit_identical(self, optname, okw, dtype,
                                             mp, monkeypatch):
        a = _train_eager(True, optname, okw, monkeypatch, dtype=dtype,
                         mp=mp)
        b = _train_eager(False, optname, okw, monkeypatch, dtype=dtype,
                         mp=mp)
        assert a[0] == b[0], "losses diverged"
        assert a[1] == b[1], "weights diverged"
        assert a[2] == b[2], "optimizer state diverged"

    def test_mixed_trainable_set(self, monkeypatch):
        """fp32 + bf16 params in one Trainer (two dtype buckets) plus a
        grad_req='null' param excluded from the sweep."""
        a = _train_eager(True, "adam", {"learning_rate": 0.01},
                         monkeypatch, mixed_dtypes=True, mp=True,
                         grad_req="null")
        b = _train_eager(False, "adam", {"learning_rate": 0.01},
                         monkeypatch, mixed_dtypes=True, mp=True,
                         grad_req="null")
        assert a[1] == b[1] and a[2] == b[2]

    def test_grad_req_add_accumulation(self, monkeypatch):
        a = _train_eager(True, "adam", {"learning_rate": 0.01},
                         monkeypatch, grad_req="add", steps=5,
                         double_backward=True)
        b = _train_eager(False, "adam", {"learning_rate": 0.01},
                         monkeypatch, grad_req="add", steps=5,
                         double_backward=True)
        assert a[1] == b[1] and a[2] == b[2]

    def test_states_roundtrip_through_save_load(self, monkeypatch,
                                                tmp_path):
        """Fused-engine updater states stay in the Updater layout —
        save_states/load_states round-trips unchanged."""
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss

        monkeypatch.setenv("MXNET_FUSED_OPTIMIZER", "1")
        net = nn.Dense(8, in_units=16)
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.01})
        x = mx.nd.array(np.random.RandomState(0).randn(4, 16)
                        .astype(np.float32))
        y = mx.nd.array(np.random.RandomState(1).randn(4, 8)
                        .astype(np.float32))
        loss_fn = L2Loss()
        for _ in range(3):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(4)
        f = str(tmp_path / "trainer.states")
        tr.save_states(f)
        net2 = nn.Dense(8, in_units=16)
        net2.initialize()
        tr2 = gluon.Trainer(net2.collect_params(), "adam",
                            {"learning_rate": 0.01})
        with autograd.record():
            loss = loss_fn(net2(x), y)
        loss.backward()
        tr2.step(4)     # materialize states
        tr2.load_states(f)
        m1 = tr._updaters[0].states[0][0].asnumpy()
        m2 = tr2._updaters[0].states[0][0].asnumpy()
        assert np.array_equal(m1, m2)
        assert tr2._optimizer.num_update == tr._optimizer.num_update


class TestFusedSweepDispatchCount:
    """ISSUE 11 acceptance gate: the eager optimizer phase collapses
    from O(params) dispatches to <= 2 per dtype bucket (LAMB: 3 — the
    reference's own phase1 / multi_sum_sq / phase2 kernel granularity,
    required for bit-identity; see _LambSweep)."""

    @staticmethod
    def _counts():
        snap = telemetry_mod.snapshot()
        fam = snap["metrics"].get("mxnet_optimizer_dispatch_total",
                                  {"samples": []})
        return {s["labels"]["path"]: s["value"] for s in fam["samples"]}

    def _one_step(self, optname, monkeypatch, fused, n_params=3,
                  mixed=False):
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss

        monkeypatch.setenv("MXNET_FUSED_OPTIMIZER",
                           "1" if fused else "0")
        net = nn.HybridSequential()
        units = 16
        for i in range(n_params):
            net.add(nn.Dense(units, in_units=units, use_bias=False))
        net.initialize()
        if mixed:
            net[0].cast("bfloat16")     # second dtype bucket (bf16-mp)
        tr = gluon.Trainer(net.collect_params(), optname,
                           {"learning_rate": 0.01,
                            "multi_precision": mixed})
        x = mx.nd.ones((4, units))
        loss_fn = L2Loss()
        with autograd.record():
            loss = loss_fn(net(x), mx.nd.zeros((4, units)))
        loss.backward()
        tr.step(4)      # states created + first sweep compiled
        telemetry_mod.enable()
        try:
            before = self._counts()
            with autograd.record():
                loss = loss_fn(net(x), mx.nd.zeros((4, units)))
            loss.backward()
            tr.step(4)
            after = self._counts()
            # counters are process-global: report this step's DELTA
            return {k: after.get(k, 0) - before.get(k, 0)
                    for k in set(after) | set(before)}
        finally:
            telemetry_mod.disable()

    def test_adam_one_dispatch_per_bucket(self, monkeypatch):
        counts = self._one_step("adam", monkeypatch, fused=True,
                                n_params=6)
        assert counts.get("fused_sweep", 0) == 1     # one fp32 bucket
        assert counts.get("per_param", 0) == 0

    def test_two_dtype_buckets_two_dispatches(self, monkeypatch):
        counts = self._one_step("adam", monkeypatch, fused=True,
                                n_params=4, mixed=True)
        assert counts.get("fused_sweep", 0) == 2     # bf16-mp + fp32
        assert counts.get("per_param", 0) == 0

    def test_lamb_three_dispatches_per_bucket(self, monkeypatch):
        counts = self._one_step("lamb", monkeypatch, fused=True,
                                n_params=5)
        assert counts.get("fused_sweep", 0) == 3
        assert counts.get("per_param", 0) == 0

    def test_per_param_path_counts_o_params(self, monkeypatch):
        counts = self._one_step("adam", monkeypatch, fused=False,
                                n_params=6)
        assert counts.get("fused_sweep", 0) == 0
        assert counts.get("per_param", 0) == 6

    def test_bucket_telemetry_recorded(self, monkeypatch):
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss

        monkeypatch.setenv("MXNET_FUSED_OPTIMIZER", "1")
        net = nn.Dense(8, in_units=8)
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.01})
        x = mx.nd.ones((2, 8))
        loss_fn = L2Loss()

        def bucketed_params():
            snap = telemetry_mod.snapshot()
            fam = snap["metrics"].get(
                "mxnet_optimizer_bucketed_params_total", {"samples": []})
            return sum(s["value"] for s in fam["samples"])

        telemetry_mod.enable()
        try:
            before = bucketed_params()
            with autograd.record():
                loss = loss_fn(net(x), mx.nd.zeros((2, 8)))
            loss.backward()
            tr.step(2)
            assert bucketed_params() - before == 2   # weight + bias
            snap = telemetry_mod.snapshot()
            assert "mxnet_optimizer_bucket_bytes" in snap["metrics"]
        finally:
            telemetry_mod.disable()


class TestFusedSweepCompileOnce:
    """ISSUE 11 acceptance gate: the sweep compiles once per bucket
    signature (zero steady-state jit misses) and participates in
    warm_start() manifest replay."""

    @pytest.mark.retrace
    def test_steady_state_trainer_records_zero_sweep_misses(
            self, monkeypatch):
        from mxnet_tpu import autograd, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss

        monkeypatch.setenv("MXNET_FUSED_OPTIMIZER", "1")
        net = nn.HybridSequential()
        net.add(nn.Dense(16, in_units=32), nn.Dense(8, in_units=16))
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.01})
        x = mx.nd.ones((4, 32))
        y = mx.nd.zeros((4, 8))
        loss_fn = L2Loss()

        def step():
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(4)

        def sweep_stats():
            snap = telemetry_mod.snapshot()
            fam = snap["metrics"].get("mxnet_jit_cache_total",
                                      {"samples": []})
            return {s["labels"]["result"]: s["value"]
                    for s in fam["samples"]
                    if s["labels"]["cache"] == "optimizer_sweep"}

        step()      # warm: compile the sweep once
        telemetry_mod.enable()
        try:
            before = sweep_stats()
            for _ in range(3):
                step()
            after = sweep_stats()
            misses = after.get("miss", 0) - before.get("miss", 0)
            hits = after.get("hit", 0) - before.get("hit", 0)
            assert misses == 0, (before, after)
            assert hits >= 3
        finally:
            telemetry_mod.disable()

    def test_warm_start_replays_sweep_signature(self, monkeypatch,
                                                tmp_path):
        from mxnet_tpu import autograd, compiler, gluon
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss
        from mxnet_tpu.optimizer import multi_tensor as mt

        monkeypatch.setenv("MXNET_FUSED_OPTIMIZER", "1")
        m = compiler.enable_recording(str(tmp_path / "m.jsonl"))
        try:
            def steps(n=2):
                mx.random.seed(0)
                net = nn.Dense(16, in_units=32)
                net.initialize()
                tr = gluon.Trainer(net.collect_params(), "adam",
                                   {"learning_rate": 0.01})
                x = mx.nd.array(np.random.RandomState(1).randn(8, 32)
                                .astype(np.float32))
                y = mx.nd.array(np.random.RandomState(2).randn(8, 16)
                                .astype(np.float32))
                loss_fn = L2Loss()
                for _ in range(n):
                    with autograd.record():
                        loss = loss_fn(net(x), y)
                    loss.backward()
                    tr.step(8)
                return loss.asnumpy()

            ref = steps()
            # reload FROM DISK (not the live recorder): the loader's
            # KNOWN_SITES filter must accept optimizer_sweep entries —
            # the path a real fresh process takes
            reloaded = compiler.Manifest(str(tmp_path / "m.jsonl"))
            assert any(e["site"] == "optimizer_sweep"
                       for e in reloaded.entries())
            # fresh-process proxy: clear the sweep cache, replay the
            # on-disk manifest with NO provider, then train with zero
            # misses
            mt.sweep_cache().clear()
            report = compiler.warm_start(str(tmp_path / "m.jsonl"))
            assert report["failed"] == 0

            def sweep_misses():
                snap = telemetry_mod.snapshot()
                fam = snap["metrics"].get("mxnet_jit_cache_total",
                                          {"samples": []})
                return sum(s["value"] for s in fam["samples"]
                           if s["labels"]["cache"] == "optimizer_sweep"
                           and s["labels"]["result"] == "miss")

            telemetry_mod.enable()
            try:
                before = sweep_misses()
                out = steps()
                assert sweep_misses() - before == 0
            finally:
                telemetry_mod.disable()
            assert out.tobytes() == ref.tobytes()
        finally:
            compiler.disable_recording()


class TestFusedSweepTrainStep:
    """TrainStep integration: the jitted step's update phase is ALWAYS
    the per-parameter loop — inside one executable there is no dispatch
    for a packed sweep to collapse, and packing the parameter set costs
    more than the update (PERF.md section 6, PR 27). Neither the
    ``MXNET_FUSED_OPTIMIZER`` knob nor an engaged Pallas sweep kernel
    can change what the step computes."""

    _OPTS = {
        "adam": {"learning_rate": 0.01},
        "sgd": {"learning_rate": 0.05, "momentum": 0.9},
        "adamw": {"learning_rate": 0.01, "wd": 0.01},
        "lamb": {"learning_rate": 0.01, "wd": 0.01},
    }

    def _run_step(self, monkeypatch, fused, force_kernel=False,
                  optname="adam"):
        import jax

        from mxnet_tpu import parallel as par
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss

        monkeypatch.setenv("MXNET_FUSED_OPTIMIZER",
                           "1" if fused else "0")
        if force_kernel:
            from mxnet_tpu.pallas_kernels import fused_optimizer as fopt

            monkeypatch.setattr(fopt, "fused_opt_supported",
                                lambda p: True)
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, in_units=32), nn.Dense(8, in_units=16))
        net.initialize()
        rs = np.random.RandomState(7)
        for p in net.collect_params().values():
            p.set_data(mx.nd.array(rs.randn(*p.shape)
                                   .astype(np.float32)))
        mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        step = par.TrainStep(net, L2Loss(), optname, mesh=mesh,
                             optimizer_params=self._OPTS[optname])
        rs2 = np.random.RandomState(11)
        x = mx.nd.array(rs2.randn(8, 32).astype(np.float32))
        y = mx.nd.array(rs2.randn(8, 8).astype(np.float32))
        for _ in range(5):
            loss, _ = step(x, y)
        return (loss.asnumpy(),
                [p.data().asnumpy()
                 for p in net.collect_params().values()])

    def test_cpu_knob_identity(self, monkeypatch):
        a = self._run_step(monkeypatch, fused=True)
        b = self._run_step(monkeypatch, fused=False)
        assert np.array_equal(a[0], b[0])
        assert all(np.array_equal(x, y) for x, y in zip(a[1], b[1]))

    @pytest.mark.parametrize("optname", sorted(_OPTS))
    def test_kernel_gate_open_step_keeps_the_loop(self, monkeypatch,
                                                  optname):
        """With the sweep kernel's gate forced open (what one TPU chip
        with MXNET_PALLAS_FUSED=1 reports) the step (a) routes nothing
        to ``fused_opt_sweep`` and (b) is bit-identical in loss and
        weights to the knob-off run."""
        telemetry_mod.enable()
        try:
            a = self._run_step(monkeypatch, fused=True, force_kernel=True,
                               optname=optname)
            snap = telemetry_mod.snapshot()
        finally:
            telemetry_mod.disable()
        fam = snap["metrics"].get("mxnet_pallas_dispatch_total",
                                  {"samples": []})
        kernels = {s["labels"]["kernel"]: s["value"]
                   for s in fam["samples"]}
        assert kernels.get("fused_opt_sweep", 0) == 0
        monkeypatch.undo()
        b = self._run_step(monkeypatch, fused=False, optname=optname)
        assert a[0].tobytes() == b[0].tobytes()
        assert all(x.tobytes() == y.tobytes()
                   for x, y in zip(a[1], b[1]))

    def test_step_never_repacks_the_parameter_set(self, monkeypatch):
        """Structural guard: no array in the compiled multi-precision
        bf16 Adam step has as many elements as the parameter set —
        nothing packs the parameters into one bucket inside the step,
        sweep kernel gate open or not."""
        import re

        import jax

        from mxnet_tpu import parallel as par
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss
        from mxnet_tpu.pallas_kernels import fused_optimizer as fopt

        monkeypatch.setattr(fopt, "fused_opt_supported", lambda p: True)
        net = nn.HybridSequential()
        net.add(nn.Dense(48, in_units=32), nn.Dense(40, in_units=48),
                nn.Dense(8, in_units=40))
        net.initialize()
        net.cast("bfloat16")
        n_params = sum(int(np.prod(p.shape))
                       for p in net.collect_params().values())
        mesh = par.make_mesh({"dp": 1}, devices=jax.devices()[:1])
        step = par.TrainStep(net, L2Loss(), "adam", mesh=mesh,
                             optimizer_params={"learning_rate": 0.01,
                                               "multi_precision": True})
        # batch 4: no activation comes near the parameter count
        x = mx.nd.zeros((4, 32), dtype="bfloat16")
        y = mx.nd.zeros((4, 8), dtype="bfloat16")
        step.warm(x, y)
        text = step.compiled(x, y).as_text()
        sizes = [int(np.prod([int(d) for d in dims.split(",")]))
                 for dims in re.findall(r"\b[a-z]+\d+\[([\d,]+)\]", text)]
        assert len(sizes) > 20 and max(sizes) < n_params, \
            (max(sizes), n_params)

    def test_row_sparse_params_stay_on_lazy_path(self, monkeypatch):
        """Row-sparse embedding grads keep the lazy-row update whatever
        the sweep knob says: the embedding's untouched rows stay
        bit-identical, and the knob changes nothing."""
        import jax

        from mxnet_tpu import parallel as par
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss

        def build():
            mx.random.seed(0)
            net = nn.HybridSequential()
            net.add(nn.Embedding(50, 16, sparse_grad=True),
                    nn.Dense(8, in_units=16, flatten=False))
            net.initialize()
            rs = np.random.RandomState(3)
            for p in net.collect_params().values():
                p.set_data(mx.nd.array(rs.randn(*p.shape)
                                       .astype(np.float32)))
            return net

        def run(fused):
            monkeypatch.setenv("MXNET_FUSED_OPTIMIZER", fused)
            net = build()
            mesh = par.make_mesh({"dp": 1},
                                 devices=jax.devices()[:1])
            step = par.TrainStep(net, L2Loss(), "adam", mesh=mesh,
                                 optimizer_params={
                                     "learning_rate": 0.01})
            ids = mx.nd.array(np.array([[1, 2, 3, 1]], np.float32))
            y = mx.nd.array(np.zeros((1, 4, 8), np.float32))
            for _ in range(3):
                loss, _ = step(ids, y)
            emb = list(net.collect_params().values())[0]
            return emb.data().asnumpy(), loss.asnumpy()

        emb_on, loss_on = run("1")
        emb_off, loss_off = run("0")
        # untouched rows keep their initial values (no dense update
        # over the full table); touched rows updated
        rs = np.random.RandomState(3)
        init = rs.randn(*emb_off.shape).astype(np.float32)
        untouched = [r for r in range(50) if r not in (1, 2, 3)]
        assert np.array_equal(emb_on[untouched], init[untouched])
        assert np.array_equal(emb_off[untouched], init[untouched])
        assert not np.allclose(emb_on[[1, 2, 3]], init[[1, 2, 3]])
        assert np.array_equal(emb_on, emb_off)
        assert np.array_equal(loss_on, loss_off)


class TestOptimizerTailClasses:
    """Round-4: FTML / Adamax / Nadam / LBSGD classes (reference
    optimizer.py tail). Gate: each drives a quadratic to ~zero."""

    @pytest.mark.parametrize("name,kw", [
        ("ftml", {"learning_rate": 0.2}),
        ("adamax", {"learning_rate": 0.2}),
        ("nadam", {"learning_rate": 0.2}),
        ("lbsgd", {"learning_rate": 0.1, "eta": 1.0}),
    ])
    def test_quadratic_converges(self, name, kw):
        opt = mx.optimizer.create(name, **kw)
        w = mx.nd.array([1.0, -2.0])
        state = opt.create_state(0, w)
        # every update re-traces on the python step count, so steps are
        # the whole cost: 30 at these rates end below 0.1 (gate: 0.5)
        for _ in range(30):
            opt.update(0, w, 2 * w, state)
        assert float((w.asnumpy() ** 2).sum()) < 0.5, name
