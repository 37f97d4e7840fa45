"""The fused, sharded training step — SURVEY.md §3.5's end state.

Reference call stack being replaced: ``Trainer.step`` → kvstore push/pull
(NCCL allreduce / ps-lite ZPush-ZPull) → per-context ``Optimizer.update``
(src/kvstore/*, python/mxnet/gluon/trainer.py). On TPU that whole stack is
ONE compiled executable: forward, loss, backward, gradient psum over the
``dp`` mesh axis (inserted by GSPMD from the batch sharding), and the
per-parameter optimizer update — all fused, parameters donated so the
update is in-place in HBM.

    step = TrainStep(net, loss, optimizer='adam', mesh=make_mesh({'dp': 8}))
    loss, outs = step(data, label)     # one device-side step, no host sync

Semantics preserved from the reference:
* optimizer state dtypes/bias corrections identical to the eager Updater
  (the same ``Optimizer`` object runs inside the trace — in dynamic mode,
  so step count and scheduled LR stay traced scalars and one executable
  serves every step);
* BatchNorm moving stats (aux states) are returned as extra outputs and
  written back, like CachedOp's aux-state contract;
* gradient clipping/rescale via the optimizer's own attributes.
"""
from __future__ import annotations

import contextlib
import logging
from typing import Dict, List, Optional, Sequence

from .. import optimizer as opt_mod
from .. import mutation, random_state
from ..base import MXNetError, execution_platform
from ..context import current_context
from ..ndarray import NDArray
from ..gluon.block import (make_pure_fn, nested_flatten_nd,
                           nested_unflatten_nd, resolve_remat_policy)
from .mesh import current_mesh, make_mesh, use_mesh
from .sharding import ShardingRules, named_sharding, spec_for_param

__all__ = ["TrainStep"]

_log = logging.getLogger(__name__)


def _as_tuple(x):
    if x is None:
        return ()
    if isinstance(x, (list, tuple)):
        return tuple(x)
    return (x,)


class TrainStep:
    """Compile ``net`` + ``loss`` + ``optimizer`` into one sharded step.

    Parameters
    ----------
    net : HybridBlock with initialized parameters.
    loss : callable ``loss(outputs, *labels) -> NDArray`` (a gluon Loss
        block works); reduced by mean inside the graph.
    optimizer : Optimizer instance or name ('sgd', 'adam', ...).
    mesh : jax Mesh; default = the active ``use_mesh`` mesh, else all
        visible devices on one ``dp`` axis.
    rules : ShardingRules for parameter layout (tensor parallelism);
        unmatched params are replicated.
    batch_axis : mesh axes the leading batch dimension is sharded over
        (default ``('dp',)``; pass e.g. ``('dp','fsdp')`` for combined axes).
    seq_axis : optional mesh axis for sequence sharding of rank>=2 inputs
        (dimension 1) — context parallelism for long sequences.
    donate_inputs : donate the batch buffers to the executable (XLA may
        reuse their HBM for activations). Only for single-use batches —
        an async input pipeline (``io.DeviceFeedIter``) stages a fresh
        buffer per step; a benchmark replaying one staged batch must NOT
        set this (the donated buffer is dead after the call).
    remat : gradient-rematerialization policy for the whole net inside
        the compiled step — ``None`` (save activations, the default),
        ``"full"`` (save nothing: recompute the forward in the backward,
        max memory headroom for ~one extra forward of FLOPs) or
        ``"dots"`` (matmul outputs saved, elementwise/norm recompute —
        no MXU work re-runs). The same policy names as
        ``gluon.block.remat_call`` / the Llama zoo's ``remat=`` kwarg,
        resolved by the one shared validator — but threaded here ANY
        compiled step can trade recompute for the batch-size headroom
        the MFU targets need, not just nets that opted in at
        construction. Composes with model-level remat_call (inner
        checkpoints nest).
    """

    def __init__(self, net, loss, optimizer, mesh=None,
                 rules: Optional[ShardingRules] = None,
                 batch_axis: Sequence[str] = ("dp",), seq_axis=None,
                 optimizer_params=None, loss_only=False,
                 donate_inputs=False, remat=None):
        self.net = net
        self.loss = loss
        # loss_only: don't return model outputs from the step — for nets
        # with huge heads (e.g. an MLM decoder's (B, L, vocab) logits) the
        # returned buffer otherwise must be materialized in HBM and shipped
        # out of the executable every step
        self.loss_only = bool(loss_only)
        if not isinstance(optimizer, opt_mod.Optimizer):
            optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        self.optimizer = optimizer
        if mesh is None:
            mesh = current_mesh() or make_mesh()
        self.mesh = mesh
        self.rules = rules
        self.batch_axis = tuple(a for a in _as_tuple(batch_axis)
                                if a in mesh.axis_names)
        self.seq_axis = seq_axis if (seq_axis in mesh.axis_names) else None
        self.donate_inputs = bool(donate_inputs)
        # validate eagerly — a typo must raise at construction, not from
        # inside the first traced step
        resolve_remat_policy(remat)
        self.remat = remat
        from ..compiler import service as _csvc

        self._cache = _csvc.SiteCache("train_step")
        self._params = None          # List[Parameter]
        self._param_specs = None     # per-param PartitionSpec
        self._trainable = None       # indices into _params
        self._state_leaf_nds = None  # flat list of state NDArrays (persist)
        self._state_meta = None      # per-trainable (treedef, n_leaves, shapes)

    # -- setup ----------------------------------------------------------
    def _abstract_settle(self, shape_vals, fallback=None, ctx=None):
        """Resolve deferred parameter shapes with an eval_shape probe.

        Shape inference is host-side — nothing is computed (parameter
        initializers still run concretely when a deferred init resolves,
        unless the param was built under ``abstract_init``). The probe
        must not advance the global PRNG stream with traced keys
        (rng-consuming ops like Dropout run under the trace), so the
        stream state is snapshotted and restored. ``fallback`` (the eager
        forward — the reference move, HybridBlock.__call__ on
        DeferredInitializationError) covers blocks whose forward needs
        concrete values.
        """
        import jax

        net = self.net

        probe_ctx = ctx or current_context()

        def _shape_probe(*vals):
            nds = [NDArray(data=v, ctx=probe_ctx) for v in vals]
            net(*nds)
            return 0

        try:
            with random_state.preserved_stream():
                jax.eval_shape(_shape_probe, *shape_vals)
        except Exception:
            if fallback is None:
                raise
            # fallback runs AFTER the stream restore: an aborted probe
            # leaves traced keys in the stateful stream, and an eager
            # fallback splitting one of those is an escaped-tracer error
            # (found live, round 5)
            fallback()

    def _bind_params(self):
        """Record the settled parameter list, trainable ordinals,
        optimizer param_dict and per-param shardings — shared by the live
        path and aot_compile so the two can't diverge.

        Per-param lr_mult/wd_mult flow through the optimizer's
        param_dict, keyed by the SAME trainable ordinals update() is
        called with (mirrors Trainer._init_optimizer wiring).
        """
        params = list(self.net.collect_params().values())
        self._params = params
        self._trainable = [i for i, p in enumerate(params)
                           if p.grad_req != "null"]
        self.optimizer.param_dict = {
            k: params[i] for k, i in enumerate(self._trainable)}
        self._param_specs = [
            spec_for_param(p.name, p.shape, self.rules, self.mesh)
            for p in params]
        self._check_sparse_sharing()
        return params

    def _check_sparse_sharing(self):
        """A row-sparse-grad embedding weight must not be shared with
        another block (weight-tied softmax head): the dense cotangent
        from the other use would be silently dropped by the lazy row
        update. Detects PARAMETER-OBJECT sharing across blocks; passing
        the same array through other ops manually remains the user's
        responsibility (same contract as the reference's stype checks).
        """
        owners = {}

        def walk(block):
            for p in getattr(block, "_reg_params", {}).values():
                if getattr(p, "grad_stype", "default") == "row_sparse":
                    owners.setdefault(id(p), [p, 0])
                    owners[id(p)][1] += 1
            for child in getattr(block, "_children", {}).values():
                walk(child)

        walk(self.net)
        for p, count in owners.values():
            if count > 1:
                raise MXNetError(
                    f"Parameter {p.name} has grad_stype='row_sparse' but "
                    f"is shared by {count} blocks (weight tying); the "
                    "lazy row update would drop the dense gradient from "
                    "the other use — build the Embedding with "
                    "sparse_grad=False for tied weights")

    def _settle_params(self, data_tuple):
        params = list(self.net.collect_params().values())
        if any(p._data is None for p in params):
            net = self.net
            # probe on the batch's own context: parameters initialised
            # on tpu(0) are not there for a cpu(0) probe
            self._abstract_settle([v.data for v in data_tuple],
                                  fallback=lambda: net(*data_tuple),
                                  ctx=data_tuple[0].context)
            if any(p._data is None
                   for p in net.collect_params().values()):
                net(*data_tuple)
        params = self._bind_params()
        # lay params out on the mesh once (single-process view: one NDArray
        # per param; its payload becomes a sharded global jax.Array)
        import jax

        for p, spec in zip(params, self._param_specs):
            arr = p.data()
            arr._set_data(
                jax.device_put(arr.data, named_sharding(self.mesh, spec)))

    def _make_state_builder(self):
        """The batched optimizer-state constructor + its treedef slots.

        ONE traced function builds every state leaf: building states
        eagerly costs hundreds of tiny device dispatches before the
        first step.
        Shared by _init_states (jit, concrete) and aot_compile
        (eval_shape, abstract) so the state layout can't diverge between
        live training and AOT memory analysis.
        """
        import jax

        is_leaf = lambda x: x is None or isinstance(x, NDArray)
        optimizer = self.optimizer
        trainable = list(self._trainable)
        ctx = self._params[0].data().context if self._params \
            else current_context()
        treedefs = [None] * len(trainable)

        def _all_states(param_vals):
            flat = []
            for k, i in enumerate(trainable):
                w = NDArray(data=param_vals[k], ctx=ctx)
                state = optimizer.create_state_multi_precision(k, w)
                leaves, treedefs[k] = jax.tree_util.tree_flatten(
                    state, is_leaf=is_leaf)
                flat.append(tuple(None if leaf is None else leaf.data
                                  for leaf in leaves))
            return tuple(flat)

        return _all_states, treedefs, ctx

    def _state_layout(self, k, i, leaves, treedef, on_leaf):
        """Per-param state-leaf layout: ``(treedef, present, specs)`` meta
        entry, calling ``on_leaf(leaf, leaf_spec)`` for each present leaf.
        The rule: a leaf shaped like its param shards like the param;
        everything else (scalars, row stats) replicates."""
        from jax.sharding import PartitionSpec as P

        p = self._params[i]
        spec = self._param_specs[i]
        present = [leaf is not None for leaf in leaves]
        specs = []
        for leaf in leaves:
            if leaf is None:
                continue
            leaf_spec = spec if tuple(leaf.shape) == tuple(p.shape) else P()
            specs.append(leaf_spec)
            on_leaf(leaf, leaf_spec)
        return (treedef, present, specs)

    def _init_states(self):
        import jax

        _all_states, treedefs, ctx = self._make_state_builder()
        trainable = list(self._trainable)
        param_data = tuple(self._params[i].data().data for i in trainable)
        # transfer-guard exemption: the builder may implicitly move host
        # scalars/param copies across platforms
        with jax.transfer_guard("allow"):
            all_leaves = jax.jit(_all_states)(param_data)

        leaf_nds: List[NDArray] = []
        meta = []
        for k, i in enumerate(trainable):
            meta.append(self._state_layout(
                k, i, all_leaves[k], treedefs[k],
                lambda leaf, spec: leaf_nds.append(NDArray(
                    data=jax.device_put(
                        leaf, named_sharding(self.mesh, spec)), ctx=ctx))))
        self._state_leaf_nds = leaf_nds
        self._state_meta = meta

    def _batch_spec(self, val):
        from jax.sharding import PartitionSpec as P

        entries = [None] * val.ndim
        if val.ndim >= 1 and self.batch_axis:
            size = 1
            for ax in self.batch_axis:
                size *= self.mesh.shape[ax]
            if size > 1 and val.shape[0] % size == 0:
                entries[0] = self.batch_axis if len(self.batch_axis) > 1 \
                    else self.batch_axis[0]
        if self.seq_axis and val.ndim >= 2:
            s = self.mesh.shape[self.seq_axis]
            if s > 1 and val.shape[1] % s == 0:
                entries[1] = self.seq_axis
        return P(*entries)

    @contextlib.contextmanager
    def tracing(self):
        """What every trace of this step's program runs under (the call,
        the two ahead-of-time compiles, ``telemetry``'s cost analysis):
        the platform the ops dispatch on, and the step's mesh with its
        batch axes, which in-graph mesh consumers resolve (ring
        attention's ``shard_map``, the Pallas gates' ``kernel_shards``)."""
        with execution_platform(self.mesh.devices.flat[0].platform), \
                use_mesh(self.mesh, batch_axes=self.batch_axis):
            yield

    # -- build ----------------------------------------------------------
    def _pipelined_1f1b(self):
        """The net itself as a 1F1B-scheduled Pipelined block, or None.

        The 1F1B schedule folds the loss into the last pipeline stage, so
        the step cannot be built as grad(loss(net(x))) — TrainStep routes
        it through :func:`pipeline_train_1f1b` instead. Supported shape:
        ``net`` IS the Pipelined trunk (embedding/head belong in the loss
        callable, which runs on the last stage)."""
        from .pipeline import Pipelined

        net = self.net
        if isinstance(net, Pipelined) and net._schedule == "1f1b":
            return net
        return None

    # -- build ----------------------------------------------------------
    def _build(self, data_tuple, label_tuple, training):
        import jax
        from jax.sharding import PartitionSpec as P

        ctx = self._params[0].data().context if self._params else current_context()
        pipe = self._pipelined_1f1b()
        if pipe is not None:
            from .pipeline import pipeline_train_1f1b

            stage_all = pipe._stage_fn_1f1b(ctx, training)
            pipe_axis, pipe_micro = pipe._axis, pipe._n_micro
            pure, cell = None, {"aux_arrays": [], "treedef": None,
                                "n_out": 0}
            if len(data_tuple) != 1 or len(label_tuple) != 1:
                raise MXNetError(
                    "TrainStep over a 1F1B Pipelined takes exactly one "
                    "data and one label array")
        else:
            param_arrays = [p.data() for p in self._params]
            pure, cell = make_pure_fn(self.net, param_arrays, ctx, training)
            if self.remat is not None:
                # net forward under jax.checkpoint: activations inside the
                # span are recomputed during the backward per the policy.
                # Parameters/batch enter as checkpoint arguments (always
                # saved); the loss head stays outside the span.
                pure = jax.checkpoint(
                    pure, policy=resolve_remat_policy(self.remat))
        if pipe is not None and self.remat is not None:
            raise MXNetError(
                "TrainStep(remat=...) does not apply to a 1F1B Pipelined "
                "net — the pipelined trunk owns its own remat "
                "(Pipelined(remat=True))")
        loss_only = self.loss_only or pipe is not None
        trainable = list(self._trainable)
        if pipe is not None:
            id2k = {id(self._params[i]): k for k, i in enumerate(trainable)}
            try:
                stacked_ks = [id2k[id(sp)] for sp in pipe._stacked]
            except KeyError:
                raise MXNetError(
                    "1F1B TrainStep requires every stacked pipeline "
                    "parameter to be trainable (grad_req != 'null')")
            if len(stacked_ks) != len(trainable):
                raise MXNetError(
                    "TrainStep(schedule='1f1b') supports a net whose "
                    "trainable params are exactly the Pipelined trunk's "
                    "stacked parameters; put embedding/head inside the "
                    "loss callable")
        n_data = len(data_tuple)
        optimizer = self.optimizer
        loss_fn = self.loss
        state_meta = self._state_meta
        params_by_i = [p.name for p in self._params]
        mesh = self.mesh

        def step_fn(param_vals, state_vals, t, lr, rng, *batch_vals):
            import jax.numpy as jnp

            data_vals = batch_vals[:n_data]
            label_vals = batch_vals[n_data:]

            def loss_of(train_vals):
                pvals = list(param_vals)
                for k, i in enumerate(trainable):
                    pvals[i] = train_vals[k]
                outs, aux = pure(tuple(pvals), rng, *data_vals)
                out_nd = [NDArray(data=v, ctx=ctx) for v in outs]
                out_tree = nested_unflatten_nd(cell["treedef"], out_nd)
                label_nds = [NDArray(data=v, ctx=ctx) for v in label_vals]
                loss_out = loss_fn(out_tree, *label_nds)
                flat_loss, _ = nested_flatten_nd(loss_out)
                loss_val = jnp.mean(flat_loss[0].data.astype(jnp.float32))
                return loss_val, (outs, aux)

            from .sparse_grad import lazy_row_update, sparse_grad_scope

            train_vals = tuple(param_vals[i] for i in trainable)
            if pipe is not None:
                # 1F1B: loss folded into the last stage; grads come from
                # the schedule, not from AD over the block forward
                def head_loss(h, y):
                    l_out = loss_fn(NDArray(data=h, ctx=ctx),
                                    NDArray(data=y, ctx=ctx))
                    flat_l, _ = nested_flatten_nd(l_out)
                    return jnp.mean(flat_l[0].data.astype(jnp.float32))

                leaves = tuple(train_vals[k] for k in stacked_ks)
                loss_val, g_stacked, _dx = pipeline_train_1f1b(
                    stage_all, head_loss, leaves, data_vals[0],
                    label_vals[0], rng, mesh=mesh, axis=pipe_axis,
                    n_microbatches=pipe_micro)
                grads = [None] * len(trainable)
                for k, g in zip(stacked_ks, g_stacked):
                    grads[k] = g
                outs, aux = (), ()
                sparse_by_k = {}
            else:
                with sparse_grad_scope() as sp_log:
                    (loss_val, (outs, aux)), grads = jax.value_and_grad(
                        loss_of, has_aux=True)(train_vals)
                # scope entries are keyed by parameter NAME (the embedding
                # op's _sparse_uid); map to trainable ordinals
                sparse_by_k = {}
                for uid, entries in sp_log.entries.items():
                    for k, i in enumerate(trainable):
                        if params_by_i[i] == uid:
                            sparse_by_k[k] = entries
                            break

            # the update phase is the per-parameter loop: inside one
            # executable there is no dispatch for a packed sweep to
            # collapse, and packing the parameter set costs more than
            # the whole update (PERF.md section 6, PR 27)
            new_params = list(param_vals)
            new_state_vals = list(state_vals)
            with optimizer.dynamic(t, lr):
                with mutation.mutation_scope():
                    cursor = 0
                    for k, i in enumerate(trainable):
                        treedef, present, _ = state_meta[k]
                        w_nd = NDArray(data=param_vals[i], ctx=ctx)
                        leaf_nds = []
                        live = []
                        for is_present in present:
                            if is_present:
                                nd_leaf = NDArray(data=state_vals[cursor], ctx=ctx)
                                leaf_nds.append(nd_leaf)
                                live.append((cursor, nd_leaf))
                                cursor += 1
                            else:
                                leaf_nds.append(None)
                        state = jax.tree_util.tree_unflatten(treedef, leaf_nds)
                        if k in sparse_by_k:
                            # row-sparse embedding grad: lazy row update;
                            # the dense zero cotangent in grads[k] stays
                            # unconsumed and DCEs out of the executable
                            lazy_row_update(optimizer, k, w_nd,
                                            sparse_by_k[k], state, ctx)
                        else:
                            g_nd = NDArray(data=grads[k], ctx=ctx)
                            optimizer.update_multi_precision(
                                k, w_nd, g_nd, state)
                        new_params[i] = w_nd.data
                        for idx, nd_leaf in live:
                            new_state_vals[idx] = nd_leaf.data
            if loss_only:
                outs = ()
            return (tuple(new_params), tuple(new_state_vals), loss_val,
                    tuple(outs), tuple(aux))

        mesh = self.mesh
        ns = lambda spec: named_sharding(mesh, spec)
        rep = ns(P())
        param_sh = tuple(ns(s) for s in self._param_specs)
        state_sh = tuple(ns(spec) for (_, _, specs) in state_meta
                         for spec in specs)
        batch_sh = tuple(ns(self._batch_spec(v))
                         for v in list(data_tuple) + list(label_tuple))
        in_sh = (param_sh, state_sh, rep, rep, rep) + batch_sh
        import os

        if os.environ.get("MXNET_TPU_DONATE", "1") == "0":
            # donation off (MXNET_TPU_DONATE=0): an HBM optimization
            # with no value on host memory; a step without aliasing also
            # takes the exported-blob (trace-skip) tier in _aot_seal
            donate: tuple = ()
        else:
            donate = (0, 1)
            if self.donate_inputs:
                # batch args start after (params, states, t, lr, rng)
                donate = donate + tuple(range(5, 5 + len(batch_sh)))
        # outputs: params/states keep their layout (no per-step reshard);
        # loss replicated; model outputs/aux left to XLA (None = inferred)
        jitted = jax.jit(
            step_fn,
            in_shardings=in_sh,
            out_shardings=(param_sh, state_sh, rep, None, None),
            donate_argnums=donate,
        )

        def cell_probe():
            # settle `cell` (output treedef + aux arrays) without a
            # compile when an exported-blob hit skipped the trace
            if pipe is not None or cell["treedef"] is not None:
                return
            pvals = tuple(
                jax.ShapeDtypeStruct(tuple(p.shape),
                                     jax.numpy.dtype(str(p.dtype)))
                for p in self._params)
            with random_state.preserved_stream():
                rng_t = random_state.get_state_key()
            jax.eval_shape(
                pure, pvals,
                jax.ShapeDtypeStruct(tuple(rng_t.shape), rng_t.dtype),
                *(jax.ShapeDtypeStruct(tuple(v.shape),
                                       jax.numpy.dtype(str(v.dtype)))
                  for v in data_tuple))

        return {"jitted": jitted, "cell": cell, "batch_sh": batch_sh,
                "loss_only": loss_only, "cell_probe": cell_probe}

    def aot_compile(self, data, label=()):
        """AOT-compile the sharded train step on ABSTRACT parameters.

        For validating recipes whose weights don't fit the host (e.g. the
        Llama-3-8B stretch config on a dev box): the net must have been
        built and "initialized" under ``gluon.parameter.abstract_init()``.
        Settle, state layout, step build, lowering and XLA compilation all
        run the normal TrainStep code path — only buffers never
        materialize. Returns the ``jax.stages.Compiled`` executable
        (``.memory_analysis()`` gives per-device HBM numbers).

        ``data``/``label``: host-shaped template NDArrays or
        ``jax.ShapeDtypeStruct``s describing one global batch.
        """
        import jax

        data_tuple = _as_tuple(data)
        label_tuple = _as_tuple(label)

        def _struct(v):
            if isinstance(v, jax.ShapeDtypeStruct):
                return v
            return jax.ShapeDtypeStruct(tuple(v.shape),
                                        jax.numpy.dtype(str(v.dtype)))

        batch_structs = [_struct(v) for v in data_tuple + label_tuple]

        # settle (abstract): eval_shape probe resolves deferred shapes with
        # zero-cost placeholder data (no eager fallback — AOT nets must
        # settle abstractly by definition)
        net = self.net
        params = list(net.collect_params().values())
        if any(p._data is None for p in params):
            self._abstract_settle(batch_structs[:len(data_tuple)])
        params = self._bind_params()
        # this instance now holds abstract params and no live state
        # buffers — it can compile but never execute
        self._aot_only = True

        # optimizer states: shape-only evaluation of the SAME batched
        # state builder _init_states compiles
        _all_states, treedefs, ctx = self._make_state_builder()
        trainable = list(self._trainable)
        param_structs = tuple(
            jax.ShapeDtypeStruct(tuple(p.shape),
                                 jax.numpy.dtype(str(p.dtype)))
            for p in params)
        train_structs = tuple(param_structs[i] for i in trainable)
        state_shapes = jax.eval_shape(_all_states, train_structs)

        state_structs = []
        meta = []
        for k, i in enumerate(trainable):
            meta.append(self._state_layout(
                k, i, state_shapes[k], treedefs[k],
                lambda leaf, spec: state_structs.append(
                    jax.ShapeDtypeStruct(
                        tuple(leaf.shape), leaf.dtype,
                        sharding=named_sharding(self.mesh, spec)))))
        self._state_meta = meta
        self._state_leaf_nds = []  # aot: no live state buffers

        entry = self._build(
            tuple(NDArray(data=s, ctx=ctx) for s in
                  batch_structs[:len(data_tuple)]),
            tuple(NDArray(data=s, ctx=ctx) for s in
                  batch_structs[len(data_tuple):]),
            True)

        import numpy as np

        param_sharded = tuple(
            jax.ShapeDtypeStruct(s.shape, s.dtype,
                                 sharding=named_sharding(self.mesh, spec))
            for s, spec in zip(param_structs, self._param_specs))
        t = jax.ShapeDtypeStruct((), np.int32)
        lr = jax.ShapeDtypeStruct((), np.float32)
        # key shape/dtype only — the stream snapshot keeps the compile
        # from advancing the program's random sequence (reproducibility)
        with random_state.preserved_stream():
            key = random_state.get_state_key()
        rng = jax.ShapeDtypeStruct(tuple(key.shape), key.dtype)
        batch_in = tuple(
            jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
            for s, sh in zip(batch_structs, entry["batch_sh"]))

        with self.tracing():
            lowered = entry["jitted"].lower(
                param_sharded, tuple(state_structs), t, lr, rng, *batch_in)
            return lowered.compile()

    def save_sharded(self, directory):
        """Per-process sharded checkpoint (SURVEY §5.4 stretch; see
        parallel/checkpoint.py)."""
        from .checkpoint import save_sharded

        save_sharded(self, directory)

    def restore_sharded(self, directory, example_data=None):
        """Restore a sharded checkpoint in place (params + optimizer
        state + counters); see parallel/checkpoint.py."""
        from .checkpoint import restore_sharded

        restore_sharded(self, directory, example_data=example_data)

    def input_shardings(self, data, label=()):
        """The NamedShardings this step will place its batch inputs with,
        one per array in ``(data..., label...)`` order.

        The async input pipeline's contract (``io.DeviceFeedIter`` passes
        itself as the consumer): a batch ``device_put`` with exactly
        these shardings enters ``__call__`` as a true no-op. Works before
        the first step — only the mesh and batch/seq axes are consulted,
        arrays just need ``shape``/``ndim`` (NDArray, numpy, jax, or
        ShapeDtypeStruct)."""
        return tuple(named_sharding(self.mesh, self._batch_spec(v))
                     for v in _as_tuple(data) + _as_tuple(label))

    def stage_batch(self, data, label=()):
        """Place host batches on the mesh with this step's input sharding.

        In-place on the NDArrays; a later ``__call__`` with the same arrays
        makes the per-step ``device_put`` a no-op. Benchmarks and
        synthetic-data loops use this to keep data device-resident.
        """
        import jax

        for v in _as_tuple(data) + _as_tuple(label):
            v._set_data(jax.device_put(
                v.data, named_sharding(self.mesh, self._batch_spec(v))))

    # -- cache spine (compilation service) -------------------------------
    def _key_for(self, data_tuple, label_tuple):
        import os

        from ..compiler import signature

        # routing knobs key the cache like shapes do: the traced body
        # dispatches on them (Pallas fused kernels, hash dropout), so a
        # knob toggled between steps must re-trace, not replay. The
        # donation knob is a BUILD-time knob of this site specifically —
        # toggling MXNET_TPU_DONATE between steps must not replay an
        # executable with the other aliasing contract
        return signature(
            "train_step", id(self),
            avals=tuple((tuple(v.shape), str(v.dtype))
                        for v in data_tuple + label_tuple),
            extra=(len(data_tuple), True,
                   os.environ.get("MXNET_TPU_DONATE", "1") != "0"))

    def _entry_for(self, data_tuple, label_tuple):
        """The compiled entry for this batch signature: cache hit, or
        build + AOT-compile through the service's executable table and
        journal the signature to the manifest."""
        key = self._key_for(data_tuple, label_tuple)
        entry = self._cache.lookup(key)
        if entry is not self._cache.MISS:
            return entry
        if self.donate_inputs and len(self._cache):
            # shape change with input donation: invalidate the stale
            # lowerings. Their input buffers were donated — a later
            # cache hit replaying a batch staged for the OLD shape
            # would dispatch against donated-dead buffers (an opaque
            # XLA RuntimeError at best, garbage reads at worst);
            # re-lowering on return to a shape forces fresh staging.
            # Deliberate trade: a donating step fed ALTERNATING
            # shapes re-lowers on every switch. Donation is for
            # single-use streamed batches (one bucket shape per
            # step instance); alternating-bucket replay wants
            # donate_inputs=False, which keeps every lowering.
            self._cache.clear()
        entry = self._build(data_tuple, label_tuple, True)
        self._aot_seal(entry, data_tuple, label_tuple)
        self._cache.insert(key, entry)
        from .. import compiler

        compiler.record_signature("train_step", {
            "ident": self.warm_ident(),
            "data": tuple((tuple(v.shape), str(v.dtype))
                          for v in data_tuple),
            "label": tuple((tuple(v.shape), str(v.dtype))
                           for v in label_tuple),
            "routing": compiler.routing_knobs()})
        return entry

    def _aot_seal(self, entry, data_tuple, label_tuple):
        """AOT-compile the entry's step executable ahead of dispatch
        through the service's persistence stack: in-process executable
        table (a duplicate step recipe shares one XLA compile), the
        exported-StableHLO blob store (a warm process skips the trace),
        and jax's persistent compile cache (it skips the compile). Falls
        back to the plain trace-at-first-call jit on any surprise."""
        import os as _os

        import jax
        import numpy as np

        try:
            from ..compiler import keys as _ckeys
            from ..compiler import service as _csvc

            jitted = entry["jitted"]
            param_sds = tuple(
                jax.ShapeDtypeStruct(tuple(p.shape),
                                     jax.numpy.dtype(str(p.dtype)))
                for p in self._params)
            state_sds = tuple(
                jax.ShapeDtypeStruct(tuple(s.shape),
                                     jax.numpy.dtype(str(s.dtype)))
                for s in self._state_leaf_nds)
            with random_state.preserved_stream():
                rng = random_state.get_state_key()
            batch_sds = tuple(
                jax.ShapeDtypeStruct(tuple(v.shape),
                                     jax.numpy.dtype(str(v.dtype)))
                for v in tuple(data_tuple) + tuple(label_tuple))
            args = (param_sds, state_sds,
                    jax.ShapeDtypeStruct((), np.int32),
                    jax.ShapeDtypeStruct((), np.float32),
                    jax.ShapeDtypeStruct(tuple(rng.shape), rng.dtype)
                    ) + batch_sds
            platform = self.mesh.devices.flat[0].platform
            donate = _os.environ.get("MXNET_TPU_DONATE", "1") != "0"
            with self.tracing():
                if donate:
                    # donation-carrying programs stay on the direct
                    # lower path (export round-trips drop aliasing);
                    # still table-deduped + disk-compile-cached
                    lowered = jitted.lower(*args)
                    fp = _csvc.fingerprint_lowered(lowered)
                    compiled = _csvc.exec_table.get_or_build(
                        fp, lowered.compile)
                    entry["jitted"] = _csvc.GuardedExec(
                        compiled, lambda: jitted)
                else:
                    loss = self.loss
                    loss_id = _ckeys.graph_ident(loss) \
                        if hasattr(loss, "collect_params") \
                        else _ckeys.callable_ident(loss)
                    sig_fp = _ckeys.fingerprint(_ckeys.encode((
                        "train_step", self.warm_ident(), loss_id,
                        tuple((tuple(s.shape), str(s.dtype))
                              for s in param_sds + state_sds + args[5:]),
                        (tuple(rng.shape), str(rng.dtype)),
                        _ckeys.routing_knobs(), platform,
                        jax.__version__)))
                    sealed = _csvc.seal_executable(
                        sig_fp, jitted, args, fallback=lambda: jitted)
                    if entry["cell"]["aux_arrays"] is None:
                        try:
                            entry["cell_probe"]()
                        except Exception:
                            # cell can't settle abstractly: keep the
                            # trace-at-first-call jit (it settles cell
                            # concretely)
                            sealed = jitted
                    entry["jitted"] = sealed
        except Exception as e:  # noqa: BLE001 - the plain jit still runs
            # trace-at-first-call path stays — but say so: a step that
            # silently lost its AOT executable also lost the executable
            # table, the disk tier and compiled()
            _log.warning("TrainStep: compiling ahead of dispatch failed "
                         "(%r); tracing at the first call instead", e,
                         exc_info=True)

    def compiled(self, data, label=()):
        """The ``jax.stages.Compiled`` executable ``__call__`` dispatches
        for this batch signature: ``.as_text()`` shows the collectives
        and kernels the compiler put in, ``.memory_analysis()`` the
        bytes on each device. Needs a live, settled step (call it after
        the first ``__call__`` or ``warm``)."""
        if self._params is None:
            raise MXNetError("TrainStep.compiled needs a settled step: "
                             "run one step (or warm()) first")
        entry = self._entry_for(_as_tuple(data), _as_tuple(label))
        exe = getattr(entry["jitted"], "compiled", None)
        if exe is None:
            raise MXNetError("this step's executable was not compiled "
                             "ahead of dispatch (trace-at-first-call "
                             "fallback); there is no Compiled to show")
        return exe

    def warm_ident(self) -> str:
        """Routing ident for ``train_step`` manifest entries: net
        architecture + optimizer class + mesh layout + step config. Loose
        by design — the replay re-lowers against THIS live step, so a
        loose match costs a compile, never a wrong executable."""
        from ..compiler import fingerprint, graph_ident

        return fingerprint((
            graph_ident(self.net), type(self.optimizer).__name__,
            tuple(self.mesh.axis_names),
            tuple(int(self.mesh.shape[a]) for a in self.mesh.axis_names),
            tuple(self.batch_axis), self.seq_axis,
            str(self.remat), bool(self.loss_only)))

    def warm(self, data, label=()) -> str:
        """AOT-compile this step for one batch signature before training
        dispatches it (the manifest replay target; callable directly with
        template NDArrays or ``(shape, dtype)`` specs). Settles
        parameters and optimizer state if needed, then builds + compiles
        the executable into the step cache — the first real ``__call__``
        with this signature is a pure cache hit, zero retraces."""
        from ..ndarray import zeros as nd_zeros

        def to_nd(v):
            if isinstance(v, NDArray):
                return nd_zeros(tuple(v.shape), dtype=str(v.dtype))
            if isinstance(v, (list, tuple)) and v \
                    and isinstance(v[0], (int,)):
                return nd_zeros(tuple(v), dtype="float32")
            shape, dtype = v
            return nd_zeros(tuple(shape), dtype=dtype)

        data_tuple = tuple(to_nd(v) for v in _as_tuple(data))
        label_tuple = tuple(to_nd(v) for v in _as_tuple(label))
        if getattr(self, "_aot_only", False):
            raise MXNetError("this TrainStep was used for aot_compile; "
                             "warm() needs a live step")
        if self._params is None:
            self._settle_params(data_tuple)
            self._init_states()
        hit = self._key_for(data_tuple, label_tuple) in self._cache
        # route through the live entry path: it owns the donation-
        # invalidation rule (a donating step must never hold two batch
        # shapes at once — a warm() that seeded several would hand real
        # traffic donated-dead buffers on the alternate shape)
        self._entry_for(data_tuple, label_tuple)
        return "deduped" if hit else "replayed"

    def warm_from_spec(self, spec) -> str:
        """``compiler.warm_start``'s train_step replay hook."""
        return self.warm(tuple(spec.get("data") or ()),
                         tuple(spec.get("label") or ()))

    # -- call ------------------------------------------------------------
    def __call__(self, data, label):
        import jax

        if getattr(self, "_aot_only", False):
            raise MXNetError(
                "this TrainStep was used for aot_compile (abstract "
                "parameters, no optimizer state buffers); build a fresh "
                "TrainStep on a concretely initialized net to train")
        data_tuple = _as_tuple(data)
        label_tuple = _as_tuple(label)
        if self._params is None:
            self._settle_params(data_tuple)
            self._init_states()
        entry = self._entry_for(data_tuple, label_tuple)
        jitted, cell = entry["jitted"], entry["cell"]

        optimizer = self.optimizer
        # advance step counts eagerly (the dynamic-mode counterpart of
        # Optimizer._update_count inside the reference's Updater)
        for k in range(len(self._trainable)):
            optimizer._update_count(k)
        import numpy as np

        # fixed-width host scalars: under jax_enable_x64 a bare Python
        # int/float would trace as i64/f64 and drip f64 math into the step
        t = np.int32(optimizer.num_update)
        lr = np.float32(optimizer.learning_rate)
        rng = random_state.get_state_key()

        param_vals = tuple(p.data().data for p in self._params)
        state_vals = tuple(s.data for s in self._state_leaf_nds)
        # explicit device_put: host batches become sharded global arrays
        # (each host feeds its slice on pods — SURVEY.md §7.1 "Data").
        # A batch already carrying the exact target sharding (staged by
        # io.DeviceFeedIter / stage_batch) skips the put entirely — the
        # async-pipeline contract that makes entry a true no-op.
        batch_vals = []
        for v, sh in zip(data_tuple + label_tuple, entry["batch_sh"]):
            d = v.data
            if self.donate_inputs and getattr(d, "is_deleted", None) \
                    and d.is_deleted():
                raise MXNetError(
                    "TrainStep(donate_inputs=True): a batch buffer passed "
                    "to this step was already donated to a previous "
                    "dispatch (its device memory was reused for "
                    "activations). Donation is for single-use batches — "
                    "stage a FRESH buffer per step (io.DeviceFeedIter "
                    "does), or build the step with donate_inputs=False "
                    "to replay one staged batch")
            if getattr(d, "sharding", None) == sh:
                batch_vals.append(d)
            else:
                batch_vals.append(jax.device_put(d, sh))
        with self.tracing():
            new_params, new_states, loss_val, outs, aux = jitted(
                param_vals, state_vals, t, lr, rng, *batch_vals)
        if not getattr(self, "_first_step_marked", False):
            self._first_step_marked = True
            from .. import compiler

            compiler.mark_event("first_train_step")

        for p, v in zip(self._params, new_params):
            p.data()._set_data(v)
        for s, v in zip(self._state_leaf_nds, new_states):
            s._set_data(v)
        for arr, v in zip(cell["aux_arrays"], aux):
            arr._set_data(v)
        ctx = self._params[0].data().context if self._params else current_context()
        # read the flag the executable was traced with, not the live
        # attribute — toggling self.loss_only between steps must not desync
        # the host return path from the compiled output arity
        if entry["loss_only"]:
            return NDArray(data=loss_val, ctx=ctx), None
        out_nd = [NDArray(data=v, ctx=ctx) for v in outs]
        out_tree = nested_unflatten_nd(cell["treedef"], out_nd)
        return NDArray(data=loss_val, ctx=ctx), out_tree
