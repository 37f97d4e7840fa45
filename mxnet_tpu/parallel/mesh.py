"""Device-mesh management — the TPU-native replacement for MXNet's
multi-device Context lists.

Reference mapping (SURVEY.md §2.4): MXNet expresses data parallelism as a
python list of contexts (``ctx=[mx.gpu(0), mx.gpu(1)]``) fed to
``DataParallelExecutorGroup`` / Gluon ``Trainer``, and model parallelism as
``group2ctx`` manual placement. The TPU-native design replaces both with ONE
``jax.sharding.Mesh`` whose named axes carry the parallelism meaning:

* ``dp`` — data parallel (batch sharding; gradient psum over this axis)
* ``tp`` — tensor parallel (GSPMD param sharding — NEW vs reference)
* ``pp`` — pipeline parallel (stage axis; collective-permute microbatching)
* ``sp`` — sequence/context parallel (ring attention over this axis)
* ``ep`` — expert parallel (MoE experts)

XLA inserts the collectives (psum/all-gather/reduce-scatter/ppermute) over
ICI; multi-host layouts ride DCN via the same mesh (jax.distributed
bootstrap — see mxnet_tpu.kvstore and tools/launch.py).
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Dict, List, Optional, Sequence

import numpy as _np

from ..base import MXNetError

__all__ = ["AXES", "make_mesh", "current_mesh", "use_mesh", "local_devices",
           "mesh_axis_size", "auto_partitioned", "current_batch_axes",
           "kernel_shards", "over_batch_shards", "batch_shard_index",
           "kernel_routing"]

# canonical axis order: outermost (slowest, crosses DCN first) to innermost
AXES = ("pp", "dp", "ep", "sp", "tp")

_state = threading.local()


def local_devices(device_type: Optional[str] = None):
    """All JAX devices visible to this process, accelerator first."""
    import jax

    if device_type:
        return jax.devices(device_type)
    return jax.devices()


def make_mesh(axes: Optional[Dict[str, int]] = None, devices=None):
    """Create a named device mesh.

    ``axes`` maps axis name -> size; at most one size may be -1 (inferred
    from the device count). Default: all devices on the ``dp`` axis — the
    reference's data-parallel ctx-list (``kvstore='device'``) equivalent.

        mesh = make_mesh({'dp': 4, 'tp': 2})
        with use_mesh(mesh):
            ...
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = local_devices()
    n = len(devices)
    if axes is None:
        axes = {"dp": n}
    names: List[str] = []
    sizes: List[int] = []
    infer_idx = None
    for name, size in axes.items():
        names.append(name)
        if size == -1:
            if infer_idx is not None:
                raise MXNetError("only one mesh axis may have size -1")
            infer_idx = len(sizes)
            sizes.append(1)
        else:
            sizes.append(int(size))
    known = int(_np.prod(sizes))
    if infer_idx is not None:
        if n % known:
            raise MXNetError(
                f"cannot infer axis {names[infer_idx]!r}: {n} devices not "
                f"divisible by {known}")
        sizes[infer_idx] = n // known
        known = n
    if known != n:
        raise MXNetError(
            f"mesh axes {dict(zip(names, sizes))} need {known} devices but "
            f"{n} are visible")
    dev_array = _np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, axis_names=tuple(names))


def current_mesh():
    """The mesh installed by :func:`use_mesh` (or None)."""
    return getattr(_state, "mesh", None)


def current_batch_axes() -> tuple:
    """The mesh axes that the step being traced shards its batch's
    leading dimension over (``use_mesh(mesh, batch_axes=...)``); ``()``
    where no step said."""
    return getattr(_state, "batch_axes", ())


@contextlib.contextmanager
def use_mesh(mesh, batch_axes=()):
    """Install ``mesh`` for the code inside. A step that shards its batch
    names the axes it does that over (``TrainStep.batch_axis``): that is
    what lets a Pallas gate run its kernel on each shard's rows
    (:func:`kernel_shards`) where it would otherwise give way."""
    prev = (current_mesh(), current_batch_axes())
    _state.mesh, _state.batch_axes = mesh, tuple(batch_axes)
    try:
        with contextlib.ExitStack() as stack:
            if isinstance(kernel_routing(), tuple):
                # what leaves a ``shard_map`` carries the mesh in its type.
                # So does every value of this trace from the start, or an
                # op met before and after the first kernel (BERT's plain
                # LayerNorm: embedding and MLM head) is traced twice
                import jax

                stack.enter_context(
                    jax.sharding.use_abstract_mesh(mesh.abstract_mesh))
            yield mesh
    finally:
        _state.mesh, _state.batch_axes = prev


def auto_partitioned(manual_axes=()) -> bool:
    """The trace in progress leaves a mesh axis of more than one device
    to the SPMD partitioner (every axis outside ``manual_axes``, the
    ones an enclosing ``shard_map`` made manual, :func:`over_batch_shards`'
    own among them).

    That is where a Pallas kernel cannot go: Mosaic kernels have no
    partitioning rule ("cannot be automatically partitioned"), so the
    kernel gates give way to the XLA reference path there, or run the
    kernel over the batch's shards (:func:`kernel_shards`)."""
    mesh = current_mesh()
    if mesh is None:
        return False
    manual = tuple(manual_axes) + getattr(_state, "manual_axes", ())
    return any(size > 1 for name, size in mesh.shape.items()
               if name not in manual)


def kernel_shards(lead: int, manual_axes=()) -> int:
    """How a Pallas kernel may run, in the trace in progress, on an
    operand whose leading dimension is ``lead``:

    * ``1``: as it is. No mesh axis of more than one device is left to
      the partitioner (no mesh, one chip, or inside a ``shard_map`` that
      holds them all).
    * ``n > 1``: on each of ``n`` shards of the leading dimension, through
      :func:`over_batch_shards`. The only axes of more than one device
      are the step's batch axes, ``n`` devices in all, and ``lead``
      divides by ``n``.
    * ``0``: not at all; the gate gives way. Another axis of more than
      one device (``tp``, ``sp``, ``pp``: the operand is then split along
      something the kernel cannot see), a ragged batch, or a caller that
      holds a ``shard_map`` of its own over part of the mesh.
    """
    if not auto_partitioned(manual_axes):
        return 1
    batch = current_batch_axes()
    if manual_axes or auto_partitioned(batch):
        return 0
    n = math.prod(current_mesh().shape[axis] for axis in batch)
    return n if lead % n == 0 else 0


def kernel_routing():
    """What a traced op body holds of the mesh, for the compile caches'
    keys (``compiler.keys.routing_knobs``): ``False`` where no axis is left
    to the partitioner, ``True`` where every Pallas gate gives way, else
    the batch axes with their sizes and the mesh's device ids. A body
    with a kernel over batch shards holds the ``shard_map``'s mesh and the
    shard's shape: it serves no mesh but that one."""
    if not auto_partitioned():
        return False
    if auto_partitioned(current_batch_axes()):
        return True
    mesh = current_mesh()
    return (tuple((a, mesh.shape[a]) for a in current_batch_axes()),
            tuple(d.id for d in mesh.devices.flat))


def batch_shard_index():
    """Which shard of the batch the code being traced works on: its
    position along the batch axes inside :func:`over_batch_shards`, 0
    anywhere else. (What a kernel that hashes a row's absolute position
    needs to know: ``fused_layer_norm``'s dropout.)"""
    import jax

    axes = getattr(_state, "manual_axes", ())
    return jax.lax.axis_index(axes) if axes else 0


def over_batch_shards(fn, shards: int, n_sharded: int):
    """``fn`` as the kernel gates call it: ``fn`` itself where ``shards``
    (a gate's answer, :func:`kernel_shards`) is 1, else ``fn`` on each
    batch shard's rows, inside a ``shard_map`` over the step's batch axes.

    The first ``n_sharded`` positional operands and the result are split
    along their leading dimension; the other operands (a bias, ``gamma``,
    a seed) are the same on every shard, and their gradients come out of
    the ``shard_map``'s transpose as a ``psum`` over the batch axes, which
    XLA's combiner merges with the parameters' other gradient all-reduces
    (the compiled BERT step holds 3 all-reduces with or without the 63
    small ones). Keywords are static; ``None`` operands pass through. Only
    the batch axes are manual, as in ``ring_attention``: any other axis
    has one device here.
    """
    if shards == 1:
        return fn
    import jax
    from jax.sharding import PartitionSpec as P

    axes = current_batch_axes()
    name = getattr(fn, "__name__", "kernel")

    def call(*args, **static):
        def on_shard(*args):
            prev = getattr(_state, "manual_axes", ())
            _state.manual_axes = prev + axes
            try:
                # the scope names the custom calls in the compiled step
                # (``%<name>.N``), as the op's own jit does off the mesh
                with jax.named_scope(name):
                    return fn(*args, **static)
            finally:
                _state.manual_axes = prev

        # check_vma=False: the kernels' out_shapes carry no varying-mesh-
        # axes annotation (see ring_attention)
        return jax.shard_map(
            on_shard, mesh=current_mesh(), out_specs=P(axes),
            in_specs=tuple(P(axes) if i < n_sharded else P()
                           for i in range(len(args))),
            axis_names=frozenset(axes), check_vma=False)(*args)

    return call


def mesh_axis_size(mesh, axis: str) -> int:
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]
