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
import threading
from typing import Dict, List, Optional, Sequence

import numpy as _np

from ..base import MXNetError

__all__ = ["AXES", "make_mesh", "current_mesh", "use_mesh", "local_devices",
           "mesh_axis_size", "auto_partitioned"]

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


@contextlib.contextmanager
def use_mesh(mesh):
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def auto_partitioned(manual_axes=()) -> bool:
    """The trace in progress leaves a mesh axis of more than one device
    to the SPMD partitioner (every axis outside ``manual_axes``, the
    ones an enclosing ``shard_map`` made manual).

    That is where a Pallas kernel cannot go: Mosaic kernels have no
    partitioning rule ("cannot be automatically partitioned"), so the
    kernel gates give way to the XLA reference path there."""
    mesh = current_mesh()
    if mesh is None:
        return False
    return any(size > 1 for name, size in mesh.shape.items()
               if name not in manual_axes)


def mesh_axis_size(mesh, axis: str) -> int:
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]
