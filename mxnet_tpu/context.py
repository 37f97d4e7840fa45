"""Device contexts.

Reference: ``include/mxnet/base.h :: Context`` — a ``(dev_type, dev_id)``
pair with kCPU / kGPU / kCPUPinned / kCPUShared. The TPU-native build adds
``kTPU`` as the accelerator type and maps every context onto a JAX device:

* ``mx.cpu(i)``        -> i-th XLA:CPU device (also the test oracle)
* ``mx.tpu(i)``        -> i-th TPU chip visible to this process
* ``mx.gpu(i)``        -> alias for the i-th local accelerator, so that
  unmodified MXNet scripts written with ``mx.gpu()`` run on TPU machines
  (the north star is a bare context swap; aliasing makes it barer still).
* ``mx.cpu_pinned()``  -> host memory staging context. XLA:TPU manages its
  own pinned staging buffers, so this is a CPU context tagged pinned; the
  DataLoader uses it as the hand-off point before ``device_put``.
"""
from __future__ import annotations

import threading
from typing import Optional

from .base import MXNetError

__all__ = [
    "Context",
    "cpu",
    "cpu_pinned",
    "cpu_shared",
    "gpu",
    "tpu",
    "current_context",
    "num_gpus",
    "num_tpus",
    "num_devices",
]


class Context:
    """A device context (device type + device id)."""

    # dev_type ids keep the reference's numbering where it exists
    # (include/mxnet/base.h :: kCPU=1, kGPU=2, kCPUPinned=3, kCPUShared=5)
    # and add kTPU=6.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if isinstance(device_type, str):
                device_type = Context.devstr2type[device_type]
            self.device_typeid = device_type
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return f"{self.device_type}({self.device_id})"

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- JAX mapping ---------------------------------------------------
    def jax_device(self):
        """Resolve this context to a concrete ``jax.Device``."""
        import jax

        dt = self.device_type
        if dt in ("cpu", "cpu_pinned", "cpu_shared"):
            # THIS process's devices: in a multi-controller job (dist_sync)
            # cpu(i)/tpu(i) is rank-local, like the reference's per-worker
            # gpu(i) — other ranks' devices are not addressable anyway
            devs = jax.local_devices(backend="cpu")
        elif dt == "tpu":
            devs = _accelerator_devices("tpu")
        elif dt == "gpu":
            # gpu(i) aliases the local accelerator so mx.gpu() scripts run
            # unchanged on TPU hosts
            devs = _accelerator_devices(None)
        else:
            raise MXNetError(f"unknown device type {dt}")
        if self.device_id >= len(devs):
            raise MXNetError(
                f"context {self} out of range: only {len(devs)} {dt} device(s)"
            )
        return devs[self.device_id]

    def empty_cache(self):
        """Release cached device memory (reference: Context::empty_cache →
        storage pool release). PjRt owns pooling; this is best-effort."""
        import gc

        gc.collect()


def _accelerator_devices(kind: Optional[str]):
    """Non-CPU jax devices of THIS process, most-specific first (rank-local
    numbering in multi-controller jobs — see Context.jax_device).

    A process without an accelerator raises: computing on the host's CPU
    under the name ``tpu(0)`` would hide exactly the failure a chip run
    exists to show. Only a process that ``JAX_PLATFORMS`` restricts to
    the CPU (tests, rehearsals) gets CPU devices, so ``mx.tpu()`` code
    paths stay testable on the oracle device."""
    import jax

    from .base import cpu_only_process

    accel = [d for d in jax.local_devices() if d.platform != "cpu"]
    if kind == "tpu":
        accel = [d for d in accel if d.platform == "tpu"] or accel
    if accel:
        return accel
    if cpu_only_process():
        return jax.local_devices(backend="cpu")
    raise MXNetError(
        f"no accelerator in this process: jax.devices() = {jax.devices()} "
        "(set JAX_PLATFORMS=cpu to run accelerator contexts on CPU "
        "devices)")


def cpu(device_id: int = 0) -> Context:
    return Context(1, device_id)


def gpu(device_id: int = 0) -> Context:
    return Context(2, device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context(3, device_id)


def cpu_shared(device_id: int = 0) -> Context:
    return Context(5, device_id)


def tpu(device_id: int = 0) -> Context:
    return Context(6, device_id)


def num_gpus() -> int:
    """Number of local accelerators (reference: mx.context.num_gpus)."""
    import jax

    try:
        return len([d for d in jax.devices() if d.platform != "cpu"])
    except RuntimeError:
        return 0


def num_tpus() -> int:
    return num_gpus()


def num_devices() -> int:
    import jax

    return jax.device_count()


def current_context() -> Context:
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value


def default_accelerator() -> Context:
    """The preferred compute context on this host: tpu if present else cpu."""
    return tpu(0) if num_gpus() > 0 else cpu(0)
