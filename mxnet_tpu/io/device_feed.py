"""``DeviceFeedIter`` — async host→device input staging.

The host-side pipeline (``PrefetchingIter``/``DataLoader``) overlaps
*decode* with compute, but the batch still crossed to the device inside
the training step — an H2D transfer serialized with every step, which
leaves the device idle while the batch crosses (PERF_HISTORY.md, ResNet-50
real-data). The reference's C++ ``iter_prefetcher.h``
double-buffers into engine-managed staging memory; the TPU-native
equivalent (tf.data ``prefetch_to_device`` / DALI-style) is this
iterator: a producer thread ``jax.device_put``s the next ``depth``
batches *with the consuming step's input sharding* while the device
crunches the current one, so by the time the step runs, its inputs are
already sharded device buffers and the per-step transfer is a no-op
(``TrainStep`` detects the matching sharding and skips its own put).

    step = par.TrainStep(net, loss, "sgd", mesh=mesh, donate_inputs=True)
    feed = mxio.DeviceFeedIter(train_iter, step=step, depth=2)
    for batch in feed:
        loss, _ = step(batch.data[0], batch.label[0])

``device_transform`` runs a jitted function over the staged arrays ON
DEVICE — e.g. cast a uint8 batch to bf16 and normalize, so the wire
carries quarter-size pixels and the VPU does the float math (the DALI
"GPU-side augmentation tail" move).

Telemetry (``MXNET_TELEMETRY=1``): ``mxnet_data_wait_seconds{stage}``
(consumer block time — the host-starved vs device-starved
discriminator), ``mxnet_data_queue_depth{stage}``. Fault site
``datafeed.put`` fires inside the producer; any producer failure
surfaces at ``next()`` as an ``MXNetError`` naming the stage — never a
hang on an empty queue. Producer/lifecycle machinery is shared with
``PrefetchingIter`` (``io.io._AsyncStage``).
"""
from __future__ import annotations

from .. import fault
from ..base import MXNetError
from ..context import cpu_pinned, current_context
from ..ndarray import NDArray
from .io import DataBatch, _AsyncStage

__all__ = ["DeviceFeedIter", "stage_on_device", "make_normalize_transform"]


def make_normalize_transform(mean, std, dtype="bfloat16"):
    """The canonical uint8-wire ``device_transform``: per-channel
    ``(x - mean) / std`` in float32 on device, cast to ``dtype``. Labels
    pass through. ``mean``/``std`` are per-channel sequences (NCHW dim 1)
    — e.g. the ImageNet constants the C++ iterator took as
    ``mean_r/g/b`` + ``std_r/g/b``."""
    import numpy as _np

    mean = _np.asarray(mean, _np.float32).reshape(1, -1, 1, 1)
    std = _np.asarray(std, _np.float32).reshape(1, -1, 1, 1)

    def transform(x, *labels):
        import jax.numpy as jnp

        xb = ((x.astype(jnp.float32) - mean) / std).astype(dtype)
        return (xb,) + labels

    return transform


def stage_on_device(batch, device_id=0, device=None):
    """Stage a host batch (NDArray / nested list) onto one device with an
    async ``device_put`` — the ``DataLoader(pin_memory=True)`` path. The
    returned NDArrays carry the ``cpu_pinned`` context (reference
    semantics: pinned staging buffers owned by the host)."""
    import jax

    if device is None:
        devs = jax.devices()
        device = devs[min(int(device_id), len(devs) - 1)]

    def go(b):
        if isinstance(b, (list, tuple)):
            return [go(x) for x in b]
        if isinstance(b, NDArray):
            return NDArray(data=jax.device_put(b.data, device),
                           ctx=cpu_pinned())
        return b

    return go(batch)


class DeviceFeedIter(_AsyncStage):
    """Asynchronously stage batches from ``data_iter`` onto the device.

    Parameters
    ----------
    data_iter : DataIter, DataLoader or any iterable of batches. A batch
        may be a ``DataBatch`` (data+label lists) or a flat list/tuple of
        NDArrays (DataLoader's shape); the staged batch keeps the form.
    step : TrainStep, optional — placement comes from
        ``step.input_shardings`` so the step's per-call ``device_put``
        becomes a no-op. Exactly one of ``step``/``shardings`` required.
    shardings : explicit placement instead of a step: a sequence (one
        entry per batch array, anything ``jax.device_put`` accepts) or a
        callable ``(arrays) -> sequence``.
    depth : producer queue depth (batches staged ahead), default 2 —
        the classic double buffer.
    device_transform : optional function over the staged jax arrays,
        jitted on first use and run on device (same arity in and out);
        e.g. uint8→bf16 normalize.
    name : stage label for telemetry/fault/error messages.
    """

    def __init__(self, data_iter, step=None, shardings=None, depth=2,
                 device_transform=None, name="device_feed"):
        self._source = data_iter
        if (step is None) == (shardings is None):
            raise MXNetError(
                "DeviceFeedIter needs exactly one of step= (a TrainStep "
                "whose input sharding to feed) or shardings=")
        self._step = step
        self._shardings = shardings
        self._device_transform = device_transform
        self._jit_transform = None
        self._sh_cache = {}
        self.name = name
        self._stage_name = name
        super().__init__(getattr(data_iter, "batch_size", 0), depth=depth,
                         thread_name=f"mxnet-{name}")
        self._start()

    # -- provide_* proxy (post-transform dtypes may differ; descriptors
    # describe the HOST side, same caveat as the reference prefetcher)
    @property
    def provide_data(self):
        return getattr(self._source, "provide_data", None)

    @property
    def provide_label(self):
        return getattr(self._source, "provide_label", None)

    # -- _AsyncStage surface -------------------------------------------
    def _source_obj(self):
        return self._source

    def _on_start(self):
        self._iter = iter(self._source)

    def _produce(self):
        return self._stage(next(self._iter))

    def _raise_failure(self):
        raise MXNetError(
            f"input pipeline stage '{self.name}' failed at datafeed.put "
            f"(producer thread died): {self._failure!r}") \
            from self._failure

    # -- staging -------------------------------------------------------
    def _resolve_shardings(self, vals):
        key = tuple((tuple(v.shape), str(v.dtype)) for v in vals)
        shs = self._sh_cache.get(key)
        if shs is None:
            if self._step is not None:
                shs = self._step.input_shardings(vals)
            elif callable(self._shardings):
                shs = tuple(self._shardings(vals))
            else:
                shs = tuple(self._shardings)
            if len(shs) != len(vals):
                raise MXNetError(
                    f"DeviceFeedIter({self.name}): {len(shs)} shardings "
                    f"for {len(vals)} batch arrays")
            self._sh_cache[key] = shs
        return shs

    def _stage(self, batch):
        """device_put every array of one batch with its target sharding
        (async — transfer overlaps downstream compute), then apply the
        on-device transform. Runs on the producer thread."""
        import jax

        if fault._state.enabled:
            fault.check("datafeed.put", detail=self.name)
        if isinstance(batch, DataBatch):
            data = list(batch.data or [])
            label = list(batch.label or [])
        elif isinstance(batch, (list, tuple)):
            data, label = list(batch), []
        else:
            data, label = [batch], []
        arrs = data + label
        ctxs = [a.context if isinstance(a, NDArray) else current_context()
                for a in arrs]
        vals = [a.data if isinstance(a, NDArray) else a for a in arrs]
        shs = self._resolve_shardings(vals)
        put = [jax.device_put(v, sh) for v, sh in zip(vals, shs)]
        if self._device_transform is not None:
            if self._jit_transform is None:
                self._jit_transform = jax.jit(self._device_transform)
            out = self._jit_transform(*put)
            if not isinstance(out, (list, tuple)):
                out = [out]
            if len(out) != len(put):
                raise MXNetError(
                    f"DeviceFeedIter({self.name}): device_transform must "
                    f"keep arity ({len(put)} in, {len(out)} out)")
            put = list(out)
        nds = [NDArray(data=v, ctx=ctx) for v, ctx in zip(put, ctxs)]
        if isinstance(batch, DataBatch):
            return DataBatch(data=nds[:len(data)], label=nds[len(data):],
                             pad=batch.pad, index=batch.index,
                             provide_data=batch.provide_data,
                             provide_label=batch.provide_label)
        if isinstance(batch, (list, tuple)):
            return nds
        return nds[0]

    # -- batch accessors -----------------------------------------------
    def getdata(self):
        b = self._current
        return b.data if isinstance(b, DataBatch) else b

    def getlabel(self):
        b = self._current
        return b.label if isinstance(b, DataBatch) else None

    def getpad(self):
        b = self._current
        return (b.pad or 0) if isinstance(b, DataBatch) else 0
