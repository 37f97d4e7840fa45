"""``mx.image`` — image decode + augmentation pipeline (reference:
``python/mxnet/image/image.py``).

The reference wraps OpenCV; here PIL decodes/encodes (the only codec in
this environment) and the augmenters are pure numpy on HWC arrays — they
run in DataLoader / iterator worker threads on host, exactly like the
reference's C++ augmenter zoo runs on CPU, and the device only ever sees
the final batched tensor.
"""
from __future__ import annotations

import contextlib as _contextlib
import io as _io
import os as _os
import random as _pyrandom
import threading as _threading
import zlib as _zlib

import numpy as np

from .. import telemetry
from ..base import MXNetError
from ..ndarray import NDArray, array as nd_array
from ..telemetry import _state as _telemetry_state

__all__ = [
    "imdecode", "imread", "imresize", "resize_short", "fixed_crop",
    "center_crop", "random_crop", "random_size_crop", "color_normalize",
    "Augmenter", "ResizeAug", "ForceResizeAug", "CenterCropAug",
    "RandomCropAug", "RandomSizedCropAug", "HorizontalFlipAug", "CastAug",
    "ColorNormalizeAug", "BrightnessJitterAug", "ContrastJitterAug",
    "SaturationJitterAug", "ColorJitterAug", "LightingAug", "RandomGrayAug",
    "CreateAugmenter", "ImageIter",
]


def _to_np(img):
    if isinstance(img, NDArray):
        return img.asnumpy()
    return np.asarray(img)


# Numpy passthrough mode: inside `_numpy_outputs()` every augmenter /
# decode helper returns plain numpy instead of wrapping into NDArrays.
# Decode WORKER PROCESSES require this — they are forked children whose
# inherited XLA threadpools are dead, so a single nd_array() there would
# hang on the first device_put — and it also drops the per-augmenter
# host->device round trip from the hot decode path.
_out_mode = _threading.local()


def _mkarr(arr):
    """Augmenter output wrapper: NDArray normally; in numpy passthrough
    mode a plain array with nd_array's float64 -> float32 rule applied,
    so both modes produce bit-identical values."""
    if getattr(_out_mode, "numpy", False):
        arr = np.asarray(arr)
        if arr.dtype == np.float64:
            arr = arr.astype(np.float32)
        return arr
    return nd_array(arr)


@_contextlib.contextmanager
def _numpy_outputs():
    prev = getattr(_out_mode, "numpy", False)
    _out_mode.numpy = True
    try:
        yield
    finally:
        _out_mode.numpy = prev


def _wrap(img, out=None):
    if out is not None:
        out._set_data(nd_array(img).data)
        return out
    return _mkarr(img)


def imdecode(buf, flag=1, to_rgb=1, out=None):
    """Decode an encoded image buffer to HWC uint8 (reference: imdecode)."""
    from PIL import Image

    if isinstance(buf, NDArray):
        buf = buf.asnumpy().tobytes()
    img = Image.open(_io.BytesIO(bytes(buf)))
    img = img.convert("RGB" if flag else "L")
    arr = np.asarray(img)
    if not flag:
        arr = arr[:, :, None]
    if flag and not to_rgb:
        arr = arr[:, :, ::-1]  # BGR, the reference's cv2 default
    return _wrap(arr, out)


def imread(filename, flag=1, to_rgb=1):
    with open(filename, "rb") as f:
        return imdecode(f.read(), flag=flag, to_rgb=to_rgb)


def imresize(src, w, h, interp=1):
    from PIL import Image

    arr = _to_np(src).astype(np.uint8)
    resample = {0: Image.NEAREST, 1: Image.BILINEAR, 2: Image.BICUBIC,
                3: Image.NEAREST, 4: Image.LANCZOS}.get(interp,
                                                        Image.BILINEAR)
    squeeze = arr.shape[-1] == 1
    pil = Image.fromarray(arr[..., 0] if squeeze else arr)
    out = np.asarray(pil.resize((w, h), resample))
    if squeeze:
        out = out[:, :, None]
    return _mkarr(out)


def resize_short(src, size, interp=2):
    """Resize so the SHORT side equals size (reference: resize_short)."""
    h, w = _to_np(src).shape[:2]
    if h > w:
        new_w, new_h = size, int(h * size / w)
    else:
        new_w, new_h = int(w * size / h), size
    return imresize(src, new_w, new_h, interp)


def fixed_crop(src, x0, y0, w, h, size=None, interp=2):
    arr = _to_np(src)[y0:y0 + h, x0:x0 + w]
    if size is not None and (w, h) != size:
        return imresize(arr, size[0], size[1], interp)
    return _mkarr(arr)


def center_crop(src, size, interp=2):
    h, w = _to_np(src).shape[:2]
    new_w, new_h = size
    x0 = max(0, (w - new_w) // 2)
    y0 = max(0, (h - new_h) // 2)
    out = fixed_crop(src, x0, y0, min(new_w, w), min(new_h, h), size, interp)
    return out, (x0, y0, new_w, new_h)


def random_crop(src, size, interp=2):
    h, w = _to_np(src).shape[:2]
    new_w, new_h = min(size[0], w), min(size[1], h)
    x0 = _pyrandom.randint(0, w - new_w)
    y0 = _pyrandom.randint(0, h - new_h)
    out = fixed_crop(src, x0, y0, new_w, new_h, size, interp)
    return out, (x0, y0, new_w, new_h)


def random_size_crop(src, size, area, ratio, interp=2, max_attempts=10):
    """Random area+aspect crop (the Inception-style crop)."""
    h, w = _to_np(src).shape[:2]
    src_area = h * w
    if isinstance(area, (int, float)):
        area = (area, 1.0)
    for _ in range(max_attempts):
        target = _pyrandom.uniform(*area) * src_area
        log_ratio = (np.log(ratio[0]), np.log(ratio[1]))
        ar = np.exp(_pyrandom.uniform(*log_ratio))
        new_w = int(round(np.sqrt(target * ar)))
        new_h = int(round(np.sqrt(target / ar)))
        if new_w <= w and new_h <= h:
            x0 = _pyrandom.randint(0, w - new_w)
            y0 = _pyrandom.randint(0, h - new_h)
            return (fixed_crop(src, x0, y0, new_w, new_h, size, interp),
                    (x0, y0, new_w, new_h))
    return center_crop(src, size, interp)


def color_normalize(src, mean, std=None):
    arr = _to_np(src).astype(np.float32)
    arr = arr - _to_np(mean)
    if std is not None:
        arr = arr / _to_np(std)
    return _mkarr(arr)


# ---------------------------------------------------------------------------
# augmenters (reference: image.py Augmenter zoo)
# ---------------------------------------------------------------------------


class Augmenter:
    def __init__(self, **kwargs):
        self._kwargs = kwargs

    def dumps(self):
        import json

        return json.dumps([self.__class__.__name__, self._kwargs])

    def __call__(self, src):
        raise NotImplementedError


class ResizeAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size, self.interp = size, interp

    def __call__(self, src):
        return resize_short(src, self.size, self.interp)


class ForceResizeAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size, self.interp = size, interp

    def __call__(self, src):
        return imresize(src, self.size[0], self.size[1], self.interp)


class CenterCropAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size, self.interp = size, interp

    def __call__(self, src):
        return center_crop(src, self.size, self.interp)[0]


class RandomCropAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size, self.interp = size, interp

    def __call__(self, src):
        return random_crop(src, self.size, self.interp)[0]


class RandomSizedCropAug(Augmenter):
    def __init__(self, size, area, ratio, interp=2):
        super().__init__(size=size, area=area, ratio=ratio, interp=interp)
        self.size, self.area, self.ratio, self.interp = \
            size, area, ratio, interp

    def __call__(self, src):
        return random_size_crop(src, self.size, self.area, self.ratio,
                                self.interp)[0]


class HorizontalFlipAug(Augmenter):
    def __init__(self, p=0.5):
        super().__init__(p=p)
        self.p = p

    def __call__(self, src):
        if _pyrandom.random() < self.p:
            return _mkarr(_to_np(src)[:, ::-1])
        return src if isinstance(src, NDArray) else _mkarr(src)


class CastAug(Augmenter):
    def __init__(self, typ="float32"):
        super().__init__(typ=typ)
        self.typ = typ

    def __call__(self, src):
        return _mkarr(_to_np(src).astype(self.typ))


class ColorNormalizeAug(Augmenter):
    def __init__(self, mean, std):
        super().__init__()
        self.mean = np.asarray(mean, np.float32)
        self.std = np.asarray(std, np.float32) if std is not None else None

    def __call__(self, src):
        return color_normalize(src, self.mean, self.std)


class BrightnessJitterAug(Augmenter):
    def __init__(self, brightness):
        super().__init__(brightness=brightness)
        self.brightness = brightness

    def __call__(self, src):
        alpha = 1.0 + _pyrandom.uniform(-self.brightness, self.brightness)
        return _mkarr(_to_np(src).astype(np.float32) * alpha)


class ContrastJitterAug(Augmenter):
    _coef = np.array([0.299, 0.587, 0.114], np.float32)

    def __init__(self, contrast):
        super().__init__(contrast=contrast)
        self.contrast = contrast

    def __call__(self, src):
        alpha = 1.0 + _pyrandom.uniform(-self.contrast, self.contrast)
        arr = _to_np(src).astype(np.float32)
        gray = (arr * self._coef).sum(-1).mean()
        return _mkarr(arr * alpha + gray * (1 - alpha))


class SaturationJitterAug(Augmenter):
    _coef = np.array([0.299, 0.587, 0.114], np.float32)

    def __init__(self, saturation):
        super().__init__(saturation=saturation)
        self.saturation = saturation

    def __call__(self, src):
        alpha = 1.0 + _pyrandom.uniform(-self.saturation, self.saturation)
        arr = _to_np(src).astype(np.float32)
        gray = (arr * self._coef).sum(-1, keepdims=True)
        return _mkarr(arr * alpha + gray * (1 - alpha))



class SequentialAug(Augmenter):
    """Apply a list of augmenters in order (reference: image.py ::
    SequentialAug)."""

    def __init__(self, ts):
        super().__init__()
        self.ts = list(ts)

    def dumps(self):
        return ["SequentialAug", [t.dumps() for t in self.ts]]

    def __call__(self, src):
        for t in self.ts:
            src = t(src)
        return src


class RandomOrderAug(Augmenter):
    """Apply a list of augmenters in random order (reference: image.py ::
    RandomOrderAug)."""

    def __init__(self, ts):
        super().__init__()
        self.ts = list(ts)

    def dumps(self):
        return ["RandomOrderAug", [t.dumps() for t in self.ts]]

    def __call__(self, src):
        order = list(self.ts)
        _pyrandom.shuffle(order)
        for t in order:
            src = t(src)
        return src

class ColorJitterAug(RandomOrderAug):
    """Random-order brightness/contrast/saturation jitter (reference:
    image.py::ColorJitterAug — a RandomOrderAug over the three jitters,
    with hue available via HueJitterAug in the builder)."""

    def __init__(self, brightness, contrast, saturation):
        ts = []
        if brightness:
            ts.append(BrightnessJitterAug(brightness))
        if contrast:
            ts.append(ContrastJitterAug(contrast))
        if saturation:
            ts.append(SaturationJitterAug(saturation))
        super().__init__(ts)
        self.brightness = brightness
        self.contrast = contrast
        self.saturation = saturation

    def dumps(self):
        return ["ColorJitterAug", [t.dumps() for t in self.ts]]

    def __call__(self, src):
        src = super().__call__(src)
        return src if isinstance(src, NDArray) else _mkarr(src)


class LightingAug(Augmenter):
    """PCA lighting noise (AlexNet-style)."""

    def __init__(self, alphastd, eigval, eigvec):
        super().__init__(alphastd=alphastd)
        self.alphastd = alphastd
        self.eigval = np.asarray(eigval, np.float32)
        self.eigvec = np.asarray(eigvec, np.float32)

    def __call__(self, src):
        alpha = np.random.normal(0, self.alphastd, size=(3,))
        rgb = (self.eigvec * alpha * self.eigval).sum(-1)
        return _mkarr(_to_np(src).astype(np.float32) + rgb)


class RandomGrayAug(Augmenter):
    _coef = np.array([0.299, 0.587, 0.114], np.float32)

    def __init__(self, p=0.5):
        super().__init__(p=p)
        self.p = p

    def __call__(self, src):
        if _pyrandom.random() < self.p:
            arr = _to_np(src).astype(np.float32)
            gray = (arr * self._coef).sum(-1, keepdims=True)
            return _mkarr(np.broadcast_to(gray, arr.shape).copy())
        return src if isinstance(src, NDArray) else _mkarr(src)


def CreateAugmenter(data_shape, resize=0, rand_crop=False, rand_resize=False,
                    rand_mirror=False, mean=None, std=None, brightness=0,
                    contrast=0, saturation=0, hue=0, pca_noise=0, rand_gray=0,
                    inter_method=2, dtype="float32"):
    """Standard augmenter list builder (reference: CreateAugmenter;
    ``dtype`` mirrors the upstream parameter — ``"uint8"`` keeps the
    chain cast-free for the quarter-size wire format, in which case the
    float augmenters (jitter/normalize/lighting) must stay off)."""
    auglist = []
    crop_size = (data_shape[2], data_shape[1])
    if resize > 0:
        auglist.append(ResizeAug(resize, inter_method))
    if rand_resize:
        assert rand_crop
        auglist.append(RandomSizedCropAug(crop_size, (0.08, 1.0),
                                          (3 / 4.0, 4 / 3.0), inter_method))
    elif rand_crop:
        auglist.append(RandomCropAug(crop_size, inter_method))
    else:
        auglist.append(CenterCropAug(crop_size, inter_method))
    if rand_mirror:
        auglist.append(HorizontalFlipAug(0.5))
    if np.dtype(dtype) != np.uint8:
        # decoded pixels are uint8 already; a cast-to-uint8 would only
        # burn a float intermediate per sample on the decode workers
        auglist.append(CastAug(str(np.dtype(dtype))))
    if brightness or contrast or saturation:
        auglist.append(ColorJitterAug(brightness, contrast, saturation))
    if hue:
        auglist.append(HueJitterAug(hue))
    if pca_noise > 0:
        eigval = np.array([55.46, 4.794, 1.148])
        eigvec = np.array([[-0.5675, 0.7192, 0.4009],
                           [-0.5808, -0.0045, -0.8140],
                           [-0.5836, -0.6948, 0.4203]])
        auglist.append(LightingAug(pca_noise, eigval, eigvec))
    if rand_gray > 0:
        auglist.append(RandomGrayAug(rand_gray))
    if mean is True:
        mean = np.array([123.68, 116.28, 103.53])
    if std is True:
        std = np.array([58.395, 57.12, 57.375])
    if mean is not None and len(np.shape(mean)):
        auglist.append(ColorNormalizeAug(mean, std))
    return auglist


def _decode_augment(payload, auglist, channels, dtype, sseed=None,
                    numpy_mode=False):
    """Decode one sample + run the augmenter chain -> CHW numpy.

    ``sseed`` reseeds the global python/numpy RNG streams first, making
    the sample's augmentation draws a function of (seed, ordinal) alone —
    bit-identical across serial and process-worker execution (the
    contract tests/test_io_pipeline.py asserts).
    ``numpy_mode`` keeps every augmenter output plain numpy (decode
    workers are forked children whose inherited XLA threadpools are dead;
    see ``_numpy_outputs``).
    """
    if sseed is not None:
        _pyrandom.seed(sseed)
        np.random.seed(sseed)
    cm = _numpy_outputs() if numpy_mode else _contextlib.nullcontext()
    with cm:
        img = imdecode(payload, flag=1 if channels == 3 else 0)
        for aug in auglist:
            img = aug(img)
    arr = img.asnumpy() if isinstance(img, NDArray) else np.asarray(img)
    arr = arr.transpose(2, 0, 1)
    if arr.dtype == dtype:
        return arr
    if np.issubdtype(dtype, np.integer) and \
            np.issubdtype(arr.dtype, np.floating):
        # an integer astype WRAPS out-of-range floats (normalized pixels
        # become 0/255 garbage) — refuse instead of silently corrupting
        raise MXNetError(
            f"augmenter chain produced {arr.dtype} but ImageIter("
            f"dtype={dtype}) was requested; keep normalization off host "
            "(io.DeviceFeedIter device_transform) or use a float dtype")
    return arr.astype(dtype)


_worker_cfg = None
_ITER_UID = 0


def _image_worker_init(auglist, channels, dtype):
    global _worker_cfg
    _worker_cfg = (list(auglist), int(channels), np.dtype(dtype))


def _image_worker_chunk(payloads, seeds, shape, shm_name=None):
    """Decode+augment one chunk in a forked worker, writing each sample
    STRAIGHT into one shared-memory block (no stack-then-copy
    intermediate); only the descriptor crosses the pipe (gluon
    dataloader's transport). ``shm_name`` is parent-assigned so a block
    whose descriptor never arrives stays sweepable by prefix."""
    from ..gluon.data.dataloader import _alloc_shm, _unlink_shm

    auglist, channels, dtype = _worker_cfg
    desc, dst, done = _alloc_shm((len(payloads),) + tuple(shape), dtype,
                                 name=shm_name)
    try:
        for j, (p, s) in enumerate(zip(payloads, seeds)):
            dst[j] = _decode_augment(p, auglist, channels, dtype, s,
                                     numpy_mode=True)
    except BaseException:
        # no descriptor will reach the parent: the failing worker owns
        # the unlink or the block outlives the run in /dev/shm
        done()
        _unlink_shm(desc)
        raise
    done()
    return desc


class ImageIter:
    """Record-file / list-backed image iterator (reference: ImageIter).

    Feeds NCHW batches; decode + augmentation run on host (worker role of
    the reference's C++ ImageRecordIter), the device sees only the final
    batch.

    Worker model (``worker_mode``):

    * ``"process"`` — a fork pool of ``preprocess_threads`` workers (the
      reference iterator's decode worker pool). Each worker decodes a
      contiguous chunk and ships it back as one shared-memory block;
      Pillow decode + numpy augmenters run truly in parallel (the thread
      pool is GIL-bound on everything but the decode itself). Default
      when ``MXNET_DATA_WORKERS`` is set (its value = worker count).
    * ``"thread"`` (default) / ``"serial"`` — the legacy in-process paths.

    ``seed`` makes augmentation deterministic: sample ordinal ``k`` of
    epoch ``e`` reseeds the RNG streams with ``crc32(base(seed, e), k)``,
    so serial and process execution produce bit-identical batches (thread
    mode shares the global streams across workers and stays
    nondeterministic). ``dtype`` is the batch dtype — ``"uint8"`` with a
    crop/flip-only augmenter list ships quarter-size batches and leaves
    normalization to the device (see io.DeviceFeedIter).
    """

    def __init__(self, batch_size, data_shape, path_imgrec=None,
                 path_imgidx=None, shuffle=False, aug_list=None,
                 label_width=1, last_batch_handle="pad",
                 preprocess_threads=4, worker_mode=None, seed=None,
                 dtype="float32", worker_timeout=120, **kwargs):
        from ..io import DataDesc
        from ..recordio import MXIndexedRecordIO, MXRecordIO

        if len(data_shape) != 3:
            raise MXNetError("data_shape must be (channels, height, width)")
        self.batch_size = batch_size
        self.data_shape = tuple(data_shape)
        self.label_width = label_width
        self.shuffle = shuffle
        env_workers = _os.environ.get("MXNET_DATA_WORKERS")
        if worker_mode is None:
            worker_mode = "process" if env_workers else "thread"
        if worker_mode not in ("serial", "thread", "process"):
            raise MXNetError(
                f"worker_mode must be 'serial', 'thread' or 'process', "
                f"got {worker_mode!r}")
        n = int(env_workers) if env_workers else int(preprocess_threads)
        self._n_workers = max(1, min(n, _os.cpu_count() or 1))
        if worker_mode == "thread" and self._n_workers == 1:
            worker_mode = "serial"
        self._worker_mode = worker_mode
        self._worker_timeout = worker_timeout
        global _ITER_UID
        _ITER_UID += 1
        # parent-assigned shm namespace: blocks whose descriptor never
        # arrives (worker timeout, terminate) stay findable for close()
        self._shm_prefix = f"mxi{_os.getpid()}u{_ITER_UID}"
        self._pool = None
        self._seed = seed
        self._dtype = np.dtype(dtype)
        self._epoch = -1
        self._drawn = 0
        self.auglist = aug_list if aug_list is not None else \
            CreateAugmenter(data_shape)
        self._rec = None
        self._keys = None
        if path_imgrec is None:
            raise MXNetError("ImageIter requires path_imgrec (use "
                             "gluon.data for folder datasets)")
        if path_imgidx:
            self._rec = MXIndexedRecordIO(path_imgidx, path_imgrec, "r")
            self._keys = list(self._rec.keys)
        else:
            if shuffle:
                raise MXNetError(
                    "ImageIter(shuffle=True) requires path_imgidx — "
                    "sequential record files cannot be reordered")
            self._rec = MXRecordIO(path_imgrec, "r")
        self._order = None
        self._cursor = 0
        self.provide_data = [DataDesc("data",
                                      (batch_size,) + self.data_shape,
                                      self._dtype, "NCHW")]
        lshape = (batch_size,) if label_width == 1 else (batch_size,
                                                         label_width)
        self.provide_label = [DataDesc("softmax_label", lshape, "float32",
                                       "N")]
        self.reset()
        if self._worker_mode == "process":
            # fork the pool NOW, on the constructing (main) thread:
            # forking later from a DeviceFeedIter producer thread while
            # the main thread dispatches XLA work maximizes the
            # fork-while-lock-held hazard window. The augmenter list is
            # captured here; mutate self.auglist before construction,
            # not after.
            self._ensure_pool()

    def reset(self):
        self._cursor = 0
        self._epoch += 1
        self._drawn = 0
        if self._seed is not None:
            self._epoch_base = (self._seed + 1000003 * self._epoch) \
                & 0x7FFFFFFF
        else:
            # process workers fork the parent's RNG state: without a
            # fresh per-epoch base every worker would replay the same
            # augmentation stream; draw one from the global stream (which
            # tests seed, keeping runs reproducible end to end)
            self._epoch_base = _pyrandom.getrandbits(31)
        if self._keys is not None:
            self._order = list(self._keys)
            if self.shuffle:
                if self._seed is not None:
                    # seeded: shuffle from a private RNG so the epoch's
                    # order is a function of (seed, epoch) alone
                    _pyrandom.Random(self._epoch_base).shuffle(self._order)
                else:
                    _pyrandom.shuffle(self._order)
        else:
            self._rec.reset()

    def _sample_seed(self, ordinal):
        """Per-sample augmentation seed, or None for the legacy
        global-stream behavior (unseeded serial/thread modes)."""
        if self._seed is None and self._worker_mode != "process":
            return None
        return _zlib.crc32(f"{self._epoch_base}:{ordinal}".encode()) \
            % (2 ** 31)

    def _next_sample(self):
        from ..recordio import unpack

        if self._keys is not None:
            if self._cursor >= len(self._order):
                return None
            rec = self._rec.read_idx(self._order[self._cursor])
            self._cursor += 1
        else:
            rec = self._rec.read()
            if rec is None:
                return None
        header, payload = unpack(rec)
        label = header.label
        if isinstance(label, (np.ndarray, list)):
            label = np.asarray(label, np.float32)
        else:
            label = np.float32(label)
        return label, payload

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def close(self):
        """Shut down the decode pool (idempotent; also runs on GC).
        Thread pools cancel queued work; process pools are terminated
        without draining, then the iterator's shm namespace is swept —
        a chunk whose descriptor never reached the parent (worker
        timeout, terminate mid-chunk) must not outlive the run."""
        pool, self._pool = self._pool, None
        if pool is None:
            return
        if hasattr(pool, "shutdown"):           # ThreadPoolExecutor
            pool.shutdown(wait=False, cancel_futures=True)
        else:                                   # multiprocessing.Pool
            pool.terminate()
            pool.join()
            import glob as _glob

            for path in _glob.glob(f"/dev/shm/{self._shm_prefix}*"):
                try:
                    _os.unlink(path)
                except OSError:  # pragma: no cover - raced cleanup
                    pass

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass

    def _decode_one(self, payload, sseed=None):
        return _decode_augment(payload, self.auglist, self.data_shape[0],
                               self._dtype, sseed)

    def _ensure_pool(self):
        if self._pool is not None:
            return self._pool
        if self._worker_mode == "process":
            import multiprocessing

            # fork, not spawn: workers inherit the augmenter list without
            # re-importing the framework. The worker path is numpy-only
            # (no jax) — forked XLA threadpools are dead in the child, so
            # touching jax there would hang (see _numpy_outputs).
            ctx = multiprocessing.get_context("fork")
            self._pool = ctx.Pool(
                self._n_workers, initializer=_image_worker_init,
                initargs=(self.auglist, self.data_shape[0],
                          str(self._dtype)))
        else:
            import concurrent.futures as _cf

            self._pool = _cf.ThreadPoolExecutor(self._n_workers)
        return self._pool

    def _decode_chunks_into(self, data, payloads, seeds):
        """Fan one batch out over the process pool in contiguous chunks;
        each comes back as one shm block copied once straight into the
        batch buffer (parent owns the unlink)."""
        from ..gluon.data.dataloader import _from_shm_into, _unlink_shm

        pool = self._ensure_pool()
        n = len(payloads)
        size = -(-n // min(self._n_workers, n))
        results = [(ofs, pool.apply_async(
            _image_worker_chunk,
            (payloads[ofs:ofs + size], seeds[ofs:ofs + size],
             self.data_shape,
             f"{self._shm_prefix}e{self._epoch}d{self._drawn}o{ofs}")))
            for ofs in range(0, n, size)]
        descs = []
        failed = None
        for ofs, res in results:
            try:
                descs.append((ofs, res.get(self._worker_timeout)))
            except Exception as e:  # noqa: BLE001 - rewrapped below
                failed = failed or e
        if failed is not None:
            # unlink the chunks that DID land: the workers unregistered
            # their blocks from the resource tracker, the parent owns
            # cleanup (same contract as the gluon loader)
            for _, d in descs:
                _unlink_shm(d)
            raise MXNetError(
                f"ImageIter decode worker failed: {failed!r}") from failed
        for ofs, desc in descs:
            _from_shm_into(desc, data, ofs)

    def next(self):
        from ..io import DataBatch

        c, h, w = self.data_shape
        data = np.zeros((self.batch_size, c, h, w), self._dtype)
        labels = np.zeros((self.batch_size,) if self.label_width == 1
                          else (self.batch_size, self.label_width),
                          np.float32)
        # record reads are serial (cheap, stateful cursor); decode +
        # augment fan out over the pool
        payloads, lab_list = [], []
        while len(payloads) < self.batch_size:
            sample = self._next_sample()
            if sample is None:
                break
            label, payload = sample
            payloads.append(payload)
            lab_list.append(label)
        i = len(payloads)
        if i == 0:
            raise StopIteration
        seeds = [self._sample_seed(self._drawn + j) for j in range(i)]
        self._drawn += i
        if self._worker_mode == "process":
            self._decode_chunks_into(data, payloads, seeds)
        elif self._worker_mode == "thread":
            decoded = list(self._ensure_pool().map(
                self._decode_one, payloads, seeds))
            for j, arr in enumerate(decoded):
                data[j] = arr
        else:
            for j, (p, s) in enumerate(zip(payloads, seeds)):
                data[j] = self._decode_one(p, s)
        for j, label in enumerate(lab_list):
            labels[j] = label
        if _telemetry_state.enabled:
            telemetry.record_images_decoded(i)
        pad = self.batch_size - i
        if pad:
            # pad by recycling real samples (NDArrayIter's wrap behavior —
            # io.py) so fit() never trains on fabricated zero images; pad
            # rows are discounted by score/predict via DataBatch.pad
            for j in range(i, self.batch_size):
                data[j] = data[j % i]
                labels[j] = labels[j % i]
        return DataBatch(data=[nd_array(data)], label=[nd_array(labels)],
                         pad=pad)


from .detection import (DetAugmenter, DetBorrowAug,  # noqa: E402
                        DetHorizontalFlipAug, DetRandomCropAug,
                        DetRandomPadAug, CreateDetAugmenter, ImageDetIter)

__all__ += ["SequentialAug", "RandomOrderAug", "HueJitterAug",
            "scale_down"]
__all__ += ["DetAugmenter", "DetBorrowAug", "DetHorizontalFlipAug",
            "DetRandomCropAug", "DetRandomPadAug", "CreateDetAugmenter",
            "ImageDetIter"]


class HueJitterAug(Augmenter):
    """Random hue jitter (reference: image.py::HueJitterAug — the YIQ
    rotation formulation)."""

    def __init__(self, hue):
        super().__init__(hue=hue)
        self.hue = hue
        self.tyiq = np.array([[0.299, 0.587, 0.114],
                              [0.596, -0.274, -0.321],
                              [0.211, -0.523, 0.311]])
        self.ityiq = np.array([[1.0, 0.956, 0.621],
                               [1.0, -0.272, -0.647],
                               [1.0, -1.107, 1.705]])

    def __call__(self, src):
        alpha = _pyrandom.uniform(-self.hue, self.hue)
        u = np.cos(alpha * np.pi)
        w = np.sin(alpha * np.pi)
        bt = np.array([[1.0, 0.0, 0.0],
                       [0.0, u, -w],
                       [0.0, w, u]])
        t = np.dot(np.dot(self.ityiq, bt), self.tyiq).T
        x = _to_np(src).astype(np.float32)
        return _mkarr(np.dot(x, t))


def scale_down(src_size, size):
    """Scale `size` down to fit in `src_size`, keeping aspect ratio
    (reference: image.py::scale_down)."""
    w, h = size
    sw, sh = src_size
    if sh < h:
        w, h = float(w * sh) / h, sh
    if sw < w:
        w, h = sw, float(h * sw) / w
    return int(w), int(h)
