"""Image IO + augmentation (parity: python/mxnet/image/image.py + the C++
augmenters in src/io/image_aug_default.cc).

Pure-python host-side pipeline: decode (cv2/PIL, gated), resize, crop,
mirror, color jitter; `ImageIter`/`ImageRecordIterPy` feed NCHW float
batches.  Heavy decode runs in the prefetch thread (io.PrefetchingIter).
"""
from __future__ import annotations

import os
import random as _pyrandom
from typing import List, Optional

import numpy as _np

from .base import MXNetError
from . import ndarray as nd
from .ndarray import NDArray
from . import io as _io
from . import recordio


def _as_np(src) -> _np.ndarray:
    return src.asnumpy() if isinstance(src, NDArray) else _np.asarray(src)


def _like(arr: _np.ndarray, src):
    """Wrap the numpy result to match the input's type.  The augmenter
    cores are numpy-native (the host decode pipeline must never pay a
    per-image jax dispatch — that is a ~7x throughput loss measured on
    the IO bench); NDArray in → NDArray out keeps API parity."""
    return nd.array(arr) if isinstance(src, NDArray) else arr


def imdecode_np(buf, flag=1, to_rgb=True) -> _np.ndarray:
    """Decode image bytes → HWC uint8 numpy (the iterator hot path)."""
    img = recordio._imdecode_bytes(bytes(buf), flag)
    if img is None:
        raise MXNetError("image decode failed")
    if to_rgb and img.ndim == 3:
        img = _np.ascontiguousarray(img[:, :, ::-1])
    return img


def imdecode(buf, flag=1, to_rgb=True, out=None):
    """Decode image bytes → HWC NDArray (parity: mx.image.imdecode)."""
    return nd.array(imdecode_np(buf, flag, to_rgb))


def imread(filename, flag=1, to_rgb=True):
    with open(filename, "rb") as f:
        return imdecode(f.read(), flag, to_rgb)


def _resize_np(src: _np.ndarray, w, h):
    try:
        import cv2
        return cv2.resize(src, (w, h), interpolation=cv2.INTER_LINEAR)
    except ImportError:
        pass
    # jax bilinear fallback
    import jax
    out = jax.image.resize(src.astype(_np.float32),
                           (h, w) + src.shape[2:], method="bilinear")
    return _np.asarray(out).astype(src.dtype)


def imresize(src, w, h, interp=1):
    return _like(_resize_np(_as_np(src), w, h), src)


def resize_short(src, size, interp=2):
    """Resize shorter edge to `size` (parity: image.resize_short)."""
    arr = _as_np(src)
    h, w = arr.shape[:2]
    if h > w:
        new_h, new_w = size * h // w, size
    else:
        new_h, new_w = size, size * w // h
    return _like(_resize_np(arr, new_w, new_h), src)


def fixed_crop(src, x0, y0, w, h, size=None, interp=2):
    arr = _as_np(src)
    out = arr[y0:y0 + h, x0:x0 + w]
    if size is not None and (w, h) != size:
        out = _resize_np(out, size[0], size[1])
    return _like(out, src)


def random_crop(src, size, interp=2):
    h, w = src.shape[:2]
    new_w, new_h = size
    x0 = _pyrandom.randint(0, max(0, w - new_w))
    y0 = _pyrandom.randint(0, max(0, h - new_h))
    out = fixed_crop(src, x0, y0, min(new_w, w), min(new_h, h), size)
    return out, (x0, y0, new_w, new_h)


def center_crop(src, size, interp=2):
    h, w = src.shape[:2]
    new_w, new_h = size
    x0 = max(0, (w - new_w) // 2)
    y0 = max(0, (h - new_h) // 2)
    out = fixed_crop(src, x0, y0, min(new_w, w), min(new_h, h), size)
    return out, (x0, y0, new_w, new_h)


def scale_down(src_size, size):
    """Shrink a requested crop (w, h) to fit inside src (w, h) keeping
    its aspect ratio (parity: image.scale_down)."""
    w, h = size
    sw, sh = src_size
    if sh < h:
        w, h = float(w * sh) / h, sh
    if sw < w:
        w, h = sw, float(h * sw) / w
    return int(w), int(h)


def random_size_crop(src, size, min_area, ratio, interp=2):
    """Random area-and-aspect crop resized to `size` (parity:
    image.random_size_crop — the inception-style crop).  Falls back to a
    random fitting crop when no sample satisfies the constraints."""
    h, w = src.shape[:2]
    area = h * w
    for _ in range(10):
        target_area = _pyrandom.uniform(min_area, 1.0) * area
        log_ratio = (_np.log(ratio[0]), _np.log(ratio[1]))
        new_ratio = _np.exp(_pyrandom.uniform(*log_ratio))
        new_w = int(round((target_area * new_ratio) ** 0.5))
        new_h = int(round((target_area / new_ratio) ** 0.5))
        if new_w <= w and new_h <= h:
            x0 = _pyrandom.randint(0, w - new_w)
            y0 = _pyrandom.randint(0, h - new_h)
            out = fixed_crop(src, x0, y0, new_w, new_h, size, interp)
            return out, (x0, y0, new_w, new_h)
    return random_crop(src, size, interp)


def color_normalize(src, mean, std=None):
    arr = _as_np(src).astype(_np.float32, copy=False)
    if mean is not None:
        arr = arr - _as_np(mean).astype(_np.float32)
    if std is not None:
        arr = arr * (1.0 / _as_np(std).astype(_np.float32))
    return _like(arr, src)


class Augmenter:
    """Base augmenter (parity: image.Augmenter)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs

    def dumps(self):
        import json
        # numpy values (mean/std arrays) serialize via tolist/str fallback
        return json.dumps([self.__class__.__name__.lower(), self._kwargs],
                          default=lambda o: o.tolist()
                          if hasattr(o, "tolist") else str(o))

    def __call__(self, src):
        raise NotImplementedError


class ResizeAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size

    def __call__(self, src):
        return [resize_short(src, self.size)]


class ForceResizeAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size

    def __call__(self, src):
        return [imresize(src, self.size[0], self.size[1])]


class RandomCropAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size

    def __call__(self, src):
        return [random_crop(src, self.size)[0]]


class CenterCropAug(Augmenter):
    def __init__(self, size, interp=2):
        super().__init__(size=size, interp=interp)
        self.size = size

    def __call__(self, src):
        return [center_crop(src, self.size)[0]]


class HorizontalFlipAug(Augmenter):
    def __init__(self, p):
        super().__init__(p=p)
        self.p = p

    def __call__(self, src):
        if _pyrandom.random() < self.p:
            return [_like(_np.ascontiguousarray(_as_np(src)[:, ::-1]), src)]
        return [src]


class CastAug(Augmenter):
    def __call__(self, src):
        return [src.astype(_np.float32)]  # np and NDArray both


class BrightnessJitterAug(Augmenter):
    def __init__(self, brightness):
        super().__init__(brightness=brightness)
        self.brightness = brightness

    def __call__(self, src):
        alpha = 1.0 + _pyrandom.uniform(-self.brightness, self.brightness)
        return [src * alpha]


class ContrastJitterAug(Augmenter):
    def __init__(self, contrast):
        super().__init__(contrast=contrast)
        self.contrast = contrast

    def __call__(self, src):
        alpha = 1.0 + _pyrandom.uniform(-self.contrast, self.contrast)
        arr = _as_np(src).astype(_np.float32, copy=False)
        coef = _np.array([[[0.299, 0.587, 0.114]]], _np.float32)
        gray = float((arr * coef).sum() * (3.0 / arr.size))
        return [_like(arr * alpha + gray * (1.0 - alpha), src)]


class SaturationJitterAug(Augmenter):
    """Parity: image.py SaturationJitterAug — blend with per-pixel gray."""

    def __init__(self, saturation):
        super().__init__(saturation=saturation)
        self.saturation = saturation

    def __call__(self, src):
        alpha = 1.0 + _pyrandom.uniform(-self.saturation, self.saturation)
        arr = _as_np(src).astype(_np.float32, copy=False)
        coef = _np.array([[[0.299, 0.587, 0.114]]], _np.float32)
        gray = (arr * coef).sum(axis=2, keepdims=True)
        return [_like(arr * alpha + gray * (1.0 - alpha), src)]


class ColorJitterAug(Augmenter):
    """Parity: image.py ColorJitterAug — random-order brightness/contrast/
    saturation jitter."""

    def __init__(self, brightness, contrast, saturation):
        super().__init__(brightness=brightness, contrast=contrast,
                         saturation=saturation)
        self.ts = []
        if brightness > 0:
            self.ts.append(BrightnessJitterAug(brightness))
        if contrast > 0:
            self.ts.append(ContrastJitterAug(contrast))
        if saturation > 0:
            self.ts.append(SaturationJitterAug(saturation))

    def __call__(self, src):
        order = list(range(len(self.ts)))
        _pyrandom.shuffle(order)
        for i in order:
            src = self.ts[i](src)[0]
        return [src]


class RandomGrayAug(Augmenter):
    """Parity: image.py RandomGrayAug — convert to 3-channel gray w.p. p."""

    def __init__(self, p):
        super().__init__(p=p)
        self.p = p

    def __call__(self, src):
        if _pyrandom.random() < self.p:
            arr = _as_np(src).astype(_np.float32, copy=False)
            coef = _np.array([[[0.299, 0.587, 0.114]]], _np.float32)
            gray = (arr * coef).sum(axis=2, keepdims=True)
            src = _like(_np.repeat(gray, 3, axis=2), src)
        return [src]


class HueJitterAug(Augmenter):
    """Parity: image.py HueJitterAug — rotate chroma in YIQ space by a
    random angle in [-hue, hue]·π."""

    _TYIQ = _np.array([[0.299, 0.587, 0.114],
                       [0.596, -0.274, -0.321],
                       [0.211, -0.523, 0.311]], _np.float32)
    _ITYIQ = _np.array([[1.0, 0.956, 0.621],
                        [1.0, -0.272, -0.647],
                        [1.0, -1.107, 1.705]], _np.float32)

    def __init__(self, hue):
        super().__init__(hue=hue)
        self.hue = hue

    def __call__(self, src):
        alpha = _pyrandom.uniform(-self.hue, self.hue)
        u = _np.cos(alpha * _np.pi)
        w = _np.sin(alpha * _np.pi)
        rot = _np.array([[1.0, 0.0, 0.0],
                         [0.0, u, -w],
                         [0.0, w, u]], _np.float32)
        t = (self._ITYIQ @ rot @ self._TYIQ).T
        arr = _as_np(src).astype(_np.float32, copy=False)
        return [_like(arr @ t, src)]


class LightingAug(Augmenter):
    """Parity: image.py LightingAug — AlexNet-style PCA lighting noise:
    add eigvec·(alpha∘eigval) with alpha ~ N(0, alphastd)."""

    def __init__(self, alphastd, eigval, eigvec):
        super().__init__(alphastd=alphastd, eigval=eigval, eigvec=eigvec)
        self.alphastd = alphastd
        self.eigval = _np.asarray(eigval, _np.float32)
        self.eigvec = _np.asarray(eigvec, _np.float32)

    def __call__(self, src):
        alpha = _np.random.normal(0, self.alphastd, size=(3,))
        rgb = self.eigvec @ (alpha * self.eigval)
        arr = _as_np(src).astype(_np.float32, copy=False)
        return [_like(arr + rgb.astype(_np.float32), src)]


class SequentialAug(Augmenter):
    """Parity: image.py SequentialAug — apply sub-augmenters in order."""

    def __init__(self, ts):
        super().__init__()
        self.ts = ts

    def __call__(self, src):
        imgs = [src]
        for aug in self.ts:
            imgs = [out for img in imgs for out in aug(img)]
        return imgs

    def dumps(self):
        return [self.__class__.__name__.lower(),
                [a.dumps() for a in self.ts]]


class RandomOrderAug(Augmenter):
    """Parity: image.py RandomOrderAug — apply sub-augmenters in a
    random order."""

    def __init__(self, ts):
        super().__init__()
        self.ts = ts

    def __call__(self, src):
        order = list(self.ts)
        _pyrandom.shuffle(order)
        imgs = [src]
        for aug in order:
            imgs = [out for img in imgs for out in aug(img)]
        return imgs

    def dumps(self):
        return [self.__class__.__name__.lower(),
                [a.dumps() for a in self.ts]]


class RandomSizedCropAug(Augmenter):
    """Parity: image.py RandomSizedCropAug — random_size_crop as an
    augmenter (inception training crop)."""

    def __init__(self, size, min_area, ratio, interp=2):
        super().__init__(size=size, min_area=min_area, ratio=ratio,
                         interp=interp)
        self.size = size
        self.min_area = min_area
        self.ratio = ratio
        self.interp = interp

    def __call__(self, src):
        return [random_size_crop(src, self.size, self.min_area,
                                 self.ratio, self.interp)[0]]


class ColorNormalizeAug(Augmenter):
    def __init__(self, mean, std):
        super().__init__(mean=mean, std=std)
        # numpy-native; the reciprocal turns the per-image divide into a
        # multiply on the hot path
        self.mean = None if mean is None \
            else _np.asarray(_as_np(mean), _np.float32)
        self._inv_std = None if std is None \
            else (1.0 / _np.asarray(_as_np(std), _np.float32))

    def __call__(self, src):
        arr = _as_np(src).astype(_np.float32, copy=False)
        if self.mean is not None:
            arr = arr - self.mean
        if self._inv_std is not None:
            arr = arr * self._inv_std
        return [_like(arr, src)]


def CreateAugmenter(data_shape, resize=0, rand_crop=False, rand_resize=False,
                    rand_mirror=False, mean=None, std=None, brightness=0,
                    contrast=0, saturation=0, hue=0, pca_noise=0, rand_gray=0,
                    inter_method=2):
    """Parity: image.CreateAugmenter (full flag set: rand_resize →
    inception crop, color jitters composed in random order, PCA
    lighting, random gray)."""
    auglist: List[Augmenter] = []
    if resize > 0:
        auglist.append(ResizeAug(resize, inter_method))
    crop_size = (data_shape[2], data_shape[1])
    if rand_resize:
        assert rand_crop
        auglist.append(RandomSizedCropAug(crop_size, 0.08,
                                          (3.0 / 4.0, 4.0 / 3.0),
                                          inter_method))
    elif rand_crop:
        auglist.append(RandomCropAug(crop_size, inter_method))
    else:
        auglist.append(CenterCropAug(crop_size, inter_method))
    if rand_mirror:
        auglist.append(HorizontalFlipAug(0.5))
    auglist.append(CastAug())
    jitters: List[Augmenter] = []
    if brightness:
        jitters.append(BrightnessJitterAug(brightness))
    if contrast:
        jitters.append(ContrastJitterAug(contrast))
    if saturation:
        jitters.append(SaturationJitterAug(saturation))
    if len(jitters) > 1:
        auglist.append(RandomOrderAug(jitters))
    else:
        auglist.extend(jitters)
    if hue:
        auglist.append(HueJitterAug(hue))
    if pca_noise > 0:
        eigval = _np.array([55.46, 4.794, 1.148])
        eigvec = _np.array([[-0.5675, 0.7192, 0.4009],
                            [-0.5808, -0.0045, -0.8140],
                            [-0.5836, -0.6948, 0.4203]])
        auglist.append(LightingAug(pca_noise, eigval, eigvec))
    if rand_gray > 0:
        auglist.append(RandomGrayAug(rand_gray))
    if mean is True:
        mean = _np.array([123.68, 116.28, 103.53])
    if std is True:
        std = _np.array([58.395, 57.12, 57.375])
    if mean is not None or std is not None:
        auglist.append(ColorNormalizeAug(mean, std))
    return auglist


class ImageIter(_io.DataIter):
    """Pure-python image iterator (parity: python/mxnet/image/image.py
    ImageIter): reads .rec or .lst+images, applies augmenters, yields NCHW."""

    def __init__(self, batch_size, data_shape, label_width=1,
                 path_imgrec=None, path_imglist=None, path_root=None,
                 path_imgidx=None, shuffle=False, part_index=0, num_parts=1,
                 aug_list=None, imglist=None, data_name="data",
                 label_name="softmax_label", preprocess_threads=4, **kwargs):
        super().__init__(batch_size)
        # decode+augment worker pool (parity: iter_image_recordio_2.cc
        # OMP-parallel decode, :139-154): cv2 releases the GIL, so a thread
        # pool gives real decode parallelism at ImageNet rates
        self._n_workers = max(1, int(preprocess_threads))
        self._pool = None
        assert path_imgrec or path_imglist or isinstance(imglist, list)
        if path_imgrec:
            if path_imgidx:
                self.imgrec = recordio.MXIndexedRecordIO(path_imgidx,
                                                         path_imgrec, "r")
                self.imgidx = list(self.imgrec.keys)
            else:
                self.imgrec = recordio.MXRecordIO(path_imgrec, "r")
                self.imgidx = None
        else:
            self.imgrec = None
        self.imglist = None
        self._rec_offsets = None
        self.path_root = path_root
        if path_imglist:
            imglist_d = {}
            imgkeys = []
            with open(path_imglist) as fin:
                for line in fin:
                    line = line.strip().split("\t")
                    label = _np.array(line[1:-1], dtype=_np.float32)
                    key = int(line[0])
                    imglist_d[key] = (label, line[-1])
                    imgkeys.append(key)
            self.imglist = imglist_d
            self.seq = imgkeys
        elif isinstance(imglist, list):
            imglist_d = {}
            imgkeys = []
            for i, img in enumerate(imglist):
                key = str(i)
                label = _np.array(img[0], dtype=_np.float32) \
                    if not isinstance(img[0], _np.ndarray) else img[0]
                imglist_d[key] = (label, img[1])
                imgkeys.append(key)
            self.imglist = imglist_d
            self.seq = imgkeys
        elif self.imgidx is not None:
            self.seq = self.imgidx
        elif shuffle and self.imgrec is not None:
            # no index file: scan the .rec once for record offsets so
            # shuffle is real (the reference asserts path_imgidx instead;
            # seekable python records make the index unnecessary)
            self._rec_offsets = []
            while True:
                pos = self.imgrec.tell()
                if self.imgrec.read() is None:
                    break
                self._rec_offsets.append(pos)
            self.imgrec.reset()
            self.seq = list(range(len(self._rec_offsets)))
        else:
            self.seq = None
        assert len(data_shape) == 3 and data_shape[0] == 3 or data_shape[0] == 1
        self.provide_data = [_io.DataDesc(data_name,
                                          (batch_size,) + tuple(data_shape))]
        if label_width > 1:
            self.provide_label = [_io.DataDesc(label_name,
                                               (batch_size, label_width))]
        else:
            self.provide_label = [_io.DataDesc(label_name, (batch_size,))]
        self.batch_size = batch_size
        self.data_shape = tuple(data_shape)
        self.label_width = label_width
        self.shuffle = shuffle
        self.num_parts = num_parts
        self.part_index = part_index
        if aug_list is None:
            self.auglist = CreateAugmenter(data_shape, **kwargs)
        else:
            self.auglist = aug_list
        self.cur = 0
        self.reset()

    def reset(self):
        if self.shuffle and self.seq is not None:
            _np.random.shuffle(self.seq)
        if self.imgrec is not None:
            self.imgrec.reset()
        self.cur = 0

    def next_sample(self):
        if self.seq is not None:
            if self.cur >= len(self.seq):
                raise StopIteration
            idx = self.seq[self.cur]
            self.cur += 1
            if self.imgrec is not None:
                if self._rec_offsets is not None:
                    self.imgrec.seek(self._rec_offsets[idx])
                    s = self.imgrec.read()
                else:
                    s = self.imgrec.read_idx(idx)
                header, img = recordio.unpack(s)
                return header.label, img
            label, fname = self.imglist[idx]
            with open(os.path.join(self.path_root or "", fname), "rb") as f:
                img = f.read()
            return label, img
        s = self.imgrec.read()
        if s is None:
            raise StopIteration
        header, img = recordio.unpack(s)
        return header.label, img

    def _decode_augment(self, s):
        # numpy end to end: decode and every augmenter stay on the host;
        # the only device transfer is the one per-batch nd.array in
        # next() (parity goal: iter_image_recordio_2.cc keeps decode on
        # the CPU pool and hands the executor one batch tensor).  The
        # HWC→CHW transpose happens HERE so it rides the worker pool
        # instead of serializing on the batch-assembly thread.
        data = imdecode_np(s)
        for aug in self.auglist:
            data = aug(data)[0]
        arr = _as_np(data)
        if arr.ndim == 2:
            arr = arr[:, :, None]
        c, h, w = self.data_shape
        return _np.ascontiguousarray(
            arr[:h, :w, :c].transpose(2, 0, 1), dtype=_np.float32)

    def _decode_geometric_u8(self, s):
        """device_augment host leg: decode + GEOMETRIC augmenters only
        (resize/crop); returns contiguous uint8 HWC.  The float work
        (mirror select, cast, mean/std, HWC->CHW) runs as ONE fused XLA
        program per batch (`_dev_aug_fn`), so the host pays JPEG decode
        only and the device upload is uint8 — 4x fewer host->device bytes
        than the float32 host path."""
        data = imdecode_np(s)
        for aug in self.auglist:
            if isinstance(aug, (ResizeAug, RandomCropAug, CenterCropAug,
                                ForceResizeAug)):
                data = aug(data)[0]
        arr = _as_np(data)
        if arr.ndim == 2:
            arr = arr[:, :, None]
        c, h, w = self.data_shape
        return _np.ascontiguousarray(arr[:h, :w, :c], dtype=_np.uint8)

    @property
    def _dev_aug_fn(self):
        if getattr(self, "_dev_aug_cached", None) is None:
            import jax
            import jax.numpy as jnp
            mean = inv_std = None
            mirror = False
            for aug in self.auglist:
                if isinstance(aug, ColorNormalizeAug):
                    mean = (None if aug.mean is None
                            else jnp.asarray(aug.mean))
                    inv_std = (None if aug._inv_std is None
                               else jnp.asarray(aug._inv_std))
                elif isinstance(aug, HorizontalFlipAug):
                    mirror = True
            out_dtype = jnp.dtype(getattr(self, "_device_dtype",
                                          "float32"))

            def fn(x_u8, flips):
                x = x_u8.astype(jnp.float32)          # (B,H,W,C)
                if mirror:
                    x = jnp.where(flips[:, None, None, None],
                                  x[:, :, ::-1, :], x)
                if mean is not None:
                    x = x - mean
                if inv_std is not None:
                    x = x * inv_std
                return x.transpose(0, 3, 1, 2).astype(out_dtype)

            self._dev_aug_cached = (jax.jit(fn), mirror)
        return self._dev_aug_cached

    def _map_pool(self, fn, items):
        """Decode/augment a batch on the worker pool (order-preserving)."""
        if self._n_workers <= 1 or len(items) <= 1:
            return [fn(x) for x in items]
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(max_workers=self._n_workers)
        return list(self._pool.map(fn, items))

    def next(self):
        batch_size = self.batch_size
        c, h, w = self.data_shape
        batch_label = _np.zeros((batch_size,) + (
            (self.label_width,) if self.label_width > 1 else ()),
            dtype=_np.float32)
        samples = []
        while len(samples) < batch_size:
            samples.append(self.next_sample())
        if getattr(self, "_device_augment", False):
            # uint8 NHWC host batch -> one fused on-device program
            batch_u8 = _np.empty((batch_size, h, w, c), dtype=_np.uint8)
            arrs = self._map_pool(self._decode_geometric_u8,
                                  [s for _, s in samples])
            for i, (arr, (label, _)) in enumerate(zip(arrs, samples)):
                batch_u8[i] = arr
                batch_label[i] = label if _np.ndim(label) else float(label)
            fn, mirror = self._dev_aug_fn
            flips = (_np.random.rand(batch_size) < 0.5) if mirror \
                else _np.zeros(batch_size, bool)
            data_nd = NDArray(fn(batch_u8, flips))
            return _io.DataBatch([data_nd], [nd.array(batch_label)], 0,
                                 provide_data=self.provide_data,
                                 provide_label=self.provide_label)
        # workers hand back contiguous CHW float32; assembly is one
        # contiguous memcpy per image + one device upload per batch
        batch_data = _np.empty((batch_size, c, h, w), dtype=_np.float32)
        arrs = self._map_pool(self._decode_augment, [s for _, s in samples])
        for i, (arr, (label, _)) in enumerate(zip(arrs, samples)):
            batch_data[i] = arr
            batch_label[i] = label if _np.ndim(label) else float(label)
        i = batch_size  # full batch assembled (pad = batch_size - i = 0)
        return _io.DataBatch([nd.array(batch_data)], [nd.array(batch_label)],
                             batch_size - i,
                             provide_data=self.provide_data,
                             provide_label=self.provide_label)


class ImageRecordIterPy(ImageIter):
    """Backend for io.ImageRecordIter (parity: iter_image_recordio_2.cc)."""

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 shuffle=False, mean=(0, 0, 0), std=(1, 1, 1), rand_crop=False,
                 rand_mirror=False, **kwargs):
        mean_arr = _np.array(mean) if any(mean) else None
        std_arr = _np.array(std) if any(s != 1 for s in std) else None
        aug = CreateAugmenter(data_shape, rand_crop=rand_crop,
                              rand_mirror=rand_mirror, mean=mean_arr,
                              std=std_arr)
        super().__init__(batch_size, data_shape, label_width,
                         path_imgrec=path_imgrec, shuffle=shuffle,
                         aug_list=aug, **kwargs)


# -- detection pipeline (parity: python/mxnet/image/detection.py namespace:
# mx.image.ImageDetIter / CreateDetAugmenter / Det*Aug) --------------------
from .detection import (DetAugmenter, DetBorrowAug, DetRandomSelectAug,  # noqa: E402,F401
                        DetHorizontalFlipAug, DetRandomCropAug,
                        DetRandomPadAug, CreateDetAugmenter, ImageDetIter)
