"""Vectorized numpy augmentations reproducing the reference's torchvision
transform stacks (``util.py:21-106``):

- MNIST: normalize (0.1307, 0.3081)                       (util.py:24-33)
- CIFAR-10/100 train: pad-4 reflect -> random crop 32 -> random hflip ->
  normalize mean [125.3,123.0,113.9]/255, std [63.0,62.1,66.7]/255
  (util.py:35-47, 61-74)
- SVHN: random crop 32 pad 4 (zeros) -> hflip -> normalize
  (0.4914,0.4822,0.4465)/(0.2023,0.1994,0.2010)           (util.py:89-101)

All functions operate on NHWC uint8/float batches and are host-side (the
per-step augmentation cost is hidden behind device compute by the prefetching
loader in datasets.py).
"""

import ctypes
from typing import Optional

import numpy as np

_loader_lib = None
_loader_tried = False


def _configure_loader(lib: "ctypes.CDLL") -> None:
    lib.psl_crop_flip_batch.argtypes = [ctypes.c_void_p] * 6 + \
        [ctypes.c_int64] * 6
    lib.psl_crop_flip_batch.restype = None
    lib.psl_rrc_batch.argtypes = [ctypes.c_void_p] * 8 + [ctypes.c_int64] * 6
    lib.psl_rrc_batch.restype = None


def _load_native_loader():
    """ctypes handle to the C++ crop+flip kernel (native/loader.cpp), built
    on demand via the shared protocol (utils/native.py); None -> numpy
    fallback."""
    global _loader_lib, _loader_tried
    if not _loader_tried:
        from ps_pytorch_tpu.utils.native import load_native_lib
        _loader_lib = load_native_lib("libpsloader.so", _configure_loader)
        _loader_tried = True
    return _loader_lib

MNIST_MEAN, MNIST_STD = (0.1307,), (0.3081,)
CIFAR_MEAN = np.array([125.3, 123.0, 113.9], np.float32) / 255.0
CIFAR_STD = np.array([63.0, 62.1, 66.7], np.float32) / 255.0
SVHN_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
SVHN_STD = np.array([0.2023, 0.1994, 0.2010], np.float32)
IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def normalize(x: np.ndarray, mean, std) -> np.ndarray:
    """x: [..., C] float in [0,1] OR uint8 in [0,255] -> channel-normalized
    float32. The uint8 path folds the /255 into the scale so conversion and
    normalization are one fused pass (values match the float path to float32
    rounding)."""
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    if x.dtype == np.uint8:
        out = x * (1.0 / (255.0 * std)).astype(np.float32)
        out -= mean / std
        return out
    return ((x - mean) / std).astype(np.float32)


def random_crop(x: np.ndarray, rng: np.random.Generator, pad: int = 4,
                mode: str = "reflect") -> np.ndarray:
    """Per-image random crop back to the original HxW after padding,
    fully vectorized (one batched fancy-index gather — the round-1
    per-image Python loop was the projected first bottleneck at TPU batch
    sizes, VERDICT r1 item 4).

    mode='reflect' matches the CIFAR stack (util.py:39-43); mode='constant'
    (zero pad) matches SVHN's RandomCrop(32, padding=4) (util.py:91).
    Offset draw order (ys then xs) is unchanged, so results are
    bit-identical to the loop implementation for a given rng state.
    """
    b, h, w, c = x.shape
    padded = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode=mode)
    ys = rng.integers(0, 2 * pad + 1, size=b)
    xs = rng.integers(0, 2 * pad + 1, size=b)
    rows = ys[:, None] + np.arange(h)[None, :]            # [b, h]
    cols = xs[:, None] + np.arange(w)[None, :]            # [b, w]
    return padded[np.arange(b)[:, None, None],
                  rows[:, :, None], cols[:, None, :]]     # [b, h, w, c]


def random_hflip(x: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    flip = rng.random(x.shape[0]) < 0.5
    x = x.copy()
    x[flip] = x[flip, :, ::-1]
    return x


def _crop_flip(x: np.ndarray, rng: np.random.Generator, pad: int,
               mode: str) -> np.ndarray:
    """Random crop + hflip via per-image strided copies.

    Measured at b=1024/32px uint8 on the build host (2026-07):
    strided-slice memcpy 9.2 ms/batch vs 29.6
    ms for the batched fancy-index gather — 3.2x faster (contiguous row
    copies beat elementwise index arithmetic; the round-1 concern about
    per-image Python only bites at small batches). Draw order (ys, xs, flip)
    matches the composed random_crop+random_hflip path bit-for-bit.

    Implemented AS crop_flip_prepadded over a batch-local pad with identity
    selection, so the bit-identity between the composed and pre-padded
    loader paths is structural rather than two hand-maintained copies."""
    b, h, w, c = x.shape
    padded = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode=mode)
    return crop_flip_prepadded(padded, np.arange(b), rng, h, w)


def crop_flip_prepadded(padded: np.ndarray, sel: np.ndarray,
                        rng: np.random.Generator, h: int, w: int,
                        out: Optional[np.ndarray] = None) -> np.ndarray:
    """Shuffle-gather + random crop + hflip in ONE pass over a dataset
    padded once at loader init (DataLoader._prepad) — each output image is
    a single strided copy straight from the padded store, where the
    composed path made three (fancy-index gather, whole-batch np.pad,
    per-image crop). Measured at b=1024/32px uint8: 9.2 ms vs 15.8 ms for
    the 3-pass path (+71% loader throughput); the one-time pad of
    CIFAR-sized train data costs ~1.3 s and 240 MB host RAM.

    Draw order (ys, xs, flip) is identical to ``_crop_flip``, so a given
    augment-rng state yields bit-identical batches to the composed path.
    """
    b = len(sel)
    c = padded.shape[-1]
    pad_h = padded.shape[1] - h
    pad_w = padded.shape[2] - w
    ys = rng.integers(0, pad_h + 1, size=b)
    xs = rng.integers(0, pad_w + 1, size=b)
    flip = rng.random(b) < 0.5
    if out is None:
        out = np.empty((b, h, w, c), padded.dtype)
    # Native path (uint8 contiguous only — the storage contract of the
    # pre-padded store): one GIL-free OpenMP pass over the batch, memcpy per
    # row. Same ys/xs/flip draws either way, so native and numpy paths are
    # bit-identical (tested: test_data.py::test_native_loader_bit_identical).
    lib = _load_native_loader()
    if (lib is not None and padded.dtype == np.uint8
            and out.shape == (b, h, w, c) and out.dtype == padded.dtype
            and padded.flags.c_contiguous and out.flags.c_contiguous):
        sel64 = np.ascontiguousarray(sel, np.int64)
        ys32 = np.ascontiguousarray(ys, np.int32)
        xs32 = np.ascontiguousarray(xs, np.int32)
        fl8 = np.ascontiguousarray(flip, np.uint8)
        lib.psl_crop_flip_batch(
            padded.ctypes.data, sel64.ctypes.data, ys32.ctypes.data,
            xs32.ctypes.data, fl8.ctypes.data, out.ctypes.data,
            b, h, w, c, padded.shape[1], padded.shape[2])
        return out
    for i in range(b):
        v = padded[sel[i], ys[i]:ys[i] + h, xs[i]:xs[i] + w]
        out[i] = v[:, ::-1] if flip[i] else v
    return out


# ---------------------------------------------------------------------------
# ImageNet-geometry random-resized-crop (area/aspect jitter -> bilinear
# resize -> hflip), the reference's known-hard input path (SURVEY §7,
# my_data_loader.py). Two implementations with ONE arithmetic contract:
# the native OpenMP kernel (native/loader.cpp psl_rrc_batch, GIL-released)
# and the vectorized numpy fallback below. Both use integer fixed-point
# separable bilinear (RRC_SHIFT fractional bits per axis), so they are
# bit-identical — CPU CI proves the native kernel against the fallback
# (tests/test_augment_rrc.py), the same contract crop_flip_prepadded has.
#
# Crop rectangles and flips come from a COUNTER-BASED RNG (splitmix64 over
# a per-image counter): any worker can sample any image's parameters
# independently of batch order, which is what makes the multi-worker
# loader pool (datasets.DataLoader workers>1) deterministic and
# bit-identical to the single-worker path.
# ---------------------------------------------------------------------------

RRC_SHIFT = 10                      # fixed-point fractional bits per axis
_RRC_ONE = 1 << RRC_SHIFT
_RRC_ATTEMPTS = 10                  # torchvision RandomResizedCrop protocol


def _mix64(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 finalizer over uint64 (wraps mod 2^64)."""
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
        x ^= x >> np.uint64(30)
        x = (x * np.uint64(0xBF58476D1CE4E5B9)).astype(np.uint64)
        x ^= x >> np.uint64(27)
        x = (x * np.uint64(0x94D049BB133111EB)).astype(np.uint64)
        return x ^ (x >> np.uint64(31))


def _counter_uniforms(seed: int, counters: np.ndarray, n: int) -> np.ndarray:
    """[B, n] uniforms in [0,1), each a pure function of (seed, counter, j)
    — the order-independent stream the RRC sampler draws from."""
    c = np.asarray(counters, np.uint64)
    with np.errstate(over="ignore"):
        base = _mix64(c ^ _mix64(np.uint64(0xABCD) + np.uint64(seed)))
        js = (np.arange(1, n + 1, dtype=np.uint64)
              * np.uint64(0x9E3779B97F4A7C15))
        bits = _mix64(base[:, None] + js[None, :])
    return (bits >> np.uint64(11)).astype(np.float64) * (2.0 ** -53)


def rrc_params(seed: int, counters: np.ndarray, src_h: int, src_w: int,
               scale=(0.08, 1.0), ratio=(3.0 / 4.0, 4.0 / 3.0)):
    """Sample torchvision-protocol RandomResizedCrop rects + hflips for a
    batch: up to 10 attempts of (area uniform in scale*src_area, aspect
    log-uniform in ratio), first in-bounds attempt wins, center-crop
    fallback otherwise. Counter-based (see _counter_uniforms): a given
    (seed, counter) always yields the same rect, whatever batch/worker it
    lands in. Returns (ys, xs, hs, ws int32[B], flip uint8[B]); rects are
    guaranteed in-bounds with hs, ws >= 1.

    Sampling runs host-side in float64 numpy and is SHARED by the native
    and numpy execution paths — bit-exactness between them never depends
    on this function, only on the fixed-point resize."""
    b = len(counters)
    u = _counter_uniforms(seed, counters, 4 * _RRC_ATTEMPTS + 1)
    area = float(src_h * src_w)
    ua = u[:, 0:4 * _RRC_ATTEMPTS:4]            # [B, attempts]
    ur = u[:, 1:4 * _RRC_ATTEMPTS:4]
    uy = u[:, 2:4 * _RRC_ATTEMPTS:4]
    ux = u[:, 3:4 * _RRC_ATTEMPTS:4]
    target = area * (scale[0] + (scale[1] - scale[0]) * ua)
    log_r = np.log(ratio[0]) + (np.log(ratio[1]) - np.log(ratio[0])) * ur
    ar = np.exp(log_r)
    ws_c = np.round(np.sqrt(target * ar)).astype(np.int64)
    hs_c = np.round(np.sqrt(target / ar)).astype(np.int64)
    ok = (ws_c > 0) & (ws_c <= src_w) & (hs_c > 0) & (hs_c <= src_h)
    first = np.argmax(ok, axis=1)               # first valid attempt
    rows = np.arange(b)
    hs = hs_c[rows, first]
    ws = ws_c[rows, first]
    ys = np.floor(uy[rows, first] * (src_h - hs + 1)).astype(np.int64)
    xs = np.floor(ux[rows, first] * (src_w - ws + 1)).astype(np.int64)
    # Fallback (no attempt fit): torchvision's center crop at the nearest
    # in-range aspect ratio.
    none_ok = ~ok.any(axis=1)
    if none_ok.any():
        in_ratio = src_w / src_h
        if in_ratio < ratio[0]:
            fw, fh = src_w, min(int(round(src_w / ratio[0])), src_h)
        elif in_ratio > ratio[1]:
            fh, fw = src_h, min(int(round(src_h * ratio[1])), src_w)
        else:
            fw, fh = src_w, src_h
        hs = np.where(none_ok, fh, hs)
        ws = np.where(none_ok, fw, ws)
        ys = np.where(none_ok, (src_h - fh) // 2, ys)
        xs = np.where(none_ok, (src_w - fw) // 2, xs)
    hs = np.maximum(hs, 1)
    ws = np.maximum(ws, 1)
    flip = (u[:, 4 * _RRC_ATTEMPTS] < 0.5).astype(np.uint8)
    return (ys.astype(np.int32), xs.astype(np.int32),
            hs.astype(np.int32), ws.astype(np.int32), flip)


def _rrc_axis_tables(crop: int, out: int):
    """Fixed-point bilinear sampling tables for one axis (half-pixel
    convention, edge-clamped): (i0, i1, w0, w1), w0 + w1 == 1<<RRC_SHIFT.
    Integer expressions mirror native/loader.cpp psl_axis_tables exactly."""
    t = np.arange(out, dtype=np.int64)
    num = (2 * t + 1) * crop - out
    fp = np.where(num > 0, (num << RRC_SHIFT) // (2 * out), 0)
    i0 = fp >> RRC_SHIFT
    fr = fp & (_RRC_ONE - 1)
    at_edge = i0 >= crop - 1
    i0 = np.where(at_edge, crop - 1, i0)
    fr = np.where(at_edge, 0, fr)
    i1 = np.minimum(i0 + 1, crop - 1)
    return (i0.astype(np.int64), i1.astype(np.int64),
            (_RRC_ONE - fr).astype(np.int32), fr.astype(np.int32))


def _rrc_numpy(src, sel, ys, xs, hs, ws, flip, oh, ow, out):
    """Numpy reference for psl_rrc_batch: per-image vectorized separable
    fixed-point bilinear, int32 accumulation — bit-identical to the native
    kernel (same tables, same rounding, same flip-by-mirrored-tables)."""
    for i in range(len(sel)):
        ch, cw = int(hs[i]), int(ws[i])
        crop = src[sel[i], ys[i]:ys[i] + ch,
                   xs[i]:xs[i] + cw].astype(np.int32)
        xi0, xi1, wx0, wx1 = _rrc_axis_tables(cw, ow)
        if flip[i]:
            xi0, xi1 = xi0[::-1], xi1[::-1]
            wx0, wx1 = wx0[::-1], wx1[::-1]
        yi0, yi1, wy0, wy1 = _rrc_axis_tables(ch, oh)
        # Horizontal pass: [ch, ow, C] int32, values <= 255 << RRC_SHIFT.
        hbuf = (wx0[None, :, None] * crop[:, xi0]
                + wx1[None, :, None] * crop[:, xi1])
        v = (wy0[:, None, None].astype(np.int32) * hbuf[yi0]
             + wy1[:, None, None].astype(np.int32) * hbuf[yi1]
             + (1 << (2 * RRC_SHIFT - 1)))
        out[i] = (v >> (2 * RRC_SHIFT)).astype(np.uint8)
    return out


def rrc_batch(src: np.ndarray, sel: np.ndarray, ys, xs, hs, ws, flip,
              oh: int, ow: int,
              out: Optional[np.ndarray] = None) -> np.ndarray:
    """Execute sampled RRC rects: gather + crop + bilinear-resize + hflip
    in one pass. Native OpenMP kernel (GIL-released) when available and
    the batch is uint8/contiguous; bit-identical numpy otherwise."""
    b = len(sel)
    c = src.shape[-1]
    if out is None:
        out = np.empty((b, oh, ow, c), np.uint8)
    lib = _load_native_loader()
    if (lib is not None and src.dtype == np.uint8 and out.dtype == np.uint8
            and out.shape == (b, oh, ow, c)
            and src.flags.c_contiguous and out.flags.c_contiguous):
        sel64 = np.ascontiguousarray(sel, np.int64)
        ys32 = np.ascontiguousarray(ys, np.int32)
        xs32 = np.ascontiguousarray(xs, np.int32)
        hs32 = np.ascontiguousarray(hs, np.int32)
        ws32 = np.ascontiguousarray(ws, np.int32)
        fl8 = np.ascontiguousarray(flip, np.uint8)
        lib.psl_rrc_batch(
            src.ctypes.data, sel64.ctypes.data, ys32.ctypes.data,
            xs32.ctypes.data, hs32.ctypes.data, ws32.ctypes.data,
            fl8.ctypes.data, out.ctypes.data,
            b, src.shape[1], src.shape[2], c, oh, ow)
        return out
    if src.dtype != np.uint8:
        src = src.astype(np.uint8)  # contract: uint8 in, uint8 out
    return _rrc_numpy(src, sel, ys, xs, hs, ws, flip, oh, ow, out)


def random_resized_crop(src: np.ndarray, sel: np.ndarray,
                        counters: np.ndarray, seed: int, oh: int, ow: int,
                        scale=(0.08, 1.0), ratio=(3.0 / 4.0, 4.0 / 3.0),
                        out: Optional[np.ndarray] = None) -> np.ndarray:
    """Sample (counter-based) + execute RRC for a batch of source indices:
    src [N,SH,SW,C] uint8 -> [B,oh,ow,C] uint8."""
    ys, xs, hs, ws, flip = rrc_params(seed, counters, src.shape[1],
                                      src.shape[2], scale, ratio)
    return rrc_batch(src, sel, ys, xs, hs, ws, flip, oh, ow, out)


def center_crop(x: np.ndarray, oh: int, ow: int) -> np.ndarray:
    """Deterministic eval-path geometry for RRC datasets: plain center
    crop (storage is decode-sized >= output, e.g. 256 -> 224)."""
    h, w = x.shape[1], x.shape[2]
    if (h, w) == (oh, ow):
        return x
    y0, x0 = (h - oh) // 2, (w - ow) // 2
    return x[:, y0:y0 + oh, x0:x0 + ow]


# RRC-augmented datasets -> (scale range, aspect-ratio range). Output
# geometry comes from datasets.DATASET_SHAPES (the model-facing shape);
# storage is the decode-sized store (datasets._STORAGE_HW).
RRC_STACKS = {
    "ImageNet": ((0.08, 1.0), (3.0 / 4.0, 4.0 / 3.0)),
    "synthetic_imagenet_rrc": ((0.08, 1.0), (3.0 / 4.0, 4.0 / 3.0)),
}


# Crop-augmented datasets -> (pad, np.pad mode). The loader keys its
# pre-padded fast path off this table; augment_train uses the same values.
CROP_STACKS = {
    "Cifar10": (4, "reflect"),
    "Cifar100": (4, "reflect"),
    "synthetic_cifar10": (4, "reflect"),
    "SVHN": (4, "constant"),
}


def norm_constants_for(dataset: str):
    """(mean, std) of the host normalize stack, or None."""
    if dataset in ("MNIST", "Digits"):
        # Digits reuses MNIST's constants: same geometry/pipeline, and the
        # normalize is an affine preprocessing choice, not a dataset fact.
        return MNIST_MEAN, MNIST_STD
    if dataset in ("Cifar10", "Cifar100", "synthetic_cifar10"):
        return CIFAR_MEAN, CIFAR_STD
    if dataset == "SVHN":
        return SVHN_MEAN, SVHN_STD
    if dataset in ("ImageNet", "synthetic_imagenet_rrc"):
        # Standard ImageNet constants (the reference's Normalize stack).
        # Plain `synthetic_imagenet` intentionally stays None: it is the
        # augment-free set, and its loader stays on the bare gather
        # path.
        return IMAGENET_MEAN, IMAGENET_STD
    return None


def augment_train(x: np.ndarray, dataset: str, rng: np.random.Generator,
                  normalize_out: bool = True) -> np.ndarray:
    """Raw batch (uint8 [0,255] or float [0,1]), NHWC -> augmented batch.

    ``normalize_out=False`` skips normalization and keeps the storage dtype:
    the TPU-native contract where the jitted step normalizes in-graph
    (``device_norm_constants``) — the host ships 4x fewer bytes and the
    normalize rides the chip's spare VPU cycles instead of host numpy.

    ``synthetic_cifar10`` runs the full CIFAR augment stack on synthetic
    data — how the ResNet benchmark cells exercise the real hot path
    without dataset files."""
    crop = CROP_STACKS.get(dataset)
    ms = norm_constants_for(dataset)
    if crop is not None:
        x = _crop_flip(x, rng, *crop)
    if ms is None:
        return x.astype(np.float32)  # synthetic: no normalization constants
    return normalize(x, *ms) if normalize_out else x


def transform_test(x: np.ndarray, dataset: str,
                   normalize_out: bool = True) -> np.ndarray:
    ms = norm_constants_for(dataset)
    if ms is None:
        return x.astype(np.float32)
    return normalize(x, *ms) if normalize_out else x


def device_norm_constants(dataset: str):
    """Per-dataset (scale[C], shift[C]) such that
    ``normalized = raw * scale - shift`` reproduces the host ``normalize``
    uint8 path exactly (and the float path to float32 rounding, raw in
    [0,1] scaled by 255). None for datasets without normalization
    (plain synthetic). Used by the in-graph normalization in the jitted
    step (parallel/dp.make_loss_fn input_norm)."""
    ms = norm_constants_for(dataset)
    if ms is None:
        return None
    mean = np.asarray(ms[0], np.float32)
    std = np.asarray(ms[1], np.float32)
    return (1.0 / (255.0 * std)).astype(np.float32), (mean / std).astype(np.float32)


def input_norm_for(cfg):
    """TrainConfig -> in-graph normalization constants, or None when host
    normalization is in effect (cfg.device_normalize off, or a dataset
    without constants). The single switch every loader/step site keys off,
    so uint8 batches can never silently reach an un-normalizing step."""
    if not getattr(cfg, "device_normalize", False):
        return None
    return device_norm_constants(cfg.dataset)
