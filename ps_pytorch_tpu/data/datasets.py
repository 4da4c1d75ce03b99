"""Dataset registry + sharded prefetching loader.

Replaces the reference's ``prepare_data`` (``util.py:21-106``) and its vendored
multiprocess DataLoader (``data_loader_ops/my_data_loader.py``). Design
differences, TPU-first:

- Whole datasets are materialized once as numpy arrays (MNIST/CIFAR fit in
  RAM); per-epoch shuffling + augmentation are vectorized numpy, overlapped
  with device compute by a background prefetch thread — no worker processes.
- Per-host sharding: each host shuffles with a shared seed and takes its
  contiguous slice, preserving the reference's data-locality property (workers
  never exchange raw data, README.md:24).
- A ``synthetic`` dataset (shape-compatible with CIFAR/MNIST) backs tests and
  throughput benches with zero I/O.

Real datasets load through the self-contained parsers in ``vision_io.py``
(MNIST IDX, CIFAR pickle batches, SVHN .mat, sklearn-bundled Digits) when
the files are already on disk (``data_prepare.py`` pre-download contract);
downloads are attempted only when ``download=True``.
"""

import os
import queue
import threading
from typing import Iterator, Optional, Tuple

import numpy as np

from ps_pytorch_tpu.data import augment

# dataset -> (H, W, C, num_classes, train_size_hint)
DATASET_SHAPES = {
    "MNIST": (28, 28, 1, 10, 60000),
    # Real handwritten-digit scans bundled with scikit-learn (UCI digits),
    # upsampled to MNIST geometry — the real-data accuracy oracle for
    # zero-egress environments (data/vision_io.load_digits28).
    "Digits": (28, 28, 1, 10, 1437),
    "Cifar10": (32, 32, 3, 10, 50000),
    "Cifar100": (32, 32, 3, 100, 50000),
    "SVHN": (32, 32, 3, 10, 73257),
    "synthetic": (32, 32, 3, 10, 50000),
    "synthetic_mnist": (28, 28, 1, 10, 60000),
    # Synthetic data run through the REAL CIFAR augment stack (pad/crop/
    # flip/normalize) — for loader-throughput benches without dataset files.
    "synthetic_cifar10": (32, 32, 3, 10, 50000),
    # CIFAR-100-shaped synthetic set: the 100-class head matters for the
    # vgg11_cifar100 bench config (BASELINE.json config 4) — the plain
    # "synthetic" set has 10 classes and would silently bench the wrong task.
    "synthetic_cifar100": (32, 32, 3, 100, 50000),
    # ImageNet-shaped synthetic set for the ResNet-50 at-scale config
    # (BASELINE.json config 5); small N — it exists to exercise 224px
    # shapes/throughput, not to be learned.
    "synthetic_imagenet": (224, 224, 3, 1000, 512),
    # ImageNet-geometry set with the REAL augment pipeline: decode-sized
    # 256px uint8 storage (_STORAGE_HW) run through random-resized-crop ->
    # bilinear 224 -> hflip (augment.RRC_STACKS) on every train batch.
    # The model-facing shape below is the RRC OUTPUT; the plain
    # `synthetic_imagenet` row keeps measuring the augment-free gather.
    "synthetic_imagenet_rrc": (224, 224, 3, 1000, 512),
}

# Datasets whose ON-DISK/IN-RAM storage geometry differs from the
# model-facing shape in DATASET_SHAPES: RRC datasets store decode-sized
# images and the loader's augment (train) / center-crop (eval) produces
# the model shape. ImageNet convention: 256px short-side storage.
_STORAGE_HW = {
    "ImageNet": (256, 256),
    "synthetic_imagenet_rrc": (256, 256),
}


def sample_shape(dataset: str) -> Tuple[int, int, int]:
    """(H, W, C) of one example — the model-init template shape."""
    h, w, c, _, _ = DATASET_SHAPES[dataset]
    return (h, w, c)


def _load_files(name: str, root: str, train: bool, download: bool):
    """Load a real dataset from its standard on-disk files (data/vision_io
    parsers — torchvision is not a dependency). ``download=True`` fetches
    the files first via tools/data_prepare's mirror list; training never
    downloads (reference util.py keeps download=False for workers)."""
    from ps_pytorch_tpu.data import vision_io

    if download and name != "Digits":
        from ps_pytorch_tpu.tools.data_prepare import ensure_downloaded
        ensure_downloaded(name, root)
    if name == "MNIST":
        x, y = vision_io.load_mnist(root, train)
    elif name == "Cifar10":
        x, y = vision_io.load_cifar10(root, train)
    elif name == "Cifar100":
        x, y = vision_io.load_cifar100(root, train)
    elif name == "SVHN":
        x, y = vision_io.load_svhn(root, train)
    elif name == "Digits":
        x, y = vision_io.load_digits28(train)
    else:
        raise ValueError(name)
    # Keep raw uint8: 4x fewer bytes through the shuffle/pad/crop hot path;
    # the augment stack folds /255 into its fused normalize.
    return x.astype(np.uint8, copy=False), y.astype(np.int32)


def _synthetic(name: str, train: bool, seed: int = 0):
    h, w, c, ncls, n = DATASET_SHAPES[name]
    h, w = _STORAGE_HW.get(name, (h, w))   # RRC sets store decode-sized
    if not train:
        # Test split ~1/6 of train with a floor, but never bigger than the
        # train hint (keeps large-image synthetic sets memory-bounded).
        n = max(n // 6, min(1000, n))
    rng = np.random.default_rng(seed + (0 if train else 1))
    # Class-dependent means make the task learnable -> convergence tests work.
    y = rng.integers(0, ncls, size=n).astype(np.int32)
    x = rng.normal(0.5, 0.25, size=(n, h, w, c)).astype(np.float32)
    x += (y[:, None, None, None].astype(np.float32) / ncls - 0.5) * 0.5
    x = np.clip(x, 0.0, 1.0)
    if name == "synthetic_cifar10" or name in augment.RRC_STACKS:
        # Mimic the real pipeline end to end: uint8 storage + the full
        # augment stack (loader-throughput bench fidelity).
        x = (x * 255.0).astype(np.uint8)
    return x, y


def load_arrays(dataset: str, data_dir: str = "./data", train: bool = True,
                download: bool = False, seed: int = 0):
    """-> (x [N,H,W,C] float32 in [0,1], y [N] int32), unnormalized."""
    if dataset.startswith("synthetic"):
        return _synthetic(dataset, train, seed)
    return _load_files(dataset, data_dir, train, download)


# Shared pre-padded stores: multi-slice/async trainers build one DataLoader
# per slice over the SAME train arrays; without sharing, each would hold its
# own ~240 MB padded copy and repeat the ~1.3 s pad. Keyed by source-array
# identity + pad geometry. Entries hold a STRONG reference to the source and
# every hit checks `is` — numpy arrays are not weakref-able, and an id-keyed
# cache without the live reference could return stale data after id reuse.
# Tiny LRU bound: a process handles a handful of datasets at most.
_PADDED_CACHE: "dict" = {}          # (id, pad, mode) -> (source, padded)
_PADDED_LOCK = threading.Lock()
_PADDED_CAP = 4


def _prepad_shared(x: np.ndarray, pad: int, mode: str) -> np.ndarray:
    key = (id(x), pad, mode)
    with _PADDED_LOCK:
        hit = _PADDED_CACHE.get(key)
        if hit is not None and hit[0] is x:
            return hit[1]
    padded = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode=mode)
    with _PADDED_LOCK:
        _PADDED_CACHE[key] = (x, padded)
        while len(_PADDED_CACHE) > _PADDED_CAP:  # evict oldest insertion
            _PADDED_CACHE.pop(next(iter(_PADDED_CACHE)))
    return padded


class DataLoader:
    """Sharded, shuffled, augmented, prefetching batch iterator.

    Equivalent in role to the reference's vendored DataLoader
    (``my_data_loader.py:254-319``) including its persistent-iterator
    ``next_batch`` accessor, but thread+numpy based.

    ``workers`` > 1 assembles batches on a thread pool (the hot paths —
    native crop/RRC kernels and numpy gathers — release or don't hold the
    GIL) with a bounded in-flight window and in-order delivery; 0 means
    one worker per CPU. RRC augmentation is bit-identical at ANY worker
    count (counter-based rects, augment.rrc_params); crop/flip datasets
    switch from one sequential rng stream to per-batch derived streams
    when workers > 1, so their draws differ from the single-worker path
    (still deterministic in (seed, epoch, host, batch)).
    """

    def __init__(self, x: np.ndarray, y: np.ndarray, batch_size: int,
                 dataset: str = "synthetic", train: bool = True,
                 shuffle: Optional[bool] = None, seed: int = 0,
                 host_id: int = 0, num_hosts: int = 1, prefetch: int = 2,
                 drop_last: bool = True, device_normalize: bool = False,
                 workers: int = 1):
        assert len(x) == len(y)
        self.x, self.y = x, y
        self.dataset = dataset
        self.train = train
        # device_normalize: emit raw (uint8) batches; the jitted step
        # normalizes in-graph (augment.device_norm_constants) — 4x less
        # host->device traffic and no host normalize pass.
        self.device_normalize = device_normalize
        self.shuffle = train if shuffle is None else shuffle
        self.seed = seed
        self.host_id, self.num_hosts = host_id, num_hosts
        self.prefetch = prefetch
        self.drop_last = drop_last
        self.workers = max(1, workers if workers > 0
                           else (os.cpu_count() or 1))
        if batch_size % num_hosts != 0:
            raise ValueError(f"global batch {batch_size} not divisible by {num_hosts} hosts")
        self.local_batch = batch_size // num_hosts
        shard = len(x) // num_hosts
        self.shard_size = shard
        if drop_last and shard < self.local_batch:
            raise ValueError(
                f"per-host shard ({shard} samples) smaller than local batch "
                f"({self.local_batch}); next_batch would never yield")
        # Pre-padded fast path for crop-augmented train data: pad the WHOLE
        # set once (CIFAR-sized: ~1.3 s, 240 MB host RAM), then each batch
        # is one strided copy per image straight from the padded store —
        # shuffle-gather + pad + crop collapse into a single pass (+71%
        # loader throughput at b=1024; numbers in augment.crop_flip_prepadded).
        self._padded = None
        if train and dataset in augment.CROP_STACKS:
            pad, mode = augment.CROP_STACKS[dataset]
            self._padded = _prepad_shared(x, pad, mode)
        # RRC datasets: storage is decode-sized (e.g. 256px), the loader
        # produces the model-facing shape — RRC on train batches,
        # deterministic center crop on eval batches.
        self._rrc = augment.RRC_STACKS.get(dataset) if train else None
        if dataset in DATASET_SHAPES:
            self._out_h, self._out_w, _ = sample_shape(dataset)
        else:
            self._out_h, self._out_w = x.shape[1], x.shape[2]
        self._epoch_iter = None
        self._epoch = 0

    def __len__(self):
        n = self.shard_size // self.local_batch
        if not self.drop_last and self.shard_size % self.local_batch:
            n += 1
        return n

    def _epoch_order(self, epoch: int) -> np.ndarray:
        # Shared-seed shuffle; each host slices its shard -> disjoint coverage.
        idx = np.arange(len(self.x))
        if self.shuffle:
            np.random.default_rng((self.seed, epoch)).shuffle(idx)
        lo = self.host_id * self.shard_size
        return idx[lo:lo + self.shard_size]

    def _assemble(self, b: int, order: np.ndarray, epoch: int,
                  aug_rng) -> Tuple[np.ndarray, np.ndarray]:
        """Assemble local batch ``b`` of one epoch — the unit of work both
        the single prefetch thread and the worker pool run."""
        sel = order[b * self.local_batch:(b + 1) * self.local_batch]
        norm_out = not self.device_normalize
        if self._rrc is not None:
            # ImageNet-geometry RRC straight from the decode-sized store.
            # The rect/flip rng is COUNTER-based: counter = epoch * N + sel
            # depends only on (epoch, sample), so any worker producing any
            # batch yields the same bytes — no rng stream to sequence.
            scale, ratio = self._rrc
            counters = (np.uint64(epoch) * np.uint64(len(self.x))
                        + sel.astype(np.uint64))
            xb = augment.random_resized_crop(
                self.x, sel, counters, self.seed,
                self._out_h, self._out_w, scale, ratio)
            if norm_out:
                mean_std = augment.norm_constants_for(self.dataset)
                if mean_std is not None:
                    xb = augment.normalize(xb, *mean_std)
        elif self._padded is not None:
            # One-pass gather+crop+flip from the pre-padded store;
            # bit-identical to the composed path for a given aug_rng state
            # (same draw order).
            xb = augment.crop_flip_prepadded(
                self._padded, sel, aug_rng, self._out_h, self._out_w)
            if norm_out:
                mean_std = augment.norm_constants_for(self.dataset)
                if mean_std is not None:
                    xb = augment.normalize(xb, *mean_std)
        elif self.train:
            xb = augment.augment_train(self.x[sel], self.dataset, aug_rng,
                                       normalize_out=norm_out)
        else:
            xb = self.x[sel]
            if self.dataset in augment.RRC_STACKS:
                # Eval geometry for RRC datasets: deterministic center crop
                # from the decode-sized store to the model shape.
                xb = augment.center_crop(xb, self._out_h, self._out_w)
            xb = augment.transform_test(xb, self.dataset,
                                        normalize_out=norm_out)
        return xb, self.y[sel]

    def epoch(self, epoch: int) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Yield (x, y) local batches for one epoch, prefetched."""
        order = self._epoch_order(epoch)
        n = len(self)
        if self.workers > 1:
            yield from self._epoch_pool(order, epoch, n)
            return
        aug_rng = np.random.default_rng((self.seed, epoch, self.host_id, 7))
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        abandoned = threading.Event()

        def _put(item) -> bool:
            # Bounded put that gives up if the consumer went away, so an
            # abandoned generator doesn't leak a blocked producer thread.
            while not abandoned.is_set():
                try:
                    q.put(item, timeout=0.5)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            try:
                for b in range(n):
                    if not _put(self._assemble(b, order, epoch, aug_rng)):
                        return
                _put(None)
            except BaseException as e:  # propagate into the consumer
                _put(e)

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is None:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            abandoned.set()

    def _epoch_pool(self, order: np.ndarray, epoch: int,
                    n: int) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Multi-worker epoch: ``workers`` threads claim batch indices from
        a shared counter, assemble concurrently (the kernels drop the GIL),
        and park results in a completed-batch buffer the consumer drains IN
        ORDER. The claim window is bounded (double buffering generalized:
        at most prefetch + workers batches live beyond the consumer), so a
        slow consumer can't make the pool run ahead unboundedly. Worker
        exceptions propagate to the consumer; abandoning the generator
        (early exit) releases all workers promptly."""
        window = self.prefetch + self.workers
        cv = threading.Condition()
        state = {"claim": 0, "emit": 0, "abandoned": False, "error": None}
        done: dict = {}

        def work():
            while True:
                with cv:
                    while (state["claim"] - state["emit"] >= window
                           and not state["abandoned"]
                           and state["error"] is None):
                        cv.wait()
                    if (state["abandoned"] or state["error"] is not None
                            or state["claim"] >= n):
                        return
                    b = state["claim"]
                    state["claim"] += 1
                try:
                    # Per-batch derived stream: any worker can produce any
                    # batch without coordinating rng state. (The RRC path
                    # ignores this rng entirely — counters cover it.)
                    rng = np.random.default_rng(
                        (self.seed, epoch, self.host_id, 7, b))
                    item = self._assemble(b, order, epoch, rng)
                except BaseException as e:
                    with cv:
                        state["error"] = e
                        cv.notify_all()
                    return
                with cv:
                    done[b] = item
                    cv.notify_all()

        threads = [threading.Thread(target=work, daemon=True)
                   for _ in range(min(self.workers, max(n, 1)))]
        for t in threads:
            t.start()
        try:
            for b in range(n):
                with cv:
                    while b not in done and state["error"] is None:
                        cv.wait()
                    if state["error"] is not None:
                        raise state["error"]
                    item = done.pop(b)
                    state["emit"] = b + 1
                    cv.notify_all()
                yield item
        finally:
            with cv:
                state["abandoned"] = True
                cv.notify_all()
            for t in threads:
                t.join(timeout=5.0)

    def next_batch(self):
        """Persistent-iterator accessor (reference ``my_data_loader.py:310-319``):
        yields forever, advancing epochs as needed."""
        while True:
            if self._epoch_iter is None:
                self._epoch_iter = self.epoch(self._epoch)
            try:
                return next(self._epoch_iter)
            except StopIteration:
                self._epoch += 1
                self._epoch_iter = None

    def fast_forward(self, n_batches: int) -> None:
        """Position the stream as if ``n_batches`` had already been drawn —
        the resume-determinism contract: a run restored at step k must see
        the SAME batch at step k+1 an uninterrupted run would. Epochs are
        seeked directly (shuffle order is a pure function of the epoch
        index); the remainder is consumed batch-by-batch so the
        augmentation rng stream stays sequence-aligned."""
        per_epoch = len(self)
        if n_batches <= 0 or per_epoch <= 0:
            return
        self._epoch = n_batches // per_epoch
        self._epoch_iter = None
        for _ in range(n_batches % per_epoch):
            self.next_batch()


def prepare_data(cfg, host_id: int = 0, num_hosts: int = 1,
                 download: bool = False) -> Tuple[DataLoader, DataLoader]:
    """Config -> (train_loader, test_loader). Reference: ``util.py:21-106``.

    When cfg.device_normalize is on (and the dataset has normalization
    constants), loaders emit raw uint8 and the jitted steps normalize
    in-graph — the single cfg switch keeps loaders and steps consistent."""
    from ps_pytorch_tpu.data.augment import input_norm_for
    dev_norm = input_norm_for(cfg) is not None
    xtr, ytr = load_arrays(cfg.dataset, cfg.data_dir, train=True,
                           download=download, seed=cfg.seed)
    xte, yte = load_arrays(cfg.dataset, cfg.data_dir, train=False,
                           download=download, seed=cfg.seed)
    train = DataLoader(xtr, ytr, cfg.batch_size, cfg.dataset, train=True,
                       seed=cfg.seed, host_id=host_id, num_hosts=num_hosts,
                       device_normalize=dev_norm,
                       workers=getattr(cfg, "loader_workers", 1))
    # Eval batches skip augmentation — the single prefetch thread keeps up.
    test = DataLoader(xte, yte, cfg.test_batch_size, cfg.dataset, train=False,
                      shuffle=False, seed=cfg.seed, drop_last=False,
                      device_normalize=dev_norm)
    return train, test
