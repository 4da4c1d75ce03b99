"""Data pipeline tests: sharding disjointness (data-locality parity,
README.md:24), augmentation shapes/determinism, persistent next_batch."""

import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.data import DataLoader, prepare_data
from ps_pytorch_tpu.data.augment import augment_train, random_crop, transform_test


def test_prepare_data_synthetic():
    cfg = TrainConfig(dataset="synthetic", batch_size=64, test_batch_size=100)
    train, test = prepare_data(cfg)
    xb, yb = next(train.epoch(0))
    assert xb.shape == (64, 32, 32, 3) and xb.dtype == np.float32
    assert yb.shape == (64,) and yb.dtype == np.int32


def test_host_shards_disjoint():
    cfg = TrainConfig(dataset="synthetic", batch_size=64)
    x = np.arange(1000, dtype=np.float32)[:, None, None, None] * np.ones((1, 4, 4, 1), np.float32)
    y = np.arange(1000, dtype=np.int32)
    loaders = [DataLoader(x, y, 100, "synthetic", train=True, seed=7,
                          host_id=h, num_hosts=4) for h in range(4)]
    seen = [set(int(v) for _, yb in ld.epoch(0) for v in yb) for ld in loaders]
    for a in range(4):
        for b in range(a + 1, 4):
            assert not (seen[a] & seen[b]), "host shards overlap"
    assert len(set().union(*seen)) == 1000


def test_augment_cifar_shapes(rng):
    x = rng.random((8, 32, 32, 3), dtype=np.float32)
    out = augment_train(x, "Cifar10", np.random.default_rng(0))
    assert out.shape == x.shape and out.dtype == np.float32
    # Normalization applied: values leave [0,1].
    assert out.min() < 0


def test_random_crop_reflect_identity_possible(rng):
    x = rng.random((4, 8, 8, 1), dtype=np.float32)
    out = random_crop(x, np.random.default_rng(0), pad=2, mode="reflect")
    assert out.shape == x.shape


def test_random_crop_vectorized_matches_loop(rng):
    """The batched-gather crop must be bit-identical to a per-image loop
    with the same rng draws (same ys-then-xs order)."""
    x = rng.random((16, 32, 32, 3)).astype(np.float32)
    for mode in ("reflect", "constant"):
        out = random_crop(x, np.random.default_rng(7), pad=4, mode=mode)
        # Reference loop with identical draw order.
        r2 = np.random.default_rng(7)
        padded = np.pad(x, ((0, 0), (4, 4), (4, 4), (0, 0)), mode=mode)
        ys = r2.integers(0, 9, size=16)
        xs = r2.integers(0, 9, size=16)
        want = np.stack([padded[i, ys[i]:ys[i] + 32, xs[i]:xs[i] + 32]
                         for i in range(16)])
        np.testing.assert_array_equal(out, want)


def test_loader_throughput_probe():
    """The persistent loader keeps handing out whole augmented batches across
    epoch turnovers: shapes, dtypes and the labels' range, no rate."""
    from ps_pytorch_tpu.data.augment import input_norm_for
    from ps_pytorch_tpu.data.datasets import load_arrays
    cfg = TrainConfig(dataset="synthetic", network="ResNet18", batch_size=512)
    x, y = load_arrays(cfg.dataset, cfg.data_dir, train=True, seed=0)
    loader = DataLoader(x[:2048], y[:2048], cfg.batch_size, cfg.dataset, train=True,
                        seed=0,
                        device_normalize=input_norm_for(cfg) is not None)
    steps = 2 * len(loader) + 1          # crosses two epoch boundaries
    seen = 0
    for _ in range(steps):
        xb, yb = loader.next_batch()
        assert xb.shape == (512, 32, 32, 3) and yb.shape == (512,)
        assert yb.dtype == np.int32 and 0 <= yb.min() and yb.max() < 10
        seen += len(xb)
    assert seen == steps * 512


def test_uint8_normalize_matches_float_path():
    """normalize() uint8 fast path == float path to float32 rounding."""
    from ps_pytorch_tpu.data.augment import CIFAR_MEAN, CIFAR_STD, normalize
    xu = np.random.default_rng(0).integers(0, 256, (8, 32, 32, 3)).astype(np.uint8)
    a = normalize(xu, CIFAR_MEAN, CIFAR_STD)
    b = normalize(xu.astype(np.float32) / 255.0, CIFAR_MEAN, CIFAR_STD)
    assert np.allclose(a, b, atol=2e-6)


def test_device_normalize_loader_emits_uint8():
    """cfg.device_normalize (default True): loaders ship raw uint8; the
    in-graph constants reproduce the host normalize exactly."""
    from ps_pytorch_tpu.data.augment import device_norm_constants, normalize
    cfg = TrainConfig(dataset="synthetic_cifar10", batch_size=32,
                      test_batch_size=32)
    assert cfg.device_normalize
    train, test = prepare_data(cfg)
    xb, _ = next(train.epoch(0))
    assert xb.dtype == np.uint8
    xt, _ = next(test.epoch(0))
    assert xt.dtype == np.uint8
    scale, shift = device_norm_constants(cfg.dataset)
    from ps_pytorch_tpu.data.augment import CIFAR_MEAN, CIFAR_STD
    np.testing.assert_allclose(xt * scale - shift,
                               normalize(xt, CIFAR_MEAN, CIFAR_STD),
                               atol=1e-6)


def test_device_normalize_step_equivalence(mesh8):
    """A train step on raw uint8 with input_norm == the same step on
    host-normalized float input (same weights, same rng)."""
    import jax
    from ps_pytorch_tpu.data.augment import (
        CIFAR_MEAN, CIFAR_STD, device_norm_constants, normalize,
    )
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.optim import build_optimizer
    from ps_pytorch_tpu.parallel import create_train_state, make_train_step

    cfg = TrainConfig(dataset="synthetic_cifar10", network="LeNet",
                      batch_size=64, lr=0.05, compute_dtype="float32",
                      num_classes=10)
    model = build_model("LeNet", 10, "float32")
    rng = np.random.default_rng(0)
    xu = rng.integers(0, 256, (64, 32, 32, 3)).astype(np.uint8)
    y = rng.integers(0, 10, 64).astype(np.int32)
    mask = np.ones(8, np.float32)
    key = jax.random.PRNGKey(1)

    losses = {}
    for name, norm, x in [
        ("device", device_norm_constants(cfg.dataset), xu),
        ("host", None, normalize(xu, CIFAR_MEAN, CIFAR_STD)),
    ]:
        tx = build_optimizer(cfg)
        state = create_train_state(model, tx, mesh8, (1, 32, 32, 3),
                                   jax.random.key(0))
        step = make_train_step(model, tx, mesh8, state, donate=False,
                               input_norm=norm)
        _, m = step(state, np.asarray(x), y, mask, key)
        losses[name] = float(m["loss"])
    assert losses["device"] == pytest.approx(losses["host"], abs=1e-5)


def test_mnist_normalize_matches_reference():
    # util.py:24-27: Normalize((0.1307,), (0.3081,)).
    x = np.full((1, 28, 28, 1), 0.1307, np.float32)
    out = transform_test(x, "MNIST")
    np.testing.assert_allclose(out, 0.0, atol=1e-6)


def test_next_batch_advances_epochs():
    cfg = TrainConfig(dataset="synthetic", batch_size=25000)
    train, _ = prepare_data(cfg)
    n = len(train)
    assert n == 2
    for _ in range(5):  # crosses epoch boundaries without StopIteration
        xb, yb = train.next_batch()
        assert xb.shape[0] == 25000


def test_native_loader_bit_identical():
    """The C++ crop+flip kernel (native/loader.cpp) must produce exactly the
    numpy fallback's batches for the same rng state — same ys/xs/flip draw
    order, same strided-copy semantics (flip included)."""
    from ps_pytorch_tpu.data import augment
    rng = np.random.default_rng(0)
    P = rng.integers(0, 256, size=(500, 40, 40, 3), dtype=np.uint8)
    sel = rng.integers(0, 500, 256)
    lib = augment._load_native_loader()
    if lib is None:
        import pytest
        pytest.skip("native loader unavailable and unbuildable")
    r1, r2 = np.random.default_rng(5), np.random.default_rng(5)
    native = augment.crop_flip_prepadded(P, sel, r1, 32, 32)
    augment._loader_lib = None
    try:
        fallback = augment.crop_flip_prepadded(P, sel, r2, 32, 32)
    finally:
        augment._loader_lib = lib
    np.testing.assert_array_equal(native, fallback)
    assert native.flags.c_contiguous


def test_shard_smaller_than_batch_rejected():
    import pytest
    x = np.zeros((100, 4, 4, 1), np.float32)
    y = np.zeros(100, np.int32)
    with pytest.raises(ValueError):
        DataLoader(x, y, batch_size=2048, num_hosts=8)
