"""Sharded weight update (parallel/zero.py) vs the replicated-update path.

The optimizer math is elementwise, so updating per-replica slices then
all-gathering must reproduce the replicated update bit-for-bit (modulo float
reassociation in the reduce) — for SGD+momentum+wd+nesterov and Adam, with
K-of-N masks and the all-zero-mask no-op guard.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _setup(mesh8, optimizer, fused=False, network="LeNet"):
    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.optim import build_optimizer

    cfg = TrainConfig(dataset="synthetic_mnist", network=network,
                      batch_size=64, lr=0.1, momentum=0.9, weight_decay=1e-4,
                      nesterov=True, optimizer=optimizer,
                      compute_dtype="float32")
    model = build_model(cfg.network, cfg.num_classes, cfg.compute_dtype)
    tx = build_optimizer(cfg)
    return cfg, model, tx


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_zero_matches_replicated_update(mesh8, rng, optimizer):
    from ps_pytorch_tpu.parallel import create_train_state, make_train_step
    from ps_pytorch_tpu.parallel.zero import (
        create_zero_train_state, make_zero_train_step,
    )

    cfg, model, tx = _setup(mesh8, optimizer)
    x = jnp.asarray(rng.normal(size=(64, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, 64).astype(np.int32))
    mask = jnp.asarray(np.array([1, 1, 1, 1, 1, 1, 1, 0], np.float32))

    s_dp = create_train_state(model, tx, mesh8, (1, 28, 28, 1), jax.random.key(0))
    s_z = create_zero_train_state(model, tx, mesh8, (1, 28, 28, 1), jax.random.key(0))
    step_dp = make_train_step(model, tx, mesh8, s_dp, donate=False)
    step_z = make_zero_train_step(model, tx, mesh8, s_z, donate=False)

    for i in range(3):
        s_dp, m_dp = step_dp(s_dp, x, y, mask, jax.random.key(i))
        s_z, m_z = step_z(s_z, x, y, mask, jax.random.key(i))
    assert float(m_dp["loss"]) == pytest.approx(float(m_z["loss"]), abs=1e-5)
    assert float(m_z["participating"]) == 7.0
    for a, b in zip(jax.tree.leaves(s_dp.params), jax.tree.leaves(s_z.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_zero_opt_state_is_sharded(mesh8):
    from ps_pytorch_tpu.parallel.zero import create_zero_train_state
    from ps_pytorch_tpu.optim import sgd

    from ps_pytorch_tpu.models import build_model
    model = build_model("LeNet", 10, jnp.float32)
    tx = sgd(lr=0.1, momentum=0.9)
    s = create_zero_train_state(model, tx, mesh8, (1, 28, 28, 1),
                                jax.random.key(0))
    n_params = sum(p.size for p in jax.tree.leaves(s.params))
    mom = s.opt_state.momentum
    # Global buffer is [n, chunk]; each replica materializes 1/n of it.
    assert mom.shape[0] == 8
    assert mom.shape[1] == -(-n_params // 8)
    assert mom.sharding.spec[0] == "data"


def test_zero_all_masked_is_noop(mesh8, rng):
    from ps_pytorch_tpu.parallel.zero import (
        create_zero_train_state, make_zero_train_step,
    )

    cfg, model, tx = _setup(mesh8, "sgd")
    x = jnp.asarray(rng.normal(size=(64, 28, 28, 1)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, 64).astype(np.int32))
    s = create_zero_train_state(model, tx, mesh8, (1, 28, 28, 1), jax.random.key(0))
    step = make_zero_train_step(model, tx, mesh8, s, donate=False)
    s2, m = step(s, x, y, jnp.zeros(8, jnp.float32), jax.random.key(0))
    for a, b in zip(jax.tree.leaves(s.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
