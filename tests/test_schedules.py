"""LR schedules (optim/schedules.py) and their TrainConfig/optimizer wiring
(VERDICT r1 item 7; reference surface: a constant lr grid-swept by
``tune.sh:1-36``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.optim import build_optimizer
from ps_pytorch_tpu.optim.schedules import (
    build_schedule, cosine, step_decay, with_warmup,
)


def _at(sched, step):
    v = sched(jnp.asarray(step)) if callable(sched) else sched
    return float(v)


def test_step_decay_staircase():
    s = step_decay(0.1, decay_steps=10, gamma=0.5)
    assert _at(s, 0) == pytest.approx(0.1)
    assert _at(s, 9) == pytest.approx(0.1)
    assert _at(s, 10) == pytest.approx(0.05)
    assert _at(s, 25) == pytest.approx(0.025)


def test_cosine_endpoints_and_floor():
    s = cosine(0.2, total_steps=100, floor_factor=0.1)
    assert _at(s, 0) == pytest.approx(0.2)
    assert _at(s, 50) == pytest.approx((0.2 + 0.02) / 2)
    assert _at(s, 100) == pytest.approx(0.02)
    assert _at(s, 500) == pytest.approx(0.02)  # flat after horizon


def test_warmup_prefix_then_base():
    s = with_warmup(0.1, warmup_steps=5)
    # Linear ramp: (step+1)/5 * 0.1.
    assert _at(s, 0) == pytest.approx(0.02)
    assert _at(s, 4) == pytest.approx(0.1)
    assert _at(s, 17) == pytest.approx(0.1)
    # Warmup shifts a decaying base so decay starts AFTER the ramp.
    s2 = with_warmup(step_decay(0.1, 10, 0.5), warmup_steps=5)
    assert _at(s2, 14) == pytest.approx(0.1)     # base step 9 < 10
    assert _at(s2, 15) == pytest.approx(0.05)    # base step 10


def test_build_schedule_from_config():
    cfg = TrainConfig(lr=0.1, lr_schedule="constant")
    assert build_schedule(cfg) == 0.1
    cfg = TrainConfig(lr=0.1, lr_schedule="cosine", max_steps=40,
                      lr_decay_factor=0.0)
    s = build_schedule(cfg)
    assert _at(s, 40) == pytest.approx(0.0, abs=1e-9)
    with pytest.raises(ValueError):
        TrainConfig(lr_schedule="linear")


def test_scheduled_sgd_updates_shrink():
    """With a decaying schedule, later update magnitudes must shrink under
    constant gradients — through the real build_optimizer wiring."""
    cfg = TrainConfig(lr=0.5, lr_schedule="step", lr_decay_steps=2,
                      lr_decay_factor=0.1, momentum=0.0)
    tx = build_optimizer(cfg)
    params = {"w": jnp.ones((8,), jnp.float32)}
    state = tx.init(params)
    grads = {"w": jnp.ones((8,), jnp.float32)}
    deltas = []
    from ps_pytorch_tpu.parallel.dp import apply_optimizer
    for _ in range(4):
        new_params, state = apply_optimizer(tx, params, state, grads)
        deltas.append(float(jnp.abs(new_params["w"] - params["w"]).max()))
        params = new_params
    assert deltas[0] == pytest.approx(0.5)
    assert deltas[1] == pytest.approx(0.5)
    assert deltas[2] == pytest.approx(0.05)   # decayed at step 2
    assert deltas[3] == pytest.approx(0.05)


def test_trainer_accepts_schedule_end_to_end(tmp_path):
    """CLI surface: a cosine+warmup LeNet run through the Trainer must work
    and keep the STEP schema intact."""
    from ps_pytorch_tpu.runtime import Trainer

    cfg = TrainConfig(dataset="synthetic_mnist", network="LeNet",
                      batch_size=64, lr=0.1, lr_schedule="cosine",
                      lr_warmup_steps=2, max_steps=6, eval_freq=0,
                      compute_dtype="float32",
                      train_dir=str(tmp_path / "ckpt"), resume=False,
                      log_every=100)
    t = Trainer(cfg)
    state = t.train()
    assert int(state.step[()] if hasattr(state.step, "__getitem__")
               else state.step) == 6


@pytest.mark.parametrize("s,rows,norm_rows", [(16384, 2048, 512),
                                              (1000, 1008, 512),
                                              (40, 48, 48)])
def test_the_mamba2_mixers_kernel_record_names_every_field(s, rows,
                                                           norm_rows):
    """The other kind of schedule: the static record of a kernel's tiles that
    ``LMTrainer`` prints on its ``KERNELS`` line. ``ssm_mix_schedule``'s
    (``ops/ssm_mix.py``, PR 45) says every field as ``name=value``, a grid
    as ``axbxc``, and its rows a step follow the sequence: 2048 (the norm's
    512, at four lane tiles a group), or a short sequence whole, in blocks of
    the 16 halo rows."""
    from ps_pytorch_tpu.ops.ssm_mix import ssm_mix_schedule
    sc = ssm_mix_schedule(2, s, 4096, 1024, 8, 4)
    assert (sc.rows, sc.norm_rows) == (rows, norm_rows)
    assert sc.rows % sc.halo == 0 and sc.rows % sc.chunk == 0 \
        and sc.norm_rows % sc.norm_chunk == 0
    said = dict(item.split("=") for item in sc.describe().split())
    assert list(said) == list(sc._fields)
    for name, value in sc._asdict().items():
        assert said[name] == ("x".join(map(str, value))
                              if isinstance(value, tuple) else str(value))
