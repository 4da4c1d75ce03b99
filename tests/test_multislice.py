"""Multi-slice async/stale-gradient training (runtime/multislice.py) on the
8-device CPU mesh split into 2x4-device slices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig


def _cfg(**kw):
    base = dict(dataset="synthetic_mnist", network="LeNet", batch_size=64,
                lr=0.05, momentum=0.9, compute_dtype="float32", mode="async",
                max_steps=10, eval_freq=0, log_every=100)
    base.update(kw)
    return TrainConfig(**base)


def test_sync_rate_slices_all_contribute():
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    t = MultiSliceTrainer(_cfg(), n_slices=2)
    info = t.tick()
    assert info["computed"] == [0, 1]
    assert sorted(info["used"]) == [0, 1]
    assert t.applied == 1


def test_per_slice_data_disjoint_by_construction():
    """Slices shard the dataset like hosts do (shared-seed shuffle, disjoint
    contiguous slices) — coverage must not depend on tick scheduling
    (round-1 weak item 6)."""
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    t = MultiSliceTrainer(_cfg(), n_slices=2)
    o0 = t.train_loaders[0]._epoch_order(0)
    o1 = t.train_loaders[1]._epoch_order(0)
    assert set(o0).isdisjoint(o1)
    assert t.train_loaders[0].local_batch == t.cfg.batch_size


def test_slow_slice_submits_stale_but_fresh_enough():
    """Slice 1 runs at half rate and re-fetches weights every 2 of its own
    steps: its contributions are stale (version < step-1) yet within
    staleness_limit, so they are used, not dropped."""
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    t = MultiSliceTrainer(_cfg(staleness_limit=4), n_slices=2,
                          slice_periods=[1, 2], fetch_every=2)
    used_counts = {0: 0, 1: 0}
    for _ in range(8):
        info = t.tick()
        for s in info["used"]:
            used_counts[s] += 1
    assert used_counts[0] == 8           # fast slice contributes every tick
    assert used_counts[1] >= 3           # slow slice still participates
    assert t.dropped_stale == 0
    assert t.applied == 8


def test_update_when_slice0_not_contributing():
    """Regression (r3 review): with slice 0 SLOW (periods=[2,1]), tick 2's
    pool holds only slice 1's gradient, which lives on slice 1's devices —
    the canonical update must realign it to the canonical params' placement
    instead of failing with incompatible devices."""
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    t = MultiSliceTrainer(_cfg(), n_slices=2, slice_periods=[2, 1])
    t.tick()                      # both compute (step 1)
    info = t.tick()               # only slice 1 computes and is pooled
    assert info["computed"] == [1]
    assert t.applied == 2         # the slice-1-only update applied fine


def test_too_stale_contributions_dropped():
    """staleness_limit=0 + a slice that only fetches every 4 steps: its
    stale gradients must be dropped, and training continues on the rest."""
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    t = MultiSliceTrainer(_cfg(staleness_limit=0), n_slices=2,
                          slice_periods=[1, 1], fetch_every=4)
    for _ in range(8):
        t.tick()
    # fetch_every=4 => 3 of each 4 submissions are computed on old weights
    # and staleness_limit=0 rejects them.
    assert t.dropped_stale > 0
    assert t.applied > 0


def test_async_training_reduces_loss():
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    # lr tuned for the mixed-rate schedule: the synthetic task's weak signal
    # blows up at higher lr (a task pathology, see the verify skill notes).
    cfg = _cfg(lr=0.02, batch_size=256, max_steps=60, staleness_limit=4)
    t = MultiSliceTrainer(cfg, n_slices=2, slice_periods=[1, 2])
    t.train(max_steps=60)
    r = t.evaluate(max_batches=2)
    # Stale gradients from the half-rate slice slow but must not prevent
    # learning; chance prec5 is 0.5 for 10 classes.
    assert r["prec5"] > 0.7, r
    assert t.applied >= 50


def test_async_cli_mode(tmp_path):
    """train.py --mode async end-to-end."""
    import subprocess, sys, os
    from pathlib import Path
    REPO = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="8")
    out = subprocess.run(
        [sys.executable, str(REPO / "train.py"), "--mode", "async",
         "--async-slices", "2", "--network", "LeNet", "--dataset",
         "synthetic_mnist", "--batch-size", "64", "--max-steps", "6",
         "--eval-freq", "0", "--resume", "false",
         "--compute-dtype", "float32", "--log-every", "1"],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SLICES 2 x 4 devices" in out.stdout
    assert "FINAL" in out.stdout


def test_async_checkpoint_and_resume(tmp_path):
    """Async mode checkpoints the canonical params and resumes from them."""
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    cfg = _cfg(max_steps=6, eval_freq=3, train_dir=str(tmp_path), resume=True)
    t = MultiSliceTrainer(cfg, n_slices=2)
    t.train()
    assert (tmp_path / "model_step_6").is_dir()
    p_end = jax.device_get(t.params)

    t2 = MultiSliceTrainer(cfg.replace(max_steps=9), n_slices=2)
    assert t2.maybe_resume() and t2.step == 6
    for a, b in zip(jax.tree.leaves(p_end), jax.tree.leaves(jax.device_get(t2.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    t2.train()
    assert t2.step == 9


def test_async_resume_falls_back_past_corrupt_checkpoint(tmp_path):
    """Manifest verification on the async resume path: a bit-flipped replica
    payload in the NEWEST checkpoint must not be silently restored — resume
    walks back to the older checkpoint that still verifies."""
    import os
    from ps_pytorch_tpu.resilience.faults import corrupt_file
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    cfg = _cfg(max_steps=6, eval_freq=3, train_dir=str(tmp_path), resume=True)
    MultiSliceTrainer(cfg, n_slices=2).train()
    assert (tmp_path / "model_step_3").is_dir()
    assert (tmp_path / "model_step_6").is_dir()
    # Corrupt the newest checkpoint's largest payload file (a replica
    # array blob, not the manifest).
    newest = tmp_path / "model_step_6"
    victim = max((p for p in newest.iterdir()
                  if "manifest" not in p.name),
                 key=lambda p: p.stat().st_size)
    assert corrupt_file(str(victim))
    assert not ckpt.verify_checkpoint(str(tmp_path), 6)
    assert ckpt.verify_checkpoint(str(tmp_path), 3)

    t = MultiSliceTrainer(cfg.replace(max_steps=9), n_slices=2)
    assert t.maybe_resume()
    assert t.step == 3
