"""Trainer keeps one step queued on the device: nothing between two
dispatches waits for it. A logged step's record is written once the next step
is queued (or before a checkpoint, at the loop's end, on the way out of an
exception), and the per-step key is made on the host. These tests pin what
that must not change: the key's bits, every step's logged values, one record
a step in step order, and log-before-checkpoint."""

import json
import os
import time

import jax
import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.parallel import dist
from ps_pytorch_tpu.runtime import Trainer
from ps_pytorch_tpu.runtime import checkpoint as ckpt
from ps_pytorch_tpu.runtime.trainer import host_prng_key
from ps_pytorch_tpu.telemetry import set_default_tracer

STEPS = 6


def _cfg(tmp_path, **kw):
    base = dict(dataset="synthetic_mnist", network="LeNet", batch_size=64,
                lr=0.01, momentum=0.9, max_steps=STEPS, epochs=0, eval_freq=0,
                train_dir=str(tmp_path / "ckpt"), compute_dtype="float32",
                metrics_file=str(tmp_path / "m.jsonl"), data_axis=8,
                log_every=1, resume=False, seed=3)
    base.update(kw)
    return TrainConfig(**base)


def _records(cfg):
    with open(cfg.metrics_file) as f:
        return [json.loads(line) for line in f]


@pytest.mark.parametrize("step", [1, 7, 10 ** 5])
@pytest.mark.parametrize("seed", [0, 1, 42, 2 ** 31 - 1, 2 ** 32 + 5,
                                  2300000101, -3])
def test_host_key_is_prngkey_bit_for_bit(seed, step):
    s = seed * 100003 + step
    for x64 in (False, True):
        with jax.enable_x64(x64):
            want = np.asarray(jax.random.PRNGKey(s))
            got = host_prng_key(s)
        assert got.dtype == want.dtype == np.uint32
        assert got.shape == want.shape == (2,)
        assert got.tolist() == want.tolist(), (x64, s)


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """(loss, accuracy, participating) of steps 1..STEPS from the step
    function called directly, in the order the loop had before it kept a step
    queued: the key from ``jax.random.PRNGKey`` on the device, and every
    step's scalars read before the next step is dispatched."""
    cfg = _cfg(tmp_path_factory.mktemp("ref"), metrics_file="")
    t = Trainer(cfg)
    set_default_tracer(t._prev_tracer)      # never trained: hand it back
    out = []
    for step in range(1, STEPS + 1):
        t.coordinator.announce_step(step)
        x, y = t.train_loader.next_batch()
        mask = t.coordinator.participation_mask(step)
        key = np.asarray(jax.random.PRNGKey(cfg.seed * 100003 + step))
        t.state, m = t.step_fn(
            t.state,
            dist.globalize_batch(t.mesh, np.asarray(x)),
            dist.globalize_batch(t.mesh, np.asarray(y)),
            dist.globalize_replicated(t.mesh, np.asarray(mask, np.float32)),
            dist.globalize_replicated(t.mesh, key,
                                      spec=jax.sharding.PartitionSpec()))
        out.append((float(m["loss"]), float(m["accuracy"]),
                    float(m["participating"])))
    return out


@pytest.mark.parametrize("log_every, logged", [(1, [1, 2, 3, 4, 5, 6]),
                                               (4, [4, 6])])
def test_one_record_a_logged_step_in_order_with_the_steps_own_values(
        tmp_path, reference, log_every, logged):
    cfg = _cfg(tmp_path, log_every=log_every)
    t = Trainer(cfg)
    t.train()
    recs = _records(cfg)        # the last step's record is there on return
    assert [r["step"] for r in recs] == logged
    for r in recs:
        assert (r["loss"], r["acc"], r["participating"]) == \
            reference[r["step"] - 1]
        assert r["dispatch_ahead"] in (0, 1)
        assert r["step_time"] > 0 and r["data_time"] >= 0
        assert {"data_wait", "host_dispatch", "device_sync"} <= set(r["phases"])
    assert recs[0]["dispatch_ahead"] == 0 or log_every > 1
    assert t.registry.get("train_steps") == STEPS
    assert 0 <= t.registry.get("dispatch_ahead_steps") <= STEPS - 1
    if log_every == 1:
        assert t.registry.get("dispatch_ahead_steps") == \
            sum(r["dispatch_ahead"] for r in recs)


def test_a_steps_record_is_in_the_log_before_its_checkpoint(tmp_path):
    cfg = _cfg(tmp_path, eval_freq=3)
    t = Trainer(cfg)
    seen = {}
    write = t._checkpoint

    def checkpoint(step):
        seen[step] = ([r["step"] for r in _records(cfg)],
                      ckpt.committed_steps(cfg.train_dir))
        write(step)

    t._checkpoint = checkpoint
    t.train()
    assert seen == {3: ([1, 2, 3], []), 6: ([1, 2, 3, 4, 5, 6], [3])}
    assert ckpt.committed_steps(cfg.train_dir) == [3, 6]
    assert [r["step"] for r in _records(cfg)] == [1, 2, 3, 4, 5, 6]


def test_an_exception_from_next_batch_leaves_every_dispatched_steps_record(
        tmp_path, reference):
    class Boom(Exception):
        pass

    cfg = _cfg(tmp_path)
    t = Trainer(cfg)
    orig, calls = t.train_loader.next_batch, []

    def next_batch():
        calls.append(1)
        if len(calls) == 5:
            raise Boom("no batch for step 5")
        return orig()

    t.train_loader.next_batch = next_batch
    with pytest.raises(Boom, match="step 5"):
        t.train()
    recs = _records(cfg)
    assert [r["step"] for r in recs] == [1, 2, 3, 4]
    assert [r["loss"] for r in recs] == [v[0] for v in reference[:4]]
    assert not os.path.exists(ckpt.checkpoint_path(cfg.train_dir, 4))


def test_a_failing_write_on_the_way_out_does_not_mask_the_exception(tmp_path):
    cfg = _cfg(tmp_path)
    t = Trainer(cfg)
    orig, calls = t.train_loader.next_batch, []
    write = t.metrics.log_step

    def next_batch():
        calls.append(1)
        if len(calls) == 3:
            raise KeyError("the real error")
        return orig()

    def log_step(step, *a, **kw):
        if step == 2:           # the record still waiting when step 3 fails
            raise OSError("disk full")
        write(step, *a, **kw)

    t.train_loader.next_batch = next_batch
    t.metrics.log_step = log_step
    with pytest.raises(KeyError, match="the real error"):
        t.train()
    assert [r["step"] for r in _records(cfg)] == [1]


def test_step_times_add_up_and_none_outruns_a_slow_host(tmp_path):
    """A host slower than the device (50 ms of injected delay a step): at a
    drain two records are read in one go and share the time since the last
    read, so none claims a step faster than half the host's pace, and what
    the records say the steps took is not more than the run took."""
    delay = 0.05
    cfg = _cfg(tmp_path, eval_freq=3, inject_step_delay=delay,
               inject_delay_process=0)
    t = Trainer(cfg)
    t0 = time.monotonic()
    t.train()
    wall = time.monotonic() - t0
    recs = _records(cfg)
    assert [r["step"] for r in recs] == [1, 2, 3, 4, 5, 6]
    assert all(r["step_time"] >= delay / 2 for r in recs)
    assert sum(r["step_time"] for r in recs) <= wall
    assert recs[1]["step_time"] == recs[2]["step_time"]     # one read
    assert recs[4]["step_time"] == recs[5]["step_time"]
