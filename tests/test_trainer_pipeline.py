"""Trainer and LMTrainer keep one step queued on the device: nothing between
two dispatches waits for it. A logged step's record is written once the next
step is queued (or before a checkpoint, at the loop's end, on the way out of
an exception), and Trainer's per-step key is made on the host. Both loops get
that from runtime/step_queue.py. These tests pin what it must not change: the
key's bits, every step's logged values, one record a step in step order,
log-before-checkpoint, and the parameters the loop leaves."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_lm_trainer import _OLMOE, _one_device
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.models.moe import DROPLESS_STATS
from ps_pytorch_tpu.parallel import dist
from ps_pytorch_tpu.runtime import Trainer
from ps_pytorch_tpu.runtime import checkpoint as ckpt
from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer
from ps_pytorch_tpu.runtime.step_queue import QueuedSteps, read_scalars
from ps_pytorch_tpu.runtime.trainer import host_prng_key
from ps_pytorch_tpu.telemetry import Tracer, set_default_tracer

STEPS = 6
KINDS = ("cnn", "lm")

_BASE = {
    "cnn": dict(dataset="synthetic_mnist", network="LeNet", batch_size=64,
                lr=0.01, momentum=0.9, epochs=0, compute_dtype="float32",
                data_axis=8),
    # the default --lm-parallelism (sp): the dense path of the GPT-2 cells
    "lm": dict(lm_vocab=64, lm_d_model=32, lm_layers=1, lm_heads=2,
               lm_seq_len=64, lm_corpus_tokens=4096, batch_size=8, lr=0.01,
               momentum=0.9),
}


def _cfg(tmp_path, kind="cnn", **kw):
    base = dict(_BASE[kind], max_steps=STEPS, eval_freq=0,
                train_dir=str(tmp_path / "ckpt"),
                metrics_file=str(tmp_path / "m.jsonl"),
                log_every=1, resume=False, seed=3)
    base.update(kw)
    return TrainConfig(**base)


def _trainer(cfg, kind):
    return (Trainer if kind == "cnn" else LMTrainer)(cfg)


def _records(cfg):
    with open(cfg.metrics_file) as f:
        return [json.loads(line) for line in f]


def _plain_lm_loop(t, steps):
    """The LM step function called directly: every step's scalars read
    before the next step is dispatched, as the loop did before it kept a
    step queued. -> [{scalar: value}] of steps 1..steps; ``t.state`` is left
    at the last step."""
    out = []
    for _ in range(steps):
        tok = dist.globalize_replicated(t.mesh, t.train_loader.next_batch(),
                                        spec=t._token_spec())
        t.state, m = t.step_fn(t.state, tok)
        out.append({k: float(v) for k, v in m.items()})
    return out


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
    """{kind: (loss, accuracy, participating) of steps 1..STEPS} from the
    step function called directly, in the order the loops had before they
    kept a step queued: Trainer's key from ``jax.random.PRNGKey`` on the
    device, and every step's scalars read before the next step is
    dispatched. (An LM record's accuracy is 0 and its participating 1.)"""
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
    lm = LMTrainer(_cfg(tmp_path_factory.mktemp("ref_lm"), "lm",
                        metrics_file=""))
    set_default_tracer(lm._prev_tracer)
    return {"cnn": out,
            "lm": [(m["loss"], 0.0, 1.0) for m in _plain_lm_loop(lm, STEPS)]}


@pytest.mark.parametrize("log_every, logged", [(1, [1, 2, 3, 4, 5, 6]),
                                               (3, [3, 6]), (4, [4, 6])])
@pytest.mark.parametrize("kind", KINDS)
def test_one_record_a_logged_step_in_order_with_the_steps_own_values(
        tmp_path, reference, kind, log_every, logged):
    cfg = _cfg(tmp_path, kind, log_every=log_every)
    t = _trainer(cfg, kind)
    t.train()
    recs = _records(cfg)        # the last step's record is there on return
    assert [r["step"] for r in recs] == logged
    for r in recs:
        assert (r["loss"], r["acc"], r["participating"]) == \
            reference[kind][r["step"] - 1]
        assert r["dispatch_ahead"] in (0, 1)
        assert r["step_time"] > 0 and r["data_time"] >= 0
        assert {"data_wait", "host_dispatch", "device_sync"} <= set(r["phases"])
    assert recs[0]["dispatch_ahead"] == 0 or log_every > 1
    assert t.registry.get("train_steps") == STEPS
    assert 0 <= t.registry.get("dispatch_ahead_steps") <= STEPS - 1
    if log_every == 1:
        assert t.registry.get("dispatch_ahead_steps") == \
            sum(r["dispatch_ahead"] for r in recs)


@pytest.mark.parametrize("kind", KINDS)
def test_a_steps_record_is_in_the_log_before_its_checkpoint(tmp_path, kind):
    cfg = _cfg(tmp_path, kind, eval_freq=3)
    t = _trainer(cfg, kind)
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


@pytest.mark.parametrize("kind", KINDS)
def test_an_exception_from_next_batch_leaves_every_dispatched_steps_record(
        tmp_path, reference, kind):
    """How the benchmark's harness leaves ``train()``: every step of its
    window must have its record in the file once the exception is out."""
    class Boom(Exception):
        pass

    cfg = _cfg(tmp_path, kind)
    t = _trainer(cfg, kind)
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
    assert [r["loss"] for r in recs] == [v[0] for v in reference[kind][:4]]
    assert not os.path.exists(ckpt.checkpoint_path(cfg.train_dir, 4))


@pytest.mark.parametrize("kind", KINDS)
def test_a_failing_write_on_the_way_out_does_not_mask_the_exception(
        tmp_path, kind):
    cfg = _cfg(tmp_path, kind)
    t = _trainer(cfg, kind)
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


@pytest.mark.parametrize("kind", KINDS)
def test_step_times_add_up_and_none_outruns_a_slow_host(tmp_path, kind):
    """A host slower than the device (50 ms of delay a step: injected after
    the dispatch in Trainer, a slow loader in LMTrainer): at a drain two
    records are read in one go and share the time since the last read, so
    none claims a step faster than half the host's pace, and what the
    records say the steps took is not more than the run took."""
    delay = 0.05
    slow = dict(inject_step_delay=delay, inject_delay_process=0) \
        if kind == "cnn" else {}
    cfg = _cfg(tmp_path, kind, eval_freq=3, **slow)
    t = _trainer(cfg, kind)
    if kind == "lm":
        orig = t.train_loader.next_batch

        def next_batch():
            time.sleep(delay)
            return orig()

        t.train_loader.next_batch = next_batch
    t0 = time.monotonic()
    t.train()
    wall = time.monotonic() - t0
    recs = _records(cfg)
    assert [r["step"] for r in recs] == [1, 2, 3, 4, 5, 6]
    assert all(r["step_time"] >= delay / 2 for r in recs)
    assert sum(r["step_time"] for r in recs) <= wall
    assert recs[1]["step_time"] == recs[2]["step_time"]     # one read
    assert recs[4]["step_time"] == recs[5]["step_time"]


# ---- LMTrainer on the queued loop: what the device computes is untouched ----

@pytest.mark.parametrize("mode, extra, one_device", [
    ("sp", {}, False),
    ("ep", dict(lm_experts=8), False),
    ("ep-olmoe", _OLMOE, True),
])
def test_lm_losses_and_final_parameters_are_the_plain_loops_bit_for_bit(
        tmp_path, monkeypatch, mode, extra, one_device):
    if one_device:
        _one_device(monkeypatch)
    extra = dict({"lm_parallelism": mode}, **extra)
    plain = LMTrainer(_cfg(tmp_path / "plain", "lm", metrics_file="", **extra))
    set_default_tracer(plain._prev_tracer)
    want = _plain_lm_loop(plain, STEPS)
    cfg = _cfg(tmp_path, "lm", **extra)
    t = LMTrainer(cfg)
    t.train()
    recs = _records(cfg)
    assert [r["step"] for r in recs] == list(range(1, STEPS + 1))
    for r, m in zip(recs, want):
        assert {k: r[k] for k in m} == m        # loss and routing scalars
    got, ref = jax.device_get((t.state.params, plain.state.params))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_a_dropless_archs_record_carries_its_own_steps_stats(
        tmp_path, monkeypatch):
    _one_device(monkeypatch)
    plain = LMTrainer(_cfg(tmp_path / "plain", "lm", metrics_file="",
                           **_OLMOE))
    set_default_tracer(plain._prev_tracer)
    want = _plain_lm_loop(plain, STEPS)
    cfg = _cfg(tmp_path, "lm", **dict(_OLMOE, log_every=2))
    t = LMTrainer(cfg)
    t.train()
    recs = _records(cfg)
    assert [r["step"] for r in recs] == [2, 4, 6]
    for r in recs:
        assert set(DROPLESS_STATS) <= set(r)
        assert {k: r[k] for k in ("loss", *DROPLESS_STATS)} == \
            want[r["step"] - 1]
    assert recs[0]["compute_dtype"] == "bfloat16"   # the run's first record
    assert all("compute_dtype" not in r for r in recs[1:])
    # the registry's gauges are the last record's
    assert all(t.registry.get(k) == recs[-1][k] for k in DROPLESS_STATS)


def test_lm_non_finite_loss_on_the_last_step_still_halts_and_checkpoints(
        tmp_path, capsys):
    """The watchdogs see a step's loss one iteration late; the last step's
    is checked after the loop."""
    cfg = _cfg(tmp_path, "lm", health_spec="nonfinite:halt")
    t = LMTrainer(cfg)
    step_fn = t.step_fn

    def poisoned(state, tokens):
        state, m = step_fn(state, tokens)
        return state, dict(m, loss=jnp.where(state.step == STEPS, jnp.nan,
                                             m["loss"]))

    t.step_fn = poisoned
    t.train()
    out = capsys.readouterr().out
    assert "HEALTH nonfinite (halt)" in out
    assert f"HEALTH halt at step {STEPS}" in out
    assert t.health.should_halt
    assert ckpt.committed_steps(cfg.train_dir) == [STEPS]
    recs = _records(cfg)
    assert [r["step"] for r in recs] == list(range(1, STEPS + 1))
    assert np.isnan(recs[-1]["loss"])
    assert all(np.isfinite(r["loss"]) for r in recs[:-1])


def test_lm_non_finite_loss_mid_run_halts_one_step_late_with_its_record(
        tmp_path, capsys):
    cfg = _cfg(tmp_path, "lm", health_spec="nonfinite:halt")
    t = LMTrainer(cfg)
    step_fn = t.step_fn

    def poisoned(state, tokens):
        state, m = step_fn(state, tokens)
        return state, dict(m, loss=jnp.where(state.step == 3, jnp.nan,
                                             m["loss"]))

    t.step_fn = poisoned
    t.train()
    assert "HEALTH halt at step 4" in capsys.readouterr().out
    assert ckpt.committed_steps(cfg.train_dir) == [4]
    assert [r["step"] for r in _records(cfg)] == [1, 2, 3, 4]


# ---- runtime/step_queue.py alone: what a CPU run cannot make happen ----

class _Scalar:
    """A step's scalar that is not ready until read, as on a busy chip."""

    def __init__(self, value, reads):
        self.value, self.reads, self.ready = value, reads, False

    def is_ready(self):
        return self.ready

    def __float__(self):
        self.ready = True
        self.reads.append(self.value)
        return float(self.value)


def test_queued_steps_reads_the_step_before_and_writes_one_step_late():
    tracer, reads, written = Tracer(), [], []
    q = QueuedSteps(tracer, 10, lambda step, own, **kw: written.append(
        (step, own, kw["tag"])), record=("loss", "acc"), watch=("loss", "gn"))
    assert q.ahead() == 0 and q.last_watched() == {}
    for step in (11, 12, 13):
        ahead = q.ahead()
        assert ahead == int(step > 11)      # the step before: not read yet
        m = {"loss": _Scalar(step + 0.5, reads), "acc": _Scalar(step, reads)}
        q.log_later(step, m, tag=f"s{step}")
        prev = q.sync(m)
        assert prev == ({} if step == 11 else {"loss": step - 0.5})
        q.log_through(step - 1)
        assert [w[0] for w in written] == list(range(11, step))
    assert q.last_watched() == {"loss": 13.5}
    q.log_on_the_way_out()
    assert written == [(s, {"loss": s + 0.5, "acc": float(s)}, f"s{s}")
                       for s in (11, 12, 13)]
    names = [e["name"] for e in tracer.spans()]
    assert names.count("device_sync") == 3
    assert names.count("metrics_sync") == names.count("log_write") == 3


def test_read_scalars_picks_the_named_ones_it_finds():
    m = {"loss": np.float32(1.5), "aux": jnp.float32(0.25)}
    assert read_scalars(m) == {"loss": 1.5, "aux": 0.25}
    assert read_scalars(m, ("loss", "grad_norm")) == {"loss": 1.5}
