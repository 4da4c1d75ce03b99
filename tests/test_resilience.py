"""Resilience tests: fault-spec grammar, deterministic injection, retry
backoff (fake clock, no real sleeps), chaos-matrix coordinator runs,
heartbeat liveness masking, hardened checkpoints, and crash auto-resume."""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from ps_pytorch_tpu import resilience
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.resilience import (
    FaultInjector, FaultyKV, Heartbeat, InjectedCrash, LivenessMonitor,
    ManualClock, PreemptionGuard, RetryBudget, RetryingKV, RetryPolicy,
    TransientKVError, call_with_retry, corrupt_file, is_retryable,
    parse_fault_spec, run_with_auto_resume,
)
from ps_pytorch_tpu.runtime import checkpoint as ckpt
from ps_pytorch_tpu.runtime.coordinator import Coordinator, KVStore
from ps_pytorch_tpu.runtime.trainer import Trainer


def _tiny_cfg(tmp_path, **kw):
    base = dict(dataset="synthetic_mnist", network="LeNet", batch_size=64,
                lr=0.01, momentum=0.9, max_steps=6, epochs=0, eval_freq=2,
                train_dir=str(tmp_path / "ckpt"), compute_dtype="float32",
                data_axis=8, log_every=2, seed=3)
    base.update(kw)
    return TrainConfig(**base)


# ---- fault-spec grammar ----

def test_fault_spec_grammar():
    faults = parse_fault_spec(
        "kv_drop:p=0.05,seed=7;replica_crash:r=2,step=40;"
        "ckpt_corrupt:step=20,mode=truncate")
    assert [f["kind"] for f in faults] == [
        "kv_drop", "replica_crash", "ckpt_corrupt"]
    assert faults[0]["p"] == 0.05 and faults[0]["seed"] == 7
    assert faults[1]["r"] == 2 and faults[1]["step"] == 40
    assert faults[2]["mode"] == "truncate"
    assert parse_fault_spec("") == []


@pytest.mark.parametrize("bad", [
    "typo_kind:p=0.1",              # unknown kind
    "kv_drop:p=1.5",                # p out of range
    "kv_drop:p",                    # not key=value
    "kv_drop:p=0.1,op=rename",      # bad op
    "kv_delay:p=0.1",               # missing s
    "replica_crash:r=1",            # missing step
    "ckpt_corrupt:step=5,mode=eat",  # bad mode
])
def test_fault_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_fault_spec(bad)


def test_config_validates_fault_spec(tmp_path):
    with pytest.raises(ValueError):
        _tiny_cfg(tmp_path, fault_spec="kv_drop:p=2.0")
    cfg = _tiny_cfg(tmp_path, fault_spec="kv_drop:p=0.1,seed=1")
    assert cfg.fault_spec


# ---- fault plane: deterministic drops/delays ----

def _drop_pattern(seed, n=200, p=0.25):
    inj = FaultInjector(f"kv_drop:p={p},seed={seed}", process_index=0)
    kv = inj.wrap_kv(KVStore())
    pattern = []
    for i in range(n):
        try:
            kv.set(f"k{i}", "v")
            pattern.append(0)
        except TransientKVError:
            pattern.append(1)
    return pattern, inj


def test_faulty_kv_deterministic_and_counted():
    a, inj_a = _drop_pattern(7)
    b, _ = _drop_pattern(7)
    c, _ = _drop_pattern(8)
    assert a == b                   # same seed -> same drop sequence
    assert a != c                   # different seed -> different sequence
    assert sum(a) == inj_a.snapshot()["kv_drops"] > 0


def test_faulty_kv_drop_is_raised_before_write():
    inj = FaultInjector("kv_drop:p=1.0,seed=0", process_index=0)
    inner = KVStore()
    kv = inj.wrap_kv(inner)
    with pytest.raises(TransientKVError):
        kv.set("k", "v")
    assert inner.get("k") is None   # a dropped set never half-writes


def test_kv_delay_uses_injected_sleep():
    clock = ManualClock()
    inj = FaultInjector("kv_delay:p=1.0,s=0.25,seed=1", process_index=0,
                        clock=clock.time, sleep=clock.sleep)
    kv = inj.wrap_kv(KVStore())
    kv.set("a", "1")
    kv.get("a")
    assert clock.sleeps == [0.25, 0.25]
    assert inj.snapshot()["kv_delays"] == 2


def test_ops_filter_restricts_fault_to_named_op():
    inj = FaultInjector("kv_drop:p=1.0,seed=0,op=set", process_index=0)
    kv = inj.wrap_kv(KVStore())
    with pytest.raises(TransientKVError):
        kv.set("k", "v")
    assert kv.get("k") is None      # get never rolls the set-only fault


# ---- retry plane ----

def test_is_retryable_classification():
    assert is_retryable(TransientKVError("UNAVAILABLE"))
    assert is_retryable(TimeoutError("deadline"))
    assert is_retryable(RuntimeError("connection reset by peer"))
    assert not is_retryable(ValueError("bad arg"))
    assert not is_retryable(KeyError("missing"))
    assert not is_retryable(RuntimeError("NOT_FOUND: key absent"))


def test_call_with_retry_backoff_fake_clock():
    clock = ManualClock()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientKVError("UNAVAILABLE")
        return "ok"

    policy = RetryPolicy(max_attempts=5, base_s=0.1, multiplier=2.0,
                         jitter=0.5, seed=42)
    assert call_with_retry(flaky, policy=policy, sleep=clock.sleep) == "ok"
    assert calls["n"] == 3
    assert len(clock.sleeps) == 2
    # Jittered exponential: delay_k in (base * mult**k * (1-jitter),
    # base * mult**k].
    for k, d in enumerate(clock.sleeps):
        cap = policy.base_s * policy.multiplier ** k
        assert cap * (1 - policy.jitter) < d <= cap


def test_call_with_retry_fatal_not_retried():
    calls = {"n": 0}

    def broken():
        calls["n"] += 1
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        call_with_retry(broken, sleep=lambda s: None)
    assert calls["n"] == 1


def test_retry_budget_exhaustion_fails_fast():
    clock = ManualClock()
    budget = RetryBudget(3)

    def always_down():
        raise TransientKVError("UNAVAILABLE")

    policy = RetryPolicy(max_attempts=10, base_s=0.01, seed=0)
    with pytest.raises(TransientKVError):
        call_with_retry(always_down, policy=policy, budget=budget,
                        sleep=clock.sleep)
    assert budget.spent == 3
    assert len(clock.sleeps) == 3   # no sleep on the fail-fast re-raise
    with pytest.raises(TransientKVError):
        call_with_retry(always_down, policy=policy, budget=budget,
                        sleep=clock.sleep)
    assert len(clock.sleeps) == 3   # exhausted budget: zero further sleeps


def test_retrying_kv_absorbs_injected_drops():
    clock = ManualClock()
    inj = FaultInjector("kv_drop:p=0.3,seed=5", process_index=0,
                        sleep=clock.sleep)
    kv = RetryingKV(inj.wrap_kv(KVStore()),
                    RetryPolicy(max_attempts=8, base_s=0.001, seed=1),
                    sleep=clock.sleep)
    for i in range(100):
        kv.set(f"k{i}", str(i))
    for i in range(100):
        assert kv.get(f"k{i}") == str(i)
    s = kv.snapshot()
    assert s["kv_retries"] > 0 and s["kv_giveups"] == 0


def test_wrap_kv_identity_when_disabled(tmp_path):
    cfg = _tiny_cfg(tmp_path, kv_retry_attempts=1)
    base = KVStore()
    kv, injector, retrier = resilience.wrap_kv(base, cfg)
    assert kv is base and injector is None and retrier is None


# ---- chaos matrix: leader+follower coordinators over a flaky KV ----

def test_coordinator_chaos_5pct_drops_50_steps():
    """Acceptance: 5% injected drops, 50-step leader+follower run, no
    TimeoutError — the retry plane absorbs every hiccup."""
    base = KVStore()
    cfgish = type("C", (), {"fault_spec": "kv_drop:p=0.05,seed=7",
                            "kv_retry_attempts": 8,
                            "kv_retry_base_s": 0.001,
                            "kv_retry_budget": 10000, "seed": 0})
    kv_l, _, retr_l = resilience.wrap_kv(base, cfgish, process_index=0)
    kv_f, _, retr_f = resilience.wrap_kv(base, cfgish, process_index=1)
    leader = Coordinator(4, mode="sync", kv=kv_l, leader=True)
    follower = Coordinator(4, mode="sync", kv=kv_f, leader=False)
    errs = []

    def follow():
        try:
            for s in range(1, 51):
                follower.wait_for_step(after=s - 1, timeout_s=30.0)
                mask = follower.participation_mask(s, timeout_s=30.0)
                assert mask.shape == (4,)
        except Exception as e:  # noqa: BLE001 — surfaced via errs
            errs.append(e)

    th = threading.Thread(target=follow)
    th.start()
    for s in range(1, 51):
        leader.announce_step(s)
        leader.participation_mask(s)
    th.join(60)
    assert not th.is_alive() and errs == []
    total = retr_l.snapshot()["kv_retries"] + retr_f.snapshot()["kv_retries"]
    assert total > 0
    assert retr_l.snapshot()["kv_giveups"] == 0
    assert retr_f.snapshot()["kv_giveups"] == 0


def test_cross_process_kill_reaches_leader_mask():
    # Kills are a KV protocol: a kill issued through ANOTHER process's
    # coordinator must land in the leader's next mask decision.
    kv = KVStore()
    leader = Coordinator(4, mode="sync", kv=kv, leader=True)
    other = Coordinator(4, mode="sync", kv=kv, leader=False)
    np.testing.assert_array_equal(leader.participation_mask(1),
                                  np.ones(4, np.float32))
    other.kill(2)
    mask = leader.participation_mask(2)
    np.testing.assert_array_equal(mask, [1, 1, 0, 1])
    assert leader.stats["mask_changes"] == 1


# ---- heartbeat liveness ----

def test_heartbeat_eviction_and_readmission():
    clock = ManualClock()
    kv = KVStore()
    hb0 = Heartbeat(kv, "run", [0], interval_s=1.0, clock=clock.time)
    hb1 = Heartbeat(kv, "run", [1], interval_s=1.0, clock=clock.time)
    mon = LivenessMonitor(kv, "run", 2, timeout_s=3.0, clock=clock.time)
    # Bootstrap grace: nobody has beaten yet, everyone is alive.
    np.testing.assert_array_equal(mon.alive_mask(), [True, True])
    hb0.beat(1)
    hb1.beat(1)
    np.testing.assert_array_equal(mon.alive_mask(), [True, True])
    # Replica 1 goes silent past the timeout; replica 0 keeps beating.
    clock.advance(4.0)
    hb0.beat(2)
    np.testing.assert_array_equal(mon.alive_mask(), [True, False])
    assert mon.snapshot() == {"evictions": 1, "readmissions": 0}
    # Recovery: one fresh beat readmits.
    hb1.beat(3)
    np.testing.assert_array_equal(mon.alive_mask(), [True, True])
    assert mon.snapshot() == {"evictions": 1, "readmissions": 1}
    assert [e["event"] for e in mon.events] == ["evict", "readmit"]


def test_heartbeat_throttle_and_garbled_beat():
    clock = ManualClock()
    kv = KVStore()
    hb = Heartbeat(kv, "run", [0], interval_s=1.0, clock=clock.time)
    assert hb.beat(1) is True
    assert hb.beat(2) is False          # throttled within interval
    assert hb.beat(2, force=True) is True
    kv.set("run/hb/0", "not json")       # torn write = just a missed beat
    mon = LivenessMonitor(kv, "run", 1, timeout_s=3.0, clock=clock.time)
    np.testing.assert_array_equal(mon.alive_mask(), [True])


def test_coordinator_masks_dead_replica_and_readmits():
    clock = ManualClock()
    kv = KVStore()
    hbs = [Heartbeat(kv, "run", [r], interval_s=1.0, clock=clock.time)
           for r in range(4)]
    mon = LivenessMonitor(kv, "run", 4, timeout_s=3.0, clock=clock.time)
    c = Coordinator(4, mode="sync", kv=kv, run_id="run", leader=True,
                    liveness=mon)
    for hb in hbs:
        hb.beat(1)
    np.testing.assert_array_equal(c.participation_mask(1),
                                  np.ones(4, np.float32))
    # Replica 3 dies (stops beating); the rest keep beating.
    clock.advance(4.0)
    for hb in hbs[:3]:
        hb.beat(2)
    np.testing.assert_array_equal(c.participation_mask(2), [1, 1, 1, 0])
    # Recovery: replica 3 beats again and is readmitted.
    hbs[3].beat(3)
    np.testing.assert_array_equal(c.participation_mask(3),
                                  np.ones(4, np.float32))
    assert mon.snapshot() == {"evictions": 1, "readmissions": 1}


def test_liveness_never_masks_everyone():
    clock = ManualClock()
    kv = KVStore()
    hb = Heartbeat(kv, "run", [0, 1], interval_s=1.0, clock=clock.time)
    mon = LivenessMonitor(kv, "run", 2, timeout_s=1.0, clock=clock.time)
    c = Coordinator(2, mode="sync", kv=kv, run_id="run", leader=True,
                    liveness=mon)
    hb.beat(1)
    clock.advance(10.0)              # everyone looks dead
    mask = c.participation_mask(1)
    assert mask.sum() > 0            # never-wedge fallback


# ---- hardened checkpoints ----

def test_checkpoint_manifest_roundtrip(tmp_path):
    tree = {"w": np.linspace(0, 1, 1000, dtype=np.float32)}
    path = ckpt.save_checkpoint(str(tmp_path), 3, tree)
    manifest = json.load(open(os.path.join(path, "manifest.json")))
    assert manifest["algo"] == "sha256"
    assert {"state.msgpack", "meta.json"} <= set(manifest["files"])
    assert "manifest.json" not in manifest["files"]
    assert ckpt.verify_checkpoint(str(tmp_path), 3)
    loaded, meta, _ = ckpt.load_checkpoint(str(tmp_path), 3, tree)
    np.testing.assert_array_equal(loaded["w"], tree["w"])


@pytest.mark.parametrize("mode", ["flip", "truncate"])
def test_checkpoint_corruption_detected(tmp_path, mode):
    tree = {"w": np.arange(4000, dtype=np.float32)}
    path = ckpt.save_checkpoint(str(tmp_path), 5, tree)
    assert corrupt_file(os.path.join(path, "state.msgpack"), mode)
    assert not ckpt.verify_checkpoint(str(tmp_path), 5)
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.load_checkpoint(str(tmp_path), 5, tree)


def test_latest_valid_step_walks_past_corruption(tmp_path):
    tree = {"w": np.ones(100, np.float32)}
    for s in (2, 4, 6):
        ckpt.save_checkpoint(str(tmp_path), s,
                             {"w": tree["w"] * s})
    assert ckpt.committed_steps(str(tmp_path)) == [2, 4, 6]
    assert ckpt.latest_valid_step(str(tmp_path)) == 6
    corrupt_file(os.path.join(ckpt.checkpoint_path(str(tmp_path), 6),
                              "state.msgpack"))
    assert ckpt.latest_step(str(tmp_path)) == 6          # newest on disk
    assert ckpt.latest_valid_step(str(tmp_path)) == 4    # newest VALID
    got = ckpt.load_latest_valid(str(tmp_path), tree)
    assert got is not None
    state, meta, _, step = got
    assert step == 4 and meta["step"] == 4
    np.testing.assert_array_equal(state["w"], tree["w"] * 4)


def test_extra_state_corruption_falls_back(tmp_path):
    """manifest.json covers extra_state.msgpack: a flipped byte in the EF
    residual blob invalidates the WHOLE checkpoint, and resume falls back
    to the previous valid one instead of restoring a torn residual."""
    tree = {"w": np.ones(100, np.float32)}
    extra = {"ef": {"r0": np.linspace(0, 1, 500).astype(np.float32)}}
    for s in (2, 4):
        ckpt.save_checkpoint(str(tmp_path), s, {"w": tree["w"] * s},
                             extra_state=extra)
    assert corrupt_file(os.path.join(
        ckpt.checkpoint_path(str(tmp_path), 4), "extra_state.msgpack"))
    assert not ckpt.verify_checkpoint(str(tmp_path), 4)
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.load_extra_state(str(tmp_path), 4)
    assert ckpt.latest_valid_step(str(tmp_path)) == 2
    state, meta, _, step = ckpt.load_latest_valid(str(tmp_path), tree)
    assert step == 2 and meta["step"] == 2
    np.testing.assert_array_equal(state["w"], tree["w"] * 2)
    restored = ckpt.load_extra_state(str(tmp_path), 2)
    np.testing.assert_array_equal(restored["ef"]["r0"], extra["ef"]["r0"])


def test_load_latest_valid_none_when_all_corrupt(tmp_path):
    tree = {"w": np.ones(10, np.float32)}
    ckpt.save_checkpoint(str(tmp_path), 1, tree)
    corrupt_file(os.path.join(ckpt.checkpoint_path(str(tmp_path), 1),
                              "state.msgpack"))
    assert ckpt.latest_valid_step(str(tmp_path)) is None
    assert ckpt.load_latest_valid(str(tmp_path), tree) is None


def test_prune_checkpoints_keeps_last_n(tmp_path):
    tree = {"w": np.ones(10, np.float32)}
    for s in (1, 2, 3, 4, 5):
        ckpt.save_checkpoint(str(tmp_path), s, tree)
    removed = ckpt.prune_checkpoints(str(tmp_path), keep_last=2)
    assert removed == [1, 2, 3]
    assert ckpt.committed_steps(str(tmp_path)) == [4, 5]
    assert ckpt.prune_checkpoints(str(tmp_path), keep_last=0) == []


# ---- trainer-level chaos ----

def test_trainer_crash_auto_resume_completes(tmp_path):
    """replica_crash mid-run -> auto-resume restores from the latest valid
    checkpoint and the run completes to max_steps."""
    cfg = _tiny_cfg(tmp_path, fault_spec="replica_crash:r=0,step=4",
                    resume=1)
    injector = FaultInjector(cfg.fault_spec, process_index=0)
    with pytest.raises(InjectedCrash):
        Trainer(cfg, injector=injector).train()   # crash really fires...
    state = run_with_auto_resume(
        lambda: Trainer(cfg, injector=injector), max_restarts=2)
    assert injector.snapshot()["crashes"] == 1    # ...exactly once
    assert int(jax.device_get(state.step)) == cfg.max_steps
    assert ckpt.latest_valid_step(cfg.train_dir) == cfg.max_steps


@pytest.mark.slow
def test_trainer_crash_resume_bitwise_equal(tmp_path):
    """Acceptance E2E: the crashed-and-resumed run's final params are
    bit-for-bit equal to an uninterrupted run's."""
    plain = Trainer(_tiny_cfg(tmp_path / "plain")).train()
    cfg = _tiny_cfg(tmp_path / "chaos",
                    fault_spec="replica_crash:r=0,step=4", resume=1)
    injector = FaultInjector(cfg.fault_spec, process_index=0)
    state = run_with_auto_resume(
        lambda: Trainer(cfg, injector=injector), max_restarts=2)
    for a, b in zip(jax.tree.leaves(jax.device_get(plain.params)),
                    jax.tree.leaves(jax.device_get(state.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_resumes_past_corrupt_newest(tmp_path):
    cfg = _tiny_cfg(tmp_path)
    Trainer(cfg).train()                          # checkpoints at 2, 4, 6
    corrupt_file(os.path.join(ckpt.checkpoint_path(cfg.train_dir, 6),
                              "state.msgpack"))
    t = Trainer(_tiny_cfg(tmp_path, resume=1))
    assert t.start_step == 4                      # fell back past step 6


def test_trainer_kv_drop_chaos_smoke(tmp_path, capsys):
    """Tier-1 fault-injection smoke: injected KV drops on the coordinator
    control plane, absorbed by the retry plane, counters emitted."""
    cfg = _tiny_cfg(tmp_path, fault_spec="kv_drop:p=0.2,seed=11",
                    eval_freq=0, max_steps=4)
    t = Trainer(cfg)
    t.train()
    stats = t.resilience_stats()
    assert stats["kv_drops"] > 0
    assert stats["kv_retries"] > 0 and stats["kv_giveups"] == 0


def test_trainer_ckpt_corrupt_fault_then_fallback(tmp_path):
    cfg = _tiny_cfg(tmp_path, fault_spec="ckpt_corrupt:step=6", resume=1)
    injector = FaultInjector(cfg.fault_spec, process_index=0)
    t = Trainer(cfg, injector=injector)
    t.train()
    assert injector.snapshot()["ckpt_corruptions"] == 1
    assert ckpt.latest_valid_step(cfg.train_dir) == 4
    t2 = Trainer(cfg, injector=injector)          # shared injector: no refire
    assert t2.start_step == 4


def test_trainer_ckpt_keep_retention(tmp_path):
    cfg = _tiny_cfg(tmp_path, ckpt_keep=1)
    Trainer(cfg).train()
    assert ckpt.committed_steps(cfg.train_dir) == [6]


def test_preemption_guard_flag_and_restore():
    guard = PreemptionGuard()
    guard.install()
    try:
        assert not guard.triggered
        guard.trigger()
        assert guard.triggered
    finally:
        guard.uninstall()


def test_trainer_preemption_writes_emergency_checkpoint(tmp_path, capsys):
    cfg = _tiny_cfg(tmp_path, eval_freq=0)        # no periodic checkpoints
    t = Trainer(cfg)
    t._preempt.trigger()                          # SIGTERM already pending
    t.train()
    out = capsys.readouterr().out
    assert "PREEMPT emergency checkpoint at step 1" in out
    assert ckpt.latest_valid_step(cfg.train_dir) == 1


def test_dataloader_fast_forward_matches_stream(tmp_path):
    from ps_pytorch_tpu.data.datasets import DataLoader, load_arrays
    x, y = load_arrays("synthetic_mnist", train=True, seed=0)
    a = DataLoader(x, y, 64, "synthetic_mnist", train=True, seed=1)
    b = DataLoader(x, y, 64, "synthetic_mnist", train=True, seed=1)
    n = len(a) + 3                                # crosses an epoch boundary
    for _ in range(n):
        a.next_batch()
    b.fast_forward(n)
    xa, ya = a.next_batch()
    xb, yb = b.next_batch()
    np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))


# ---- tooling ----

def test_analyze_faults_mode(tmp_path, capsys):
    rows = [{"step": s, "step_time": 0.1, "kv_drops": 4 * s,
             "kv_retries": 4 * s, "kv_giveups": 0} for s in (2, 4, 6)]
    p = tmp_path / "m.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    from ps_pytorch_tpu.tools.analyze import main
    assert main(["faults", str(p), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["steps"] == 3 and out["last_step"] == 6
    assert out["counters"]["kv_drops"] == 24      # cumulative -> max
    assert out["clean"] is False


def test_analyze_faults_clean_run(tmp_path, capsys):
    rows = [{"step": s, "step_time": 0.1, "kv_retries": 0} for s in (1, 2)]
    p = tmp_path / "m.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    from ps_pytorch_tpu.tools.analyze import main
    assert main(["faults", str(p), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["clean"] is True


# ---- leader lease (LeaderLost detection) ----

def _lease_pair(clock, interval=1.0, kv=None, **follower_kw):
    kv = kv if kv is not None else KVStore()
    leader = Coordinator(4, mode="sync", kv=kv, leader=True,
                         lease_interval_s=interval, clock=clock.time)
    follower = Coordinator(4, mode="sync", kv=kv, leader=False,
                           lease_interval_s=interval, clock=clock.time,
                           **follower_kw)
    return kv, leader, follower


def test_leader_lease_stale_raises_leader_lost():
    from ps_pytorch_tpu.runtime.coordinator import LeaderLost
    clock = ManualClock()
    kv, leader, follower = _lease_pair(clock)
    leader.announce_step(1)
    leader.participation_mask(1)           # publishes mask 1 + lease
    np.testing.assert_array_equal(
        follower.participation_mask(1, timeout_s=5.0), np.ones(4, np.float32))
    # Leader dies: no refresh, clock sails past 3x interval. The follower's
    # wait for step 2's (never-published) mask must fail as LeaderLost long
    # before the run deadline, not as a TimeoutError at it.
    clock.now += 10.0
    with pytest.raises(LeaderLost, match="stale"):
        follower.participation_mask(2, timeout_s=60.0)
    assert follower.stats["leader_lost"] == 1


def test_leader_lease_fresh_is_not_leader_lost():
    # A slow leader (lease refreshed, mask late) stays a TimeoutError:
    # the lease distinguishes dead-vs-slow, it must not misfire on slow.
    clock = ManualClock()
    kv, leader, follower = _lease_pair(clock)
    leader.announce_step(1)
    leader.participation_mask(1)
    with pytest.raises(TimeoutError):
        follower.participation_mask(2, timeout_s=0.3)
    assert "leader_lost" not in follower.stats


def test_leader_lease_bootstrap_grace_without_publish():
    # No lease ever written (leader hasn't reached its first publish):
    # followers fall back to the plain deadline instead of LeaderLost.
    clock = ManualClock(start=50.0)
    follower = Coordinator(4, mode="sync", kv=KVStore(), leader=False,
                           lease_interval_s=1.0, clock=clock.time)
    with pytest.raises(TimeoutError):
        follower.participation_mask(1, timeout_s=0.3)
    assert "leader_lost" not in follower.stats


def test_leader_lease_refresh_throttled():
    clock = ManualClock()
    kv, leader, _ = _lease_pair(clock, interval=5.0)
    for s in (1, 2, 3):
        leader.announce_step(s)
        leader.participation_mask(s)       # same clock tick: one write
    assert json.loads(kv.get(f"{leader.run_id}/lease"))[0] == 1
    clock.now += 6.0
    leader.announce_step(4)
    leader.participation_mask(4)
    assert json.loads(kv.get(f"{leader.run_id}/lease"))[0] == 4


def test_leader_lease_survives_kv_chaos_then_detects_death():
    """Chaos acceptance: with injected KV drops on the follower's plane,
    transient errors during lease reads are absorbed (counted, not fatal);
    a genuinely stale lease still surfaces as LeaderLost."""
    from ps_pytorch_tpu.runtime.coordinator import LeaderLost
    clock = ManualClock()
    base = KVStore()
    inj = FaultInjector("kv_drop:p=0.5,seed=11", process_index=1)
    kv_f = inj.wrap_kv(base)
    leader = Coordinator(4, mode="sync", kv=base, leader=True,
                         lease_interval_s=1.0, clock=clock.time)
    follower = Coordinator(4, mode="sync", kv=kv_f, leader=False,
                           lease_interval_s=1.0, clock=clock.time)
    for s in (1, 2):
        leader.announce_step(s)
        leader.participation_mask(s)
        np.testing.assert_array_equal(
            follower.participation_mask(s, timeout_s=30.0),
            np.ones(4, np.float32))
    assert follower.stats.get("mask_wait_errors", 0) >= 0  # absorbed, never raised
    clock.now += 10.0                       # leader silent past the timeout
    with pytest.raises(LeaderLost):
        follower.participation_mask(3, timeout_s=60.0)
    assert inj.snapshot()["kv_drops"] > 0


def test_lease_throttle_state_does_not_leak_across_epochs():
    """ISSUE 7 edge case: a deposed leader's refresh throttle (``_last``)
    must be RESET when it wins a later epoch. The claim write IS the new
    epoch's first refresh — an inherited ``_last`` would either suppress
    that first refresh (recent ``_last``) or double-write it (ancient
    ``_last``), and followers would see a lease whose cadence belongs to
    the dead epoch."""
    from ps_pytorch_tpu.elastic import Deposed, LeaderElection
    clock, kv = ManualClock(), KVStore()

    def make(pid):
        return LeaderElection(kv, "run", pid, 2, interval_s=1.0,
                              settle_s=0.0, preferred=0, clock=clock.time,
                              sleep=lambda s: None)

    el = make(0)
    el.claim_initial()                      # epoch 1, _last = 0.0
    assert el._last == 0.0
    # A usurper claims epoch 2 while el is stalled; el's next refresh
    # hits the fence and demotes — but its old throttle state survives.
    clock.now = 0.5
    kv.set("run/elect/lease", json.dumps([2, 1, clock.time()]))
    with pytest.raises(Deposed):
        el.refresh()
    # The usurper dies too; el campaigns at T and wins epoch 3.
    clock.now = 10.5
    assert el.campaign() is True
    assert el.epoch == 3 and el.is_leader
    # The claim reset the throttle to the claim time, NOT a value carried
    # over from epoch 1.
    assert el._last == 10.5
    # Claim counts as the epoch's first refresh: within the interval the
    # refresh is throttled (no redundant write)...
    clock.now = 10.5 + 0.9
    assert el.refresh() is False
    # ...and at the interval boundary the cadence resumes normally.
    clock.now = 10.5 + 1.0
    assert el.refresh() is True
    assert json.loads(kv.get("run/elect/lease")) == [3, 0, 11.5]
    # A follower sees a FRESH epoch-3 lease owned by the re-elected 0.
    follower = make(1)
    assert follower.check() == "fresh"
    assert (follower.epoch, follower.owner) == (3, 0)


def _distributed_kv(client):
    from ps_pytorch_tpu.runtime.coordinator import DistributedKV
    kv = DistributedKV.__new__(DistributedKV)
    kv._client = client
    return kv


def test_distributed_kv_get_maps_not_found_to_default():
    class FakeClient:
        store = {}

        def key_value_try_get(self, key):
            if key in self.store:
                return self.store[key]
            raise RuntimeError(f"NOT_FOUND: key {key} not found")

    kv = _distributed_kv(FakeClient())
    assert kv.get("run/adone", None) is None
    assert kv.get("run/adone", "dflt") == "dflt"
    kv._client.store["run/adone"] = "1"
    assert kv.get("run/adone") == "1"


def test_distributed_kv_get_reraises_other_errors():
    # A dead coordination service must surface to the retry layer, not be
    # read as "key not published yet".
    class FakeClient:
        def key_value_try_get(self, key):
            raise RuntimeError("UNAVAILABLE: coordination service down")

    with pytest.raises(RuntimeError, match="UNAVAILABLE"):
        _distributed_kv(FakeClient()).get("run/adone")


# ---- resilience counters on the scrape endpoint ----

def test_trainer_metrics_exposes_resilience_counters(tmp_path):
    """Injector/retry counters reach the Prometheus /metrics exposition
    (not just the JSONL) through the exporter's collect hook."""
    import urllib.request

    from conftest import free_port
    from ps_pytorch_tpu.telemetry import parse_exposition

    cfg = _tiny_cfg(tmp_path, fault_spec="kv_drop:p=0.25,seed=11",
                    kv_retry_attempts=6, metrics_port=free_port(),
                    eval_freq=0, max_steps=4)
    t = Trainer(cfg)
    try:
        for i in range(40):      # through the fault + retry shims
            try:
                t.coordinator.kv.set(f"probe/{i}", "x")
            except TransientKVError:
                pass             # a giveup past the retry budget is fine
        url = f"http://127.0.0.1:{t.exporter.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            samples = parse_exposition(resp.read().decode())
        assert samples["kv_drops_total"] > 0
        assert samples["kv_retries_total"] > 0
        assert "kv_giveups_total" in samples
        assert "kv_partition_drops_total" in samples
        assert "link_jitters_total" in samples
    finally:
        t.exporter.stop()


# ---- leader_kill x compressed wire (PR 7 x PR 9 interaction) ----

def test_async_ef_residual_survives_resume_bitwise(tmp_path):
    """The async leader's error-feedback residual rides the checkpoint as
    extra state and reloads BIT-FOR-BIT, so an auto-resumed run re-encodes
    exactly what the uninterrupted one would have."""
    from ps_pytorch_tpu.runtime.async_trainer import AsyncTrainer

    cfg = TrainConfig(dataset="synthetic_mnist", network="LeNet",
                      batch_size=64, lr=0.05, momentum=0.9,
                      compute_dtype="float32", mode="async", max_steps=8,
                      eval_freq=4, train_dir=str(tmp_path / "ckpt"),
                      resume=False, log_every=100, compress_grad=True,
                      grad_codec="int8lat", ef=True)
    t = AsyncTrainer(cfg)
    t.train()
    assert t._ef is not None and t._ef.residual_nbytes() > 0
    step = ckpt.latest_valid_step(cfg.train_dir)
    saved = ckpt.load_extra_state(cfg.train_dir, step)["ef"]
    t2 = AsyncTrainer(cfg.replace(resume=True))
    assert t2._maybe_resume()
    restored = t2._ef.state_dict()
    assert set(restored) == set(saved) and restored
    for k in saved:
        np.testing.assert_array_equal(np.asarray(saved[k]),
                                      np.asarray(restored[k]))


@pytest.mark.slow
def test_leader_kill_int8lat_ef_chaos_soak(tmp_path):
    """Chaos soak combining leader_kill with the compressed homomorphic
    wire: the drill's failover phase under --grad-codec int8lat --ef. The
    kill fires, a follower promotes (its own sender-side EF residual is
    untouched by _promote), survivors finish, and the promoted leader's
    checkpoint carries a reloadable nonzero EF residual."""
    import re

    from ps_pytorch_tpu.compression.codecs import ErrorFeedback
    from ps_pytorch_tpu.tools import elastic_drill as ed

    run_dir = tmp_path / "failover"
    rc = ed._launch(run_dir, ed._free_port(), [
        "--phase", "failover", "--train-dir", str(run_dir / "ckpt"),
        "--max-steps", "40", "--kill-step", "2",
        "--grad-codec", "int8lat", "--ef"])
    logs = ed._logs(run_dir)
    dump = "\n\n".join(f"== proc_{i} ==\n{t[-3000:]}"
                       for i, t in enumerate(logs))
    assert rc != 2, dump
    assert "FAULT leader_kill: SIGKILL" in logs[1], dump
    elected = re.findall(r"ELECTED async leader process (\d+)",
                         "\n".join(logs))
    assert len(elected) == 1 and elected[0] in ("0", "2"), dump
    finals = [i for i, t in enumerate(logs) if i != 1 and "FINAL" in t]
    assert finals == [0, 2], dump
    step = ckpt.latest_valid_step(str(run_dir / "ckpt"))
    assert step is not None, dump
    extra = ckpt.load_extra_state(str(run_dir / "ckpt"), step)
    assert extra and extra.get("ef"), dump
    ef = ErrorFeedback()
    ef.load_state_dict(extra["ef"])
    assert ef.residual_nbytes() > 0
