"""Elastic control plane: lease-based leader election (epoch fencing,
deterministic tie-break, claim races), epoch'd membership (join / leave /
evict / readmit), ZeRO shard rebalancing (bitwise exactness at every N and
across rebalances), Coordinator failover, the new fault kinds
(leader_kill / kv_partition), and the elastic-vs-static trainer identity.

All control-plane tests run on an in-process KVStore with a ManualClock —
no real sleeps, no real processes; tools/elastic_drill.py is the
multi-process version of the same assertions over a real DistributedKV.
"""

import json
import signal
import subprocess
import sys

import numpy as np
import pytest

import jax

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.elastic import (
    Deposed, LeaderElection, MemberAnnouncer, MembershipRegistry,
    ShardedKVUpdate, plan_shards, read_view, reslice,
)
from ps_pytorch_tpu.resilience import (
    FaultInjector, ManualClock, TransientKVError, parse_fault_spec,
)
from ps_pytorch_tpu.runtime.coordinator import Coordinator, KVStore


def _noop(_s):
    pass


def _election(kv, pid, n=3, clock=None, **kw):
    kw.setdefault("interval_s", 1.0)
    kw.setdefault("settle_s", 0.01)
    return LeaderElection(kv, "run", pid, n, clock=clock.time, sleep=_noop,
                         **kw)


# ---- election ----

def test_election_bootstrap_claim_and_follow():
    clock, kv = ManualClock(), KVStore()
    leader = _election(kv, 1, preferred=1, clock=clock)
    follower = _election(kv, 0, preferred=1, clock=clock)
    assert leader.claim_initial() == 1
    assert leader.is_leader and leader.epoch == 1
    # The follower observes the fresh lease and adopts epoch/owner.
    assert follower.check() == "fresh"
    assert (follower.epoch, follower.owner) == (1, 1)
    assert not follower.is_leader


def test_election_check_none_before_any_claim():
    clock, kv = ManualClock(start=50.0), KVStore()
    assert _election(kv, 0, clock=clock).check() == "none"


def test_election_stale_then_campaign_wins():
    clock, kv = ManualClock(), KVStore()
    leader = _election(kv, 1, preferred=1, clock=clock)
    leader.claim_initial()
    survivor = _election(kv, 0, preferred=1, clock=clock)
    assert survivor.check() == "fresh"
    clock.now += 10.0                       # leader silent past 3x interval
    assert survivor.check() == "stale"
    assert survivor.campaign() is True      # only candidate -> wins epoch 2
    assert survivor.is_leader and survivor.epoch == 2
    lease = survivor.read_lease()
    assert lease[0] == 2 and lease[1] == 0
    # The claim IS the first refresh of the new epoch: fresh immediately.
    other = _election(kv, 2, preferred=1, clock=clock)
    assert other.check() == "fresh"
    assert (other.epoch, other.owner) == (2, 0)


def test_election_campaign_follows_fresh_lease():
    # A campaign started against an already-reclaimed (fresh) lease must
    # follow it, not fight it.
    clock, kv = ManualClock(), KVStore()
    a = _election(kv, 0, clock=clock)
    a.claim_initial()
    b = _election(kv, 1, clock=clock)
    assert b.campaign() is False
    assert (b.epoch, b.owner) == (1, 0) and not b.is_leader


def test_election_tie_break_min_pid():
    # Two candidacies land for the same epoch; the winner function is
    # deterministic: preferred if a candidate, else the lowest pid.
    clock, kv = ManualClock(), KVStore()
    c0 = _election(kv, 0, preferred=5, clock=clock)   # preferred absent
    kv.set("run/elect/cand/1/2", json.dumps([0.0]))   # pid 2 already ran
    assert c0.campaign() is True                      # min(0, 2) == 0
    assert c0.epoch == 1 and c0.read_lease()[1] == 0


def test_election_preferred_honoured_when_candidate():
    clock, kv = ManualClock(), KVStore()
    c1 = _election(kv, 1, preferred=1, clock=clock)
    kv.set("run/elect/cand/1/0", json.dumps([0.0]))   # pid 0 also running
    assert c1.campaign() is True                      # preferred beats min
    assert c1.read_lease()[1] == 1


def test_election_claim_race_read_back():
    # A concurrent claimer with a different candidate view writes the lease
    # AFTER ours: the read-back detects the lost race and follows.
    clock, kv = ManualClock(), KVStore()
    c2 = _election(kv, 2, preferred=2, clock=clock)
    calls = []

    def racing_sleep(s):
        calls.append(s)
        if len(calls) == 2:     # the post-claim settle
            kv.set("run/elect/lease", json.dumps([1, 0, clock.time()]))

    c2.sleep = racing_sleep
    assert c2.campaign() is False
    assert (c2.epoch, c2.owner) == (1, 0) and not c2.is_leader


def test_election_deposed_fencing_on_refresh():
    clock, kv = ManualClock(), KVStore()
    old = _election(kv, 0, clock=clock)
    old.claim_initial()
    # A higher epoch claims while `old` is paused (GC, network, SIGSTOP).
    kv.set("run/elect/lease", json.dumps([2, 1, clock.time()]))
    with pytest.raises(Deposed, match="epoch 2 owner 1"):
        old.refresh(step=7)
    assert not old.is_leader and old.stats["deposed"] == 1
    assert (old.epoch, old.owner) == (2, 1)
    # Same-epoch different-owner is equally fatal (split-brain guard).
    usurped = _election(kv, 3, clock=clock)
    usurped._claim(5)
    kv.set("run/elect/lease", json.dumps([5, 4, clock.time()]))
    with pytest.raises(Deposed):
        usurped.refresh()


def test_election_torn_lease_reads_as_absent():
    clock, kv = ManualClock(), KVStore()
    kv.set("run/elect/lease", "{half a json")
    el = _election(kv, 0, clock=clock)
    assert el.read_lease() is None
    assert el.check() == "none"
    assert el.campaign() is True            # claims over the garbage


# ---- membership ----

def _membership(kv, clock, n=3, timeout_s=3.0):
    return MembershipRegistry(kv, "run", n, n, timeout_s=timeout_s,
                              clock=clock.time)


def test_membership_join_view_evict_readmit():
    clock, kv = ManualClock(), KVStore()
    reg = _membership(kv, clock)
    anns = [MemberAnnouncer(kv, "run", p, [p], interval_s=0.5,
                            clock=clock.time) for p in range(3)]
    for a in anns:
        a.join()
    view = reg.update(step=0)
    assert view["members"] == [0, 1, 2] and view["epoch"] == 1
    np.testing.assert_array_equal(reg.mask(), np.ones(3, np.float32))
    # Process 1 goes silent past the timeout: evicted, epoch bumps, its
    # replica leaves the mask.
    clock.now += 5.0
    for a in (anns[0], anns[2]):
        a.beat(step=1, force=True)
    view = reg.update(step=1)
    assert view["members"] == [0, 2] and view["epoch"] == 2
    np.testing.assert_array_equal(reg.mask(),
                                  np.array([1, 0, 1], np.float32))
    assert reg.counters["evictions"] == 1
    # Readmission: a restarted process re-joins with a bumped incarnation.
    inc = anns[1].join()
    assert inc >= 2
    view = reg.update(step=2)
    assert view["members"] == [0, 1, 2] and view["epoch"] == 3
    # Followers read the leader's published view back off the KV.
    assert read_view(kv, "run")["epoch"] == 3


def test_membership_graceful_leave_counts_as_leave_not_eviction():
    clock, kv = ManualClock(), KVStore()
    reg = _membership(kv, clock)
    anns = [MemberAnnouncer(kv, "run", p, [p], clock=clock.time)
            for p in range(2)]
    for a in anns:
        a.join()
    reg.update(step=0)
    anns[1].leave()
    reg.update(step=1)
    assert reg.members == [0]
    assert reg.counters["leaves"] == 1 and reg.counters["evictions"] == 0


def test_membership_mask_all_ones_before_any_join():
    clock, kv = ManualClock(), KVStore()
    reg = _membership(kv, clock)
    reg.update(step=0)
    # Nobody announced: degrade to the static world, never mask everyone out.
    np.testing.assert_array_equal(reg.mask(), np.ones(3, np.float32))


# ---- shard rebalancing ----

def test_plan_shards_matches_zero_chunking():
    plan = plan_shards(10, 3)
    assert plan.chunk == 4                  # ceil(10/3), zero.py's scheme
    assert plan.bounds == ((0, 4), (4, 8), (8, 10))
    assert plan.padded == 12
    wide = plan_shards(3, 5)                # trailing shards empty, valid
    assert wide.bounds[3] == (3, 3) and wide.bounds[4] == (3, 3)
    with pytest.raises(ValueError):
        plan_shards(0, 3)


def test_reslice_is_bitwise_neutral():
    rng = np.random.default_rng(0)
    full = rng.standard_normal(11).astype(np.float32)
    old, new = plan_shards(11, 2), plan_shards(11, 4)
    shards = [full[lo:hi] for lo, hi in old.bounds]
    out = reslice(old, new, shards)
    np.testing.assert_array_equal(np.concatenate(out), full)
    with pytest.raises(ValueError):
        reslice(old, plan_shards(12, 4), shards)


def _drivers(kv, members, size, p0, lr, momentum):
    ds = {}
    for m in members:
        d = ShardedKVUpdate(kv, "s", size, members, m, lr,
                            momentum=momentum, sleep=_noop, timeout_s=0.1)
        d.init(p0)
        ds[m] = d
    return ds


def _round(drivers, grad):
    # Single-threaded collective discipline: publish ALL, then assemble ALL.
    for d in drivers.values():
        d.publish(grad)
    outs = [d.assemble() for d in drivers.values()]
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])
    return outs[0]


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_sharded_update_bitwise_equals_replicated(momentum):
    rng = np.random.default_rng(7)
    size, lr = 13, 0.05
    p0 = rng.standard_normal(size).astype(np.float32)
    grads = [rng.standard_normal(size).astype(np.float32) for _ in range(6)]
    kv = KVStore()
    drivers = _drivers(kv, [0, 1, 2], size, p0, lr, momentum)
    full = None
    for g in grads:
        full = _round(drivers, g)
    ref = ShardedKVUpdate.replicated_reference(p0, grads, lr, momentum)
    np.testing.assert_array_equal(full, ref)    # bitwise, not allclose


def test_sharded_update_exact_across_rebalances():
    """The exactness guard of the ISSUE: shrink (eviction), grow (joiners),
    full replacement — after every membership change the sharded update
    still equals the replicated recurrence bit-for-bit, momentum included."""
    rng = np.random.default_rng(11)
    size, lr, mu = 29, 0.1, 0.9
    p0 = rng.standard_normal(size).astype(np.float32)
    grads = [rng.standard_normal(size).astype(np.float32) for _ in range(9)]
    kv = KVStore()
    drivers = _drivers(kv, [0, 1, 2], size, p0, lr, mu)
    applied = []

    def run_rounds(gs):
        out = None
        for g in gs:
            out = _round(drivers, g)
            applied.append(g)
        return out

    run_rounds(grads[:3])
    # Shrink: member 1 evicted. Its shard (params AND momentum) moves
    # through the KV to the survivors.
    for d in drivers.values():
        d.handoff([0, 2])
    for d in drivers.values():
        d.adopt([0, 2])
    drivers = {m: d for m, d in drivers.items() if m in (0, 2)}
    assert all(d.epoch == 2 for d in drivers.values())
    run_rounds(grads[3:5])
    # Grow: two joiners. A joiner is constructed against the CURRENT set
    # (what it reads from the published view), then rebalances with it.
    new_members = [0, 2, 3, 4]
    for m in (3, 4):
        j = ShardedKVUpdate(kv, "s", size, [0, 2], m, lr, momentum=mu,
                            sleep=_noop, timeout_s=0.1)
        j.epoch = drivers[0].epoch          # join at the current epoch
        j.round = drivers[0].round
        drivers[m] = j
    for d in drivers.values():
        d.handoff(new_members)
    for d in drivers.values():
        d.adopt(new_members)
    run_rounds(grads[5:7])
    # Full replacement: everyone hands off to one fresh member.
    lone = ShardedKVUpdate(kv, "s", size, new_members, 7, lr, momentum=mu,
                           sleep=_noop, timeout_s=0.1)
    lone.epoch, lone.round = drivers[0].epoch, drivers[0].round
    drivers[7] = lone
    for d in drivers.values():
        d.handoff([7])
    for d in drivers.values():
        d.adopt([7])
    drivers = {7: lone}
    final = run_rounds(grads[7:])
    ref = ShardedKVUpdate.replicated_reference(p0, applied, lr, mu)
    np.testing.assert_array_equal(final, ref)
    assert lone.snapshot()["n_shards"] == 1


# ---- Coordinator failover ----

def _elastic_coordinator(kv, clock, pid, leader, n=2):
    el = _election(kv, pid, n=n, preferred=0, clock=clock)
    return Coordinator(4, mode="sync", kv=kv, leader=leader,
                       lease_interval_s=1.0, clock=clock.time,
                       election=el), el


def test_coordinator_failover_elects_follower():
    clock, kv = ManualClock(), KVStore()
    c0, el0 = _elastic_coordinator(kv, clock, 0, True)
    c1, el1 = _elastic_coordinator(kv, clock, 1, False)
    el0.claim_initial()
    c0.announce_step(1)
    np.testing.assert_array_equal(c0.participation_mask(1),
                                  np.ones(4, np.float32))
    np.testing.assert_array_equal(c1.participation_mask(1, timeout_s=5.0),
                                  np.ones(4, np.float32))
    # Leader dies (stops refreshing); the follower's wait for step 2's
    # mask fails over: campaign -> win -> decide+publish the mask itself.
    clock.now += 10.0
    mask = c1.participation_mask(2, timeout_s=5.0)
    np.testing.assert_array_equal(mask, np.ones(4, np.float32))
    assert c1.leader and el1.is_leader and el1.epoch == 2
    assert c1.stats["leader_lost"] == 1 and c1.stats["elections"] == 1
    assert any(e["event"] == "elected" for e in c1.events)
    # The old leader comes back: its refresh hits the fence, it demotes,
    # and it CONSUMES the new leader's mask instead of publishing its own.
    np.testing.assert_array_equal(c0.participation_mask(2, timeout_s=5.0),
                                  np.ones(4, np.float32))
    assert not c0.leader and c0.stats["deposed"] == 1
    assert el0.epoch == 2 and el0.owner == 1


def test_coordinator_without_election_unchanged():
    # The legacy contract: no election wired -> LeaderLost still raises.
    from ps_pytorch_tpu.runtime.coordinator import LeaderLost
    clock, kv = ManualClock(), KVStore()
    leader = Coordinator(4, mode="sync", kv=kv, leader=True,
                         lease_interval_s=1.0, clock=clock.time)
    follower = Coordinator(4, mode="sync", kv=kv, leader=False,
                           lease_interval_s=1.0, clock=clock.time)
    leader.announce_step(1)
    leader.participation_mask(1)
    follower.participation_mask(1, timeout_s=5.0)
    clock.now += 10.0
    with pytest.raises(LeaderLost):
        follower.participation_mask(2, timeout_s=5.0)


# ---- fault kinds ----

def test_fault_spec_leader_kill_and_kv_partition_grammar():
    faults = parse_fault_spec("leader_kill:step=6;"
                              "kv_partition:r=1+2,step=5,steps=4")
    assert faults[0]["kind"] == "leader_kill" and faults[0]["step"] == 6
    assert faults[1]["r"] == [1, 2] and faults[1]["steps"] == 4
    assert parse_fault_spec("kv_partition:r=1,step=5")[0]["steps"] == 1
    for bad in ("leader_kill:p=0.5", "kv_partition:r=1",
                "kv_partition:r=x,step=2", "kv_partition:r=1,step=2,steps=0"):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)


def test_kv_partition_drops_only_named_processes_in_window():
    spec = "kv_partition:r=1,step=5,steps=2"
    inside = FaultInjector(spec, process_index=1)
    kv = inside.wrap_kv(KVStore())
    kv.set("a", "1")                        # before the window: clean
    inside.maybe_crash(5)                   # advance the fault clock
    with pytest.raises(TransientKVError, match="kv_partition"):
        kv.get("a")
    with pytest.raises(TransientKVError):
        kv.set("b", "2")
    inside.maybe_crash(7)                   # window [5, 7) closed
    assert kv.get("a") == "1"
    assert inside.snapshot()["kv_partition_drops"] == 2
    outside = FaultInjector(spec, process_index=0)
    kv0 = outside.wrap_kv(KVStore())
    outside.maybe_crash(5)
    kv0.set("a", "1")                       # not in r: never partitioned
    assert kv0.get("a") == "1"


def test_leader_kill_only_fires_on_leader_at_step():
    inj = FaultInjector("leader_kill:step=6", process_index=0)
    inj.maybe_kill_leader(5, is_leader=True)    # before the step: alive
    inj.maybe_kill_leader(9, is_leader=False)   # not the leader: alive
    assert inj.snapshot()["leader_kills"] == 0


def test_leader_kill_sigkills_the_leader_process():
    code = ("from ps_pytorch_tpu.resilience import FaultInjector; "
            "i = FaultInjector('leader_kill:step=3', process_index=0); "
            "i.maybe_kill_leader(3, is_leader=True); "
            "print('SURVIVED')")
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, cwd=repo)
    assert res.returncode == -signal.SIGKILL
    assert "SURVIVED" not in res.stdout
    assert "FAULT leader_kill" in res.stdout


# ---- config ----

def test_elastic_config_validation():
    cfg = TrainConfig(elastic=True, leader_lease_s=1.0, elastic_leader=1)
    assert cfg.elastic and cfg.elastic_leader == 1
    with pytest.raises(ValueError, match="leader_lease_s"):
        TrainConfig(elastic=True)
    with pytest.raises(ValueError, match="elastic_leader"):
        TrainConfig(elastic=True, leader_lease_s=1.0, elastic_leader=-1)


# ---- trainer identity (elastic on vs off, no faults) ----

def test_trainer_elastic_bit_identical_to_static(tmp_path):
    """--elastic with no faults must be a no-op on the MATH: same seed,
    same steps, final params bitwise-identical to the static run (the
    mask stays all-ones, the control plane only watches)."""
    from ps_pytorch_tpu.runtime.trainer import Trainer

    def run(elastic, d):
        cfg = TrainConfig(dataset="synthetic_mnist", network="LeNet",
                          batch_size=64, lr=0.05, momentum=0.9,
                          max_steps=4, epochs=0, eval_freq=2,
                          train_dir=str(tmp_path / d),
                          compute_dtype="float32", data_axis=8,
                          log_every=2, seed=5, elastic=elastic,
                          leader_lease_s=1.0 if elastic else 0.0)
        t = Trainer(cfg)
        t.train()
        return jax.device_get(t.state.params)

    static = run(False, "a")
    elastic = run(True, "b")
    flat_s = jax.tree.leaves(static)
    flat_e = jax.tree.leaves(elastic)
    assert len(flat_s) == len(flat_e)
    for a, b in zip(flat_s, flat_e):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- telemetry surfaces ----

def test_elastic_metrics_declared():
    from ps_pytorch_tpu.telemetry import Registry, declare_elastic_metrics
    r = declare_elastic_metrics(Registry())
    r.inc("membership_changes")
    r.inc("elections")
    r.set("leader_epoch", 3.0)
    r.set("world_size", 2.0)
    from ps_pytorch_tpu.telemetry.prometheus import render
    text = render(r)
    assert "membership_changes_total 1" in text
    assert "leader_epoch 3" in text


def test_analyze_membership_mode(tmp_path, capsys):
    flight = {"kind": "flight_recorder", "pid": 11, "events": [
        {"kind": "membership", "event": "join", "pid": 0, "step": 0,
         "t": 5.0},
        {"kind": "election", "event": "elected", "pid": 1, "epoch": 2,
         "t": 6.0},
        {"kind": "shard_replan", "epoch": 2, "t": 6.1},
    ]}
    p = tmp_path / "flightrec.json"
    p.write_text(json.dumps(flight))
    from ps_pytorch_tpu.tools.analyze import main
    assert main(["membership", str(p), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["summary"]["max_epoch"] == 2
    assert out["summary"]["counts"]["elected"] == 1


def test_elastic_drill_verdict(committed_record):
    from ps_pytorch_tpu.tools.elastic_drill import verdict
    good = committed_record("RESILIENCE_r11.json")
    assert verdict(good) == []
    # a drill in which nobody was elected proved nothing
    assert verdict(dict(good, elastic={"elections": 0}))
    # no sections: a result's own "ok" is not believed
    assert verdict({"ok": True})


def test_checkpoint_meta_carries_leader_epoch(tmp_path):
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    state = {"params": {"w": np.ones(4, np.float32)},
             "opt_state": {"w": np.zeros(4, np.float32)}}
    ckpt.save_checkpoint(str(tmp_path), 3, state,
                         extra_meta={"leader_epoch": 2, "leader_pid": 1})
    got = ckpt.load_latest_valid(str(tmp_path), state)
    assert got is not None
    _, meta, _, _ = got
    assert meta["leader_epoch"] == 2 and meta["leader_pid"] == 1
