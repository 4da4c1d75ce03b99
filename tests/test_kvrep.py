"""Quorum-replicated coordination plane tests (runtime/kvrep.py): tagged
envelopes, majority writes, newest-of-quorum reads with read-repair,
ejection/probation/rejoin with anti-entropy resync, the per-backend fault
kinds, composition with the retry plane, FileKV durability ordering, and
the config-time safety checks — all real-time-free (ManualClock)."""

import os
import threading

import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.resilience import (
    FaultInjector, ManualClock, RetryBudget, RetryingKV, RetryPolicy,
    TransientKVError, is_retryable,
)
from ps_pytorch_tpu.runtime.coordinator import FileKV, KVStore
from ps_pytorch_tpu.runtime.kvrep import (
    HttpKV, ReplicatedKV, build_replicated_kv, parse_backend_specs,
    serve_kv, unwrap_value, wrap_value,
)
from ps_pytorch_tpu.utils.armor import WireCorrupt


def _rkv(n=3, **kw):
    backends = [KVStore() for _ in range(n)]
    kw.setdefault("clock", ManualClock().time)
    return ReplicatedKV(backends, **kw), backends


class _FlakyKV(KVStore):
    """Backend whose every op raises while ``down`` — a SIGKILLed store."""

    def __init__(self):
        super().__init__()
        self.down = False

    def _gate(self):
        if self.down:
            raise TransientKVError("UNAVAILABLE: backend down (test)")

    def set(self, key, value):
        self._gate()
        super().set(key, value)

    def get(self, key, default=None):
        self._gate()
        return super().get(key, default)

    def delete(self, key):
        self._gate()
        super().delete(key)

    def keys(self, prefix=""):
        self._gate()
        return super().keys(prefix)


# ---- envelope ----

def test_envelope_roundtrip_and_unframed():
    env = wrap_value(7, "p3", "hello\nworld")
    tag, val = unwrap_value(env)
    assert tag == (7, "p3") and val == "hello\nworld"
    # Unframed (pre-replication) text is valid but oldest possible.
    assert unwrap_value("plain") == ((0, ""), "plain")
    assert unwrap_value(None) == (None, None)
    # A garbled header degrades to unframed, never crashes.
    assert unwrap_value("@kvr1 notanint p0\nx")[0] == (0, "")


def test_tag_ordering_version_then_writer():
    # Version dominates; the writer string breaks exact-version duels the
    # same way for every reader.
    assert (3, "p9") > (2, "p0")
    assert (3, "p2") > (3, "p1")


# ---- quorum basics ----

def test_set_get_delete_keys_roundtrip():
    rkv, backends = _rkv()
    rkv.set("a/x", "1")
    rkv.set("a/y", "2")
    rkv.set("b/z", "3")
    assert rkv.get("a/x") == "1"
    assert rkv.get("missing", "dflt") == "dflt"
    assert rkv.keys("a/") == ["a/x", "a/y"]
    rkv.delete("a/x")
    assert rkv.get("a/x") is None
    # Every backend holds the surviving keys as tagged envelopes.
    for b in backends:
        tag, val = unwrap_value(b.get("a/y"))
        assert tag == (1, "w0") and val == "2"


def test_quorum_bounds_enforced():
    with pytest.raises(ValueError):
        _rkv(quorum=1)          # two quorums of 1 of 3 need not overlap
    with pytest.raises(ValueError):
        _rkv(quorum=4)          # more acks than backends
    rkv, _ = _rkv(quorum=3)     # all-acks is safe (if fragile)
    assert rkv.quorum == 3


def test_writer_id_must_fit_envelope():
    with pytest.raises(ValueError):
        _rkv(writer="p 0")
    with pytest.raises(ValueError):
        _rkv(writer="p\n0")


def test_observed_version_bump_orders_read_modify_write():
    """A client that READ version 7 writes 8, even though its own counter
    never issued 7 — the ordering lease claimants depend on."""
    rkv, backends = _rkv(writer="p0")
    for b in backends:
        b.set("lease", wrap_value(7, "p9", "held-by-p9"))
    assert rkv.get("lease") == "held-by-p9"
    rkv.set("lease", "held-by-p0")
    tag, val = unwrap_value(backends[0].get("lease"))
    assert tag == (8, "p0") and val == "held-by-p0"


def test_concurrent_duel_resolves_identically_everywhere():
    rkv, backends = _rkv()
    # Same version from two writers on different replicas: every reader
    # must pick the same winner (higher writer string).
    backends[0].set("k", wrap_value(5, "p1", "from-p1"))
    backends[1].set("k", wrap_value(5, "p2", "from-p2"))
    backends[2].set("k", wrap_value(5, "p2", "from-p2"))
    assert rkv.get("k") == "from-p2"


# ---- read-repair ----

def test_read_repair_heals_missing_and_stale_copies():
    rkv, backends = _rkv()
    rkv.set("k", "v1")
    backends[2].delete("k")                              # lost copy
    backends[1].set("k", wrap_value(0, "", "ancient"))   # stale copy
    assert rkv.get("k") == "v1"
    assert rkv.counters["kvrep_read_repairs"] >= 2
    for b in backends:
        tag, val = unwrap_value(b.get("k"))
        assert val == "v1" and tag == (1, "w0")


def test_unframed_find_is_reframed_before_repair():
    rkv, backends = _rkv()
    backends[0].set("legacy", "old-data")    # pre-replication value
    backends[1].delete("legacy")
    assert rkv.get("legacy") == "old-data"
    # (0, "") never wins a repair race, so nothing propagates — but a
    # TAGGED write over it wins everywhere.
    rkv.set("legacy", "new-data")
    for b in backends:
        assert unwrap_value(b.get("legacy"))[1] == "new-data"


# ---- health: ejection, probation, rejoin resync ----

def test_sub_quorum_outage_is_absorbed_then_backend_ejected():
    clock = ManualClock()
    backends = [KVStore(), KVStore(), _FlakyKV()]
    rkv = ReplicatedKV(backends, clock=clock.time, resync_s=1.0, seed=5)
    rkv.set("k0", "v0")
    backends[2].down = True
    rkv.set("k1", "v1")                 # 2/3 acks — fine
    rkv.set("k2", "v2")                 # second consecutive failure ejects
    assert rkv.healthy_count() == 2
    assert rkv.counters["kvrep_ejections"] == 1
    # Ejected backend sits out: ops stop even TRYING it.
    errs = rkv.counters["kvrep_backend_errors"]
    rkv.set("k3", "v3")
    assert rkv.counters["kvrep_backend_errors"] == errs


def test_probation_rejoin_resyncs_to_tag_equality():
    clock = ManualClock()
    backends = [KVStore(), KVStore(), _FlakyKV()]
    rkv = ReplicatedKV(backends, clock=clock.time, resync_s=1.0, seed=5)
    backends[2].down = True
    rkv.set("a", "1")
    rkv.set("b", "2")                   # ejection point
    rkv.set("c", "3")                   # missed by backend 2
    rkv.delete("a")
    backends[2].down = False            # the process came back...
    clock.advance(1.0)                  # ...and probation expired
    rkv.get("c")                        # any op runs the probe + resync
    assert rkv.healthy_count() == 3
    assert rkv.counters["kvrep_rejoins"] == 1
    assert rkv.counters["kvrep_resyncs"] == 1
    assert rkv.backend_tags(2) == rkv.backend_tags(0)
    assert unwrap_value(backends[2].get("c"))[1] == "3"


def test_failed_probe_grows_backoff():
    clock = ManualClock()
    backends = [KVStore(), KVStore(), _FlakyKV()]
    rkv = ReplicatedKV(backends, clock=clock.time, resync_s=1.0, seed=5)
    backends[2].down = True
    rkv.set("a", "1")
    rkv.set("b", "2")
    clock.advance(1.0)
    rkv.get("a")                        # probe fires, backend still down
    assert rkv.counters["kvrep_probes"] == 1
    assert rkv.counters["kvrep_rejoins"] == 0
    # Second probe deadline is further out (2x base, jittered <= 2.0).
    clock.advance(0.5)
    rkv.get("a")
    assert rkv.counters["kvrep_probes"] == 1    # not due yet


def test_total_outage_raises_transient_unavailable():
    backends = [_FlakyKV(), _FlakyKV(), _FlakyKV()]
    rkv = ReplicatedKV(backends, clock=ManualClock().time)
    for b in backends:
        b.down = True
    with pytest.raises(TransientKVError, match="UNAVAILABLE"):
        rkv.set("k", "v")
    with pytest.raises(TransientKVError):
        rkv.get("k")
    with pytest.raises(TransientKVError):
        rkv.keys("")
    assert rkv.counters["kvrep_quorum_failures"] == 3


def test_resync_deletes_majority_absent_keys():
    """A key no healthy backend holds was never committed (or was GC'd) —
    the rejoiner must not resurrect it."""
    clock = ManualClock()
    backends = [KVStore(), KVStore(), _FlakyKV()]
    rkv = ReplicatedKV(backends, clock=clock.time, resync_s=1.0, seed=5)
    rkv.set("keep", "v")
    backends[2].set("orphan", wrap_value(9, "p9", "sub-quorum junk"))
    backends[2].down = True
    rkv.set("x1", "1")
    rkv.set("x2", "2")                  # ejects backend 2
    backends[2].down = False
    clock.advance(1.0)
    rkv.get("keep")                     # rejoin + resync
    assert backends[2].get("orphan") is None
    assert rkv.backend_tags(2) == rkv.backend_tags(0)


def test_gauges_and_snapshot_shapes():
    rkv, _ = _rkv()
    assert rkv.gauges() == {"kvrep_backends": 3.0,
                            "kvrep_backends_healthy": 3.0}
    snap = rkv.snapshot()
    assert snap["kvrep_ejections"] == 0 and "kvrep_resync_keys" in snap


# ---- per-backend fault kinds (kv_backend_kill / kv_backend_wipe) ----

def _mem_cfg(**kw):
    base = dict(dataset="synthetic_mnist", network="LeNet", batch_size=64,
                lr=0.01, max_steps=4, epochs=0, data_axis=8, seed=3,
                kv_replicas="mem:,mem:,mem:", kv_resync_s=1.0)
    base.update(kw)
    return TrainConfig(**base)


def test_backend_kill_window_absorbed_inside_quorum():
    clock = ManualClock()
    inj = FaultInjector("kv_backend_kill:backend=1,step=2,steps=2",
                        process_index=0)
    rkv = build_replicated_kv(_mem_cfg(), process_index=0, injector=inj,
                              clock=clock.time)
    rkv.set("k0", "v0")                 # step 0: all healthy
    inj.maybe_crash(2)                  # window opens
    rkv.set("k1", "v1")                 # backend 1 drops, 2/3 acks
    rkv.set("k2", "v2")                 # second failure ejects it
    assert inj.counters["kv_backend_kills"] == 1
    assert inj.counters["kv_backend_drops"] >= 2
    assert rkv.healthy_count() == 2
    assert rkv.get("k1") == "v1"        # callers never saw the outage
    inj.maybe_crash(4)                  # window closed
    clock.advance(1.0)
    rkv.get("k0")                       # probe + resync readmits
    assert rkv.counters["kvrep_rejoins"] == 1
    assert rkv.backend_tags(1) == rkv.backend_tags(0)


def test_backend_wipe_masked_then_repaired():
    clock = ManualClock()
    inj = FaultInjector("kv_backend_wipe:backend=2,step=3",
                        process_index=0)
    rkv = build_replicated_kv(_mem_cfg(), process_index=0, injector=inj,
                              clock=clock.time)
    rkv.set("a", "1")
    rkv.set("b", "2")
    inj.maybe_crash(3)
    # The wiped backend answers (empty) — newest-of-quorum masks it and
    # read-repair writes the lost copy straight back.
    assert rkv.get("a") == "1"
    assert inj.counters["kv_backend_wipes"] == 1
    assert rkv.counters["kvrep_read_repairs"] >= 1
    # One forced anti-entropy pass finishes the repair key-by-key.
    rkv.resync_backend(2)
    assert rkv.backend_tags(2) == rkv.backend_tags(0)


def test_wrap_backend_identity_when_index_not_named():
    inj = FaultInjector("kv_backend_kill:backend=1,step=0", process_index=0)
    kv = KVStore()
    assert inj.wrap_backend(kv, 0) is kv
    assert inj.wrap_backend(kv, 1) is not kv
    assert inj.has_backend_faults
    # Backend kinds are NOT logical-KV kinds: wrap_kv stays identity.
    assert inj.wrap_kv(kv) is kv and not inj.has_kv_faults


@pytest.mark.parametrize("bad", [
    "kv_backend_kill:step=1",                    # missing backend
    "kv_backend_kill:backend=-1,step=1",         # negative index
    "kv_backend_kill:backend=0",                 # missing step
    "kv_backend_kill:backend=0,step=1,steps=-2",
    "kv_backend_wipe:backend=0",                 # missing step
])
def test_backend_fault_spec_rejects(bad):
    from ps_pytorch_tpu.resilience import parse_fault_spec
    with pytest.raises(ValueError):
        parse_fault_spec(bad)


# ---- composition with the retry plane (satellite: RetryingKV outside) ----

def test_retrying_over_replicated_sub_quorum_costs_no_budget():
    """One dead backend of three is the replication layer's problem: the
    logical op succeeds first try, the budget is untouched."""
    backends = [KVStore(), KVStore(), _FlakyKV()]
    rkv = ReplicatedKV(backends, clock=ManualClock().time)
    backends[2].down = True
    budget = RetryBudget(10)
    retrier = RetryingKV(rkv, policy=RetryPolicy(max_attempts=3, seed=1),
                         budget=budget, sleep=lambda s: None)
    retrier.set("k", "v")
    assert retrier.get("k") == "v"
    assert retrier.keys("") == ["k"]    # scans ride the same composition
    assert retrier.counters == {"kv_retries": 0, "kv_giveups": 0}
    assert budget.spent == 0


def test_retrying_over_replicated_quorum_loss_charged_per_logical_op():
    """Quorum loss surfaces as ONE retryable logical failure per op —
    attempts-1 budget per op, never per backend."""
    backends = [_FlakyKV(), _FlakyKV(), _FlakyKV()]
    rkv = ReplicatedKV(backends, clock=ManualClock().time)
    for b in backends:
        b.down = True
    budget = RetryBudget(10)
    retrier = RetryingKV(rkv, policy=RetryPolicy(max_attempts=3, seed=1),
                         budget=budget, sleep=lambda s: None)
    with pytest.raises(TransientKVError, match="UNAVAILABLE"):
        retrier.set("k", "v")
    assert retrier.counters["kv_retries"] == 2      # max_attempts - 1
    assert retrier.counters["kv_giveups"] == 1
    assert budget.spent == 2


def test_retrying_recovers_when_quorum_returns_mid_op():
    backends = [_FlakyKV(), _FlakyKV(), KVStore()]
    rkv = ReplicatedKV(backends, clock=ManualClock().time, eject_after=5)
    backends[0].down = backends[1].down = True
    heal = {"n": 0}

    def sleep(_s):
        heal["n"] += 1
        backends[0].down = False        # quorum back before the retry

    retrier = RetryingKV(rkv, policy=RetryPolicy(max_attempts=3, seed=1),
                         budget=RetryBudget(10), sleep=sleep)
    retrier.set("k", "v")
    assert heal["n"] == 1 and retrier.counters["kv_retries"] == 1
    assert retrier.get("k") == "v"


def test_wire_corrupt_is_fatal_not_retryable():
    """Corrupt payload is a data error, not an outage: retrying re-reads
    the same poisoned bytes and burns budget for nothing."""
    assert not is_retryable(WireCorrupt("armor checksum mismatch"))
    assert is_retryable(TransientKVError("UNAVAILABLE: quorum write"))

    class _Corrupting(KVStore):
        def get(self, key, default=None):
            raise WireCorrupt("bad frame")

    retrier = RetryingKV(_Corrupting(), sleep=lambda s: None)
    with pytest.raises(WireCorrupt):
        retrier.get("k")
    assert retrier.counters == {"kv_retries": 0, "kv_giveups": 0}


# ---- HTTP backend pair ----

def test_http_backend_roundtrip_and_kill():
    srv = serve_kv(0)                   # ephemeral port
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever,
                         kwargs={"poll_interval": 0.05}, daemon=True)
    t.start()
    try:
        kv = HttpKV(f"http://127.0.0.1:{port}", timeout_s=2.0)
        kv.set("a/b c", "v1\nline2")    # keys/values survive quoting
        assert kv.get("a/b c") == "v1\nline2"
        assert kv.get("missing", "d") == "d"
        assert kv.keys("a/") == ["a/b c"]
        kv.delete("a/b c")
        assert kv.get("a/b c") is None
    finally:
        srv.shutdown()
        srv.server_close()
        t.join(timeout=5)
    # A dead backend is an UNAVAILABLE transient, same as a gRPC outage.
    with pytest.raises(TransientKVError, match="UNAVAILABLE"):
        HttpKV(f"http://127.0.0.1:{port}", timeout_s=0.3).get("a")


def test_replicated_over_http_survives_one_dead_server(tmp_path):
    srvs = [serve_kv(0) for _ in range(3)]
    threads = [threading.Thread(target=s.serve_forever,
                                kwargs={"poll_interval": 0.05}, daemon=True)
               for s in srvs]
    for t in threads:
        t.start()
    try:
        rkv = ReplicatedKV(
            [HttpKV(f"http://127.0.0.1:{s.server_address[1]}",
                    timeout_s=1.0) for s in srvs],
            clock=ManualClock().time)
        rkv.set("k", "v")
        srvs[1].shutdown()              # one backend dies mid-run
        srvs[1].server_close()
        assert rkv.get("k") == "v"
        rkv.set("k2", "v2")
        assert rkv.get("k2") == "v2"
    finally:
        for s in (srvs[0], srvs[2]):
            s.shutdown()
            s.server_close()
        for t in threads:
            t.join(timeout=5)


# ---- spec plumbing ----

def test_parse_backend_specs_grammar():
    assert parse_backend_specs("dir:/a, http://h:1,mem:") == \
        ["dir:/a", "http://h:1", "mem:"]
    assert parse_backend_specs("") == []
    with pytest.raises(ValueError):
        parse_backend_specs("ftp://nope")
    with pytest.raises(ValueError):
        parse_backend_specs("/bare/path")


def test_build_replicated_kv_writer_identity(tmp_path):
    cfg = _mem_cfg(kv_replicas=f"dir:{tmp_path}/a,mem:,mem:", kv_quorum=2)
    rkv = build_replicated_kv(cfg, process_index=7)
    assert rkv.writer == "p7" and rkv.quorum == 2 and rkv.n == 3
    assert isinstance(rkv._backends[0].kv, FileKV)
    with pytest.raises(ValueError):
        build_replicated_kv(_mem_cfg(kv_replicas=""), process_index=0)


# ---- config-time safety (satellite: reject inversions before the run) ----

def test_config_rejects_heartbeat_inversions():
    with pytest.raises(ValueError, match="heartbeat_timeout_s"):
        _mem_cfg(heartbeat_interval_s=2.0, heartbeat_timeout_s=1.0)
    with pytest.raises(ValueError, match="heartbeat_timeout_s"):
        _mem_cfg(heartbeat_interval_s=2.0, heartbeat_timeout_s=2.0)
    with pytest.raises(ValueError, match="leader_lease_s"):
        _mem_cfg(heartbeat_timeout_s=1.0, leader_lease_s=2.0,
                 heartbeat_interval_s=0.5)
    # Healthy orderings still pass.
    cfg = _mem_cfg(heartbeat_interval_s=0.5, heartbeat_timeout_s=2.0,
                   leader_lease_s=1.0)
    assert cfg.heartbeat_timeout_s == 2.0


def test_config_rejects_unsafe_quorum_and_bad_specs():
    with pytest.raises(ValueError, match="kv_quorum"):
        _mem_cfg(kv_quorum=1)           # 1 of 3: split-brain-capable
    with pytest.raises(ValueError, match="kv_quorum"):
        _mem_cfg(kv_quorum=4)
    with pytest.raises(ValueError, match="kv replica spec"):
        _mem_cfg(kv_replicas="mem:,bogus-spec")
    with pytest.raises(ValueError, match="kv_resync_s"):
        _mem_cfg(kv_resync_s=0.0)
    assert _mem_cfg(kv_quorum=3).kv_quorum == 3


# ---- FileKV durability ordering (satellite: fsync before/after rename) ----

def test_filekv_set_fsyncs_data_before_rename_and_dir_after(
        tmp_path, monkeypatch):
    """Pin the commit protocol by interposing on the syscalls: the DATA
    fsync must precede os.replace, and a DIRECTORY fsync must follow it —
    otherwise a power cut can commit the rename with the bytes still in
    the page cache."""
    events = []
    real_fsync, real_replace = os.fsync, os.replace
    monkeypatch.setattr(
        os, "fsync",
        lambda fd: (events.append("fsync"), real_fsync(fd))[1])
    monkeypatch.setattr(
        os, "replace",
        lambda a, b: (events.append("replace"), real_replace(a, b))[1])
    kv = FileKV(str(tmp_path / "kv"))
    events.clear()                      # drop any mkdir-era noise
    kv.set("k", "v")
    assert events == ["fsync", "replace", "fsync"]
    assert kv.get("k") == "v"


def test_filekv_failed_write_leaves_no_tmp_litter(tmp_path, monkeypatch):
    kv = FileKV(str(tmp_path / "kv"))
    monkeypatch.setattr(os, "replace",
                        lambda a, b: (_ for _ in ()).throw(OSError("disk")))
    with pytest.raises(OSError):
        kv.set("k", "v")
    assert os.listdir(str(tmp_path / "kv")) == []


# ---- trainer wiring smoke ----

def test_trainer_runs_over_replicated_kv(tmp_path):
    """End-to-end: elastic single-process training with the control plane
    on a 3-way ReplicatedKV — completes, and the kvrep counters surface
    through resilience_stats."""
    from ps_pytorch_tpu.runtime.trainer import Trainer
    cfg = _mem_cfg(train_dir=str(tmp_path / "ckpt"), max_steps=4,
                   eval_freq=0, log_every=2, elastic=1, leader_lease_s=5.0,
                   compute_dtype="float32", momentum=0.9)
    t = Trainer(cfg)
    assert t._kvrep is not None and t._kvrep.n == 3
    t.train()
    stats = t.resilience_stats()
    assert stats["kvrep_quorum_failures"] == 0
    assert t._kvrep.healthy_count() == 3


# ---- the drill's verdict ----

def test_kvrep_drill_verdict(committed_record):
    from ps_pytorch_tpu.tools.kvrep_drill import verdict
    good = committed_record("RESILIENCE_r17.json")
    assert verdict(good) == []

    def phase(name, **kw):
        return dict(good, kvrep=dict(
            good["kvrep"], **{name: dict(good["kvrep"][name], **kw)}))

    # every lifecycle floor is demanded independently
    for key in ("backend_kills", "backend_wipes", "rejoins", "resyncs"):
        bad = dict(good, kvrep=dict(good["kvrep"], **{key: 0}))
        assert verdict(bad) == [f"{key} >= 1"]
    # a retry giveup means the quorum failed to mask the outage
    assert verdict(phase("train", giveups=1)) == [
        "train: zero retry give-ups"]
    # the reborn backend must come back to key-by-key tag equality
    assert verdict(phase("train", resync_tag_equal=False)) == [
        "train: the reborn backend is tag-equal key by key"]
    # serving availability stays 1.00 through the wipe, with zero 5xx
    assert verdict(phase("serve", availability=0.99)) == [
        "serve: availability 1.00 through the wipe"]
    assert verdict(phase("serve", failed_5xx=2)) == [
        "serve: zero client 5xx"]
    # a CPU clock is no part of the rule: the record's old overhead is ignored
    assert verdict(phase("overhead", overhead_frac=0.05)) == []
    assert verdict({"ok": True})   # no sections; its own "ok" is not believed


def test_kvrep_drill_verdict_passes_committed_record(committed_record):
    """The committed round-17 record must pass the verdict of the drill that
    wrote it, with or without its old overhead section."""
    from ps_pytorch_tpu.tools.kvrep_drill import verdict
    rec = committed_record("RESILIENCE_r17.json")
    assert "overhead" in rec["kvrep"]
    assert verdict(rec) == []
    del rec["kvrep"]["overhead"]
    assert verdict(rec) == []
