"""Each drill's pass rule, one invariant at a time.

``tools/<x>_drill.py:verdict(result)`` is the whole rule a drill exits by.
The committed RESILIENCE_r*.json records are the good inputs; every case
below breaks exactly one fact of one record and expects exactly that
invariant named. (The floors the drills' own test files already break —
tests/test_{elastic,hierarchy,integrity,kvrep}.py — are not repeated.)
"""

import copy
import importlib

import pytest

RECORDS = {"elastic": "RESILIENCE_r11.json", "hierarchy": "RESILIENCE_r14.json",
           "router": "RESILIENCE_r15.json", "poison": "RESILIENCE_r16.json",
           "kvrep": "RESILIENCE_r17.json"}

_FINITE4 = {"0": 2.3, "1": 2.3, "2": 2.3, "3": 2.3}

# (drill, dotted path into the record, broken value, words of the invariant)
CASES = [
    ("elastic", "phases.failover.rc", 2, "launcher ran"),
    ("elastic", "counters.leader_kills", 0, "SIGKILLed"),
    ("elastic", "elastic.elections", 2, "exactly one election"),
    ("elastic", "elastic.final_epoch", 1, "epoch >= 2"),
    ("elastic", "phases.failover.survivors_finished", 1, "survivors finished"),
    ("elastic", "elastic.membership_changes", 3, "folded at least one"),
    ("elastic", "counters.kv_giveups", 1, "never gave up"),
    ("elastic", "phases.rebalance.rc", 3, "exited 0"),
    ("elastic", "bitwise_equal", False, "bitwise equal"),
    ("elastic", "phases.rebalance.membership.epoch", 2, "bumped the view"),

    ("hierarchy", "phases.partition.rc", 2, "launcher ran"),
    ("hierarchy", "phases.partition.declared_at_version", -1, "declared"),
    ("hierarchy", "phases.partition.regrafted_at_version", -1, "re-grafted"),
    ("hierarchy", "phases.partition.finished", 3, "every process finished"),
    ("hierarchy", "counters.kv_partition_drops", 0, "dropped KV ops"),
    ("hierarchy", "hierarchy.groups_healthy_final", 1, "both groups healthy"),
    ("hierarchy", "phases.bitwise.counters.partitions", 0,
     "bitwise replay: partitions"),
    ("hierarchy", "phases.bitwise.counters.regrafts", 0,
     "bitwise replay: regrafts"),
    ("hierarchy", "phases.bitwise.counters.degraded_steps", 0,
     "bitwise replay: degraded_steps"),
    ("hierarchy", "phases.bitwise.bitwise_equal", False, "same bits"),

    ("router", "bitwise_equal", False, "same tokens"),
    ("router", "router.kill.replica_kills", 0, "SIGKILLed"),
    ("router", "router.kill.failed_5xx", 1, "kill: zero client 5xx"),
    ("router", "router.kill.availability", 0.98, "availability"),
    ("router", "router.reload.failed_5xx", 1, "none failed"),
    ("router", "router.reload.requests", 0, "requests flowed"),
    ("router", "router.reload.replicas_rolled", 2, "every one of them rolled"),
    ("router", "router.reload.model_step_advanced", False, "new model step"),
    ("router", "router.hedge.p99_ratio", 1.0, "hedged p99 below"),
    ("router", "router.hedge.hedges", 0, "one hedge fired"),
    ("router", "router.hedge.failed_5xx", 1, "hedge: zero client 5xx"),

    ("poison", "phases.clean.finals.0", 11.0, "clean: every process"),
    ("poison", "phases.clean.finals", {"0": 2.2}, "clean: every process"),
    ("poison", "phases.poison.finals.2", float("nan"),
     "poison: every process finished"),
    ("poison", "phases.poison.quarantined_at_version", -1, "was quarantined"),
    ("poison", "phases.poison.readmitted_at_version", -1, "readmitted"),
    ("poison", "counters.grad_poisons", 2, "fault plane poisoned"),
    ("poison", "counters.payload_bitflips", 0, "fault plane poisoned"),
    ("poison", "integrity.loss_gap", 0.8, "within 0.75"),
    ("poison", "phases.control.rc", 2, "control:"),
    ("poison", "bitwise_equal", False, "bit for bit"),
    ("poison", "phases.bitwise.counters.integrity_quarantined", 1,
     "nobody quarantined"),
    ("poison", "phases.poison_ef",
     {"rc": 0, "finals": _FINITE4, "quarantined_at_version": -1},
     "poison_ef:"),

    ("kvrep", "kvrep.train.rc", 1, "train: every process exited 0"),
    ("kvrep", "kvrep.train.finals", 2, "train: every process exited 0"),
    ("kvrep", "kvrep.train.kills", 0, "train: a backend was SIGKILLed"),
    ("kvrep", "kvrep.train.rejoins", 0, "train: the clients rejoined"),
    ("kvrep", "kvrep.train.max_steps", 25, "every version"),
    ("kvrep", "counters.kv_giveups", 1, "never gave up"),
    ("kvrep", "kvrep.serve.offered", 61, "every offered request"),
    ("kvrep", "kvrep.serve.min_fleet_view", 2, "never lost a replica"),
    ("kvrep", "kvrep.serve.repopulated_keys", 0, "wiped and repopulated"),
    ("kvrep", "bitwise_equal", False, "equals the oracle"),
    ("kvrep", "kvrep.bitwise.resumed_at_step", -1, "resumed mid-outage"),
    ("kvrep", "kvrep.bitwise.resync_tag_equal", False, "to tag equality"),
]

# A time the record holds, made absurd: no verdict reads a clock.
CLOCKS = [
    ("elastic", "elastic.election_latency_s"),
    ("hierarchy", "hierarchy.bench.hier_s"),
    ("router", "router.kill.latency_p99_ms"),
    ("poison", "integrity.overhead_frac"),
    ("kvrep", "kvrep.train.kill_at_s"),
]


def _verdict(drill):
    return importlib.import_module(
        f"ps_pytorch_tpu.tools.{drill}_drill").verdict


def _broken(record, path, value):
    doc = copy.deepcopy(record)
    *parents, leaf = path.split(".")
    node = doc
    for key in parents:
        node = node[key]
    node[leaf] = value
    return doc


@pytest.mark.parametrize("drill,path,value,words", CASES,
                         ids=[f"{c[0]}-{c[1]}" for c in CASES])
def test_verdict_names_the_one_broken_invariant(drill, path, value, words,
                                                committed_record):
    record = committed_record(RECORDS[drill])
    violations = _verdict(drill)(_broken(record, path, value))
    assert len(violations) == 1 and words in violations[0], violations


@pytest.mark.parametrize("drill", ["elastic", "router"])
def test_committed_record_passes_its_drills_verdict(drill, committed_record):
    # r14, r16 and r17 pass theirs in tests/test_{hierarchy,integrity,kvrep}.py
    assert _verdict(drill)(committed_record(RECORDS[drill])) == []


@pytest.mark.parametrize("drill,path", CLOCKS, ids=[c[0] for c in CLOCKS])
def test_no_verdict_reads_a_clock(drill, path, committed_record):
    record = committed_record(RECORDS[drill])
    assert _verdict(drill)(_broken(record, path, 1e9)) == []


@pytest.mark.parametrize("drill", sorted(RECORDS))
def test_verdict_does_not_believe_a_results_own_ok(drill, committed_record):
    verdict = _verdict(drill)
    assert verdict(dict(committed_record(RECORDS[drill]), ok=False)) == []
    assert verdict({"ok": True})     # nothing happened: every floor is missed
