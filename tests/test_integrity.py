"""Gradient-integrity tests: wire digests, compressed-domain payload
screening, MAD outlier gating, quarantine/readmission lifecycle, the
aggregator screening hook (bitwise exclusion), transport digest demotion,
typed armour corruption errors, and the payload/poison fault plane."""

import json

import numpy as np
import pytest

from ps_pytorch_tpu.compression.codecs import encode_leaves
from ps_pytorch_tpu.parallel.async_dp import StaleGradientAggregator
from ps_pytorch_tpu.parallel.transport import KVPytreeChannel
from ps_pytorch_tpu.resilience import FaultInjector, parse_fault_spec
from ps_pytorch_tpu.resilience.faults import _KINDS, _is_chunk_key
from ps_pytorch_tpu.resilience.integrity import (
    GradIntegrity, QuarantineManager, contribution_norm, mad_outliers,
    payload_norm, validate_float_leaf, validate_payload, verify_digest,
    wire_digest,
)
from ps_pytorch_tpu.runtime.coordinator import KVStore
from ps_pytorch_tpu.utils import armor
from ps_pytorch_tpu.utils.armor import WireCorrupt


# ---- layer 1: wire digests ----

def test_wire_digest_roundtrip_and_tamper():
    chunk = "payload-text-" * 40
    tok = wire_digest(chunk)
    algo, _, hexval = tok.partition(":")
    assert algo in ("crc32", "crc32c") and len(hexval) == 8
    assert verify_digest(chunk, tok)
    assert verify_digest(chunk.encode("ascii"), tok)  # str/bytes agree
    assert not verify_digest(chunk[:-1] + "X", tok)
    assert not verify_digest(chunk + "y", tok)


def test_wire_digest_token_policies():
    chunk = "abc123"
    # Unknown algorithm = version skew, NOT corruption.
    assert verify_digest(chunk, "sha999:0011aabb")
    # Malformed tokens never verify.
    assert not verify_digest(chunk, "")
    assert not verify_digest(chunk, None)
    assert not verify_digest(chunk, "crc32")
    assert not verify_digest(chunk, "crc32:xyz")
    assert not verify_digest(chunk, wire_digest(chunk).split(":")[1])


# ---- layer 2: payload validators ----

def test_validate_int8lat_payload():
    good = {"v": np.zeros((3, 4), np.int8), "e": -7}
    assert validate_payload(good) is None
    assert validate_payload(good, expect_shape=(3, 4)) is None
    assert "expected" in validate_payload(good, expect_shape=(4, 3))
    assert validate_payload({"v": np.zeros(3, np.int8), "e": -32768}) is None
    assert "out of bounds" in validate_payload(
        {"v": np.zeros(3, np.int8), "e": 99})
    assert "not an integer" in validate_payload(
        {"v": np.zeros(3, np.int8), "e": "huge"})
    assert "int8" in validate_payload(
        {"v": np.zeros(3, np.int16), "e": 0})


def test_validate_sparse_payload():
    good = {"i": np.array([1, 5, 9], np.int32),
            "v": np.ones(3, np.float32), "s": np.array([10], np.int64)}
    assert validate_payload(good) is None
    bad = dict(good, i=np.array([1, 5, 5], np.int32))
    assert "increasing" in validate_payload(bad)
    bad = dict(good, i=np.array([1, 5, 10], np.int32))
    assert "out of range" in validate_payload(bad)
    bad = dict(good, i=np.array([-1, 5, 9], np.int32))
    assert "out of range" in validate_payload(bad)
    bad = dict(good, v=np.array([1.0, np.nan, 1.0], np.float32))
    assert "finite" in validate_payload(bad)
    bad = dict(good, i=np.array([1.0, 5.0, 9.0], np.float32))
    assert "integer" in validate_payload(bad)
    bad = {"i": good["i"], "v": good["v"]}
    assert "missing shape" in validate_payload(bad)
    assert validate_payload({"x": 1}) == "not a payload dict"
    assert validate_payload(np.zeros(3)) == "not a payload dict"
    assert "unrecognized" in validate_payload({"v": np.zeros(3)})


def test_validate_float_leaf():
    assert validate_float_leaf(np.ones((2, 2), np.float32)) is None
    assert validate_float_leaf(np.array([1, 2], np.int32)) is None
    assert "finite" in validate_float_leaf(np.array([1.0, np.inf]))


def test_payload_norms():
    p = {"v": np.array([3, 4], np.int8), "e": 1}
    assert payload_norm(p) == pytest.approx(4.0 * 25.0)  # (2^1)^2 * 25
    assert payload_norm({"v": np.array([7], np.int8), "e": -32768}) == 0.0
    sp = {"i": np.array([0, 2]), "v": np.array([3.0, 4.0]),
          "s": np.array([5])}
    assert payload_norm(sp) == pytest.approx(25.0)
    assert contribution_norm([p, sp]) == pytest.approx(np.sqrt(125.0))
    # Opaque leaves (bytes, tuples) are skipped, not crashed on.
    assert contribution_norm([b"blosc-frame", ("qt",), sp]) == \
        pytest.approx(5.0)


def test_mad_outliers():
    base = {0: 1.0, 1: 1.1, 2: 0.9, 3: 1.05}
    assert mad_outliers(base) == []
    assert mad_outliers({**base, 4: 900.0}) == [4]
    # Non-finite is always an outlier; gate abstains below min contributors.
    assert mad_outliers({0: 1.0, 1: np.nan}) == [1]
    assert mad_outliers({0: 1.0, 1: 500.0}) == []
    # Degenerate MAD (identical norms) stays quiet without the 4x floor.
    same = {i: 2.5 for i in range(6)}
    assert mad_outliers({**same, 9: 2.6}) == []


# ---- layer 3: quarantine lifecycle ----

def test_quarantine_lifecycle():
    events = []
    q = QuarantineManager(strike_limit=3, readmit_clean=2,
                          on_event=lambda k, c, s, d: events.append((k, c)))
    assert not q.strike(7, "bad", step=1)
    assert not q.strike(7, "bad", step=2)
    assert q.strike(7, "bad", step=3)          # third strike quarantines
    assert q.is_quarantined(7) and q.quarantined_ids() == [7]
    assert not q.observe_clean(7, step=4)
    assert q.observe_clean(7, step=5)          # streak of 2 readmits
    assert not q.is_quarantined(7)
    # Probation: ONE more strike re-quarantines immediately.
    assert q.strike(7, "bad again", step=6)
    snap = q.snapshot()
    assert snap["integrity_quarantines"] == 2
    assert snap["integrity_readmissions"] == 1
    assert snap["integrity_quarantined"] == 1
    kinds = [k for k, _ in events]
    assert kinds == ["strike", "strike", "strike", "quarantine",
                     "readmit", "strike", "quarantine"]


def test_strike_decay_on_clean():
    q = QuarantineManager(strike_limit=3, readmit_clean=2)
    q.strike(1, "torn write")
    q.observe_clean(1)
    q.strike(1, "torn write")
    q.observe_clean(1)
    q.strike(1, "torn write")                  # never accumulates to 3
    assert not q.is_quarantined(1)


def test_grad_integrity_screen_real_payloads():
    rng = np.random.default_rng(0)
    leaves = [rng.normal(size=(8, 4)).astype(np.float32),
              rng.normal(size=(16,)).astype(np.float32)]
    contribs = []
    for sid in range(4):
        scale = 1000.0 if sid == 2 else 1.0
        contribs.append((sid, encode_leaves(
            "int8lat", [l * scale for l in leaves], slice_id=sid, step=0)))
    gi = GradIntegrity(mad_threshold=6.0, strike_limit=2, readmit_clean=1)
    admitted, reasons = gi.screen(contribs, step=1)
    assert admitted == [0, 1, 3]
    assert "outlier" in reasons[2]
    # Second poisoned round quarantines (strike_limit=2) ...
    gi.screen(contribs, step=2)
    assert gi.quarantine.is_quarantined(2)
    # ... and a clean round readmits on probation (readmit_clean=1).
    clean = [(sid, encode_leaves("int8lat", leaves, slice_id=sid, step=3))
             for sid in range(4)]
    admitted, reasons = gi.screen(clean, step=3)
    assert admitted == [0, 1, 2, 3] and reasons == {}
    snap = gi.snapshot()
    assert snap["integrity_outlier_rejects"] == 2
    assert snap["integrity_quarantines"] == 1
    assert snap["integrity_readmissions"] == 1


def test_aggregator_screen_bitwise_exclusion():
    """A screened-out contributor must leave the SAME aggregate as that
    contributor never having submitted — the homomorphic sum runs over
    admitted payloads only."""
    rng = np.random.default_rng(1)
    leaves = [rng.normal(size=(6, 3)).astype(np.float32)]

    def agg(n, integrity):
        return StaleGradientAggregator(
            n, staleness_limit=8, num_aggregate=n, compress=True,
            codec="int8lat", integrity=integrity)

    screened = agg(4, GradIntegrity())
    control = agg(4, None)
    for sid in range(4):
        scale = 1e6 if sid == 3 else 1.0
        wire = encode_leaves("int8lat", [l * scale for l in leaves],
                             slice_id=sid, step=0)
        screened.submit_encoded(sid, 0, wire)
        if sid < 3:
            control.submit_encoded(sid, 0, wire)
    avg, info = screened.collect(0)
    assert info["used"] == [0, 1, 2]
    assert 3 in info["rejected"]
    avg_control, info_control = control.collect(0)
    assert "rejected" not in info_control      # legacy info dict unchanged
    np.testing.assert_array_equal(np.asarray(avg[0]),
                                  np.asarray(avg_control[0]))


# ---- transport: digest demotion ----

def _chan(kv):
    tpl = [np.zeros((4, 3), np.float32), np.zeros(5, np.float32)]
    return KVPytreeChannel(kv, "t/grads", tpl, codec="raw")


def test_transport_crc_in_meta_and_clean_read():
    kv = KVStore()
    chan = _chan(kv)
    tree = [np.arange(12, dtype=np.float32).reshape(4, 3),
            np.ones(5, np.float32)]
    chan.publish(1, tree)
    meta = json.loads(kv.get("t/grads/1/meta"))
    assert len(meta["crc"]) == 2
    for row in meta["crc"]:
        for tok in row:
            algo, _, hexval = tok.partition(":")
            assert algo in ("crc32", "crc32c") and len(hexval) == 8
    got = chan.read()
    assert got is not None
    np.testing.assert_array_equal(got[1][0], tree[0])
    assert chan.integrity_failures == 0


def test_transport_corrupt_chunk_demotes_to_absent():
    kv = KVStore()
    chan = _chan(kv)
    chan.publish(1, [np.ones((4, 3), np.float32), np.ones(5, np.float32)])
    chunk_keys = [k for k in kv.keys("t/grads/1/") if _is_chunk_key(k)]
    assert chunk_keys
    val = kv.get(chunk_keys[0])
    kv.set(chunk_keys[0], ("0" if val[0] != "0" else "1") + val[1:])
    assert chan.read() is None
    assert chan.integrity_failures == 1


def test_transport_corrupt_meta_demotes_to_absent():
    kv = KVStore()
    chan = _chan(kv)
    chan.publish(1, [np.ones((4, 3), np.float32), np.ones(5, np.float32)])
    kv.set("t/grads/1/meta", "{not json")
    assert chan.read() is None
    assert chan.integrity_failures == 1


def test_transport_pre_digest_meta_still_reads():
    """Metas written before the crc field existed read unverified."""
    kv = KVStore()
    chan = _chan(kv)
    tree = [np.ones((4, 3), np.float32), np.zeros(5, np.float32)]
    chan.publish(1, tree)
    meta = json.loads(kv.get("t/grads/1/meta"))
    del meta["crc"]
    kv.set("t/grads/1/meta", json.dumps(meta))
    got = chan.read()
    assert got is not None and chan.integrity_failures == 0
    np.testing.assert_array_equal(got[1][0], tree[0])


# ---- armour: typed corruption errors ----

def test_armor_wire_corrupt_typed():
    blob = np.arange(300, dtype=np.float32).tobytes()
    enc = armor.b85encode(blob)
    assert armor.b85decode(enc) == blob        # clean path bit-identical
    assert issubclass(WireCorrupt, ValueError)
    with pytest.raises(WireCorrupt):
        armor.b85decode("~" * 5)               # base85 group overflow
    with pytest.raises(WireCorrupt):
        armor.b85decode('"' * 10)              # outside the b85 alphabet
    with pytest.raises(WireCorrupt):
        armor.b85decode("ÿ" * 8)          # non-ascii input


# ---- fault plane: payload + poison kinds ----

def test_fault_spec_new_kinds():
    faults = parse_fault_spec(
        "payload_bitflip:p=0.05,seed=9,prefix=async-3/agrad;"
        "payload_truncate:p=0.02,seed=4;"
        "grad_poison:scale=1000,r=2,step=3,steps=20")
    assert [f["kind"] for f in faults] == [
        "payload_bitflip", "payload_truncate", "grad_poison"]
    assert faults[0]["prefix"] == "async-3/agrad"
    assert faults[2]["scale"] == 1000 and faults[2]["steps"] == 20
    for bad in ("payload_bitflip:seed=1",      # missing p
                "payload_bitflip:p=2,seed=1",  # p out of range
                "grad_poison:r=1",             # missing scale
                "grad_poison:scale=0",         # zero scale is a no-op
                "grad_poison:scale=10,steps=-1"):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)


def test_fault_docstring_table_covers_all_kinds():
    import ps_pytorch_tpu.resilience.faults as faults_mod
    for kind in _KINDS:
        assert kind + ":" in faults_mod.__doc__, kind


def test_poison_scale_window():
    inj = FaultInjector("grad_poison:scale=1000,r=2,step=3,steps=8",
                        process_index=2)
    active = [s for s in range(20) if inj.poison_scale(s) is not None]
    assert active == list(range(3, 11))
    assert inj.poison_scale(5) == 1000.0
    assert inj.counters["grad_poisons"] > 0
    other = FaultInjector("grad_poison:scale=1000,r=2,step=3,steps=8",
                          process_index=1)
    assert all(other.poison_scale(s) is None for s in range(20))
    forever = FaultInjector("grad_poison:scale=-9", process_index=0)
    assert forever.poison_scale(10 ** 6) == -9.0


def test_faulty_kv_bitflip_targets_chunk_keys_only():
    assert _is_chunk_key("run/agrad/0/5/0/1")
    assert not _is_chunk_key("run/agrad/meta/5")
    assert not _is_chunk_key("run/hb/3")
    kv = KVStore()
    chunk = "x" * 60
    kv.set("run/agrad/0/5/0/1", chunk)
    kv.set("run/agrad/5/meta", chunk)
    inj = FaultInjector("payload_bitflip:p=1.0,seed=11", process_index=0)
    fkv = inj.wrap_kv(kv)
    got = fkv.get("run/agrad/0/5/0/1")
    assert got != chunk and len(got) == len(chunk)
    assert fkv.get("run/agrad/5/meta") == chunk    # meta never mutated
    assert inj.counters["payload_bitflips"] >= 1
    # Digest layer catches exactly this class of corruption.
    assert not verify_digest(got, wire_digest(chunk))


def test_faulty_kv_truncate_and_prefix_scope():
    kv = KVStore()
    kv.set("a/agrad/0/1/0/0", "y" * 40)
    kv.set("b/agrad/0/1/0/0", "y" * 40)
    inj = FaultInjector("payload_truncate:p=1.0,seed=5,prefix=a/",
                        process_index=0)
    fkv = inj.wrap_kv(kv)
    assert len(fkv.get("a/agrad/0/1/0/0")) == 20
    assert fkv.get("b/agrad/0/1/0/0") == "y" * 40  # out of scope
    assert inj.counters["payload_truncates"] == 1


# ---- the drill's verdict ----

def test_poison_drill_verdict(committed_record):
    from ps_pytorch_tpu.tools.poison_drill import verdict
    good = committed_record("RESILIENCE_r16.json")
    assert verdict(good) == []
    # every lifecycle floor is demanded independently
    for key in ("quarantines", "readmissions", "screen_rejects",
                "wire_integrity_failures"):
        bad = dict(good, integrity=dict(good["integrity"], **{key: 0}))
        assert len(verdict(bad)) == 1, key
    # a crash is never an acceptable way to reject a payload
    crashed = dict(good, integrity=dict(good["integrity"], crashes=1))
    assert verdict(crashed) == ["poison: nobody crashed"]
    # a control run that did NOT diverge means the poison proved nothing
    weak = dict(good, integrity=dict(good["integrity"],
                                     control_diverged=False))
    assert verdict(weak) == ["control: without the screen the run diverged"]
    # a CPU clock is no part of the rule: the record's old overhead is ignored
    slow = dict(good, integrity=dict(good["integrity"], overhead_frac=0.05))
    assert verdict(slow) == []
    assert verdict({"ok": True})   # no sections; its own "ok" is not believed


def test_poison_drill_verdict_passes_committed_record(committed_record):
    """The committed round-16 record must pass the verdict of the drill that
    wrote it, with or without its old bench sections (it predates the
    poison_ef leg, which the verdict judges only where it was run)."""
    from ps_pytorch_tpu.tools.poison_drill import verdict
    rec = committed_record("RESILIENCE_r16.json")
    assert "bench" in rec["integrity"] and "poison_ef" not in rec["phases"]
    assert verdict(rec) == []
    del rec["integrity"]["bench"], rec["integrity"]["overhead_frac"]
    del rec["phases"]["bench"]
    assert verdict(rec) == []


def test_poison_drill_bitwise_phase():
    """The drill's in-process arc: MAD-outlier payloads from contributor 3
    strike it into quarantine, the clean tail readmits it on probation,
    and a ledger-free control fed exactly the admitted sets lands on
    bitwise-identical parameters."""
    from ps_pytorch_tpu.tools.poison_drill import _phase_bitwise
    r = _phase_bitwise()
    assert r["bitwise_equal"]
    kinds = [e[0] for e in r["events"]]
    assert "quarantine" in kinds and "readmit" in kinds
    assert kinds.index("quarantine") < kinds.index("readmit")
    assert r["counters"]["integrity_quarantined"] == 0  # ends readmitted


@pytest.mark.slow
def test_poison_drill_quarantine_under_real_wire(tmp_path):
    """Multi-process soak of the drill's poison leg: process 2 publishes
    1e30-scaled int8lat payloads over the real KV wire while the leader's
    grad reads are bit-flipped at p=0.02. The leader must quarantine
    contributor 2, readmit it after the window closes, catch >=1 digest
    failure, and all four processes must finish with finite losses."""
    import re

    from ps_pytorch_tpu.tools import poison_drill as pd

    run_dir = tmp_path / "poison"
    rc = pd._launch(run_dir, pd._free_port(), [
        "--phase", "worker", "--train-dir", str(run_dir / "ckpt"),
        "--max-steps", "40", "--fault-spec",
        "grad_poison:scale=1e38,r=2,step=3,steps=16;"
        "payload_bitflip:p=0.02,seed=11,prefix=async-42/agrad"])
    logs = pd._logs(run_dir)
    dump = "\n\n".join(f"== proc_{i} ==\n{t[-3000:]}"
                       for i, t in enumerate(logs))
    assert rc != 2, dump
    assert re.search(r"INTEGRITY quarantine contributor 2 at version \d+",
                     logs[0]), dump
    assert re.search(r"INTEGRITY readmit contributor 2 at version \d+",
                     logs[0]), dump
    m = re.search(
        r"INTEGRITY pid 0 screen_rejects (\d+) outlier_rejects \d+ "
        r"strikes \d+ quarantines (\d+) readmissions (\d+) "
        r"wire_failures (\d+)", logs[0])
    assert m, dump
    assert int(m.group(1)) >= 3 and int(m.group(2)) >= 1, dump
    assert int(m.group(3)) >= 1 and int(m.group(4)) >= 1, dump
    finals = pd._final_losses(logs)
    assert len(finals) == 4, dump
    assert all(l == l and l < 10 for l in finals.values()), dump
