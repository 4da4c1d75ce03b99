"""Telemetry subsystem tests: span tracing + Chrome-trace export, metrics
v2 schema (round-trip, v1 back-compat, drift guard), MFU arithmetic,
cross-host KV aggregation, analyze timeline mode, and the trainer smoke
that ties them together (the ISSUE's CPU acceptance run, in-process)."""

import json
import threading
import time

import jax
import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.runtime.metrics import (
    JSONL_BASE_KEYS, SCHEMA_VERSION, V1_LINE_KEYS, V2_LINE_KEYS,
    MetricsLogger, format_line, parse_line,
)
from ps_pytorch_tpu.telemetry import (
    ProfileWindow, TelemetryAggregator, Tracer, compute_mfu,
    data_stall_fraction, declare_training_metrics, derive_step_record,
    device_memory_record, get_default_tracer, latest_tracer, read_timeline,
    self_times, set_default_tracer, set_device_memory_gauges, span,
)
from ps_pytorch_tpu.telemetry.registry import MetricSpec, Registry


# ---- trace.py: spans, nesting, Chrome export ----

def test_span_nesting_and_step_summary():
    tr = Tracer(pid=3)
    with tr.span("outer", step=1):
        with tr.span("inner", step=1):
            pass
    with tr.span("outer", step=2):
        pass
    evs = tr.spans()
    assert [e["name"] for e in evs] == ["inner", "outer", "outer"]
    # Containment: outer's window covers inner's.
    inner, outer1 = evs[0], evs[1]
    assert outer1["t0"] <= inner["t0"]
    assert outer1["t0"] + outer1["dur"] >= inner["t0"] + inner["dur"]
    s1 = tr.step_summary(1)
    assert set(s1) == {"outer", "inner"} and all(v >= 0 for v in s1.values())
    assert set(tr.step_summary(2)) == {"outer"}
    assert tr.step_summary(99) == {}
    names = [e["name"] for e in evs]
    assert names.count("outer") == 2 and names.count("inner") == 1


def test_chrome_trace_json_validity(tmp_path):
    tr = Tracer(pid=1, process_name="hostA")
    with tr.span("data_wait", step=5, bytes=123):
        pass
    path = tr.write_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        doc = json.load(f)          # must be valid JSON, whole-file
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert meta and meta[0]["args"]["name"] == "hostA"
    assert len(spans) == 1
    e = spans[0]
    for k in ("ph", "ts", "dur", "pid", "tid", "name"):
        assert k in e
    assert e["pid"] == 1 and e["name"] == "data_wait"
    assert e["args"]["step"] == 5 and e["args"]["bytes"] == 123
    assert doc["metadata"]["dropped_spans"] == 0


def test_ring_buffer_bounds_and_drop_count():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span("s", step=i):
            pass
    assert len(tr.spans()) == 4
    assert tr.dropped == 6
    assert len(tr.spans()) + tr.dropped == 10    # every span kept or counted


def test_ambient_span_noop_without_tracer():
    prev = set_default_tracer(None)     # whatever was installed, clear it
    try:
        with span("anything", step=1) as got:   # must not raise, yields None
            assert got is None
        tr = Tracer()
        assert set_default_tracer(tr) is None   # returns the prior default
        with span("landed", step=2):
            pass
        assert [e["name"] for e in tr.spans()] == ["landed"]
        set_default_tracer(None)
    finally:
        set_default_tracer(prev)


# ---- metrics v2 schema ----

def test_v1_line_emission_unchanged():
    # No v2 fields passed -> byte-identical v1 line, 7-key parse (pre-v2
    # call sites and logs keep working).
    line = format_line(12, 3, loss=1.234567, acc=0.5, participating=7,
                       step_time=0.123, data_time=0.01)
    assert " mfu " not in line
    d = parse_line(line)
    assert set(d) == set(V1_LINE_KEYS)


def test_v2_line_roundtrip():
    line = format_line(12, 3, loss=1.2, acc=0.5, participating=7,
                       step_time=0.123, data_time=0.01,
                       mfu=0.4321, examples_per_sec=1040.5,
                       data_stall_frac=0.081)
    d = parse_line("prefix " + line)
    assert set(d) == set(V2_LINE_KEYS)
    assert d["mfu"] == pytest.approx(0.4321)
    assert d["examples_per_sec"] == pytest.approx(1040.5)
    assert d["data_stall_frac"] == pytest.approx(0.081)


def test_v2_line_unknown_mfu_is_na_not_zero():
    line = format_line(1, 0, loss=1.0, acc=0.0, participating=1,
                       step_time=0.1, data_time=0.0,
                       examples_per_sec=640.0, data_stall_frac=0.0)
    assert " mfu n/a " in line
    assert parse_line(line)["mfu"] is None


def test_jsonl_record_keys_and_schema_version(tmp_path):
    p = tmp_path / "m.jsonl"
    with MetricsLogger(str(p), printer=lambda *_: None) as ml:
        ml.log_step(1, 0, loss=1.0, acc=0.5, participating=8,
                    step_time=0.2, data_time=0.01, mfu=0.3,
                    examples_per_sec=100.0, data_stall_frac=0.05,
                    phases={"data_wait": 0.01})
    (rec,) = [json.loads(l) for l in p.read_text().splitlines()]
    assert rec["schema_version"] == SCHEMA_VERSION
    for k in JSONL_BASE_KEYS:
        assert k in rec
    assert rec["phases"] == {"data_wait": 0.01}


def test_schema_drift_guard():
    """Fails when the line format or JSONL key set changes without a
    SCHEMA_VERSION bump. If this test fails: you changed the metrics
    schema — bump SCHEMA_VERSION and extend parse_line additively."""
    assert SCHEMA_VERSION == 2
    assert V1_LINE_KEYS == ("step", "epoch", "loss", "acc", "participating",
                            "step_time", "data_time")
    assert V2_LINE_KEYS == V1_LINE_KEYS + ("mfu", "examples_per_sec",
                                           "data_stall_frac")
    assert JSONL_BASE_KEYS == ("schema_version", "ts") + V2_LINE_KEYS
    # The emitted artifacts must carry exactly the declared keys.
    line = format_line(1, 0, loss=1.0, acc=0.0, participating=1,
                       step_time=0.1, data_time=0.0, mfu=0.1,
                       examples_per_sec=1.0, data_stall_frac=0.0)
    assert set(parse_line(line)) == set(V2_LINE_KEYS)


def test_multiprocess_metrics_file_suffix(tmp_path):
    base = str(tmp_path / "m.jsonl")
    m0 = MetricsLogger(base, process_index=0, num_processes=4,
                       printer=lambda *_: None)
    m2 = MetricsLogger(base, process_index=2, num_processes=4,
                       printer=lambda *_: None)
    assert m0.jsonl_path == base            # leader keeps the bare path
    assert m2.jsonl_path == base + ".p2"    # followers never clobber it
    m0.close(), m2.close()
    # Single-process: bare path regardless of index conventions.
    m = MetricsLogger(base, process_index=0, num_processes=1,
                      printer=lambda *_: None)
    assert m.jsonl_path == base
    m.close()


def test_metrics_logger_closes_on_exception(tmp_path):
    p = tmp_path / "m.jsonl"
    with pytest.raises(RuntimeError):
        with MetricsLogger(str(p), printer=lambda *_: None) as ml:
            ml.log_step(1, 0, loss=1.0, acc=0.0, participating=1,
                        step_time=0.1, data_time=0.0)
            raise RuntimeError("trainer died")
    assert ml._fh is None                   # handle closed by __exit__
    assert p.read_text().count("\n") == 1   # the pre-crash record flushed


# ---- registry: MFU / goodput arithmetic ----

def test_compute_mfu_hand_arithmetic():
    # 100 GFLOP step in 0.25 s on 4 chips of 200 GFLOP/s peak:
    # (100e9 / 0.25) / (4 * 200e9) = 0.5 exactly.
    assert compute_mfu(100_000_000_000, 0.25, 200e9, 4) == pytest.approx(0.5)
    # Any unknown input -> None, never 0.
    assert compute_mfu(None, 0.25, 200e9, 4) is None
    assert compute_mfu(100, 0.0, 200e9, 4) is None
    assert compute_mfu(100, 0.25, None, 4) is None
    assert compute_mfu(-1, 0.25, 200e9, 4) is None


def test_mfu_vs_lenet_training_step():
    """MFU arithmetic against the LeNet training step counted by
    utils/flops.training_flops — the two FLOPs paths (direct trace vs
    model-level helper) must agree on the same program."""
    from ps_pytorch_tpu.models import build_model
    from ps_pytorch_tpu.utils.flops import training_flops

    model = build_model("LeNet", 10, "float32")
    flops = training_flops(model, (4, 28, 28, 1), 10)
    assert flops > 0
    # Hand-check: with peak = flops (per chip, 1 chip), a 1 s step is
    # exactly MFU=1.0; a 2 s step is 0.5.
    assert compute_mfu(flops, 1.0, float(flops), 1) == pytest.approx(1.0)
    assert compute_mfu(flops, 2.0, float(flops), 1) == pytest.approx(0.5)


def test_data_stall_fraction_clamps():
    assert data_stall_fraction(0.02, 0.1) == pytest.approx(0.2)
    assert data_stall_fraction(5.0, 0.1) == 1.0     # clamped
    assert data_stall_fraction(-1.0, 0.1) == 0.0    # clamped
    assert data_stall_fraction(0.1, 0.0) is None


def test_derive_step_record_contract():
    rec = derive_step_record(step_time_s=0.5, data_time_s=0.1, examples=256,
                             tokens=1024, flops_per_step=None,
                             peak_flops_per_chip=None, with_memory=False)
    # The KEYS are the schema: present even when the value is unknowable.
    assert set(rec) >= {"mfu", "examples_per_sec", "data_stall_frac"}
    assert rec["mfu"] is None
    assert rec["examples_per_sec"] == pytest.approx(512.0)
    assert rec["data_stall_frac"] == pytest.approx(0.2)
    assert rec["tokens_per_sec"] == pytest.approx(2048.0)


def test_registry_typed_metrics():
    r = Registry()
    r.counter("steps", help="completed steps")
    r.gauge("lr", unit="1/s")
    assert r.inc("steps") == 1.0
    assert r.inc("steps", 2) == 3.0
    assert r.set("lr", 0.01) == 0.01
    with pytest.raises(KeyError):
        r.inc("undeclared")
    with pytest.raises(TypeError):
        r.set("steps", 5)           # counter, not gauge
    with pytest.raises(ValueError):
        r.inc("steps", -1)          # counters are monotonic
    with pytest.raises(ValueError):
        r.gauge("steps")            # re-declare as a different kind
    assert r.snapshot() == {"steps": 3.0, "lr": 0.01}
    with pytest.raises(ValueError):
        MetricSpec("x", "summary")      # histogram IS valid now; summary isn't


# ---- aggregate.py: cross-host KV aggregation ----

def test_kv_aggregation_two_fake_processes(tmp_path):
    from ps_pytorch_tpu.runtime.coordinator import KVStore

    kv = KVStore()      # both "processes" share one in-process KV
    pub0 = TelemetryAggregator(kv, 0, 2, run_id="t")
    pub1 = TelemetryAggregator(kv, 1, 2, run_id="t")
    leader = pub0
    out = tmp_path / "timeline.jsonl"
    leader.open_timeline(str(out))
    # Process 1 runs ahead of the leader's drain; step 2 lands before the
    # leader looks — both must merge in (step, process) order.
    pub1.publish_step(1, {"step_time": 0.30, "phases": {"data_wait": 0.2}})
    pub1.publish_step(2, {"step_time": 0.31})
    pub0.publish_step(1, {"step_time": 0.10})
    assert leader.drain_to_file() == 3
    pub0.publish_step(2, {"step_time": 0.11})
    leader.close(final_step=2, timeout_s=1.0)
    rows = read_timeline(str(out))
    assert [(r["step"], r["process"]) for r in rows] == \
        [(1, 0), (1, 1), (2, 1), (2, 0)]
    assert all(r["schema_version"] == 2 for r in rows)
    assert rows[1]["phases"] == {"data_wait": 0.2}


def test_kv_aggregation_gc_and_holes():
    from ps_pytorch_tpu.runtime.coordinator import KVStore

    kv = KVStore()
    pub = TelemetryAggregator(kv, 0, 1, run_id="g", window=4)
    for s in range(1, 11):
        pub.publish_step(s, {"step_time": s * 0.1})
    # Publisher GC'd everything beyond the window.
    assert pub.fetch(0, 1) is None
    assert pub.fetch(0, 10) is not None
    # A fresh leader (cursor 0) drains what survives; holes advance the
    # cursor instead of wedging.
    leader = TelemetryAggregator(kv, 0, 1, run_id="g", window=4)
    rows = leader.drain()
    assert [r["step"] for r in rows] == [7, 8, 9, 10]
    assert leader.drain() == []     # nothing new


def test_kv_aggregation_close_bounded_wait(tmp_path):
    from ps_pytorch_tpu.runtime.coordinator import KVStore

    kv = KVStore()
    agg = TelemetryAggregator(kv, 0, 2, run_id="w")
    agg.open_timeline(str(tmp_path / "t.jsonl"))
    agg.publish_step(1, {"step_time": 0.1})
    # Process 1 never publishes: close must return within the timeout.
    agg.close(final_step=1, timeout_s=0.2, poll_s=0.01)
    assert agg.rows_written == 1


# ---- analyze timeline mode ----

def _fake_metrics_jsonl(path, n_proc=1):
    with open(path, "w") as f:
        for step in range(1, 5):
            for p in range(n_proc):
                rec = {"schema_version": 2, "step": step, "process": p,
                       "step_time": 0.1 + 0.05 * p, "data_time": 0.02,
                       "phases": {"data_wait": 0.02,
                                  "host_dispatch": 0.06 + 0.05 * p}}
                f.write(json.dumps(rec) + "\n")


def test_analyze_timeline_breakdown(tmp_path, capsys):
    from ps_pytorch_tpu.tools.analyze import main, phase_breakdown

    p = tmp_path / "m.jsonl"
    _fake_metrics_jsonl(str(p), n_proc=2)
    assert main(["timeline", str(p)]) == 0
    out = capsys.readouterr().out
    assert "| phase |" in out and "host_dispatch" in out and "data_wait" in out
    rows = phase_breakdown(
        [json.loads(l) for l in p.read_text().splitlines()], skip_first=1)
    by = {r["phase"]: r for r in rows}
    assert by["data_wait"]["mean_s"] == pytest.approx(0.02)
    # 'other' = un-spanned remainder of the step.
    assert "other" in by
    assert 0 < by["host_dispatch"]["frac_of_step"] <= 1.0


def test_analyze_timeline_json_heatmap(tmp_path, capsys):
    from ps_pytorch_tpu.tools.analyze import main

    p = tmp_path / "timeline.jsonl"
    _fake_metrics_jsonl(str(p), n_proc=2)
    assert main(["timeline", str(p), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["phases"]
    grid = doc["heatmap"]
    assert {(g["step"], g["process"]) for g in grid} == \
        {(s, p) for s in range(1, 5) for p in range(2)}
    # Process 1 is the slower host in the fixture — visible in the grid.
    assert all(g["step_time"] > 0.1 for g in grid if g["process"] == 1)


# ---- trainer end-to-end (the ISSUE's CPU smoke, in-process) ----

def _tiny_cfg(tmp_path, **kw):
    base = dict(dataset="synthetic_mnist", network="LeNet", batch_size=64,
                lr=0.01, momentum=0.9, max_steps=4, epochs=0, eval_freq=0,
                train_dir=str(tmp_path / "ckpt"), compute_dtype="float32",
                data_axis=8, log_every=1, seed=3)
    base.update(kw)
    return TrainConfig(**base)


def test_trainer_emits_v2_metrics_and_chrome_trace(tmp_path, capsys):
    from ps_pytorch_tpu.runtime import Trainer

    mfile = tmp_path / "m.jsonl"
    tfile = tmp_path / "trace.json"
    cfg = _tiny_cfg(tmp_path, metrics_file=str(mfile),
                    trace_file=str(tfile), eval_freq=2)
    Trainer(cfg).train()
    set_default_tracer(None)    # don't leak this trainer's tracer
    # (a) metrics JSONL: v2 records with the derived triple + phases.
    recs = [json.loads(l) for l in mfile.read_text().splitlines()]
    assert len(recs) == 4
    for rec in recs:
        assert rec["schema_version"] == SCHEMA_VERSION
        for k in ("mfu", "examples_per_sec", "data_stall_frac", "phases"):
            assert k in rec
    assert recs[-1]["examples_per_sec"] > 0
    assert recs[-1]["mfu"] is None          # CPU: no peak -> null, not 0
    assert recs[-1]["data_stall_frac"] is not None
    # Human lines carry the v2 suffix.
    out = capsys.readouterr().out
    v2_lines = [parse_line(l) for l in out.splitlines()
                if l.startswith("STEP")]
    assert v2_lines and all("mfu" in d for d in v2_lines if d)
    # (b) Chrome trace: valid JSON, spans cover the step phases incl. the
    # ambient checkpoint span from runtime/checkpoint.py.
    with open(tfile) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    for phase in ("data_wait", "host_dispatch", "device_sync",
                  "metrics_sync", "checkpoint", "checkpoint_write",
                  "coordinator_mask"):
        assert phase in names, f"missing span {phase}; have {names}"
    # (c) analyze timeline reads the metrics file directly.
    from ps_pytorch_tpu.tools.analyze import phase_breakdown
    rows = phase_breakdown(recs, skip_first=1)
    assert {"data_wait", "host_dispatch"} <= {r["phase"] for r in rows}


def test_trainer_timeline_file_single_process(tmp_path):
    # timeline_file set explicitly on one process: the aggregator rides the
    # coordinator's in-process KV and the leader (us) writes the merged file.
    from ps_pytorch_tpu.runtime import Trainer

    tl = tmp_path / "run.timeline"
    cfg = _tiny_cfg(tmp_path, timeline_file=str(tl))
    Trainer(cfg).train()
    set_default_tracer(None)
    rows = read_timeline(str(tl))
    assert [r["step"] for r in rows] == [1, 2, 3, 4]
    assert all(r["process"] == 0 and "phases" in r for r in rows)


def test_lm_trainer_schema_parity(tmp_path, capsys):
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    mfile = tmp_path / "lm.jsonl"
    cfg = TrainConfig(
        lm_vocab=64, lm_d_model=32, lm_layers=1, lm_heads=2, lm_seq_len=64,
        lm_corpus_tokens=4096, batch_size=8, max_steps=3, eval_freq=0,
        log_every=1, lr=0.01, train_dir=str(tmp_path / "ckpt"),
        metrics_file=str(mfile), trace_file=str(tmp_path / "lm_trace.json"),
        resume=False, seed=0)
    LMTrainer(cfg).train()
    set_default_tracer(None)
    recs = [json.loads(l) for l in mfile.read_text().splitlines()]
    assert len(recs) == 3
    for rec in recs:
        assert rec["schema_version"] == SCHEMA_VERSION
        for k in ("mfu", "examples_per_sec", "data_stall_frac", "phases"):
            assert k in rec
        assert rec["tokens_per_sec"] > 0    # LM goodput rides the same record
    # analyze reads LM runs identically to vision runs.
    from ps_pytorch_tpu.tools.analyze import per_step_times, phase_breakdown
    assert per_step_times([str(mfile)], skip_first=1)["steps"] == 2
    assert phase_breakdown(recs, skip_first=0)
    with open(tmp_path / "lm_trace.json") as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]
                 if e["ph"] == "X"}
    assert {"data_wait", "host_dispatch", "device_sync",
            "metrics_sync"} <= names


# ---- trace.py: parents, self time, the iteration's root, the Unix anchor ----

def _by_name(spans):
    return {e["name"]: e for e in spans}


def test_parent_ids_and_self_time_nested_and_siblings():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("a"):
            with tr.span("a_inner"):
                time.sleep(0.002)
        with tr.span("b"):
            time.sleep(0.001)
    ev = _by_name(tr.spans())
    assert len({e["id"] for e in ev.values()}) == 4
    assert ev["outer"]["parent"] is None
    assert ev["a"]["parent"] == ev["b"]["parent"] == ev["outer"]["id"]
    assert ev["a_inner"]["parent"] == ev["a"]["id"]
    st = self_times(tr.spans())
    # a leaf's self time is its duration; a parent's is its duration less its
    # children's (siblings on one thread do not overlap: the union is the sum)
    assert st[ev["a_inner"]["id"]] == ev["a_inner"]["dur"]
    assert st[ev["a"]["id"]] == pytest.approx(
        ev["a"]["dur"] - ev["a_inner"]["dur"])
    assert st[ev["outer"]["id"]] == pytest.approx(
        ev["outer"]["dur"] - ev["a"]["dur"] - ev["b"]["dur"])
    assert all(v >= 0 for v in st.values())


def test_self_time_is_duration_minus_the_union_of_children():
    # hand-built: two children that overlap each other and one that sticks
    # out of the parent cover 0-6 and 8-10 of a parent 0-10
    spans = [dict(id=1, parent=None, name="p", t0=0.0, dur=10.0),
             dict(id=2, parent=1, name="c", t0=0.0, dur=4.0),
             dict(id=3, parent=1, name="c", t0=3.0, dur=3.0),
             dict(id=4, parent=1, name="c", t0=8.0, dur=5.0),
             dict(id=5, parent=99, name="orphan", t0=1.0, dur=1.0)]
    st = self_times(spans)
    assert st[1] == pytest.approx(2.0)
    assert st[2] == 4.0 and st[5] == 1.0


def test_parents_are_per_thread():
    tr = Tracer()
    seen = {}

    def worker():
        with tr.span("w_outer"):
            with tr.span("w_inner"):
                seen["ok"] = True

    with tr.span("main_outer"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive() and seen["ok"]
    ev = _by_name(tr.spans())
    # the worker's spans are not children of what the main thread had open
    assert ev["w_outer"]["parent"] is None
    assert ev["w_inner"]["parent"] == ev["w_outer"]["id"]
    assert ev["main_outer"]["parent"] is None
    assert ev["w_outer"]["tid"] != ev["main_outer"]["tid"]
    st = self_times(tr.spans())
    assert st[ev["main_outer"]["id"]] == ev["main_outer"]["dur"]


def test_root_is_not_a_phase_and_nested_spans_are_counted_once():
    tr = Tracer()
    tr.begin_step(7)
    with tr.span("coordinator"):
        with tr.span("coordinator_mask", step=7):
            time.sleep(0.002)
    with tr.span("data_wait"):
        pass
    with tr.span("coordinator"):        # a second span of the name adds up
        time.sleep(0.001)
    tr.end_step()
    ev = tr.spans()
    root = [e for e in ev if e.get("root")]
    assert len(root) == 1 and root[0]["name"] == "train_step"
    assert root[0]["step"] == 7 and root[0]["parent"] is None
    # children inherit the iteration's step
    assert all(e["step"] == 7 for e in ev)
    phases = tr.step_summary(7)
    assert set(phases) == {"coordinator", "coordinator_mask", "data_wait"}
    # each phase is self time, so the phases add up to the time under spans
    # once: the root's duration less its own self time
    st = self_times(ev)
    assert sum(phases.values()) == pytest.approx(
        root[0]["dur"] - st[root[0]["id"]], abs=5e-6)
    mask = [e for e in ev if e["name"] == "coordinator_mask"][0]
    coord = sum(e["dur"] for e in ev if e["name"] == "coordinator")
    assert phases["coordinator"] == pytest.approx(coord - mask["dur"], abs=5e-6)
    # the recorded spans stay whole, one a span, the root among them
    assert sum(e["name"] == "coordinator" for e in ev) == 2
    assert sum(e["name"] == "train_step" for e in ev) == 1


def test_begin_step_closes_what_was_left_open():
    tr = Tracer()
    tr.begin_step(1)
    try:
        with tr.span("data_wait"):
            raise KeyError("the loop left the iteration by an exception")
    except KeyError:
        pass
    tr.begin_step(2)        # closes step 1's root first
    tr.end_step()
    tr.end_step()           # no root open: nothing happens
    roots = [e for e in tr.spans() if e.get("root")]
    assert [r["step"] for r in roots] == [1, 2]
    child = [e for e in tr.spans() if e["name"] == "data_wait"][0]
    assert child["parent"] == roots[0]["id"]
    with tr.span("after"):      # the stack is clean: a top-level span again
        pass
    assert tr.spans()[-1]["parent"] is None


def test_anchored_start_is_on_the_unix_clock():
    tr = Tracer()
    before = time.time_ns()
    tr.begin_step(1)
    time.sleep(0.003)
    with tr.span("late_child"):
        mid = time.time_ns()
    tr.end_step()
    with tr.span("top_level"):      # every top-level span anchors itself
        pass
    after = time.time_ns()
    ev = _by_name(tr.spans())
    assert abs(ev["train_step"]["wall_ns"] - before) < 1_000_000
    assert abs(ev["late_child"]["wall_ns"] - mid) < 1_000_000
    assert ev["late_child"]["wall_ns"] - ev["train_step"]["wall_ns"] == \
        pytest.approx((ev["late_child"]["t0"] - ev["train_step"]["t0"]) * 1e9,
                      abs=2)
    assert before <= ev["top_level"]["wall_ns"] <= after


def test_span_records_normally_without_a_profiler_session():
    # every span is also a jax.profiler TraceAnnotation; with no session
    # running that is a no-op and the span is recorded all the same
    from jax._src import profiler as _jp
    assert _jp._profile_state.profile_session is None
    tr = Tracer()
    tr.begin_step(3)
    with tr.span("host_dispatch", bytes=12) as sargs:
        sargs["late"] = True
    tr.end_step()
    ev = _by_name(tr.spans())
    assert ev["host_dispatch"]["args"] == {"bytes": 12, "late": True}
    assert ev["host_dispatch"]["dur"] >= 0 and ev["train_step"]["step"] == 3
    doc = tr.chrome_events()
    assert {e["name"] for e in doc if e["ph"] == "X"} == \
        {"train_step", "host_dispatch"}


def test_latest_tracer_outlives_the_default():
    a, b = Tracer(), Tracer()
    prev = set_default_tracer(a)
    try:
        assert latest_tracer() is a
        set_default_tracer(b)
        set_default_tracer(None)
        assert get_default_tracer() is None and latest_tracer() is b
    finally:
        set_default_tracer(prev)


def test_profile_window_membership(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    off = ProfileWindow("", "10-12")
    for step in range(1, 20):
        off.on_step(step)
    off.close()
    assert calls == []
    w = ProfileWindow("/tmp/p", "3-4")
    for step in range(1, 8):
        w.on_step(step)
    w.close()           # already stopped: nothing more
    assert calls == [("start", "/tmp/p"), ("stop",)]
    # a resumed run that enters the loop inside the window, and ends in it
    del calls[:]
    w = ProfileWindow("/tmp/q", "3-9")
    w.on_step(5)
    w.on_step(6)
    assert calls == [("start", "/tmp/q")] and w.active
    w.close()
    assert calls[-1] == ("stop",) and not w.active
    # a single step
    del calls[:]
    w = ProfileWindow("/tmp/r", "2")
    for step in (1, 2, 3):
        w.on_step(step)
    assert calls == [("start", "/tmp/r"), ("stop",)]


# ---- registry: device memory with a faked memory_stats() ----

class _FakeDevice:
    def __init__(self, id_, stats):
        self.id, self._stats = id_, stats

    def memory_stats(self):
        return self._stats


def test_device_memory_record_reports_reserved_peak_and_each_device(monkeypatch):
    # the TPU's shape (chip run, PR 22: ResNet-18 b=4096): live buffers
    # 0.16 GB, temporaries reserved apart 7.15 GB
    devs = [_FakeDevice(0, {"peak_bytes_in_use": 159_000_000,
                            "bytes_in_use": 140_000_000,
                            "peak_bytes_reserved": 7_153_000_000}),
            _FakeDevice(1, {"peak_bytes_in_use": 161_000_000,
                            "bytes_in_use": 120_000_000,
                            "peak_bytes_reserved": 7_100_000_000}),
            _FakeDevice(2, None)]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    rec = device_memory_record()
    # existing fields unchanged: the fullest device's live buffers
    assert rec["device_mem_peak_bytes"] == 161_000_000
    assert rec["device_mem_bytes"] == 140_000_000
    assert rec["device_mem_reserved_peak_bytes"] == 7_153_000_000
    assert rec["device_mem_per_device"]["1"] == {
        "device_mem_peak_bytes": 161_000_000, "device_mem_bytes": 120_000_000,
        "device_mem_reserved_peak_bytes": 7_100_000_000}
    assert set(rec["device_mem_per_device"]) == {"0", "1"}
    json.dumps(rec)
    one = device_memory_record(devs[0])
    assert one["device_mem_peak_bytes"] == 159_000_000
    # a backend without the reserved counter, and one without stats at all
    old = device_memory_record(_FakeDevice(0, {"peak_bytes_in_use": 5,
                                               "bytes_in_use": 3}))
    assert "device_mem_reserved_peak_bytes" not in old
    assert old["device_mem_peak_bytes"] == 5
    assert device_memory_record(devs[2]) == {}
    reg = declare_training_metrics(Registry())
    set_device_memory_gauges(reg, rec)
    set_device_memory_gauges(reg, rec)          # declared once, set again
    assert reg.get("device_mem_reserved_peak_bytes") == 7_153_000_000
    assert reg.get("device_mem_peak_bytes") == 161_000_000
    assert reg.get("device_mem_reserved_peak_bytes_d1") == 7_100_000_000
    assert reg.get("device_mem_bytes_d0") == 140_000_000
    set_device_memory_gauges(reg, {})           # CPU: nothing to set
    assert reg.get("device_mem_peak_bytes") == 161_000_000


# ---- the trainers' iterations under the root span ----

CNN_LEAVES = {"coordinator", "data_wait", "rng_key", "batch_put",
              "host_dispatch", "device_sync", "ops_step",
              "telemetry_publish", "metrics_sync", "log_write"}
LM_LEAVES = {"data_wait", "batch_put", "host_dispatch", "device_sync",
             "ops_step", "metrics_sync", "log_write"}


@pytest.fixture(scope="module")
def traced_runs(tmp_path_factory):
    """One tiny Trainer run and one tiny LMTrainer run -> {kind: (spans,
    JSONL records, steps, the trainer's tracer, latest_tracer() after
    train())}."""
    from ps_pytorch_tpu.runtime import Trainer
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    out = {}
    tmp = tmp_path_factory.mktemp("cnn")
    cfg = _tiny_cfg(tmp, metrics_file=str(tmp / "m.jsonl"), eval_freq=2,
                    timeline_file=str(tmp / "run.timeline"))
    tmp_lm = tmp_path_factory.mktemp("lm")
    lm_cfg = TrainConfig(
        lm_vocab=64, lm_d_model=32, lm_layers=1, lm_heads=2, lm_seq_len=64,
        lm_corpus_tokens=4096, batch_size=8, max_steps=4, eval_freq=2,
        log_every=1, lr=0.01, train_dir=str(tmp_lm / "ckpt"),
        metrics_file=str(tmp_lm / "m.jsonl"), resume=False, seed=0)
    for kind, cls, c in (("cnn", Trainer, cfg), ("lm", LMTrainer, lm_cfg)):
        t = cls(c)
        t.train()
        with open(c.metrics_file) as f:
            recs = [json.loads(line) for line in f]
        out[kind] = (t.tracer.spans(), recs, c.max_steps, t.tracer,
                     latest_tracer())
        assert get_default_tracer() is None
    return out


@pytest.mark.parametrize("kind, leaves", [("cnn", CNN_LEAVES),
                                          ("lm", LM_LEAVES)])
def test_every_iteration_is_one_root_with_its_phases_as_children(
        traced_runs, kind, leaves):
    spans, _, steps, _, _ = traced_runs[kind]
    roots = [e for e in spans if e.get("root")]
    assert [r["step"] for r in roots] == list(range(1, steps + 1))
    assert all(r["name"] == "train_step" and r["parent"] is None
               and r["wall_ns"] > 0 for r in roots)
    for r in roots:
        kids = {e["name"] for e in spans if e["parent"] == r["id"]}
        want = leaves | ({"flops_trace"} if r["step"] == 1 else set()) \
            | ({"checkpoint"} if r["step"] % 2 == 0 else set())
        if r["step"] % 2 == 1:
            # Both trainers write a step's record once the next step is
            # queued, or before the checkpoint that follows it: with
            # eval_freq=2, iteration 2k writes records 2k-1 and 2k,
            # iteration 2k+1 none.
            want -= {"metrics_sync", "log_write"}
        assert kids == want, (r["step"], kids ^ want)
        assert all(e["step"] == r["step"] for e in spans
                   if e["parent"] == r["id"])


@pytest.mark.parametrize("kind", ["cnn", "lm"])
def test_root_self_time_is_duration_minus_children(traced_runs, kind):
    spans = traced_runs[kind][0]
    st = self_times(spans)
    for r in (e for e in spans if e.get("root")):
        kids = [e for e in spans if e["parent"] == r["id"]]
        assert st[r["id"]] >= 0
        assert st[r["id"]] == pytest.approx(
            r["dur"] - sum(k["dur"] for k in kids), abs=1e-9)
        # the children lie inside the root, in order, without overlap
        kids.sort(key=lambda k: k["t0"])
        assert kids[0]["t0"] >= r["t0"]
        assert kids[-1]["t0"] + kids[-1]["dur"] <= r["t0"] + r["dur"] + 1e-9
        assert all(a["t0"] + a["dur"] <= b["t0"] + 1e-9
                   for a, b in zip(kids, kids[1:]))


def test_ambient_spans_nest_under_the_trainers_phases(traced_runs):
    spans = traced_runs["cnn"][0]
    by_id = {e["id"]: e for e in spans}
    masks = [e for e in spans if e["name"] == "coordinator_mask"]
    assert len(masks) == 4
    assert all(by_id[m["parent"]]["name"] == "coordinator" for m in masks)
    writes = [e for e in spans if e["name"] == "checkpoint_write"]
    assert writes and all(by_id[w["parent"]]["name"] == "checkpoint"
                          for w in writes)


@pytest.mark.parametrize("kind", ["cnn", "lm"])
def test_jsonl_phases_keep_their_keys_and_leave_the_root_out(traced_runs, kind):
    spans, recs, steps, _, _ = traced_runs[kind]
    assert len(recs) == steps
    wall = {e["step"]: e["dur"] for e in spans if e.get("root")}
    for rec in recs:
        phases = rec["phases"]
        assert "train_step" not in phases
        assert {"data_wait", "host_dispatch"} <= set(phases)
        # A record holds its whole iteration, the write of the record
        # before it included (here: in the even iterations).
        assert ("metrics_sync" in phases) == (rec["step"] % 2 == 0)
        assert "batch_put" in phases            # a new leaf is a new key
        assert all(v >= 0 for v in phases.values())
        # nothing counted twice: the phases fit into their iteration's wall
        # time (a record's step_time is the time between two reads of the
        # device, which trail the iteration by one step)
        assert sum(phases.values()) <= wall[rec["step"]] + 1e-5
    assert all("device_sync" in r["phases"] for r in recs)
    if kind == "cnn":
        assert all("coordinator_mask" in r["phases"] and
                   "coordinator" in r["phases"] for r in recs)


@pytest.mark.parametrize("kind", ["cnn", "lm"])
def test_latest_tracer_survives_train(traced_runs, kind):
    _, _, _, tracer, latest = traced_runs[kind]
    assert latest is tracer


def test_lm_trainer_profile_window_writes_the_programs_spans(tmp_path):
    """train_lm.py's --profile-dir/--profile-steps: the xplane of the traced
    steps carries the program's spans as the profiler's own events."""
    import glob
    from jax.profiler import ProfileData
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer

    cfg = TrainConfig(
        lm_vocab=64, lm_d_model=32, lm_layers=1, lm_heads=2, lm_seq_len=64,
        lm_corpus_tokens=4096, batch_size=8, max_steps=5, eval_freq=0,
        log_every=1, lr=0.01, train_dir=str(tmp_path / "ckpt"),
        profile_dir=str(tmp_path / "prof"), profile_steps="3-4",
        resume=False, seed=0)
    LMTrainer(cfg).train()
    found = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    assert len(found) == 1
    steps, names = [], set()
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    names.add(ev.name)
                    if ev.name == "train_step":
                        steps.append(dict(ev.stats)["step_num"])
    assert {"train_step", "batch_put", "host_dispatch", "data_wait"} <= names
    assert sorted(steps) == [3, 4]
