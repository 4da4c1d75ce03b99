"""Live ops plane (ISSUE 6): Prometheus exposition golden format + exporter
HTTP roundtrip, training-health watchdog grammar and detectors, flight
recorder dump/load + analyze flight, the trainer E2E (injected NaN gradient
-> watchdog halt -> checkpoint + flight dump within one step), cross-process
trace stitching (wire corr -> Chrome flow events), and serving /healthz +
/metrics through the real HTTP stack."""

import json
import math
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.telemetry import (
    FlightRecorder, HealthMonitor, MetricsExporter, Registry, Tracer,
    load_flight, parse_exposition, parse_health_spec, render_prometheus,
    sanitize_name, set_default_tracer, span,
)


# ---- prometheus.py: golden exposition format ----

def _full_registry():
    r = Registry()
    r.counter("steps_done", unit="steps", help="completed steps")
    r.gauge("loss_now", help="latest loss")
    r.histogram("lat_s", unit="s", help="latency",
                buckets=(0.1, 0.5, 1.0))
    r.inc("steps_done", 3)
    r.set("loss_now", 0.25)
    for v in (0.05, 0.3, 0.7, 2.0):
        r.observe("lat_s", v)
    return r


def test_render_golden_format():
    r = _full_registry()
    text = render_prometheus(r)
    lines = text.splitlines()
    # Counter: _total suffix, HELP carries the unit, integral ints.
    assert "# HELP steps_done_total completed steps [steps]" in lines
    assert "# TYPE steps_done_total counter" in lines
    assert "steps_done_total 3" in lines
    assert "# TYPE loss_now gauge" in lines
    assert "loss_now 0.25" in lines
    # Histogram: cumulative ascending le ending in +Inf.
    bucket_lines = [l for l in lines if l.startswith("lat_s_bucket")]
    assert bucket_lines == ['lat_s_bucket{le="0.1"} 1',
                            'lat_s_bucket{le="0.5"} 2',
                            'lat_s_bucket{le="1"} 3',
                            'lat_s_bucket{le="+Inf"} 4']
    # _sum/_count agree with the registry's own readout of the same data.
    summ = r.hist_summary("lat_s")
    assert f"lat_s_count {summ['count']}" in lines
    assert any(l.startswith("lat_s_sum") and
               math.isclose(float(l.split()[1]), summ["sum"])
               for l in lines)
    # The whole document parses as valid exposition text covering every
    # metric kind.
    samples = parse_exposition(text)
    assert samples["steps_done_total"] == 3
    assert samples['lat_s_bucket{le="+Inf"}'] == 4
    assert samples["lat_s_count"] == 4


def test_sanitize_and_collision():
    assert sanitize_name("a.b/c") == "a_b_c"
    assert sanitize_name("0abc") == "_0abc"
    assert sanitize_name("fine_name") == "fine_name"
    r = Registry()
    r.gauge("a.b", help="x")
    r.gauge("a/b", help="y")        # both sanitize to a_b
    with pytest.raises(ValueError, match="collision"):
        render_prometheus(r)


def test_parse_exposition_rejects_malformed():
    with pytest.raises(ValueError):
        parse_exposition("not-a-sample-line-without-value")
    with pytest.raises(ValueError):
        parse_exposition("9bad_name 1")


def test_exporter_http_roundtrip():
    r = _full_registry()
    calls = []
    health = {"ok": True, "detail": "fine"}
    with MetricsExporter(r, health_fn=lambda: health,
                         collect=[lambda: calls.append(1)]) as ex:
        url = f"http://127.0.0.1:{ex.port}"
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        assert calls, "collect hook did not run"
        assert parse_exposition(text)["steps_done_total"] == 3
        with urllib.request.urlopen(f"{url}/healthz", timeout=10) as resp:
            assert resp.status == 200
            assert json.loads(resp.read())["ok"] is True
        health["ok"] = False
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{url}/healthz", timeout=10)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["ok"] is False


# ---- health.py: spec grammar ----

def test_health_spec_grammar():
    checks = parse_health_spec(
        "nonfinite:skip;spike:halt,factor=5;stall,min_s=2")
    by = {c["detector"]: c for c in checks}
    assert by["nonfinite"]["action"] == "skip"
    assert by["spike"]["action"] == "halt" and by["spike"]["factor"] == 5.0
    assert by["spike"]["warmup"] == 20          # default preserved
    assert by["stall"]["action"] == "warn" and by["stall"]["min_s"] == 2.0
    assert parse_health_spec("") == []


@pytest.mark.parametrize("bad", [
    "gradnorm:halt",            # unknown detector
    "spike:explode",            # unknown action
    "spike,windowz=3",          # unknown param
    "spike;spike",              # duplicate
    "spike:skip",               # skip only valid for nonfinite
    "spike,factor=abc",         # non-numeric param
])
def test_health_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_health_spec(bad)


def test_config_validates_health_spec_and_port():
    with pytest.raises(ValueError):
        TrainConfig(health_spec="bogus:halt")
    with pytest.raises(ValueError):
        TrainConfig(metrics_port=-1)


# ---- health.py: detectors (fake clock — no sleeps) ----

def test_nonfinite_detector_halts_and_gauges():
    r = Registry()
    h = HealthMonitor("nonfinite:halt", registry=r)
    assert h.observe_step(1, loss=1.0, grad_norm=1.0, nonfinite=0.0) == []
    evs = h.observe_step(2, loss=float("nan"), grad_norm=1.0)
    assert [e.detector for e in evs] == ["nonfinite"]
    assert h.should_halt and h.halt_event.step == 2
    assert r.snapshot()["health_ok"] == 0.0
    assert r.snapshot()["health_nonfinite_trips"] == 1
    # The in-graph flag alone also trips, even with finite host values.
    h2 = HealthMonitor("nonfinite:warn")
    assert h2.observe_step(1, loss=1.0, nonfinite=1.0)
    assert not h2.should_halt                    # warn never halts


def test_skip_nonfinite_property():
    assert HealthMonitor("nonfinite:skip").skip_nonfinite is True
    assert HealthMonitor("nonfinite:halt").skip_nonfinite is False
    assert HealthMonitor("spike:warn").skip_nonfinite is False


def test_spike_detector_ewma():
    h = HealthMonitor("spike:warn,warmup=5,factor=10")
    for i in range(6):
        assert h.observe_step(i + 1, grad_norm=1.0) == []
    evs = h.observe_step(7, grad_norm=50.0)
    assert [e.detector for e in evs] == ["spike"]
    assert evs[0].value == 50.0 and evs[0].threshold == pytest.approx(10.0)
    # NaN norms don't poison the EWMA baseline (no spike detector trip on
    # the next finite value).
    h.observe_step(8, grad_norm=float("nan"))
    assert h.observe_step(9, grad_norm=1.0) == []


def test_divergence_detector():
    h = HealthMonitor("divergence:halt,warmup=5,factor=1.5,decay=0.0")
    # decay=0 -> EWMA == latest loss; best tracks the minimum.
    for i, loss in enumerate((5.0, 4.0, 3.0, 2.0, 1.0)):
        assert h.observe_step(i + 1, loss=loss) == []
    evs = h.observe_step(6, loss=2.0)           # 2.0 > 1.0 * 1.5
    assert [e.detector for e in evs] == ["divergence"]
    assert h.should_halt


def test_stall_detector_fake_clock():
    t = [0.0]
    h = HealthMonitor("stall:warn,factor=10,min_s=5,window=8",
                      clock=lambda: t[0])
    for i in range(6):
        t[0] += 0.1
        h.observe_step(i + 1, step_time=0.1)
    # median step time 0.1 -> deadline max(1.0, 5.0) = 5.0
    t[0] += 4.0
    assert h.check_stall() is None and h.ok
    t[0] += 2.0
    ev = h.check_stall()
    assert ev is not None and ev.detector == "stall" and not h.ok
    assert h.check_stall() is None              # latched until re-armed
    h.beat()
    assert h.ok
    status = h.status()
    assert status["stalled"] is False
    assert status["detectors"]["stall"]["trips"] == 1
    assert status["events"][-1]["detector"] == "stall"


# ---- flightrec.py + analyze flight ----

def test_flight_recorder_dump_load_and_analyze(tmp_path, capsys):
    r = _full_registry()
    tr = Tracer()
    with tr.span("host_dispatch", step=1):
        pass
    rec = FlightRecorder(str(tmp_path / "fr.json"), capacity=4, tracer=tr,
                         registry=r, snapshot_every=2)
    for i in range(6):                  # ring holds the LAST 4
        rec.record_step(i + 1, loss=float(i))
    rec.record_event("fault", {"kind": "grad_nan"})
    rec.record_health({"detector": "nonfinite", "action": "halt", "step": 6,
                       "value": None, "threshold": None, "message": "nan",
                       "t": 0.0})
    path = rec.dump("watchdog:nonfinite", extra={"note": "test"})
    doc = load_flight(path)
    assert doc["reason"] == "watchdog:nonfinite"
    assert [s["step"] for s in doc["steps"]] == [3, 4, 5, 6]
    assert doc["events"][0]["kind"] == "fault"
    assert doc["health_events"][0]["detector"] == "nonfinite"
    assert doc["metric_snapshots"]            # snapshot_every=2 fired
    assert doc["final_metrics"]["steps_done"] == 3
    assert doc["spans"][0]["name"] == "host_dispatch"
    assert doc["extra"] == {"note": "test"}
    # load_flight refuses unrelated JSON.
    other = tmp_path / "other.json"
    other.write_text('{"kind": "something_else"}')
    with pytest.raises(ValueError):
        load_flight(str(other))
    # analyze flight renders the post-mortem (markdown and --json).
    from ps_pytorch_tpu.tools.analyze import main as analyze_main
    assert analyze_main(["flight", path]) == 0
    out = capsys.readouterr().out
    assert "watchdog:nonfinite" in out and "health events" in out
    assert analyze_main(["flight", path, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["reason"] == \
        "watchdog:nonfinite"


# ---- trace.py: spans yield their mutable args ----

def test_span_yields_mutable_args():
    tr = Tracer()
    with tr.span("wire_read", step=1, channel="g") as sargs:
        sargs["corr"] = "g@7"
    ev = tr.spans()[0]
    assert ev["args"]["corr"] == "g@7" and ev["args"]["channel"] == "g"
    prev = set_default_tracer(tr)
    try:
        with span("ambient", step=2) as sargs:
            sargs["k"] = "v"
    finally:
        set_default_tracer(prev)
    assert tr.spans()[-1]["args"]["k"] == "v"


# ---- cross-process stitching: corr ids -> Chrome flow events ----

def test_stitch_joins_publish_to_read(tmp_path):
    from ps_pytorch_tpu.parallel.transport import KVPytreeChannel
    from ps_pytorch_tpu.runtime.coordinator import KVStore
    from ps_pytorch_tpu.tools.analyze import stitch_chrome_traces

    kv = KVStore()
    tree = {"a": np.arange(8, dtype=np.float32),
            "b": np.ones((4,), np.float32)}
    worker, leader = Tracer(pid=1), Tracer(pid=0)
    prev = set_default_tracer(worker)
    try:
        writer = KVPytreeChannel(kv, "grads/w1", tree)
        writer.publish(3, tree)
        set_default_tracer(leader)
        reader = KVPytreeChannel(kv, "grads/w1", tree)
        got = reader.read()
    finally:
        set_default_tracer(prev)
    assert got is not None and got[0] == 3
    wpath = tmp_path / "trace.json.p1"
    lpath = tmp_path / "trace.json"
    worker.write_chrome_trace(str(wpath))
    leader.write_chrome_trace(str(lpath))
    docs = [json.load(open(lpath)), json.load(open(wpath))]
    merged, n_flows = stitch_chrome_traces(docs)
    assert n_flows >= 1
    starts = [e for e in merged["traceEvents"] if e.get("ph") == "s"]
    finishes = [e for e in merged["traceEvents"] if e.get("ph") == "f"]
    assert starts and finishes
    # Every flow pair shares an id and joins DIFFERENT pids (worker
    # publish -> leader read), and the corr round-trips through the wire
    # meta, not just local span args.
    by_id = {}
    for e in starts + finishes:
        by_id.setdefault(e["id"], []).append(e)
    corr = f"grads/w1@3"
    joined = [evs for evs in by_id.values()
              if {x["args"]["corr"] for x in evs} == {corr}]
    assert joined and {e["pid"] for e in joined[0]} == {0, 1}
    for e in joined[0]:
        if e["ph"] == "f":
            assert e["bp"] == "e"
    # CLI: stitch writes the merged doc and reports the flow count.
    from ps_pytorch_tpu.tools.analyze import main as analyze_main
    out_path = tmp_path / "merged.json"
    assert analyze_main(["stitch", str(lpath), str(wpath),
                         "--out", str(out_path)]) == 0
    assert json.load(open(out_path))["metadata"]["wire_flows"] == n_flows


# ---- trainer E2E: injected NaN gradient -> halt + flight dump ----

def test_trainer_grad_nan_trips_watchdog(tmp_path, capsys):
    from ps_pytorch_tpu.runtime import Trainer
    from ps_pytorch_tpu.runtime.checkpoint import latest_step

    cfg = TrainConfig(
        dataset="synthetic_mnist", network="LeNet", batch_size=64,
        lr=0.01, momentum=0.9, max_steps=8, epochs=0, eval_freq=0,
        train_dir=str(tmp_path / "ckpt"), compute_dtype="float32",
        data_axis=8, log_every=1, seed=3,
        fault_spec="grad_nan:step=3",
        health_spec="nonfinite:halt;spike:warn")
    Trainer(cfg).train()
    set_default_tracer(None)
    out = capsys.readouterr().out
    assert "FAULT grad_nan" in out and "HEALTH nonfinite (halt)" in out
    # The 1-deep pipeline materializes step N at step N+1's sync: poison
    # at 3 must halt by 4 ("within one step"), not run to max_steps.
    halt_step = latest_step(cfg.train_dir)
    assert halt_step is not None and halt_step <= 4
    doc = load_flight(str(tmp_path / "ckpt" / "flightrec.json"))
    assert doc["reason"] == "watchdog:nonfinite"
    assert doc["health_events"][-1]["detector"] == "nonfinite"
    assert any(ev.get("kind") == "fault_grad_nan" for ev in doc["events"])


def test_trainer_skip_nonfinite_keeps_training(tmp_path, capsys):
    from ps_pytorch_tpu.runtime import Trainer

    cfg = TrainConfig(
        dataset="synthetic_mnist", network="LeNet", batch_size=64,
        lr=0.01, momentum=0.9, max_steps=6, epochs=0, eval_freq=0,
        train_dir=str(tmp_path / "ckpt"), compute_dtype="float32",
        data_axis=8, log_every=1, seed=3,
        fault_spec="grad_nan:step=3",
        health_spec="nonfinite:skip")
    tr = Trainer(cfg)
    state = tr.train()
    set_default_tracer(None)
    # skip action: poisoned update dropped in-graph, run completes, and the
    # params that come out are finite.
    for leaf in jax.tree.leaves(state.params):
        assert bool(jnp.isfinite(leaf).all())
    assert tr.health.trips["nonfinite"] >= 1 and not tr.health.should_halt


def test_trainer_exports_metrics_over_http(tmp_path):
    import socket

    from ps_pytorch_tpu.runtime import Trainer

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    cfg = TrainConfig(
        dataset="synthetic_mnist", network="LeNet", batch_size=64,
        lr=0.01, momentum=0.9, max_steps=3, epochs=0, eval_freq=0,
        train_dir=str(tmp_path / "ckpt"), compute_dtype="float32",
        data_axis=8, log_every=1, seed=3, metrics_port=port,
        health_spec="nonfinite:warn")
    tr = Trainer(cfg)
    # Scrape mid-lifetime (exporter runs during train; here we hit the
    # running server right after construction, then train and re-render).
    url = f"http://127.0.0.1:{tr.exporter.port}"
    with urllib.request.urlopen(f"{url}/healthz", timeout=10) as resp:
        body = json.loads(resp.read())
        assert body["ok"] is True and body["process_index"] == 0
    with urllib.request.urlopen(f"{url}/metrics", timeout=10) as resp:
        before = parse_exposition(resp.read().decode())
    assert before["train_steps_total"] == 0
    tr.train()
    set_default_tracer(None)
    after = parse_exposition(render_prometheus(tr.registry))
    assert after["train_steps_total"] == 3
    assert after["train_step"] == 3
    assert after["train_step_latency_s_count"] == 3
    assert after["health_ok"] == 1
    assert "host_rss_bytes" in after and after["host_rss_bytes"] > 0


# ---- serving: /healthz health block + /metrics on the HTTP front-end ----

V, D, L, H, S = 61, 32, 2, 2, 96


def test_serving_healthz_and_metrics_http(tmp_path):
    from ps_pytorch_tpu.models.transformer import TransformerLM
    from ps_pytorch_tpu.serving.engine import ServingEngine
    from ps_pytorch_tpu.serving.server import ServingFrontend
    from ps_pytorch_tpu.telemetry.registry import declare_serving_metrics

    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          max_seq_len=S)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                        positions=jnp.arange(8))["params"]
    registry = declare_serving_metrics(Registry())
    engine = ServingEngine(params, slots=2, vocab=V, d_model=D, n_layers=L,
                           n_heads=H, max_seq_len=S, model_step=11,
                           registry=registry)
    health = HealthMonitor("stall:warn,min_s=60", registry=registry)
    with ServingFrontend(engine, port=0, max_queue=4, health=health) as fe:
        url = f"http://127.0.0.1:{fe.port}"
        # One real generation so the histograms have samples.
        req = urllib.request.Request(
            f"{url}/v1/generate",
            data=json.dumps({"tokens": [1, 2, 3], "n_new": 4,
                             "temperature": 0.0}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
        with urllib.request.urlopen(f"{url}/healthz", timeout=10) as resp:
            assert resp.status == 200
            body = json.loads(resp.read())
        assert body["ok"] is True and body["model_step"] == 11
        assert body["health"]["ok"] is True
        assert "stall" in body["health"]["detectors"]
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            samples = parse_exposition(resp.read().decode())
    assert samples["serve_requests_total"] >= 1
    assert samples["health_ok"] == 1
    assert any(k.startswith("serve_ttft_s_bucket") for k in samples)
    # PR 8 contract gap-fill: the queue-wait histogram samples on every
    # admission, and the SLO metrics are declared (counter stays 0 until
    # an SLOTracker observes a violation).
    assert any(k.startswith("serve_queue_wait_s_bucket") for k in samples)
    assert samples["serve_queue_wait_s_count"] >= 1
    assert samples["slo_violations_total"] == 0
    assert "slo_compliance" in samples and "slo_burn_rate" in samples
