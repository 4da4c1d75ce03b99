"""Request-scoped serving observability (ISSUE 8): the --slo-spec grammar
and config knobs, WindowPercentile / SLOTracker burn-rate transitions under
a ManualClock, the request-trace ring's tail-based sampling determinism,
the exact phase partition (queue_wait + prefill + decode + stream_out ==
latency) on real engine runs WITH bitwise generate() parity preserved,
queue shed-on-submit/reap, summarize hardening, the SLO sweep ladder,
analyze's requests mode + request↔engine stitch flows, the /slo and
/debug/requests HTTP routes, and the health steptime watchdog.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.models.generate import generate
from ps_pytorch_tpu.models.transformer import TransformerLM
from ps_pytorch_tpu.resilience.faults import ManualClock
from ps_pytorch_tpu.serving.engine import Request, ServingEngine
from ps_pytorch_tpu.serving.loadgen import (
    make_requests, run_closed_loop, run_slo_sweep, summarize,
)
from ps_pytorch_tpu.serving.queue import AdmissionQueue
from ps_pytorch_tpu.serving.reqtrace import (
    RequestTrace, RequestTraceLog, _hash_frac, corr_id,
    format_requests_table, trace_from_request,
)
from ps_pytorch_tpu.telemetry.registry import Registry, declare_serving_metrics
from ps_pytorch_tpu.telemetry.slo import (
    SLOTracker, WindowPercentile, check_slo, parse_slo_spec,
)

V, D, L, H, S = 61, 32, 2, 2, 96


@pytest.fixture(scope="module")
def params():
    model = TransformerLM(vocab_size=V, d_model=D, n_layers=L, n_heads=H,
                          max_seq_len=S)
    return model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                      positions=jnp.arange(8))["params"]


def _engine(params, slots, **kw):
    return ServingEngine(params, slots=slots, vocab=V, d_model=D,
                         n_layers=L, n_heads=H, max_seq_len=S, **kw)


# ---- telemetry/slo.py: the --slo-spec grammar ----

def test_parse_slo_spec_full():
    objs = parse_slo_spec("ttft_p99<100ms; latency_p99<2s;"
                          "availability>=99.5")
    assert [o.name for o in objs] == ["ttft_p99", "latency_p99",
                                      "availability"]
    assert objs[0].threshold == pytest.approx(0.1)     # ms -> s
    assert objs[1].threshold == pytest.approx(2.0)
    assert objs[2].threshold == 99.5 and objs[2].percentile is None
    # Error budgets: p99 tolerates 1%, availability>=99.5 tolerates 0.5%.
    assert objs[0].budget_frac == pytest.approx(0.01)
    assert objs[2].budget_frac == pytest.approx(0.005)


def test_parse_slo_spec_units_and_ops():
    (o,) = parse_slo_spec("queue_wait_p50<=2500us")
    assert o.metric == "queue_wait" and o.percentile == 50.0
    assert o.op == "<=" and o.threshold == pytest.approx(2.5e-3)
    assert o.check(2.5e-3) is True and o.check(2.6e-3) is False
    assert o.check(None) is None
    assert parse_slo_spec("") == []


@pytest.mark.parametrize("bad", [
    "p99<100ms",                    # no metric
    "loss_p99<1s",                  # unknown metric
    "ttft_p0<1s",                   # percentile out of (0, 100)
    "ttft_p99<0ms",                 # non-positive threshold
    "ttft_p99>100ms",               # > is availability-only
    "availability>=0",              # out of (0, 100]
    "availability>=101",
    "ttft_p99<1s;ttft_p99<2s",      # duplicate objective
    "garbage",
])
def test_parse_slo_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_slo_spec(bad)


def test_config_validates_slo_knobs():
    from ps_pytorch_tpu.config import TrainConfig
    cfg = TrainConfig(slo_spec="ttft_p99<100ms", reqtrace_keep=8,
                      reqtrace_sample=0.5)
    assert cfg.slo_spec == "ttft_p99<100ms"
    with pytest.raises(ValueError, match="SLO|slo"):
        TrainConfig(slo_spec="bogus_p99<1s")
    with pytest.raises(ValueError, match="reqtrace"):
        TrainConfig(reqtrace_keep=-1)
    with pytest.raises(ValueError, match="reqtrace"):
        TrainConfig(reqtrace_sample=1.5)


# ---- telemetry/slo.py: WindowPercentile ----

def test_window_percentile_prunes_and_gates():
    clk = ManualClock()
    w = WindowPercentile(10.0, clock=clk.time)
    for i in range(10):
        w.observe(float(i), now=float(i))
    assert w.count(now=9.0) == 10
    assert w.percentile(50.0, now=9.0) == pytest.approx(4.5)
    assert w.percentile(99.0, now=9.0, min_n=20) is None   # below min_n
    assert w.frac_over(6.5, now=9.0) == pytest.approx(0.3)
    # Advance: samples with t < now - window fall out.
    assert w.count(now=15.1) == 4                          # 6, 7, 8, 9
    assert w.frac_over(100.0, now=30.0) is None            # empty window
    with pytest.raises(ValueError):
        WindowPercentile(0.0)


def test_window_percentile_bounds_memory():
    clk = ManualClock()
    w = WindowPercentile(1e9, clock=clk.time, max_samples=64)
    for i in range(1000):
        w.observe(float(i), now=0.0)
    assert w.count(now=0.0) == 64


# ---- telemetry/slo.py: burn-rate state machine ----

def _tracker(clk, **kw):
    kw.setdefault("fast_window_s", 60.0)
    kw.setdefault("slow_window_s", 300.0)
    kw.setdefault("min_samples", 10)
    return SLOTracker("ttft_p99<100ms;availability>=99", clock=clk.time,
                      **kw)


def test_slo_tracker_ok_to_page_and_recovery():
    clk = ManualClock()
    t = _tracker(clk)
    # Below min_samples: no verdict, no alarm.
    t.observe_request(ttft_s=0.01, latency_s=0.02, now=0.0)
    ev = t.evaluate(now=0.0)
    assert ev["state"] == "ok"
    assert ev["objectives"][0]["compliant"] is None
    # 20 healthy requests -> compliant, zero burn.
    for i in range(20):
        t.observe_request(ttft_s=0.01, latency_s=0.02, now=1.0 + i)
    ev = t.evaluate(now=21.0)
    assert ev["state"] == "ok" and ev["compliance"] == 1.0
    ttft_row = ev["objectives"][0]
    assert ttft_row["compliant"] is True and ttft_row["state"] == "ok"
    # A violation storm: every request over the TTFT bound burns 100x a
    # 1% budget in BOTH windows -> page.
    for i in range(20):
        t.observe_request(ttft_s=0.5, latency_s=0.6, now=22.0 + i)
    ev = t.evaluate(now=42.0)
    assert ev["state"] == "page"
    assert ev["objectives"][0]["state"] == "page"
    assert ev["burn_rate"] > 2.0
    assert t.violations >= 20
    # Recovery: the fast window drains past the storm while the slow one
    # still remembers it — the multi-window rule stops paging immediately
    # (fast burn cleared) even though slow burn is still hot.
    for i in range(30):
        t.observe_request(ttft_s=0.01, latency_s=0.02, now=120.0 + i)
    ev = t.evaluate(now=160.0)    # storm left the 60s fast window [100,160]
    assert ev["objectives"][0]["burn_fast"] == pytest.approx(0.0)
    assert ev["objectives"][0]["burn_slow"] > 0.0
    assert ev["objectives"][0]["state"] == "ok"


def test_slo_tracker_availability_and_rejected():
    clk = ManualClock()
    t = _tracker(clk)
    for i in range(18):
        t.observe_request(outcome="done", ttft_s=0.01, latency_s=0.02,
                          now=float(i))
    # Rejected requests are excluded from availability entirely.
    t.observe_request(outcome="rejected", now=18.0)
    t.observe_request(outcome="shed", now=19.0)
    t.observe_request(outcome="shed", now=20.0)
    ev = t.evaluate(now=21.0)
    avail = next(r for r in ev["objectives"]
                 if r["metric"] == "availability")
    # 18 done out of 20 eligible (rejected doesn't count) = 90%.
    assert avail["value"] == pytest.approx(90.0)
    assert avail["compliant"] is False
    assert avail["samples_slow"] == 20


def test_slo_tracker_registry_gauges_idempotent_with_serving_contract():
    clk = ManualClock()
    registry = declare_serving_metrics(Registry())
    # Declaring on a registry that already carries the serving contract
    # must not conflict (MetricSpec equality), nor on a bare one.
    t = SLOTracker("ttft_p99<100ms", clock=clk.time, registry=registry,
                   min_samples=5)
    t2 = SLOTracker("ttft_p99<100ms", registry=Registry())
    assert t2.observed == 0
    for i in range(10):
        t.observe_request(ttft_s=0.5, latency_s=0.5, now=float(i))
    t.evaluate(now=10.0)
    snap = registry.snapshot()
    assert snap["slo_compliance"] == 0.0
    assert snap["slo_burn_rate"] > 2.0
    assert snap["slo_violations"] == 10


def test_check_slo_offline_maps_summarize_stats():
    objs = parse_slo_spec("latency_p99<2s;availability>=99")
    good = {"latency_p99_ms": 150.0, "availability": 1.0}
    v = check_slo(good, objs)
    assert v["compliant"] is True
    # None stats (suppressed percentiles) read as non-compliant.
    v = check_slo({"latency_p99_ms": None, "availability": 1.0}, objs)
    assert v["compliant"] is False
    v = check_slo({"latency_p99_ms": 150.0, "availability": 0.98}, objs)
    assert v["compliant"] is False


# ---- serving/reqtrace.py: phase partition + tail sampling ----

def _req(rid, state="done", t=(1.0, 1.0, 2.0, 3.0, 5.0, 6.0), tokens=3):
    """Request with an explicit (submit, enqueue, admit, first, last, done)
    timeline."""
    r = Request(prompt=np.ones(4, np.int32), n_new=8, rid=rid)
    r.state = state
    r.t_submit, r.t_enqueue, r.t_admit, r.t_first, r.t_last, r.t_done = t
    r.tokens = list(range(tokens))
    return r


def test_trace_phase_partition_done():
    tr = trace_from_request(_req("a"))
    assert tr.queue_wait_s == pytest.approx(1.0)
    assert tr.prefill_s == pytest.approx(1.0)
    assert tr.decode_s == pytest.approx(2.0)
    assert tr.stream_out_s == pytest.approx(1.0)
    assert tr.latency_s == pytest.approx(5.0)
    assert (tr.queue_wait_s + tr.prefill_s + tr.decode_s
            + tr.stream_out_s) == pytest.approx(tr.latency_s)


def test_trace_phase_partition_never_admitted_and_no_token():
    # Never admitted (shed in queue): all latency is queue wait; t_done
    # backfilled from `now`.
    tr = trace_from_request(_req("b", state="shed",
                                 t=(1.0, 1.0, 0.0, 0.0, 0.0, 0.0),
                                 tokens=0), now=4.0)
    assert tr.outcome == "shed" and tr.t_done == 4.0
    assert tr.queue_wait_s == pytest.approx(3.0) == tr.latency_s
    assert tr.prefill_s == tr.decode_s == tr.stream_out_s == 0.0
    # Admitted but resolved before a first token.
    tr = trace_from_request(_req("c", state="failed",
                                 t=(1.0, 1.0, 2.0, 0.0, 0.0, 6.0),
                                 tokens=0))
    assert tr.queue_wait_s == pytest.approx(1.0)
    assert tr.stream_out_s == pytest.approx(4.0)
    assert (tr.queue_wait_s + tr.prefill_s + tr.decode_s
            + tr.stream_out_s) == pytest.approx(tr.latency_s)


def test_ring_tail_sampling_deterministic():
    def feed(log):
        # 40 fast done requests, one slow one, and every bad outcome.
        for i in range(40):
            log.offer_request(_req(f"r{i}",
                                   t=(0.0, 0.0, 0.1, 0.2, 0.3, 0.4)))
        log.offer_request(_req("slowpoke",
                               t=(0.0, 0.0, 1.0, 2.0, 90.0, 91.0)))
        for state in ("shed", "rejected", "failed"):
            log.offer_request(_req(f"x-{state}", state=state, tokens=0),
                              now=50.0)
        return [t.rid for t in log.traces()]

    a = feed(RequestTraceLog(64, sample=0.25, min_window=10))
    b = feed(RequestTraceLog(64, sample=0.25, min_window=10))
    assert a == b                          # replay-identical ring
    log = RequestTraceLog(64, sample=0.25, min_window=10)
    feed(log)
    kept = {t.rid: t.kept for t in log.traces()}
    # Non-done outcomes are ALWAYS retained; the slow tail too.
    for state in ("shed", "rejected", "failed"):
        assert kept[f"x-{state}"] == "outcome"
    assert kept["slowpoke"] == "slow"
    # The fast majority is hash-coin sampled: exactly the rids whose
    # deterministic coin lands under `sample` (modulo slow-threshold keeps).
    for rid, why in kept.items():
        if why == "sampled":
            assert _hash_frac(rid) < 0.25
    st = log.stats()
    assert st["offered"] == 44
    assert st["kept"] == len(kept) and st["dropped"] == 44 - len(kept)
    assert st["by_outcome"]["done"] == 41


def test_ring_bounded_and_validates():
    log = RequestTraceLog(4, sample=1.0)
    for i in range(10):
        log.offer_request(_req(f"r{i}"))
    assert len(log.traces()) == 4          # oldest evicted
    assert log.stats()["offered"] == 10
    with pytest.raises(ValueError):
        RequestTraceLog(0)
    with pytest.raises(ValueError):
        RequestTraceLog(4, sample=1.5)
    with pytest.raises(ValueError):
        RequestTraceLog(4, slow_frac=0.0)


def test_chrome_events_carry_corr():
    log = RequestTraceLog(8, sample=1.0)
    log.offer_request(_req("abc"))
    evs = log.chrome_events(pid=3)
    names = [e["name"] for e in evs]
    assert names[0] == "request"
    assert set(names[1:]) == {"req_queue_wait", "req_prefill",
                              "req_decode", "req_stream_out"}
    for e in evs:
        assert e["args"]["corr"] == corr_id("abc") == "req/abc"
        assert e["pid"] == 3 and e["ph"] == "X"
    umbrella = evs[0]
    assert umbrella["ts"] == pytest.approx(1.0 * 1e6)
    assert umbrella["dur"] == pytest.approx(5.0 * 1e6)


def test_format_requests_table():
    log = RequestTraceLog(8, sample=1.0)
    log.offer_request(_req("abc"))
    text = format_requests_table(log.snapshot())
    lines = text.splitlines()
    assert lines[0].split()[:2] == ["rid", "outcome"]
    assert "abc" in lines[2] and "done" in lines[2]


# ---- E2E: traced engine keeps parity, monotone lifecycle, exact phases --

def test_engine_with_full_plane_parity_and_invariants(params):
    registry = declare_serving_metrics(Registry())
    reqtrace = RequestTraceLog(64, sample=1.0)
    slo = SLOTracker("ttft_p99<60s;latency_p99<120s;availability>=99",
                     registry=registry, min_samples=3)
    eng = _engine(params, 2, registry=registry, reqtrace=reqtrace, slo=slo)
    specs = [dict(n_new=7, temperature=0.8, top_k=7, seed=3, plen=5),
             dict(n_new=1, temperature=1.3, top_k=5, seed=9, plen=3),
             dict(n_new=10, temperature=0.0, top_k=0, seed=4, plen=8)]
    rng = np.random.default_rng(0)
    reqs, refs = [], []
    for i, s in enumerate(specs):
        prompt = rng.integers(0, V, size=s["plen"]).astype(np.int32)
        reqs.append(Request(prompt=prompt, n_new=s["n_new"],
                            temperature=s["temperature"], top_k=s["top_k"],
                            seed=s["seed"], rid=f"e{i}"))
        out = generate(params, jnp.asarray(prompt[None]), n_new=s["n_new"],
                       vocab=V, d_model=D, n_layers=L, n_heads=H,
                       max_seq_len=S, temperature=s["temperature"],
                       top_k=s["top_k"], seed=s["seed"])
        refs.append(np.asarray(out[0])[s["plen"]:].tolist())
    run_closed_loop(eng, reqs)
    # Bitwise generate() parity with the WHOLE plane attached.
    for req, ref in zip(reqs, refs):
        assert req.state == "done" and req.tokens == ref
    traces = {t.rid: t for t in reqtrace.traces()}
    assert len(traces) == len(reqs)        # sample=1.0 keeps everything
    for req in reqs:
        tr = traces[req.rid]
        # Monotone lifecycle timestamps (closed loop bypasses the
        # admission queue, so t_enqueue may legitimately stay unset).
        stamps = [t for t in (tr.t_submit, tr.t_enqueue, tr.t_admit,
                              tr.t_first, tr.t_last, tr.t_done) if t]
        assert stamps == sorted(stamps) and len(stamps) >= 5
        # Phases partition latency exactly.
        assert (tr.queue_wait_s + tr.prefill_s + tr.decode_s
                + tr.stream_out_s) == pytest.approx(tr.latency_s, abs=1e-9)
        # One tick timestamp per emitted token, monotone.
        assert len(tr.ticks) == tr.n_tokens == len(req.tokens)
        assert tr.ticks == sorted(tr.ticks)
    # The SLO plane saw every terminal request and is compliant.
    ev = slo.evaluate()
    assert ev["observed"] == len(reqs) and ev["state"] == "ok"
    assert registry.snapshot()["slo_compliance"] == 1.0
    # run_to_completion's t_submit == t_enqueue == admission-time clock
    # feeds the queue-wait histogram via admit.
    assert registry.hist_summary("serve_queue_wait_s")["count"] == len(reqs)


# ---- queue: shed on submit / reap ----

def _qreq(rid, deadline_t=None):
    r = Request(prompt=np.ones(4, np.int32), n_new=4, rid=rid)
    r.t_submit = 0.0
    r.deadline_t = deadline_t
    return r


def test_queue_submit_reaps_expired_and_frees_depth():
    clk = ManualClock()
    reqtrace = RequestTraceLog(16, sample=1.0)
    q = AdmissionQueue(2, clock=clk.time, reqtrace=reqtrace)
    a, b = _qreq("a", deadline_t=5.0), _qreq("b", deadline_t=5.0)
    assert q.submit(a) and q.submit(b)
    clk.advance(10.0)                      # both deadlines pass
    c = _qreq("c")
    # A full queue of corpses still admits live traffic: submit sheds the
    # expired entries first instead of bouncing c with a 503.
    assert q.submit(c) is True
    assert a.state == "shed" and b.state == "shed"
    assert c.state == "queued" and q.depth() == 1
    assert q.shed_deadline == 2 and q.rejected_full == 0
    # The shed requests landed in the trace ring with their outcome.
    kept = {t.rid: t.outcome for t in reqtrace.traces()}
    assert kept == {"a": "shed", "b": "shed"}


def test_queue_reap_resolves_without_take():
    clk = ManualClock()
    q = AdmissionQueue(4, clock=clk.time)
    a = _qreq("a", deadline_t=1.0)
    b = _qreq("b")
    assert q.submit(a) and q.submit(b)
    clk.advance(2.0)
    assert q.reap() == 1                   # idle-tick path
    assert a.state == "shed" and a.wait(timeout=0)
    assert b.state == "queued" and q.depth() == 1
    assert q.take() is b


def test_queue_reject_records_terminal():
    clk = ManualClock()
    reqtrace = RequestTraceLog(16, sample=1.0)
    q = AdmissionQueue(1, clock=clk.time, reqtrace=reqtrace)
    assert q.submit(_qreq("a"))
    r = _qreq("b")
    assert q.submit(r) is False
    assert r.state == "rejected"
    assert [t.outcome for t in reqtrace.traces()] == ["rejected"]


# ---- loadgen: summarize hardening + the SLO sweep ----

def _done_req(i, ttft=0.01, lat=0.05):
    r = Request(prompt=np.ones(4, np.int32), n_new=4, rid=f"d{i}")
    r.state = "done"
    r.tokens = [1, 2, 3]
    r.t_submit, r.t_admit = 10.0 * i, 10.0 * i + 0.001
    r.t_first, r.t_done = 10.0 * i + ttft, 10.0 * i + lat
    return r


def test_summarize_suppresses_percentiles_below_min_samples():
    reqs = [_done_req(i) for i in range(3)]
    stats = summarize(reqs, wall_s=1.0)
    assert stats["completed"] == 3
    # Keys PRESENT but None: 3 samples don't get to claim a p99.
    for k in ("ttft_p50_ms", "ttft_p99_ms", "latency_p50_ms",
              "latency_p99_ms", "queue_wait_p99_ms"):
        assert k in stats and stats[k] is None
    stats = summarize([_done_req(i) for i in range(5)], wall_s=1.0)
    assert stats["ttft_p99_ms"] == pytest.approx(10.0, rel=0.01)
    assert stats["queue_wait_p99_ms"] == pytest.approx(1.0, rel=0.01)


def test_summarize_availability():
    reqs = [_done_req(i) for i in range(8)]
    shed = Request(prompt=np.ones(4, np.int32), n_new=4, rid="s")
    shed.state, shed.t_submit = "shed", 0.0
    rej = Request(prompt=np.ones(4, np.int32), n_new=4, rid="j")
    rej.state, rej.t_submit = "rejected", 0.0
    stats = summarize(reqs + [shed, rej], wall_s=1.0)
    # 8 done / (10 - 1 rejected) eligible.
    assert stats["availability"] == pytest.approx(8 / 9)
    assert summarize([rej], wall_s=1.0)["availability"] is None


def test_run_slo_sweep_finds_knee(params):
    eng = _engine(params, 2)
    run_closed_loop(eng, make_requests(2, prompt_len=4, n_new=2, vocab=V,
                                       seed=777))     # warm the jit cache
    sweep = run_slo_sweep(eng, "latency_p99<60s;availability>=99",
                          rates=(40.0, 80.0), n_req=5, prompt_len=4,
                          n_new=3, seed=5, timeout_s=60.0)
    assert [r["rate_rps"] for r in sweep["ladder"]] == [40.0, 80.0]
    for rung in sweep["ladder"]:
        assert rung["completed"] == 5
        assert rung["slo"]["compliant"] is True
    assert sweep["knee_rps"] == 80.0 and sweep["ok"] is True
    assert sweep["goodput_under_slo_tps"] == pytest.approx(
        sweep["ladder"][-1]["tokens_per_sec"])
    with pytest.raises(ValueError):
        run_slo_sweep(eng, "latency_p99<60s", rates=())
    with pytest.raises(ValueError):
        run_slo_sweep(eng, "", rates=(1.0,))


@pytest.mark.slow
def test_slo_sweep_soak_overload_rung_breaks(params):
    """Soak: push offered load to where a tight deadline + tiny queue shed
    requests — the overloaded rung must read non-compliant while a gentle
    rung stays compliant (the knee is real, not vacuous)."""
    eng = _engine(params, 1)
    run_closed_loop(eng, make_requests(2, prompt_len=4, n_new=2, vocab=V,
                                       seed=778))
    sweep = run_slo_sweep(eng, "availability>=99;latency_p99<60s",
                          rates=(2.0, 200.0), n_req=12, prompt_len=8,
                          n_new=12, deadline_s=0.001, max_queue=2,
                          seed=11, timeout_s=60.0)
    top = sweep["ladder"][-1]
    assert top["shed"] + top["rejected"] > 0
    assert top["slo"]["compliant"] is False


# ---- tools/analyze.py: requests mode + request<->engine stitch ----

def test_analyze_requests_waterfall(tmp_path):
    from ps_pytorch_tpu.tools.analyze import (
        read_request_rows, requests_markdown, requests_summary,
    )
    log = RequestTraceLog(16, sample=1.0)
    for i in range(4):
        # Nonzero t_submit: zero means "never set" to the phase partition.
        log.offer_request(_req(f"r{i}", t=(1, 1, 2, 3, 4 + i, 5 + i)))
    p = tmp_path / "reqs.json"
    p.write_text(json.dumps({"requests": log.snapshot()}))
    rows = read_request_rows(str(p))
    assert len(rows) == 4
    s = requests_summary(rows, top=2)
    assert s["requests"] == 4 and s["outcomes"] == {"done": 4}
    shares = sum(ph["share"] for ph in s["phases"].values())
    assert shares == pytest.approx(1.0)
    assert len(s["slowest"]) == 2
    assert s["slowest"][0]["rid"] == "r3"     # largest latency first
    md = requests_markdown(s)
    assert "| queue_wait |" in md and "r3" in md
    # JSONL shape reads identically.
    p2 = tmp_path / "reqs.jsonl"
    p2.write_text("\n".join(json.dumps(r) for r in log.snapshot()))
    assert read_request_rows(str(p2)) == rows


def test_stitch_joins_request_and_engine_spans():
    from ps_pytorch_tpu.tools.analyze import stitch_chrome_traces
    log = RequestTraceLog(8, sample=1.0)
    log.offer_request(_req("abc"))
    doc = {"traceEvents": log.chrome_events(pid=0) + [
        {"ph": "X", "name": "serve_admit", "pid": 1, "tid": 1, "ts": 2e6,
         "dur": 1e5, "args": {"corr": "req/abc", "rid": "abc"}},
        {"ph": "X", "name": "serve_decode", "pid": 1, "tid": 1, "ts": 3e6,
         "dur": 1e5, "args": {"active": 2, "rids": ["abc", "zzz"]}},
    ]}
    merged, n_flows = stitch_chrome_traces([doc])
    meta = merged["metadata"]
    # request -> serve_admit and request -> serve_decode (via rids fan-out;
    # the unmatched rid "zzz" has no request span, so no flow for it).
    assert meta["request_flows"] == 2 and meta["wire_flows"] == 0
    assert n_flows == 2
    flows = [e for e in merged["traceEvents"] if e.get("name") == "req_flow"]
    assert len(flows) == 4                 # two s/f pairs
    assert all(e["args"]["corr"] == "req/abc" for e in flows)
    starts = [e for e in flows if e["ph"] == "s"]
    assert all(e["ts"] == pytest.approx(1e6) for e in starts)


# ---- server: /slo + /debug/requests routes ----

def test_http_slo_and_debug_requests(params):
    import urllib.error
    import urllib.request
    from ps_pytorch_tpu.serving.server import ServingFrontend

    registry = declare_serving_metrics(Registry())
    reqtrace = RequestTraceLog(32, sample=1.0)
    slo = SLOTracker("ttft_p99<60s;availability>=99", registry=registry,
                     min_samples=1)
    eng = _engine(params, 2, registry=registry, reqtrace=reqtrace, slo=slo)
    with ServingFrontend(eng, port=0, max_queue=4) as fe:
        url = f"http://127.0.0.1:{fe.port}"
        body = json.dumps({"tokens": [1, 2, 3], "n_new": 3,
                           "temperature": 0.0}).encode()
        req = urllib.request.Request(
            f"{url}/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
        with urllib.request.urlopen(f"{url}/slo", timeout=10) as resp:
            ev = json.loads(resp.read())
        assert ev["state"] == "ok" and ev["observed"] >= 1
        assert {r["name"] for r in ev["objectives"]} == {"ttft_p99",
                                                         "availability"}
        with urllib.request.urlopen(f"{url}/debug/requests",
                                    timeout=10) as resp:
            dbg = json.loads(resp.read())
        assert dbg["stats"]["kept"] >= 1
        assert dbg["requests"][0]["outcome"] == "done"
        assert dbg["requests"][0]["n_tokens"] == 3
        with urllib.request.urlopen(f"{url}/debug/requests?text=1",
                                    timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            assert "outcome" in resp.read().decode()
    # Routes 404 when the plane is off.
    eng2 = _engine(params, 1)
    with ServingFrontend(eng2, port=0, max_queue=4) as fe:
        url = f"http://127.0.0.1:{fe.port}"
        for route in ("/slo", "/debug/requests"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{url}{route}", timeout=10)
            assert ei.value.code == 404


# ---- telemetry/health.py: steptime watchdog ----

def test_health_steptime_rising_edge_latch():
    from ps_pytorch_tpu.telemetry.health import (
        HealthMonitor, parse_health_spec,
    )
    with pytest.raises(ValueError, match="p99_s"):
        parse_health_spec("steptime:warn")          # no sane default bound
    clk = ManualClock()
    h = HealthMonitor("steptime:warn,p99_s=0.5,min_n=5,window_s=60",
                      clock=clk.time)
    events = []
    for i in range(10):
        clk.advance(1.0)
        events += h.observe_step(i + 1, loss=1.0, step_time=0.1,
                                 now=clk.now)
    assert events == []                             # healthy: no trips
    for i in range(10):
        clk.advance(1.0)
        events += h.observe_step(11 + i, loss=1.0, step_time=1.0,
                                 now=clk.now)
    trips = [e for e in events if e.detector == "steptime"]
    assert len(trips) == 1                          # latched: ONE event
    assert trips[0].threshold == pytest.approx(0.5)
    # Recovery re-arms the latch; a second excursion trips again.
    events = []
    for i in range(70):                             # flush the 60s window
        clk.advance(1.0)
        events += h.observe_step(21 + i, loss=1.0, step_time=0.1,
                                 now=clk.now)
    assert events == []
    for i in range(10):
        clk.advance(1.0)
        events += h.observe_step(91 + i, loss=1.0, step_time=1.0,
                                 now=clk.now)
    assert len([e for e in events if e.detector == "steptime"]) == 1
