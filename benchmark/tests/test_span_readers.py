"""The readers over the program's own spans (``readers/program_spans.py``,
``readers/idle_by_span.py``): on spans and a trace built by hand, where the
arithmetic can be checked by eye."""

import re
from types import SimpleNamespace as NS

import pytest

import harness
import trace_reduce as tr
from conftest import BENCH  # noqa: F401  (puts the repo on sys.path)
from ps_pytorch_tpu.telemetry import trace as program_trace

FILES = harness.Files()
SPANS = FILES.module("readers", "program_spans.py")
IDLE = FILES.module("readers", "idle_by_span.py")

ORIGIN = 1_790_000_000_000_000_000      # Unix ns at which the xplane reads 0
MONO0 = 5000.0                          # time.monotonic() at that instant
PERIOD = 100_000                        # ns a step
# one iteration, ns from its start: the root and its children
ROOT = (0, 98_000)
CHILDREN = [("data_wait", 0, 5_000), ("batch_put", 5_000, 20_000),
            ("host_dispatch", 20_000, 30_000), ("metrics_sync", 30_000, 92_000),
            ("log_write", 92_000, 96_000)]
# the step program runs 24-88 us into its step: launched 4 us after
# host_dispatch opens, seen by metrics_sync 4 us after it ends
MODULE = (24_000, 88_000)
# idle per step, by what the host did: 88-92 metrics_sync, 92-96 log_write,
# 96-98 the root alone, 98-100 outside, then 0-5 data_wait, 5-20 batch_put,
# 20-24 host_dispatch
IDLE_NS = {"metrics_sync": 4_000, "log_write": 4_000, "train_step(self)": 2_000,
           "outside_every_iteration": 2_000, "data_wait": 5_000,
           "batch_put": 15_000, "host_dispatch": 4_000}


def program_spans(steps, extra=None):
    """Recorded spans (as ``Tracer.spans()`` gives them) of ``steps``
    iterations; ``extra`` maps a step to more (name, lo, hi) children."""
    spans, ids = [], iter(range(1, 10_000))

    def span(name, step, lo, hi, parent=None, **kw):
        base = (step - 1) * PERIOD
        spans.append(dict(id=next(ids), parent=parent, name=name, step=step,
                          t0=MONO0 + (base + lo) * 1e-9, dur=(hi - lo) * 1e-9,
                          wall_ns=ORIGIN + base + lo, tid=1, **kw))
        return spans[-1]["id"]

    for step in range(1, steps + 1):
        root = span("train_step", step, *ROOT, root=True)
        for name, lo, hi in CHILDREN + (extra or {}).get(step, []):
            span(name, step, lo, hi, parent=root)
    return spans


def install(spans):
    """Make ``spans`` what ``latest_tracer()`` returns."""
    program_trace.set_default_tracer(NS(spans=lambda: list(spans)))
    program_trace.set_default_tracer(None)


def plane(name, stats=(), **lines):
    return NS(name=name, stats=list(stats), lines=[
        NS(name=ln.replace("_", " "), events=[
            NS(name=n, start_ns=s, duration_ns=d) for n, s, d in evs])
        for ln, evs in lines.items()])


def profile(steps, annotations, device_early_ns=0):
    """An xplane of ``steps`` steps: chip 0 busy while the step program
    runs, its events ``device_early_ns`` too early on the host's axis; the
    program's annotations on a host thread, or none (host tracer off)."""
    mods, host = [], []
    for k in range(steps):
        s = k * PERIOD + MODULE[0] - device_early_ns
        mods.append(("jit_local_step(7)", s, MODULE[1] - MODULE[0]))
        if annotations:
            host.append(("train_step", k * PERIOD + ROOT[0], ROOT[1] - ROOT[0]))
            host += [(n, k * PERIOD + lo, hi - lo) for n, lo, hi in CHILDREN]
    return NS(planes=[
        plane("/device:TPU:0",
              XLA_Ops=[("%fusion.1 = f32[8] fusion(...), kind=kOutput", s, d)
                       for _, s, d in mods],
              XLA_Modules=mods),
        plane("/host:CPU", python=host),
        plane("Task Environment", stats=[("profile_start_time", ORIGIN),
                                         ("profile_stop_time", ORIGIN + 10 ** 9)])])


def run_of(data, records=()):
    lines = []
    run = harness.Run(trace=tr.from_profile_data(data) if data else None,
                      window_records=list(records), say=lines.append,
                      files=FILES)
    return run, lines


# ------------------------------------------------------- program_spans --

def test_span_statistics_over_the_windows_steps():
    # step 1 also traces the FLOPs and compiles; step 4 ends an epoch: its
    # data_wait is 31 us, not 5; a second coordinator-like pair of one name
    extra = {1: [("flops_trace", 96_000, 97_000)],
             3: [("coordinator", 96_000, 96_500), ("coordinator", 97_000, 97_500)]}
    spans = program_spans(6, extra)
    for s in spans:
        if s["name"] == "data_wait" and s["step"] == 4:
            s["dur"] = 31_000e-9
    install(spans)
    # the window's first step is the harness's: left out
    run, _ = run_of(None, [{"step": k} for k in (1, 2, 3, 4, 5, 6)])
    read = SPANS.read
    assert read(run, "median_ms", ["batch_put"]) == pytest.approx(15e-3)
    # a step's spans of one name add up; a name absent from a step counts 0
    # there; a name the window never saw counts for nothing
    assert read(run, "median_ms", ["batch_put", "log_write", "telemetry_publish"]) \
        == pytest.approx(19e-3)
    assert read(run, "median_ms", ["coordinator"]) == pytest.approx(0.0)
    assert read(run, "mean_ms", ["coordinator"]) == pytest.approx(1e-3 / 5)
    # the median hides the epoch's turnover, the mean holds it
    assert read(run, "median_ms", ["data_wait"]) == pytest.approx(5e-3)
    assert read(run, "mean_ms", ["data_wait"]) == pytest.approx((4 * 5 + 31) / 5 * 1e-3)
    # the root's self time: 98 us less the 96 its children cover (in step 3
    # the two coordinator spans take 1 us more, in step 4 data_wait overlaps
    # batch_put and still covers nothing new)
    assert read(run, "self_median_ms") == pytest.approx(2e-3)
    assert read(run, "first_step_s", ["flops_trace"]) == pytest.approx(1e-6)
    assert read(run, "first_step_s", ["host_dispatch"]) == pytest.approx(10e-6)
    assert read(run, "first_step_s", ["checkpoint"]) is None
    assert read(run, "median_ms", ["checkpoint"]) is None
    with pytest.raises(ValueError):
        read(run, "mode_ms", ["batch_put"])


def test_a_program_without_root_spans_reads_as_nothing():
    # the parent commit's tracer: spans, but no iteration root
    install([dict(id=1, parent=None, name="data_wait", t0=1.0, dur=0.1, step=1)])
    run, _ = run_of(None, [{"step": 1}])
    assert SPANS.read(run, "median_ms", ["data_wait"]) is None
    assert SPANS.read(run, "self_median_ms") is None
    assert IDLE.read(run) is None
    install(program_spans(3))
    assert SPANS.read(run_of(None)[0], "median_ms", ["data_wait"]) is None


# --------------------------------------------------------- idle_by_span --

def idle_lines(lines):
    return {line.split()[1]: float(line.split()[2]) for line in lines
            if line.startswith("IDLE_BY_SPAN") and "total" not in line}


# the chip's events 7 us early on the host's axis: moved by the least that
# lets every run start after its dispatch opened, 3 us, so still 4 early
IDLE_NS_EARLY = dict(IDLE_NS, metrics_sync=8_000, host_dispatch=0)


@pytest.mark.parametrize("annotations, device_early_ns", [
    (True, 0), (False, 0), (True, 7_000), (False, 7_000)])
def test_idle_under_known_spans(monkeypatch, annotations, device_early_ns):
    """The annotation path (host tracer on) and the anchor path (off) give
    the same attribution, and a chip whose events contradict the spans is
    moved by the least that mends it."""
    install(program_spans(6))
    data = profile(6, annotations, device_early_ns)
    monkeypatch.setattr(IDLE, "load_profile", lambda run: data)
    run, lines = run_of(data)
    share = IDLE.read(run)
    # steady window: second to last run of the step program, 4 periods
    want = IDLE_NS_EARLY if device_early_ns else IDLE_NS
    assert idle_lines(lines) == {k: pytest.approx(v * 1e-6, abs=1e-9)
                                 for k, v in want.items()}
    assert share == pytest.approx(100 * 4_000 / 36_000)
    total = [line for line in lines if line.startswith("IDLE_BY_SPAN total")]
    assert total and float(total[0].split()[2]) == pytest.approx(36e-3)
    clock = [line for line in lines if line.startswith("SPANCLOCK")]
    if annotations:
        assert "median 0.0 us, worst 0.0 us" in clock[0]
    else:
        assert "no annotations" in clock[0]
    dev = [line for line in lines if line.startswith("DEVCLOCK")][0]
    fits_lo, fits_hi, moved = map(float, re.findall(r"[+-]\d+\.\d+", dev))
    assert fits_lo == pytest.approx((device_early_ns - 4_000) * 1e-6)
    assert fits_hi == pytest.approx((device_early_ns + 4_000) * 1e-6)
    assert moved == pytest.approx(max(0, device_early_ns - 4_000) * 1e-6)


def test_anchors_that_disagree_with_the_annotations_are_reported(monkeypatch):
    spans = program_spans(6)
    for s in spans:
        s["wall_ns"] += 250_000         # the program's clock 250 us late
    install(spans)
    data = profile(6, annotations=True)
    monkeypatch.setattr(IDLE, "load_profile", lambda run: data)
    run, lines = run_of(data)
    assert IDLE.read(run) == pytest.approx(100 * 4_000 / 36_000)
    clock = [line for line in lines if line.startswith("SPANCLOCK")][0]
    assert "median 50.0 us" in clock     # nearest span of the name, mod 100 us


def test_no_trace_no_origin_no_idle(monkeypatch):
    install(program_spans(6))
    run, _ = run_of(None)
    assert IDLE.read(run) is None                   # no trace
    data = profile(6, annotations=False)
    data.planes.pop()                               # no Task Environment
    monkeypatch.setattr(IDLE, "load_profile", lambda run: data)
    assert IDLE.read(run_of(data)[0]) is None       # neither path
    assert IDLE.origin_ns(None) is None
    assert IDLE.origin_ns(profile(2, True)) == ORIGIN
