"""The reader over the program's set-up spans (``readers/setup_spans.py``): on
spans built by hand, where every number can be checked by eye. What the
reader takes from the program's fold (``tracer.startup``) is folded here by
the program's own ``setup_summary``, from those spans."""

import os
from types import SimpleNamespace as NS

import pytest

import harness
from conftest import BENCH  # noqa: F401  (puts the repo on sys.path)
from ps_pytorch_tpu.telemetry import trace as program_trace

FILES = harness.Files()
SETUP = FILES.module("readers", "setup_spans.py")
METRICS = ("setup_before_build_s", "setup_build_s", "setup_data_build_s",
           "setup_state_init_s", "setup_unspanned_s",
           "first_step_trace_lower_s", "first_step_backend_compile_s",
           "setup_compile_s", "setup_cache_misses", "recompiles_after_step1")

# Seconds on one axis. The constructor runs 100..110: backend_init 100..101,
# data_build 101..104, state_init 104..108 (its initialiser compiled anew,
# 1.5 s), a key made under setup itself at 108..108.5 (two sub-second
# programs), ops_plane_build 109..109.5: 1.0 s of the ten under no child.
# Between the build and train() the caller compiles under no span (the
# tally). Iteration k runs 120 + 10 k for 8 s.
BUILD = [("backend_init", 100.0, 1.0, {"devices": 1}),
         ("data_build", 101.0, 3.0, {"bytes": 4096}),
         ("state_init", 104.0, 4.0, {"params": 7, "bytes": 56,
                                     "jit_trace_s": 0.25, "jit_lower_s": 0.25,
                                     "backend_compile_s": 1.5, "programs": 1,
                                     "cache_misses": 1}),
         ("ops_plane_build", 109.0, 0.5, {})]
SETUP_ARGS = {"process_age_s": 12.5, "backend_compile_s": 0.125,
              "programs": 2, "cache_misses": 2}
TALLY = {"backend_compile_s": 2.0, "programs": 3, "cache_misses": 1,
         "cache_hits": 2, "cache_load_s": 1.0}
STEP1_DISPATCH = {"jit_trace_s": 0.5, "jit_lower_s": 1.0,
                  "backend_compile_s": 3.0, "cache_load_s": 2.75,
                  "programs": 1, "cache_hits": 1}


def spans_of(steps, recompiles=None, setup=True):
    """Recorded spans of a build and ``steps`` iterations. ``recompiles``
    maps a step to {span name: programs} counted there."""
    spans, ids = [], iter(range(1, 10_000))

    def span(name, t0, dur, parent=None, args=None, **kw):
        ev = dict(id=next(ids), parent=parent, name=name, t0=t0, dur=dur,
                  tid=1, **kw)
        if args:
            ev["args"] = dict(args)
        spans.append(ev)
        return ev["id"]

    if setup:
        top = span("setup", 100.0, 10.0, args=SETUP_ARGS)
        for name, t0, dur, args in BUILD:
            span(name, t0, dur, parent=top, args=args)
        span("resume", 111.0, 0.25)

    def counted(step, name):
        n = (recompiles or {}).get(step, {}).get(name)
        return {"programs": n, "backend_compile_s": 0.75 * n,
                "cache_misses": n} if n else None

    for step in range(1, steps + 1):
        t0 = 120.0 + 10 * step
        root = span("train_step", t0, 8.0, step=step, root=True,
                    args=counted(step, "train_step"))
        span("data_wait", t0, 1.0, parent=root, step=step)
        if step == 1:
            span("flops_trace", t0 + 1.0, 1.5, parent=root, step=step,
                 args={"jit_trace_s": 1.5})
        span("host_dispatch", t0 + 2.5, 5.0, parent=root, step=step,
             args=STEP1_DISPATCH if step == 1
             else counted(step, "host_dispatch"))
    return spans


def install(spans, tally=None, since=None):
    """Make ``spans`` what ``latest_tracer()`` holds. With ``tally`` (what
    was counted under no span before iteration 1 closed) the tracer is this
    PR's: it has folded its set-up then, and ``since`` is what its tally grew
    by afterwards. Without, it is the parent's, which folds nothing."""
    tracer = NS(spans=lambda: list(spans))
    if tally is not None:
        closed = [s for s in spans if s["t0"] + s["dur"] <= 138.0]
        tracer.totals = {k: tally.get(k, 0) + sum(
            (s.get("args") or {}).get(k, 0) for s in closed) for k in (
            "programs", "backend_compile_s", "cache_hits", "cache_misses")}
        tracer.startup = program_trace.setup_summary(tracer, 1)
        tracer.startup_tally = dict(tally)
        tracer.tally = {k: v + (since or {}).get(k, 0)
                        for k, v in tally.items()}
    program_trace.set_default_tracer(tracer)
    program_trace.set_default_tracer(None)


def run_of(records=()):
    lines = []
    return harness.Run(window_records=list(records), say=lines.append,
                       files=FILES), lines


@pytest.mark.parametrize("stat,names,want", [
    ("before_build_s", (), 12.5),
    ("build_s", (), 10.0),
    ("child_s", ("data_build",), 3.0),
    ("child_s", ("state_init",), 4.0),
    ("child_s", ("data_build", "state_init"), 7.0),
    ("child_s", ("control_plane_build",), None),
    # setup: 10 - (1 + 3 + 4 + 0.5); the first root: 8 - (1 + 1.5 + 5)
    ("unspanned_s", (), 1.5 + 0.5),
    ("first_step_trace_lower_s", (), 1.5),
    ("first_step_backend_compile_s", (), 3.0),
    # setup's own + state_init + step 1's dispatch + the tally; the
    # recompile in step 3 came after iteration 1 closed
    ("compile_s", (), 0.125 + 1.5 + 3.0 + 2.0),
    ("cache_misses", (), 2 + 1 + 0 + 1),
])
def test_every_stat_on_a_build_read_by_eye(stat, names, want):
    install(spans_of(4, {3: {"host_dispatch": 1}}), TALLY)
    run, _ = run_of([{"step": k} for k in (2, 3, 4)])
    got = SETUP.read(run, stat, names) if names else SETUP.read(run, stat)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("window,want,said", [
    # steps 2 .. the window's last; the first iteration never counts
    ((5, 6, 7, 8), 3, ["step=3 span=host_dispatch", "step=6 span=train_step",
                       "step=6 span=host_dispatch"]),
    ((4, 5), 1, ["step=3 span=host_dispatch"]),     # cut to the window's end
    ((2,), 0, []),
    ((), None, []),                                 # no window, no reading
])
def test_recompiles_after_step_1_are_cut_to_the_window(window, want, said):
    install(spans_of(9, {3: {"host_dispatch": 1},
                         6: {"train_step": 1, "host_dispatch": 1},
                         9: {"host_dispatch": 2}}), TALLY)
    run, lines = run_of([{"step": k} for k in window])
    assert SETUP.read(run, "recompiles_after_step1") == want
    assert len(lines) == len(said)
    for line, part in zip(sorted(lines), sorted(said)):
        assert line.startswith("RECOMPILE " + part)
        assert " programs=1 s=0.750000" in line


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_a_setup_span_reads_as_nothing(metric):
    """The parent commit: iterations, but no ``setup`` and no fold. Every
    metric file's own parameters, as the harness calls the reader."""
    install(spans_of(4, setup=False))
    run, lines = run_of([{"step": k} for k in (2, 3, 4)])
    spec = FILES.json("layer_metrics", metric + ".json")
    assert spec["reader"] == "setup_spans"
    assert SETUP.read(run, **spec["params"]) is None
    # ... a tracer of this PR's that no trainer built (its fold holds Nones)
    install(spans_of(4, setup=False), TALLY)
    assert SETUP.read(run, **spec["params"]) is None
    assert lines == []
    # ... and no root spans at all (a commit before PR 23), or no tracer
    install([dict(id=1, parent=None, name="setup", t0=1.0, dur=1.0)])
    assert SETUP.read(run, **spec["params"]) is None


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_file_reads_a_number_from_a_full_tracer(metric):
    install(spans_of(4), TALLY)
    run, _ = run_of([{"step": k} for k in (2, 3, 4)])
    spec = FILES.json("layer_metrics", metric + ".json")
    value = SETUP.read(run, **spec["params"])
    assert isinstance(value, (int, float)) and value >= 0
    entry = next(m for m in harness.load_json(
        os.path.join(harness.CHECKOUT, "BENCHMARK.json"))["per_layer"]
        if m["name"] == metric)
    assert "workloads" not in entry      # every cell reports setup_s and mfu
    assert entry["moves"] == ("mfu" if metric == "recompiles_after_step1"
                              else "setup_s")
    assert entry["source"] in ("program_span", "program_counter")


def test_a_compile_under_no_span_after_the_fold_is_a_recompile_not_set_up():
    """Another thread's compile once iteration 1 has closed (a coordinator's,
    the caller's): the fold has been taken, so set-up does not have it, and
    the count that must equal ``compiles_in_window`` does."""
    since = {"programs": 2, "backend_compile_s": 0.5, "cache_misses": 2}
    install(spans_of(4, {3: {"host_dispatch": 1}}), TALLY, since)
    run, lines = run_of([{"step": k} for k in (2, 3, 4)])
    assert SETUP.read(run, "compile_s") == pytest.approx(0.125 + 1.5 + 3.0 + 2.0)
    assert SETUP.read(run, "cache_misses") == 4
    assert SETUP.read(run, "recompiles_after_step1") == 1 + 2
    assert lines[0].startswith("RECOMPILE step=3 span=host_dispatch ")
    assert lines[1] == ("RECOMPILE under no span since step 1 closed: "
                        "programs=2 s=0.500000")
    with pytest.raises(ValueError):
        SETUP.read(run, "mode_s")


def test_the_parts_of_step_1s_dispatch_are_no_more_than_the_span():
    """What the acceptance holds every cell to, on the fixture: trace + lower
    + compile-or-load of iteration 1's dispatch against ``first_dispatch_s``
    of ``program_spans``."""
    install(spans_of(3), TALLY)
    run, _ = run_of([{"step": 2}, {"step": 3}])
    parts = SETUP.read(run, "first_step_trace_lower_s") + \
        SETUP.read(run, "first_step_backend_compile_s")
    whole = FILES.module("readers", "program_spans.py").read(
        run, "first_step_s", ["host_dispatch"])
    assert parts <= whole == pytest.approx(5.0)
