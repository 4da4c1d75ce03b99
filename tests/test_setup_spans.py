"""Set-up under the program's own spans: both trainers create their tracer
before anything is built, so the constructor is one tree under ``setup``;
every compile is counted in the span that caused it
(``utils/compile_cache.py`` -> ``Tracer.add``); the fold of it all rides on a
run's first record and the ``STARTUP`` line. Structure and counts only: no
assertion here is on the size of a duration."""

import contextlib
import importlib.util
import io
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.runtime import Trainer
from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer
from ps_pytorch_tpu.telemetry import (
    Registry, Tracer, declare_training_metrics, get_default_tracer,
    latest_tracer, self_times, set_default_tracer, startup_line,
)
from ps_pytorch_tpu.telemetry import trace
from ps_pytorch_tpu.utils import compile_cache

STEPS = 6
ODD = 4         # the step that is fed a batch of another shape
KINDS = ("cnn", "lm")
_BASE = {
    "cnn": dict(dataset="synthetic_mnist", network="LeNet", batch_size=64,
                lr=0.01, momentum=0.9, epochs=0, compute_dtype="float32",
                data_axis=8),
    "lm": dict(lm_vocab=64, lm_d_model=32, lm_layers=1, lm_heads=2,
               lm_seq_len=64, lm_corpus_tokens=4096, batch_size=8, lr=0.01,
               momentum=0.9),
}
# the table of ISSUE 49: what both constructors must name
PHASES = {"backend_init", "data_build", "model_build", "state_init",
          "step_build", "ops_plane_build"}
SETUP_KEYS = {"process_age_s", "build_s", "phases", "step1", "compile"}
STEP1_KEYS = {"flops_trace_s", "jit_trace_s", "jit_lower_s",
              "backend_compile_s", "cache_load_s", "cache_hits",
              "cache_misses"}
COMPILE_KEYS = {"programs", "seconds", "cache_hits", "cache_misses"}


def _cfg(tmp, kind, **kw):
    base = dict(_BASE[kind], max_steps=STEPS, eval_freq=0,
                train_dir=str(tmp / "ckpt"),
                metrics_file=str(tmp / "m.jsonl"), log_every=1, resume=True,
                seed=3)
    base.update(kw)
    return TrainConfig(**base)


def _build(cfg, kind):
    return (Trainer if kind == "cnn" else LMTrainer)(cfg)


def _halve_one_batch(trainer):
    """Call ``ODD`` of ``next_batch`` hands back half the rows: a new shape,
    so that step's dispatch compiles a second step program."""
    orig, calls = trainer.train_loader.next_batch, [0]

    def next_batch():
        calls[0] += 1
        batch = orig()
        if calls[0] != ODD:
            return batch
        half = lambda a: a[:len(a) // 2]                        # noqa: E731
        return tuple(half(a) for a in batch) \
            if isinstance(batch, tuple) else half(batch)
    trainer.train_loader.next_batch = next_batch


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One run of each trainer: -> {kind: (trainer, spans, records, stdout)}."""
    out = {}
    for kind in KINDS:
        cfg = _cfg(tmp_path_factory.mktemp(kind), kind)
        said = io.StringIO()
        with contextlib.redirect_stdout(said):
            t = _build(cfg, kind)
            _halve_one_batch(t)
            t.train()
        with open(cfg.metrics_file) as f:
            records = [json.loads(line) for line in f]
        out[kind] = (t, t.tracer.spans(), records, said.getvalue())
    return out


def _setup_of(spans):
    tops = [s for s in spans if s["name"] == "setup"]
    assert len(tops) == 1
    return tops[0]


@pytest.mark.parametrize("kind", KINDS)
def test_setup_is_one_top_level_span_and_not_a_root(runs, kind):
    setup = _setup_of(runs[kind][1])
    assert setup["parent"] is None and "root" not in setup
    assert "step" not in setup
    assert setup["args"]["process_age_s"] > 0


@pytest.mark.parametrize("kind", KINDS)
def test_the_builds_phases_are_setups_children(runs, kind):
    spans = runs[kind][1]
    setup = _setup_of(spans)
    children = [s for s in spans if s["parent"] == setup["id"]]
    names = {s["name"] for s in children}
    # the same names in both trainers where the work is the same; Trainer
    # alone has a control plane and restores inside its constructor
    extra = {"control_plane_build", "resume"} if kind == "cnn" else set()
    assert names == PHASES | extra
    for s in children:
        assert "root" not in s and "step" not in s
    resume = [s for s in spans if s["name"] == "resume"]
    assert len(resume) == 1
    assert resume[0]["parent"] == (setup["id"] if kind == "cnn" else None)


@pytest.mark.parametrize("kind", KINDS)
def test_what_the_phases_say_of_themselves(runs, kind):
    t, spans = runs[kind][:2]
    by_name = {s["name"]: s for s in spans if s["name"] in PHASES}
    assert by_name["backend_init"]["args"]["devices"] == len(jax.devices())
    assert by_name["data_build"]["args"]["bytes"] > 0
    made = by_name["state_init"]["args"]
    assert made["params"] == sum(a.size
                                 for a in jax.tree.leaves(t.state.params))
    assert made["bytes"] >= 4 * made["params"]
    # the initialiser is a program of its own, compiled under its span
    assert made["programs"] >= 1 and made["backend_compile_s"] > 0
    assert t.tracer.pid == jax.process_index()
    assert t.tracer.process_name == f"host{jax.process_index()}"


@pytest.mark.parametrize("kind", KINDS)
def test_self_times_of_the_setup_tree_add_up_to_setup(runs, kind):
    spans = runs[kind][1]
    setup = _setup_of(spans)
    selfs = self_times(spans)
    by_id = {s["id"]: s for s in spans}

    def under_setup(s):
        while s["parent"] is not None:
            s = by_id[s["parent"]]
        return s is setup
    tree = [s for s in spans if under_setup(s)]
    assert len(tree) > len(PHASES)
    assert sum(selfs[s["id"]] for s in tree) == pytest.approx(setup["dur"],
                                                              abs=1e-6)


@pytest.mark.parametrize("kind", KINDS)
def test_the_benchmarks_reader_sees_the_iterations_it_saw_before(runs, kind):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "readers",
        "program_spans.py")
    spec = importlib.util.spec_from_file_location("program_spans", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    spans = runs[kind][1]
    its = reader.iterations(spans, self_times(spans))
    assert [it["step"] for it in its] == list(range(1, STEPS + 1))
    assert sum(1 for s in spans if s.get("root")) == STEPS
    for it in its:
        assert it["root"]["name"] == "train_step"
        assert {"data_wait", "host_dispatch"} <= set(it["by_name"])
        assert not set(it["by_name"]) & (
            PHASES | {"control_plane_build", "resume", "setup"})


@pytest.mark.parametrize("kind", KINDS)
def test_the_first_record_holds_setup_and_no_later_one_does(runs, kind):
    records = runs[kind][2]
    assert [r["step"] for r in records] == list(range(1, STEPS + 1))
    setup = records[0]["setup"]
    assert set(setup) == SETUP_KEYS
    assert set(setup["step1"]) == STEP1_KEYS
    assert set(setup["compile"]) == COMPILE_KEYS
    assert PHASES | {"setup", "resume"} <= set(setup["phases"])
    assert setup["build_s"] > 0 and setup["process_age_s"] > 0
    # no compile cache in the tests: step 1 compiled, and so did set-up
    assert setup["step1"]["cache_misses"] >= 1
    assert setup["step1"]["cache_hits"] == 0
    assert setup["compile"]["programs"] >= 2
    for r in records[1:]:
        assert "setup" not in r
    json.dumps(setup)       # plain numbers all the way down


@pytest.mark.parametrize("kind", KINDS)
def test_step_1_says_what_its_dispatch_was_made_of(runs, kind):
    spans = runs[kind][1]
    first = {s["name"]: s for s in spans if s.get("step") == 1}
    dispatch = first["host_dispatch"]["args"]
    assert dispatch["programs"] == 1 and dispatch["cache_misses"] == 1
    assert dispatch["backend_compile_s"] > 0 and dispatch["jit_lower_s"] > 0
    # net of what ran inside: the parts are no more than the span
    parts = sum(dispatch[k] for k in ("jit_trace_s", "jit_lower_s",
                                      "backend_compile_s"))
    assert parts <= first["host_dispatch"]["dur"]
    # the FLOPs trace is the step's Python trace (no program: nothing runs)
    flops = first["flops_trace"]["args"]
    assert flops["jit_trace_s"] > 0 and "programs" not in flops


@pytest.mark.parametrize("kind", KINDS)
def test_a_step_fed_a_new_shape_shows_on_its_record_alone(runs, kind):
    t, spans, records = runs[kind][:3]
    assert "compiles" in records[0]
    by_step = {r["step"]: r["compiles"] for r in records}
    assert by_step[ODD] >= 1
    assert by_step[ODD - 1] == 0 and by_step[ODD + 1] == 0
    assert all(n == 0 for s, n in by_step.items() if s not in (1, ODD))
    odd = [s for s in spans if s.get("step") == ODD
           and (s.get("args") or {}).get("programs")]
    assert {s["name"] for s in odd} == {"host_dispatch"}
    # ... and on the counter an operator alerts on
    assert t.registry.get("jax_programs_compiled_total") == \
        t.tracer.totals["programs"]
    assert t.registry.get("jax_compile_cache_misses_total") == \
        t.tracer.totals["cache_misses"] >= by_step[1] + by_step[ODD]
    assert t.registry.get("jax_compile_seconds_total") == pytest.approx(
        t.tracer.totals["backend_compile_s"])


@pytest.mark.parametrize("kind", KINDS)
def test_the_startup_line_is_printed_once_before_the_first_step_line(runs, kind):
    lines = runs[kind][3].splitlines()
    startup = [i for i, ln in enumerate(lines) if ln.startswith("STARTUP ")]
    assert len(startup) == 1
    first_step = next(i for i, ln in enumerate(lines) if ln.startswith("STEP "))
    assert startup[0] < first_step
    line = lines[startup[0]]
    assert " step1=compile[" in line and " compile[programs=" in line
    for name in PHASES:
        assert f"{name}=" in line
    assert runs[kind][0].tracer.startup == runs[kind][2][0]["setup"]


@pytest.mark.parametrize("kind", KINDS)
def test_a_constructor_that_raises_leaves_no_tracer_behind(tmp_path, kind):
    before = get_default_tracer()
    bad = dict(network="NoSuchNet") if kind == "cnn" else dict(lm_seq_len=63)
    with pytest.raises(ValueError):
        _build(_cfg(tmp_path, kind, **bad), kind)
    assert get_default_tracer() is before
    # the spans of what ran are there all the same
    setup = _setup_of(latest_tracer().spans())
    assert setup["parent"] is None


def test_building_two_trainers_registers_the_listeners_once(tmp_path):
    from jax._src import monitoring
    for i in range(2):
        t = _build(_cfg(tmp_path, "lm", resume=False,
                        metrics_file=str(tmp_path / f"m{i}.jsonl")), "lm")
        t.metrics.close()
        set_default_tracer(t._prev_tracer)
    compile_cache.count_compiles()
    assert monitoring.get_event_duration_listeners().count(
        compile_cache._on_duration) == 1
    assert monitoring.get_event_listeners().count(
        compile_cache._on_event) == 1


# ---- Tracer.add and the listeners, without a trainer ----

@pytest.fixture()
def tracer():
    compile_cache.count_compiles()
    tr = Tracer(registry=declare_training_metrics(Registry()),
                counters=compile_cache.COMPILE_COUNTERS)
    prev = set_default_tracer(tr)
    yield tr
    set_default_tracer(prev)


def test_a_fresh_jit_counts_once_in_the_span_that_called_it(tracer):
    fresh = jax.jit(lambda x: jnp.tanh(x) * 3.25 + 1)
    x = np.ones((7, 3), np.float32)     # jnp.ones would be a program too
    with tracer.span("x"):
        fresh(x).block_until_ready()
    with tracer.span("y"):
        fresh(x).block_until_ready()
    x_span, y_span = tracer.spans()
    assert x_span["args"]["programs"] == 1
    assert x_span["args"]["cache_misses"] == 1
    assert x_span["args"]["backend_compile_s"] > 0
    assert x_span["args"]["jit_trace_s"] > 0
    assert "args" not in y_span
    assert tracer.tally == {}
    assert tracer.registry.get("jax_programs_compiled_total") == 1


def test_with_no_span_open_the_count_goes_to_the_tally(tracer):
    jax.jit(lambda x: x * 1.75 - 2)(np.ones(5, np.float32)).block_until_ready()
    assert tracer.tally["programs"] == 1
    assert tracer.tally["backend_compile_s"] > 0
    assert tracer.totals == tracer.tally
    assert tracer.spans() == []


def test_another_threads_compile_never_lands_on_this_threads_span(tracer):
    done = []

    def work(name):
        f = jax.jit(lambda x: jnp.sin(x) * 0.125 + len(name))
        x = np.ones((3, 2), np.float32)
        if name:
            with tracer.span(name):
                f(x).block_until_ready()
        else:
            f(x).block_until_ready()
        done.append(name)

    with tracer.span("main"):
        for name in ("theirs", ""):
            th = threading.Thread(target=work, args=(name,))
            th.start()
            th.join(timeout=120)
            assert not th.is_alive()
    assert done == ["theirs", ""]
    by_name = {s["name"]: s for s in tracer.spans()}
    assert "args" not in by_name["main"]
    assert by_name["theirs"]["args"]["programs"] == 1
    assert by_name["theirs"]["parent"] is None
    assert tracer.tally["programs"] == 1
    assert tracer.totals["programs"] == 2


def test_add_reaches_the_innermost_span_and_a_root(tracer):
    tracer.begin_step(5)
    tracer.add("programs", 1)
    with tracer.span("outer"):
        with tracer.span("inner"):
            tracer.add("programs", 2)
            tracer.add("jit_trace_s", 0.5)
        tracer.add("cache_hits", 1)
    tracer.end_step()
    by_name = {s["name"]: s for s in tracer.spans()}
    assert by_name["inner"]["args"] == {"programs": 2, "jit_trace_s": 0.5}
    assert by_name["outer"]["args"] == {"cache_hits": 1}
    assert by_name["train_step"]["args"] == {"programs": 1}
    assert tracer.totals == {"programs": 3, "jit_trace_s": 0.5,
                             "cache_hits": 1}
    # a record takes what its iterations counted once, key by key
    assert tracer.counted_through(4, "programs") == 0
    assert tracer.counted_through(5, "programs") == 3
    assert tracer.counted_through(5, "programs") == 0
    assert tracer.counted_through(5, "cache_hits") == 1
    # a key that feeds no counter of the registry's feeds none
    assert tracer.registry.get("jax_programs_compiled_total") == 3
    assert tracer.registry.get("jax_compile_seconds_total") == 0


_STAGE_EVENTS = {"trace": "/jax/core/compile/jaxpr_trace_duration",
                 "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "compile": "/jax/core/compile/backend_compile_duration"}


@pytest.fixture()
def fire(monkeypatch):
    """fire(stage, secs, at, inside=False): JAX's duration event of a stage
    that took ``secs`` and ended at ``at`` on a clock of the test's,
    ``inside`` a trace that is still open. On a thread state of its own."""
    monkeypatch.setattr(compile_cache, "_thread", threading.local())
    now, traced = [0.0], [False]
    monkeypatch.setattr(compile_cache.time, "monotonic", lambda: now[0])
    monkeypatch.setattr(compile_cache, "_no_trace_open",
                        lambda: not traced[0])

    def fire(stage, secs, at, inside=False):
        now[0], traced[0] = at, inside
        compile_cache._on_duration(_STAGE_EVENTS[stage], secs)
    return fire


@pytest.mark.parametrize("events,wall,want", [
    # (stage, seconds, how long before the last event it ended, inside a
    # trace still open), as they fire
    ([("trace", 1.0, 0.0, False)], 1.0, {"jit_trace_s": 1.0}),
    # a jitted function traced inside the step's trace
    ([("trace", 0.25, 0.5, True), ("trace", 1.0, 0.0, False)], 1.0,
     {"jit_trace_s": 1.0}),
    # a constant's small program run whole inside the trace, then the
    # step's own lowering and compile after it
    ([("trace", 0.125, 1.5, True), ("lower", 0.125, 1.25, True),
      ("compile", 0.25, 1.0, True), ("trace", 2.0, 0.5, False),
      ("lower", 0.25, 0.25, False), ("compile", 0.25, 0.0, False)],
     2.5, {"jit_trace_s": 1.625, "jit_lower_s": 0.375,
           "backend_compile_s": 0.5, "programs": 2, "cache_misses": 2}),
    # what a lowering traced, with no trace open round it
    ([("trace", 0.25, 0.25, False), ("lower", 1.0, 0.0, False)], 1.0,
     {"jit_trace_s": 0.25, "jit_lower_s": 0.75}),
])
def test_nested_stages_are_counted_net_of_what_ran_inside(
        tracer, fire, events, wall, want):
    with tracer.span("x"):
        for stage, secs, ago, inside in events:
            fire(stage, secs, 1000.0 - ago, inside)
    got = tracer.spans()[0]["args"]
    assert got == pytest.approx(want)
    # what a span's keys add up to is no more than the span
    assert sum(v for k, v in got.items() if k.endswith("_s")) == \
        pytest.approx(wall)


@pytest.mark.parametrize("siblings", [5, 4097, 20_000])
def test_a_trace_is_net_of_every_call_it_traced_however_many(
        tracer, fire, siblings):
    """An initialiser's trace that traces ``siblings`` jitted functions, 2 ms
    each with 1 ms between them, and closes round them all; a program that
    finished before it stays outside."""
    with tracer.span("before"):
        fire("trace", 0.25, 0.5)
        fire("compile", 0.25, 0.875)
    with tracer.span("state_init"):
        for i in range(siblings):
            fire("trace", 0.002, 1.0 + 0.003 * (i + 1), inside=True)
        assert len(compile_cache._thread.seen) == siblings + 1
        end = 1.0 + 0.003 * siblings + 0.001
        fire("trace", end - 1.0, end)
    # with no trace open, the thread keeps the last stage alone
    assert len(compile_cache._thread.seen) == 1
    before, init = tracer.spans()
    assert before["args"]["jit_trace_s"] == 0.25
    assert init["args"] == {"jit_trace_s": pytest.approx(end - 1.0)}


def test_a_thread_that_traces_nothing_keeps_nothing_of_what_finished(tracer):
    """With JAX itself: a jitted function that calls a dozen jitted functions,
    called at top level."""
    inner = [jax.jit(lambda x, k=k: x * (1.5 + k)) for k in range(12)]

    @jax.jit
    def outer(x):
        for f in inner:
            x = f(x)
        return x
    with tracer.span("x") as counted:
        outer(np.ones(3, np.float32)).block_until_ready()
    assert len(compile_cache._thread.seen) == 1
    assert counted["programs"] == 1
    parts = sum(v for k, v in counted.items() if k.endswith("_s"))
    assert 0 < parts <= tracer.spans()[0]["dur"]


def test_a_load_is_a_hit_and_no_miss(tracer, monkeypatch):
    monkeypatch.setattr(compile_cache, "_thread", threading.local())
    with tracer.span("x"):
        compile_cache._on_event("/jax/compilation_cache/cache_hits")
        compile_cache._on_duration(     # no reader: not listened to
            "/jax/compilation_cache/compile_time_saved_sec", 40.0)
        compile_cache._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 2.0)
        compile_cache._on_duration(
            "/jax/core/compile/backend_compile_duration", 2.5)
        compile_cache._on_duration(
            "/jax/core/compile/backend_compile_duration", 0.25)
        compile_cache._on_event("/jax/some/other/event")
        compile_cache._on_duration("/jax/some/other/duration", 9.0)
    assert tracer.spans()[0]["args"] == {
        "cache_hits": 1, "cache_load_s": 2.0,
        "backend_compile_s": 2.75, "programs": 2, "cache_misses": 1}


def test_the_listeners_do_nothing_without_a_default_tracer():
    prev = set_default_tracer(None)
    try:
        compile_cache._on_event("/jax/compilation_cache/cache_hits")
        compile_cache._on_duration(
            "/jax/core/compile/backend_compile_duration", 1.0)
    finally:
        set_default_tracer(prev)


@pytest.mark.parametrize("load_s,backend_s,word", [
    (0.0, 12.0, "compile"), (6.5, 7.0, "load"), (0.0, 0.0, "compile"),
    (0.2, 3.0, "compile")])
def test_the_startup_line_says_load_or_compile(load_s, backend_s, word):
    summary = {"process_age_s": 9.5, "build_s": 4.25,
               "phases": {"setup": 0.01, "state_init": 3.0},
               "step1": {"flops_trace_s": 1.0, "jit_trace_s": 0.0,
                         "jit_lower_s": 0.5, "backend_compile_s": backend_s,
                         "cache_load_s": load_s, "cache_hits": int(load_s > 0),
                         "cache_misses": int(load_s == 0)},
               "compile": {"programs": 9, "seconds": 14.0, "cache_hits": 1,
                           "cache_misses": 8}}
    line = startup_line(summary)
    assert line.startswith("STARTUP process_age_s=9.5 build_s=4.25 "
                           "phases[setup=0.01 state_init=3.0] ")
    assert f" step1={word}[flops_trace_s=1.0 " in line
    assert line.endswith("compile[programs=9 seconds=14.0 cache_hits=1 "
                         "cache_misses=8]")
    assert "\n" not in line


def test_process_age_is_the_os_clock_since_the_process_started():
    a = trace.process_age_s()
    b = trace.process_age_s()
    assert 0 < a <= b < 24 * 3600


def test_a_tracer_with_no_setup_span_folds_to_nones():
    tr = Tracer()
    tr.begin_step(1)
    with tr.span("host_dispatch"):
        tr.add("backend_compile_s", 0.5)
    tr.end_step()
    got = tr.startup
    assert got["process_age_s"] is None and got["build_s"] is None
    assert got["phases"] == {}
    assert got["step1"]["backend_compile_s"] == 0.5
    assert got["step1"]["flops_trace_s"] is None
    assert got["compile"]["seconds"] == 0.5
    assert tr.startup_summary(7) is got     # folded once
