"""Reader over the program's set-up spans (``ps_pytorch_tpu/telemetry/trace.py``).

Since PR 49 both trainers create their tracer before anything is built. The
constructor is one top-level span ``setup`` (not a root: a root is an
iteration) whose children name the phases of the build (``backend_init``,
``data_build``, ``model_build``, ``state_init``, ``step_build``,
``control_plane_build``, ``ops_plane_build``, ``resume``), and JAX's compile
events are counted into the args of the span that was open on the thread that
caused them (``jit_trace_s``, ``jit_lower_s``, ``backend_compile_s``: each net
of what ran inside it; ``cache_load_s``, ``programs``, ``cache_hits``,
``cache_misses``), or into the tracer's ``tally`` where no span was open: the
harness's reference check and activation probe, which run between the build
and ``train()``. The spans are read from ``latest_tracer()`` as
``program_spans.py`` reads them. These are host-clock spans: the benchmark's
profile starts after warm-up, never covers set-up, and nothing here says
anything about the device.

``stat``:

- ``before_build_s`` / ``build_s``: ``setup``'s ``process_age_s`` (the
  seconds from the process's start as the OS has it to the constructor's
  first line) / its duration;
- ``first_step_trace_lower_s`` / ``first_step_backend_compile_s``:
  ``jit_trace_s + jit_lower_s`` / ``backend_compile_s`` (a compile or a load)
  counted under the first iteration's ``host_dispatch``;
- ``compile_s`` / ``cache_misses``: the backend's seconds / the programs
  compiled anew, the whole process up to the close of the first iteration.

Those six are not derived here: they are keys of the program's own fold of
its set-up (``tracer.startup``, ``telemetry/trace.py:setup_summary``: what the
``STARTUP`` line and the first JSONL record show), taken when iteration 1
closes, so a compile after it is no set-up. Read from the spans:

- ``child_s``: what ``setup``'s children of ``names`` took;
- ``unspanned_s``: ``setup``'s self time plus the first iteration's root's;
- ``recompiles_after_step1``: ``programs`` counted under the iterations after
  the first, up to the window's last step, plus what the tally (compiles under
  no span: another thread's, the caller's) grew by since the fold; one
  ``RECOMPILE`` line each through ``run.say``.

None where the program folds no set-up (a commit before PR 49).
"""

import os

import harness


def _program_spans():
    """The sibling reader's module (``harness.load_module`` caches it)."""
    return harness.load_module(
        os.path.join(os.path.dirname(__file__), "program_spans.py"))


def _counted(span, key):
    return (span.get("args") or {}).get(key, 0)


# stat -> the keys of ``tracer.startup`` it adds up
_FOLDED = {
    "before_build_s": (("process_age_s",),),
    "build_s": (("build_s",),),
    "first_step_trace_lower_s": (("step1", "jit_trace_s"),
                                 ("step1", "jit_lower_s")),
    "first_step_backend_compile_s": (("step1", "backend_compile_s"),),
    "compile_s": (("compile", "seconds"),),
    "cache_misses": (("compile", "cache_misses"),),
}


def read(run, stat, names=()):
    sibling = _program_spans()
    got = sibling.tracer_spans()
    if got is None:
        return None
    from ps_pytorch_tpu.telemetry import trace
    tracer = trace.latest_tracer()
    startup = getattr(tracer, "startup", None)
    if startup is None or startup["build_s"] is None:
        return None

    if stat in _FOLDED:
        total = 0
        for path in _FOLDED[stat]:
            value = startup
            for key in path:
                value = value[key]
            if value is None:
                return None
            total += value
        return total

    spans, self_times = got
    setup = next(s for s in spans if s["name"] == "setup"
                 and s.get("parent") is None)
    first = sibling.iterations(spans, self_times)[0]
    if stat == "child_s":
        mine = [s["dur"] for s in spans
                if s.get("parent") == setup["id"] and s["name"] in names]
        return sum(mine) if mine else None
    if stat == "unspanned_s":
        return self_times[setup["id"]] + first["self"]
    if stat == "recompiles_after_step1":
        steps = [r["step"] for r in run.window_records]
        if not steps:
            return None
        total = 0
        for s in spans:
            n = _counted(s, "programs")
            if n and first["step"] < s.get("step", first["step"]) <= max(steps):
                total += n
                run.say(f"RECOMPILE step={s['step']} span={s['name']} "
                        f"programs={n} s={_counted(s, 'backend_compile_s'):.6f}")
        since = {k: tracer.tally.get(k, 0) - tracer.startup_tally.get(k, 0)
                 for k in ("programs", "backend_compile_s")}
        if since["programs"]:
            total += since["programs"]
            run.say(f"RECOMPILE under no span since step {first['step']} "
                    f"closed: programs={since['programs']} "
                    f"s={since['backend_compile_s']:.6f}")
        return total
    raise ValueError(f"unknown stat {stat!r}")
