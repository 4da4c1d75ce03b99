"""Reader over the program's own spans (``ps_pytorch_tpu/telemetry/trace.py``).

Both trainers put every iteration under one root span, ``train_step``, whose
children are the iteration's phases (``coordinator``, ``data_wait``,
``rng_key``, ``batch_put``, ``flops_trace``, ``host_dispatch``,
``device_sync``, ``ops_step``, ``telemetry_publish``, ``metrics_sync``,
``log_write``, ``checkpoint``); the root's self time is what no span explains.
The spans are read from the process's tracer (``latest_tracer()``: the
harness's ``Run`` carries no trainer) and cut to the steps of the window
(``run.window_records``) but its first: the harness opens its window from
inside that step's ``next_batch``, under the program's ``data_wait`` span, and
what it does there (a drain; after a traced run, stopping the profiler: 0.6
and 1.9 s, chip runs, PR 23) is not the program's.

``stat``, over ``names`` (a step's value is the sum of its spans of those
names; a name the window never saw counts for nothing):

- ``median_ms``: the sum over the names of each one's median over the steps;
- ``mean_ms``: everything the names took in the window over its steps (a
  median hides what only every n-th step pays);
- ``self_median_ms``: the median of the root's self time (``names`` unused);
- ``first_step_s``: what the names took in the run's first iteration, seconds.

None where the program records no root spans (a commit before PR 23) or the
names never occur.
"""

import statistics

ROOT = "train_step"


def tracer_spans():
    """The recorded spans of the run's tracer and their self times, or None
    where the program has no such tracer or it holds no root span."""
    try:
        from ps_pytorch_tpu.telemetry import trace
    except ImportError:
        return None
    tracer = getattr(trace, "latest_tracer", lambda: None)()
    if tracer is None:
        return None
    spans = tracer.spans()
    if not any(s.get("root") for s in spans):
        return None
    return spans, trace.self_times(spans)


def iterations(spans, self_times):
    """[{"step", "root": the root span, "self": its self seconds, "children":
    its direct children, "by_name": {child name: seconds}}], by step."""
    its = {s["id"]: {"step": s["step"], "root": s, "self": self_times[s["id"]],
                     "children": [], "by_name": {}}
           for s in spans if s.get("root")}
    for s in spans:
        it = its.get(s.get("parent"))
        if it is not None:
            it["children"].append(s)
            it["by_name"][s["name"]] = it["by_name"].get(s["name"], 0.0) + s["dur"]
    return sorted(its.values(), key=lambda it: it["step"])


def read(run, stat, names=()):
    got = tracer_spans()
    if got is None:
        return None
    its = iterations(*got)
    if stat == "first_step_s":
        first = its[0]["by_name"]
        return sum(first[n] for n in names if n in first) \
            if any(n in first for n in names) else None
    steps = [r["step"] for r in run.window_records]
    if not steps:
        return None
    window = [it for it in its if min(steps) < it["step"] <= max(steps)]
    if not window:
        return None
    if stat == "self_median_ms":
        return 1e3 * statistics.median(it["self"] for it in window)
    seen = [n for n in names if any(n in it["by_name"] for it in window)]
    if not seen:
        return None
    per_name = {n: [it["by_name"].get(n, 0.0) for it in window] for n in seen}
    if stat == "median_ms":
        return 1e3 * sum(statistics.median(v) for v in per_name.values())
    if stat == "mean_ms":
        return 1e3 * sum(sum(v) for v in per_name.values()) / len(window)
    raise ValueError(f"unknown stat {stat!r}")
