"""The benchmark harness: one cell, one run, through the trainer's own loop.

A cell is a configuration (``configs/<config>.json``, with its plain reference
``reference/<config>.py``) under a traffic mix (``traffic/<traffic>.json``).
The configuration names the driver (``drivers/<driver>.py``), which knows how
one entry point of the program is built from an argv and how its model is
called; nothing else here knows a model, a trainer or a metric by name. A
per-layer metric is ``layer_metrics/<name>.json``: a reader
(``readers/<reader>.py``) and its parameters. A later PR adds a
configuration, a mix, a metric, a reader, a driver or a kernel's cost
function as new files and a ``BENCHMARK.json`` entry.

How a run measures. The driver builds the trainer as ``train.py`` /
``train_lm.py`` do, from ``config.program_args + traffic.args`` and the
driver's fixed args, with ``--max-steps`` out of reach. ``StepShim`` replaces
``trainer.train_loader.next_batch`` — the first call of every iteration of
both loops — and from there stamps step boundaries, ends warm-up, runs the
profiler over ``trace_steps`` steps of a traced run, and after ``--seconds``
of steady state drains the device and leaves ``trainer.train()`` by
``WindowDone``. The measured loop is therefore the one a user runs, host work
included, at the program's defaults. The rate is work over time across
whole periods of the loop; ``steady_step_s`` says how a step is timed.
"""

import functools
import importlib.util
import json
import math
import os
import shutil
import statistics
import time
from typing import Any, Dict, List, Optional

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
RUNS_DIR = os.path.join(CHECKOUT, ".bench_runs")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WARMUP_STEPS = 5           # steps before the window may open
MAX_EXTRA_WARMUP = 40      # steps past WARMUP_STEPS before warm-up ends anyway
STABLE = 0.2               # two consecutive steps within this of each other
MIN_REPEATS = 5            # periods a window must hold to be rated by phase
MIN_RECORDS = 5            # logged steps a window must hold to be judged


class WindowDone(Exception):
    """Leaves the trainer's loop when the measured window is over."""


# ------------------------------------------------------------- loading --

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Files:
    """Where a benchmark's data files live. The tests point ``root`` at their
    fixture directory for what they add; anything not found there is taken
    from the benchmark's own directory."""

    def __init__(self, root: str = HERE):
        self.roots = [root] if root == HERE else [root, HERE]

    def path(self, *parts: str) -> str:
        for root in self.roots:
            p = os.path.join(root, *parts)
            if os.path.exists(p):
                return p
        raise FileNotFoundError(os.path.join(self.roots[0], *parts))

    def json(self, *parts: str) -> dict:
        return load_json(self.path(*parts))

    def module(self, *parts: str):
        return load_module(self.path(*parts))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[c['name'] for c in bench['workloads']]}")


def metrics_for(bench: dict, group: str, cell_name: str) -> List[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def peak_for(files: Files, device_kind: str) -> dict:
    peaks = files.json("peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json "
                       f"({sorted(peaks)}); add it with its source")
    return peaks[device_kind]


# ---------------------------------------------------------------- shim --

class StepShim:
    """Stands in for ``train_loader.next_batch``. Call ``i`` (1-based) opens
    step ``i``: ``entry[i]`` is the host clock on entry (the end of step
    ``i-1``), ``start[i]`` the clock when the harness's own work in this
    call is done and the loader is asked. Step ``i`` lasted ``entry[i+1] -
    start[i]``."""

    def __init__(self, trainer, drain, *, seconds: float, period: int = 1,
                 trace_steps: int = 0, trace_dir: str = "",
                 host_tracer_level: int = 2, clock=time.monotonic):
        self.trainer, self.drain, self.clock = trainer, drain, clock
        self.seconds = float(seconds)
        self.period = max(int(period), 1)
        self.align = 1      # the window ends on a multiple of this many steps
        self.trace_steps = int(trace_steps) if trace_dir else 0
        self.trace_dir, self.host_tracer_level = trace_dir, host_tracer_level
        self.orig = trainer.train_loader.next_batch
        trainer.train_loader.next_batch = self
        self.entry: Dict[int, float] = {}
        self.start: Dict[int, float] = {}
        self.calls = 0
        self.state = "warmup"
        self.trace_first = self.trace_last = 0
        self.window_first = self.window_last = 0
        self.t_window0 = self.t_window1 = 0.0
        self.compile_times: List[float] = []

    def duration(self, i: int) -> float:
        return self.entry[i + 1] - self.start[i]

    def _warm(self, i: int) -> bool:
        done = i - 1
        if done < WARMUP_STEPS:
            return False
        if done >= WARMUP_STEPS + MAX_EXTRA_WARMUP:
            return True
        a, b = self.duration(i - 2), self.duration(i - 1)
        return abs(a - b) <= STABLE * max(a, b)

    def _open_window(self, i: int) -> None:
        self.state = "window"
        self.window_first = i
        # Whole periods of the loop, where the window can hold enough of them
        # to be rated by phase (steady_step_s).
        if self.period * MIN_REPEATS * self.duration(i - 1) <= self.seconds:
            self.align = self.period
        self.t_window0 = self.clock()

    def __call__(self):
        self.calls += 1
        i = self.calls
        self.entry[i] = self.clock()
        if self.state == "warmup" and self._warm(i):
            self.drain(self.trainer)
            if self.trace_steps:
                import jax
                # The Python tracer is always off (it slows the host loop
                # and swells the trace). The host tracer is the mix's choice:
                # where a step puts a large batch on the device, each small
                # transpose of the transfer becomes a host event, and the
                # traced step runs five times slower (0.80 s against 0.14 s,
                # ResNet-18 b=4096, 2.4 million events in eight steps).
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = self.host_tracer_level
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=options)
                self.state, self.trace_first = "trace", i
            else:
                self._open_window(i)
        elif self.state == "trace" and i - self.trace_first >= self.trace_steps:
            import jax
            self.drain(self.trainer)
            jax.profiler.stop_trace()
            self.trace_last = i - 1
            self._open_window(i)
        elif self.state == "window" and \
                self.entry[i] - self.t_window0 >= self.seconds and \
                (i - self.window_first) % self.align == 0:
            self.drain(self.trainer)
            self.t_window1 = self.clock()
            self.window_last = i - 1
            self.state = "done"
            raise WindowDone
        self.start[i] = self.clock()
        return self.orig()

    def window_durations(self) -> List[float]:
        """Seconds of each step of the window; the last ends with the drain."""
        return [self.duration(i)
                for i in range(self.window_first, self.window_last)] + \
            [self.t_window1 - self.start[self.window_last]]

    def compiles_in_window(self) -> int:
        return sum(1 for t in self.compile_times
                   if self.t_window0 <= t <= self.t_window1)


# ----------------------------------------------------------------- run --

class Run:
    """What the readers may look at after a traced run."""

    def __init__(self, **kw: Any):
        self.__dict__.update(kw)

    def steady(self, index: int = 0):
        """(chip, (lo, hi), periods) of the steady window of chip ``index``'s
        trace (``trace_reduce.Chip.steady_window``), or None without one."""
        chip = self.trace.chip(index) if self.trace else None
        w = chip.steady_window() if chip else None
        return (chip, w[:2], w[2]) if w else None


def activation_dtypes(apply, *args) -> List[str]:
    """dtypes of a flax model's intermediate outputs, read from their avals
    under ``jax.eval_shape``; ``apply`` must pass ``capture_intermediates=True,
    mutable=["intermediates"]``. The last leaf is the float32 logits and is
    left out. The flag a run was given is not proof of the dtype it ran in."""
    import jax
    _, state = jax.eval_shape(apply, *args)
    leaves = jax.tree.leaves(state["intermediates"])
    return sorted({str(a.dtype) for a in leaves[:-1] or leaves})


def peak_bytes(stats: dict) -> int:
    """Peak device memory from one device's ``memory_stats()``. On the TPU
    runtime ``peak_bytes_in_use`` counts live buffers only (parameters,
    optimizer state, batches); the space a running program takes for its
    temporaries is reserved apart and shows as ``peak_bytes_reserved``
    (ResNet-18 b=4096: 0.16 GB in use, 7.15 GB reserved, 7.27 GB by
    ``memory_analysis()``). The peak a chip has to hold is their sum."""
    return int(stats.get("peak_bytes_in_use", 0)) + \
        int(stats.get("peak_bytes_reserved", 0))


def _device_info(devices, busy_window=None) -> dict:
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peak_bytes(d.memory_stats() or {})
                                     for d in devices)}
    if busy_window:
        info["busy_s"], info["window_s"] = busy_window
    return info


def _unsettle(variables, rng):
    """The model's variables with every vector leaf (biases, norm scales and
    offsets, running means and variances) moved off its initial value by
    seeded noise within 0.2. At 0 or 1 such a leaf is an identity (an
    inference-mode BatchNorm with mean 0 and variance 1 changes nothing), and
    a system that dropped it would still agree with the reference."""
    import jax
    import numpy as np

    def move(a):
        if a.ndim != 1:
            return a
        a = np.asarray(a)
        return a + rng.uniform(-0.2, 0.2, a.shape).astype(a.dtype)
    return jax.tree.map(move, variables)


def _reference_check(driver, reference, trainer, config, seed: int) -> dict:
    """System forward against the plain float32 reference, on a seeded
    sample; -> {"max_abs_err", "tolerance", "ok", "logit_scale"}."""
    import jax
    import numpy as np

    rng = np.random.default_rng(seed)
    variables = _unsettle(driver.variables(trainer), rng)
    x = driver.sample_input(trainer, config, rng)
    got = np.asarray(jax.jit(
        lambda v, x: driver.system_forward(trainer, v, x))(variables, x),
        np.float32)
    want = np.asarray(jax.jit(
        lambda v, x: reference.forward(v, x, config))(variables, x),
        np.float32)
    err = float(np.max(np.abs(got - want)))
    tol = float(config["reference_check"]["max_abs_logit_err"])
    ok = bool(np.isfinite(got).all() and got.shape == want.shape and err <= tol)
    return {"max_abs_err": err, "tolerance": tol, "ok": ok,
            "logit_scale": float(np.max(np.abs(want)))}


def _loss_checks(records: List[dict], first: int, last: int,
                 expect_participating: Optional[float]) -> dict:
    """Judges the steps the program logged in the window (how often it logs
    is its own default, and a better one is a gain), not one record a step."""
    window = [r for r in records if first <= r["step"] <= last]
    failed = 0
    for r in window:
        bad = not math.isfinite(r["loss"])
        if expect_participating is not None and \
                r["participating"] != expect_participating:
            bad = True
        failed += bad
    head = [r["loss"] for r in records[:10]]
    tail = [r["loss"] for r in window[-10:]]
    learned = bool(head and tail and
                   statistics.median(tail) < statistics.median(head))
    return {"window": window, "failed": failed, "learned": learned,
            "loss_first10": statistics.median(head) if head else None,
            "loss_last10": statistics.median(tail) if tail else None,
            "enough": len(window) >= MIN_RECORDS}


def steady_step_s(durations: List[float], period: int, window_s: float):
    """Seconds a step of the window takes, and how that was rated.

    The rate is work over time across the drained window. Taken plainly, as
    steps over seconds, it spread by 3.6% over twelve runs of the one-chip
    ResNet cell: that machine shares its host's cores, and single steps stall
    (one of 2.6 s in a 20 s window, three to ten of 50-100 ms in most). So
    the window is rated as so many typical periods. The loop's work repeats
    every ``period`` steps (the driver's: an epoch of the loader, a sync every
    ``log_every`` steps); the window holds a whole number of periods; each
    position in the period takes the median of the steps that stood there,
    and the sum is one period's seconds. What the loop pays once a period is
    in it in full: the epoch's turnover, and in a loop that syncs only every
    ``log_every`` steps the device time that step waits for. A stall that
    struck one step of one period is not. One that recurs out of step with
    the period goes unseen too: it shows in ``step_ms_p90`` and in the plain
    rate on the WINDOW line. A window with fewer than MIN_REPEATS whole
    periods is rated plainly."""
    n = len(durations)
    if n >= MIN_REPEATS * period and n % period == 0:
        return sum(statistics.median(durations[p::period])
                   for p in range(period)) / period, "typical period"
    return window_s / n, "steps over seconds"


def run_cell(bench: dict, cell_name: str, *, seed: int, seconds: float,
             trace: bool, files: Optional[Files] = None,
             t_origin: Optional[float] = None, say=print) -> dict:
    """Run one cell once; -> the result object of the contract's last line.
    ``t_origin`` is the ``time.monotonic()`` of process start (set-up counts
    from there)."""
    import jax
    import jax.monitoring

    t_origin = time.monotonic() if t_origin is None else t_origin
    files = files or Files()
    cell = find_cell(bench, cell_name)
    config = files.json("configs", cell["config"] + ".json")
    traffic = files.json("traffic", cell["traffic"] + ".json")
    driver = files.module("drivers", config["driver"] + ".py")
    reference = files.module("reference", cell["config"] + ".py")

    devices = jax.devices()
    if len(devices) != cell["chips"]:
        raise RuntimeError(f"cell {cell_name} asks for {cell['chips']} "
                           f"device(s), jax sees {len(devices)}")

    run_dir = os.path.join(RUNS_DIR, cell_name)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    metrics_file = os.path.join(run_dir, "metrics.jsonl")
    trace_dir = os.path.join(run_dir, "trace") if trace else ""
    argv = (list(config["program_args"]) + list(traffic["args"])
            + list(driver.FIXED_ARGS)
            + ["--seed", str(seed), "--max-steps", str(10 ** 9),
               "--train-dir", os.path.join(run_dir, "train_dir"),
               "--metrics-file", metrics_file])
    say(f"ARGV {' '.join(argv)}")

    t_build0 = time.monotonic()
    trainer = driver.build(argv)
    t_built = time.monotonic()
    act = driver.activation_dtype(trainer)
    say(f"ACTIVATIONS {act}")
    check = _reference_check(driver, reference, trainer, config, seed)
    t_checked = time.monotonic()
    say(f"REFERENCE max abs logit err {check['max_abs_err']:.6g} "
        f"(tolerance {check['tolerance']:g}, logits up to "
        f"{check['logit_scale']:.4g}) {'ok' if check['ok'] else 'FAILED'}")

    period = driver.period_steps(trainer)
    shim = StepShim(trainer, driver.drain, seconds=seconds, period=period,
                    trace_steps=traffic["trace_steps"], trace_dir=trace_dir,
                    host_tracer_level=traffic.get("host_tracer_level", 2))
    compile_times = shim.compile_times   # the listener outlives the run: it
    jax.monitoring.register_event_duration_secs_listener(   # holds the list only
        lambda name, _dur, **_kw: name == COMPILE_EVENT
        and compile_times.append(time.monotonic()))
    try:
        trainer.train()
        raise RuntimeError("the trainer's loop ended before the window did")
    except WindowDone:
        pass

    with open(metrics_file) as f:
        records = [json.loads(line) for line in f]
    losses = _loss_checks(records, shim.window_first, shim.window_last,
                          traffic.get("expect_participating"))
    durations = shim.window_durations()
    n_steps = len(durations)
    window_s = shim.t_window1 - shim.t_window0
    step_s, rated = steady_step_s(durations, period, window_s)
    per_step = driver.samples_per_step(trainer)
    rate = per_step / step_s
    shape = dict(driver.shape(trainer), activation_dtypes=act)
    flops = reference.train_flops_per_sample(config, **shape)
    peak = peak_for(files, devices[0].device_kind)
    mfu = 100.0 * flops * rate / (peak["bf16_flops_per_s"] * len(devices))
    setup_s = shim.t_window0 - t_origin
    say(f"SETUP {setup_s:.2f} s: imports and files {t_build0 - t_origin:.2f}, "
        f"trainer build {t_built - t_build0:.2f}, reference check "
        f"{t_checked - t_built:.2f}, step 1 {shim.entry[2] - t_checked:.2f}, "
        f"steps 2..{shim.window_first - 1} and traced steps "
        f"{shim.t_window0 - shim.entry[2]:.2f}")
    compiles = shim.compiles_in_window()
    correct = bool(check["ok"] and losses["failed"] == 0 and losses["learned"]
                   and losses["enough"] and compiles == 0)
    say(f"WINDOW steps {shim.window_first}..{shim.window_last} ({n_steps}) in "
        f"{window_s:.3f} s; step {step_s:.6f} s rated by {rated} (period "
        f"{period}), {window_s / n_steps:.6f} s by steps over seconds; "
        f"{len(losses['window'])} steps logged; warm-up took "
        f"{shim.window_first - 1} steps; loss {losses['loss_first10']} -> "
        f"{losses['loss_last10']}; compiles in window {compiles}")

    values = {driver.THROUGHPUT: rate, "mfu": mfu, "setup_s": setup_s}
    result = {"correct": correct, "attempted": n_steps,
              "failed": losses["failed"], "metrics": {}, "device": None}

    if not trace:
        for m in metrics_for(bench, "end_to_end", cell_name):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        result["device"] = _device_info(devices)
        return result

    xplane = trace_reduce.find_xplane(trace_dir)
    tr = trace_reduce.load(xplane) if xplane else None
    run = Run(durations=durations, step_s=step_s,
              first_step_s=shim.entry[2] - t_build0,
              window_records=losses["window"], trace=tr, config=config,
              traffic=traffic, shape=shape, peak=peak, files=files,
              memory_stats=[d.memory_stats() or {} for d in devices],
              compiles_in_window=compiles, say=say)
    reported = {m["name"] for m in metrics_for(bench, "end_to_end", cell_name)}
    for m in metrics_for(bench, "per_layer", cell_name):
        if m["moves"] not in reported:
            continue
        spec = files.json("layer_metrics", m["name"] + ".json")
        reader = files.module("readers", spec["reader"] + ".py")
        value = reader.read(run, **spec.get("params", {}))
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    traced = [shim.duration(i)
              for i in range(shim.trace_first + 1, shim.trace_last + 1)]
    phases: Dict[str, List[float]] = {}
    for r in losses["window"]:
        for k, v in (r.get("phases") or {}).items():
            phases.setdefault(k, []).append(v)
    med = statistics.median(durations)
    say(f"TRACED step wall median {statistics.median(traced):.6f} s traced, "
        f"{med:.6f} s untraced; step wall - sum of JSONL phases = "
        f"{med - sum(statistics.median(v) for v in phases.values()):.6f} s")

    busy_window = None
    steady = [s for s in (run.steady(c.index) for c in (tr.chips if tr else []))
              if s]
    if steady:
        busy_window = (sum(c.busy_s(w) for c, w, _ in steady) / len(steady),
                       sum(w[1] - w[0] for _, w, _ in steady) / len(steady))
        chip0, w, _ = steady[0]
        ops = sorted(chip0.op_seconds(w).items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in tr.attribute_gaps(chip0, w, 10)]}
    result["device"] = _device_info(devices, busy_window)
    return result
