"""Host-side span tracer — the one instrumentation idiom for the runtime.

The reference's observability was scattered wall-clock prints
(``distributed_worker.py:169-173``), and rounds 1-6 of this port generalized
that to ad-hoc ``time.monotonic()`` pairs in every trainer. This module
replaces all of them: a nestable context-manager span with monotonic
timestamps, recorded into a thread-safe ring buffer, exportable as Chrome
``trace_event`` JSON so the HOST timeline (data wait -> host dispatch ->
device sync -> coordinator round -> checkpoint) opens directly in Perfetto
next to the ``jax.profiler`` device trace.

Two ways in:

- explicit: ``tracer = Tracer()`` and ``with tracer.span("data_wait",
  step=7): ...`` — trainers own a tracer. A trainer creates it as the first
  thing its constructor does, so the constructor itself is one tree: a
  top-level span ``setup`` (NOT a root: a root is an iteration) whose
  children are the phases of the build (``backend_init``, ``data_build``,
  ``model_build``, ``state_init``, ``step_build``, ``ops_plane_build``, ...),
  and ``pid``, a plain attribute, is set once ``jax.process_index()`` is
  known.
- ambient: library layers that must not grow a tracer parameter
  (checkpoint.py, transport.py, coordinator.py) call the module-level
  ``span(...)``, which records into the current default tracer and is a
  no-op when none is installed — instrumentation without API churn.
  ``Tracer.add(key, value)`` is the same for a count or for seconds that
  something outside the program's control flow reports (JAX's compile
  events, ``utils/compile_cache.py``): it adds into the args of the innermost
  span open on the CALLING thread, so the span that caused a compile says so
  itself; with no span open there, into the tracer's ``tally``.

Spans tagged with ``step=`` additionally feed a per-step phase accumulator
(``step_summary``), which is what the MetricsLogger v2 record and the
cross-host aggregator publish (telemetry/aggregate.py).

What a span records (the tracing contract every reader relies on): ``id``,
the ``parent`` id from the opening thread's stack (None at top level),
``name``, ``t0`` / ``dur`` (``time.monotonic`` seconds), ``tid`` and the
``step`` the spans of one iteration share (inherited from the parent when
not given), and ``args``: what the opener gave, what the code inside learned
(``bytes=``, ``params=``) and what ``Tracer.add`` counted there
(``jit_trace_s``, ``programs``, ...). A trainer's iteration is one ROOT span (``begin_step`` /
``end_step``, named ``train_step``) whose children are the iteration's
phases, so the root's self time (``self_times``) is the part of the
iteration no span explains. Every span is also a ``jax.profiler``
``TraceAnnotation`` (the root a ``StepTraceAnnotation``), so a profile taken
with the host tracer on carries the program's spans on the device trace's
own clock. Where the host tracer is off, ``wall_ns`` does: every top-level
span (a root among them) reads ``time.time_ns()`` beside its monotonic
start, and each recorded span carries its own start on the Unix clock,
converted through that anchor. The xplane counts from its session's start,
which it records as Unix ns (plane ``Task Environment``, stat
``profile_start_time``).

The device's side of a step has spans of its own kind: ``device_scope``
(below), one of ``DEVICE_SCOPES``, opened where each layer of the jitted step
begins.
"""

import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterable, List, Optional

import jax
import jax.profiler
from jax.profiler import StepTraceAnnotation, TraceAnnotation

# Chrome trace_event "complete" events need ph/ts/dur/pid/tid/name; ts and
# dur are MICROseconds. https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU
_US = 1e6


ROOT_SPAN = "train_step"      # the root span of one trainer iteration
SETUP_SPAN = "setup"          # a trainer's constructor, first line to last

# The layers of a jitted train step, as the device trace is read back by
# them: the one list of these names. One level: a scope is not opened inside
# another (flax's module names stay round them and tell the layer:
# ``block_3/attn_core/...``), but for the held experts' overflow path, whose
# ``cond`` runs a whole part under ``moe_dispatch`` (``models/moe._when``); a
# reader takes the innermost.
DEVICE_SCOPES = (
    "embed",         # token and position rows, the embedding's multiplier
    "attn_proj",     # pre-norm, q / k / v / gate / o projections, post_attn_norm
    "attn_pos",      # q/k norm, RoPE, to_heads and its inverse, the gate's product; differential heads' lambda, difference and norm
    "attn_core",     # flash / full / ring / cached attention; an EVA layer's core (both kernels, the copies round them)
    "eva_pool",      # an EVA layer's chunk summaries: the pooling forward and backward, phi's and mu's sums
    "ffn",           # a dense feed-forward: norm, its matmuls, activation
    "ssm_proj",      # a Mamba (-1 or -2) layer's norm, in / x / dt / out projections, the residual sum
    "ssm_conv",      # ... its causal convolution, silu, softplus, the gate's product (Mamba-2: the gated group norm)
    "ssm_scan",      # ... Mamba-1's recurrence, forward and backward, with the copies into and out of its layout
    "ssd_core",      # ... Mamba-2's state-space-dual form: both kernels, the running sum of dt A, the copies into and out of the kernels' layout
    "gmu",           # a gated memory unit whole: norm, both projections, the product with the handed-on scan output
    "gdn_proj",      # a Gated DeltaNet layer's norm, qkvz / ba / out projections, the residual sum
    "gdn_mix",       # ... its causal convolution and silu, beta, the gate, the l2 norms and q's scale, the gated output norm
    "gdn_core",      # ... the delta rule, forward and backward, with the copies into and out of its layout
    "moe_route",     # the expert layer's norm, router, scores, top-k, gates, counts, sort, statistics
    "moe_dispatch",  # gather of the sorted rows, gate multiply, scatter-add (capacity path: one-hot dispatch and combine); the layer's output norm
    "moe_experts",   # the (grouped) expert matmuls and the activation between them
    "moe_shared",    # the shared expert
    "head",          # ln_f and lm_head; a CNN's pool and classifier
    "loss",          # the loss call, its weights, the auxiliary terms
    "grad_reduce",   # every psum / pmean of gradients, counts and metrics
    "optimizer",     # tx.update + apply_updates, the step counter
    "router_bias",   # the selection bias's move and its two counters
    "conv", "batchnorm", "shortcut",    # the CIFAR ResNets' blocks and stem
)


def device_scope(name: str):
    """A span on the DEVICE's side of a step: ``jax.named_scope(name)`` for a
    name of ``DEVICE_SCOPES`` (any other raises).

    Beside a host span it records nothing and times nothing. It exists only
    while the step is traced: every op traced under it carries the name in
    its ``op_name`` (``jit(step)/jvp(block_0)/attn_core/...``, the backward
    pass's as ``transpose(jvp(...))``, a rematerialised forward's under
    ``rematted_computation``), the compiled program is the same program
    without it, and a run that takes no profile pays nothing. A profile's
    ``XLA Ops`` events carry that name as the stat ``tf_op``, and
    ``benchmark/readers/device_scopes.py`` adds the device's time up by it
    (the ``DEVICE_BY_SCOPE`` table, the ``dev_*`` metrics). A fused op counts
    to the scope of the one op whose name the fusion keeps: the matmul or
    convolution of an output fusion (the SGD update XLA fuses behind a weight
    gradient reads under the layer, not under ``optimizer``), else its root."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"{name!r} is not a device scope; "
                         f"telemetry.trace.DEVICE_SCOPES has {DEVICE_SCOPES}")
    return jax.named_scope(name)


class _Frame:
    """One open span on its thread's stack."""
    __slots__ = ("id", "parent", "top", "name", "step", "args", "root",
                 "t0", "wall_ns", "children_s", "annotation")


class Tracer:
    """Thread-safe ring buffer of completed spans.

    ``capacity`` bounds memory (oldest spans drop; ``dropped`` counts them).
    ``step_window`` bounds the per-step phase accumulator — summaries older
    than the window are discarded, so a million-step run stays O(window).
    """

    def __init__(self, pid: int = 0, process_name: str = "",
                 capacity: int = 65536, step_window: int = 256,
                 registry=None, counters: Optional[Dict[str, str]] = None):
        self.pid = int(pid)
        self._process_name = process_name
        # {a key of ``add``: the counter of ``registry`` it also feeds}
        self.registry = registry
        self._counters = dict(counters or {}) if registry is not None else {}
        # What ``add`` was given: in all, with no span open on the caller's
        # thread, and under each step until a record takes it.
        self.totals: Dict[str, float] = {}
        self.tally: Dict[str, float] = {}
        self._step_counts: Dict[int, Dict[str, float]] = {}
        # ``startup_summary``'s fold, and the tally as it stood then
        self.startup: Optional[dict] = None
        self.startup_tally: Dict[str, float] = {}
        self.capacity = max(int(capacity), 1)
        self.dropped = 0
        self._buf: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._ids = itertools.count(1)
        self._step_window = max(int(step_window), 1)
        self._step_totals: Dict[int, Dict[str, float]] = {}

    @property
    def process_name(self) -> str:
        return self._process_name or f"host{self.pid}"

    # ---- recording ----
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _open(self, name: str, step: Optional[int], args: dict,
              root: bool = False) -> _Frame:
        stack = self._stack()
        parent = stack[-1] if stack else None
        f = _Frame()
        f.id = next(self._ids)
        f.parent = parent.id if parent else None
        f.top = parent.top if parent else f
        f.name, f.args, f.root = name, args, root
        # The spans of one iteration share its step.
        f.step = step if step is not None or parent is None else parent.step
        f.children_s = 0.0
        if root:
            f.annotation = StepTraceAnnotation(name, step_num=f.step)
        elif f.step is None:
            f.annotation = TraceAnnotation(name)
        else:
            f.annotation = TraceAnnotation(name, step=f.step)
        f.annotation.__enter__()
        stack.append(f)
        # A top-level span anchors itself on the Unix clock; what it holds
        # converts through it (one clock pair a tree, read back to back).
        f.wall_ns = time.time_ns() if parent is None else None
        f.t0 = time.monotonic()
        return f

    def _close(self, f: _Frame) -> None:
        t1 = time.monotonic()
        f.annotation.__exit__(None, None, None)
        stack = self._stack()
        while stack and stack.pop() is not f:
            pass                        # frames an exception left above f
        dur = t1 - f.t0
        if stack:
            stack[-1].children_s += dur
        self._record(f, dur)

    @contextmanager
    def span(self, name: str, step: Optional[int] = None, **args):
        """Nestable timed region; its parent is the span open on this thread.

        Yields the span's mutable args dict — recorded at EXIT, so code
        inside the region can attach facts it only learns mid-span
        (``sargs["corr"] = ...`` for cross-process stitching, byte counts,
        versions) without a second recording API."""
        f = self._open(name, step, args)
        try:
            yield args
        finally:
            self._close(f)

    def add(self, key: str, value: float) -> None:
        """Adds ``value`` under ``key`` into the args of the innermost span
        open on the CALLING thread (it is recorded with them at the span's
        exit), into ``tally`` where none is open there, and in both cases
        into ``totals`` and the registry counter the constructor's
        ``counters`` names for the key. For what the program's control flow
        does not see happen: a compile inside a call
        (``utils/compile_cache.py``)."""
        stack = getattr(self._tls, "stack", None)
        with self._lock:
            self.totals[key] = self.totals.get(key, 0) + value
            into = stack[-1].args if stack else self.tally
            into[key] = into.get(key, 0) + value
            if stack and stack[-1].step is not None:
                acc = self._step_counts.setdefault(int(stack[-1].step), {})
                acc[key] = acc.get(key, 0) + value
                if len(self._step_counts) > self._step_window:
                    self._step_counts.pop(min(self._step_counts), None)
        counter = self._counters.get(key)
        if counter is not None:
            self.registry.inc(counter, value)

    def counted_through(self, step: int, key: str) -> float:
        """What ``add`` was given under ``key`` in the iterations up to
        ``step`` that no earlier call took: ``programs`` is a step record's
        ``compiles`` (0 on a steady step, at the cost of one truth test)."""
        if not self._step_counts:
            return 0
        with self._lock:
            due = [s for s in self._step_counts
                   if s <= step and key in self._step_counts[s]]
            return sum(self._step_counts[s].pop(key) for s in due)

    def setup_span(self):
        """The span ``setup`` round a trainer's constructor, with how old
        the process was when it opened (``process_age_s``)."""
        age = process_age_s()
        return self.span(SETUP_SPAN,
                         **({} if age is None else {"process_age_s": age}))

    def begin_step(self, step: int) -> None:
        """Open the root span of one trainer iteration on this thread (the
        trace's ``StepTraceAnnotation``). The spans opened until ``end_step``
        are its descendants; an iteration left open is closed first."""
        self.end_step()
        self._tls.root = self._open(ROOT_SPAN, step, {}, root=True)

    def end_step(self) -> None:
        """Close this thread's open root span, if any, and whatever an
        exception or a ``break`` left open under it."""
        root = getattr(self._tls, "root", None)
        if root is not None:
            self._tls.root = None
            self._close(root)
            if self.startup is None:
                self.startup_summary(root.step)

    def startup_summary(self, step: int) -> dict:
        """``setup_summary`` of a run whose first iteration is ``step``,
        folded once: when that iteration closes (``end_step``), or before,
        for a record written inside it."""
        if self.startup is None:
            with self._lock:
                self.startup_tally = dict(self.tally)
            self.startup = setup_summary(self, step)
        return self.startup

    def _record(self, f: _Frame, dur: float) -> None:
        top = f.top
        ev = {"id": f.id, "parent": f.parent, "name": f.name, "t0": f.t0,
              "dur": dur, "tid": threading.get_ident(),
              "wall_ns": top.wall_ns + int(round((f.t0 - top.t0) * 1e9))}
        if f.step is not None:
            ev["step"] = int(f.step)
        if f.root:
            ev["root"] = True
        if f.args:
            ev["args"] = f.args
        with self._lock:
            if len(self._buf) == self.capacity:
                self.dropped += 1
            self._buf.append(ev)
            if f.step is not None and not f.root:
                # A phase is the SELF time of its spans: the phases of a
                # step add up to the time under spans once, however the
                # spans nest. The root is the iteration, not a phase.
                acc = self._step_totals.setdefault(int(f.step), {})
                acc[f.name] = acc.get(f.name, 0.0) + dur - f.children_s
                if len(self._step_totals) > self._step_window:
                    self._step_totals.pop(min(self._step_totals), None)

    # ---- summaries ----
    def step_summary(self, step: int, pop: bool = False) -> Dict[str, float]:
        """{phase name: seconds} of the spans of ``step`` closed so far, each
        at its self time (children's time is under the children's names);
        the iteration's root span is not a phase."""
        with self._lock:
            acc = (self._step_totals.pop(int(step), {}) if pop
                   else dict(self._step_totals.get(int(step), {})))
        return {k: round(v, 6) for k, v in acc.items()}

    def spans(self) -> List[dict]:
        with self._lock:
            return list(self._buf)

    # ---- Chrome trace export ----
    def chrome_events(self) -> List[dict]:
        """trace_event 'X' (complete) events + process metadata, ts in us."""
        events: List[dict] = [
            {"ph": "M", "pid": self.pid, "tid": 0, "name": "process_name",
             "args": {"name": self.process_name}},
        ]
        for ev in self.spans():
            e = {"ph": "X", "pid": self.pid, "tid": ev["tid"],
                 "name": ev["name"], "cat": "host",
                 "ts": round(ev["t0"] * _US, 3),
                 "dur": round(ev["dur"] * _US, 3)}
            args = dict(ev.get("args", {}))
            if "step" in ev:
                args["step"] = ev["step"]
            if args:
                e["args"] = args
            events.append(e)
        return events

    def write_chrome_trace(self, path: str,
                           extra_events: Optional[List[dict]] = None) -> str:
        """Write ``{"traceEvents": [...]}`` (the JSON-object flavor chrome://
        tracing and Perfetto both load). Returns the path written."""
        doc = {"traceEvents": self.chrome_events() + list(extra_events or []),
               "displayTimeUnit": "ms",
               "metadata": {"tracer": "ps_pytorch_tpu.telemetry",
                            "dropped_spans": self.dropped}}
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


# ---- self time (choosing-metrics: duration minus what the children cover) ----

def self_times(spans: Iterable[dict]) -> Dict[int, float]:
    """{span id: its duration minus the union of its children's intervals,
    clipped to it}, over recorded spans (``Tracer.spans()``). Children are
    found by ``parent`` id; one whose parent left the ring buffer counts for
    nothing."""
    spans = list(spans)
    kids: Dict[int, List[dict]] = {}
    for ev in spans:
        if ev.get("parent") is not None:
            kids.setdefault(ev["parent"], []).append(ev)
    out = {}
    for ev in spans:
        lo, hi = ev["t0"], ev["t0"] + ev["dur"]
        covered, edge = 0.0, lo
        for k in sorted(kids.get(ev["id"], ()), key=lambda k: k["t0"]):
            s, e = max(k["t0"], edge), min(k["t0"] + k["dur"], hi)
            if e > s:
                covered += e - s
                edge = e
        out[ev["id"]] = ev["dur"] - covered
    return out


# ---- set-up: the record that outlives the ring ----

def process_age_s() -> Optional[float]:
    """Seconds since the OS started this process (``/proc/self/stat`` field
    22, clock ticks after boot, against ``CLOCK_BOOTTIME``): the interpreter,
    the imports and whatever the caller did before it built a trainer. None
    where the platform has neither."""
    try:
        with open("/proc/self/stat") as f:
            # the second field, the command's name, may hold spaces
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) \
            - started / os.sysconf("SC_CLK_TCK")
    except (OSError, AttributeError, ValueError, IndexError):
        return None


def setup_summary(tracer: "Tracer", step: int) -> dict:
    """A run's set-up folded into one dictionary once its first iteration,
    ``step``, has run; a long run pushes the spans themselves out of the
    ring. ``process_age_s`` and ``build_s`` are ``setup``'s (None without
    one); ``phases`` the self seconds by name of ``setup``, of a top-level
    ``resume`` and of everything under them; ``step1`` what iteration
    ``step``'s ``flops_trace`` took and what ``add`` counted under its
    ``host_dispatch``; ``compile`` the tracer's ``totals``: every program
    since the tracer was created, the caller's between the build and
    ``train()`` among them."""
    spans = tracer.spans()
    selfs = self_times(spans)
    by_id = {s["id"]: s for s in spans}

    def top(s):
        while s.get("parent") in by_id:
            s = by_id[s["parent"]]
        return s

    setup = next((s for s in spans if s["name"] == SETUP_SPAN
                  and s.get("parent") is None), None)
    phases: Dict[str, float] = {}
    for s in spans:
        t = top(s)
        if t.get("parent") is None and t["name"] in (SETUP_SPAN, "resume"):
            phases[s["name"]] = phases.get(s["name"], 0.0) + selfs[s["id"]]
    first = {s["name"]: s for s in spans if s.get("step") == step
             and s["name"] in ("flops_trace", "host_dispatch")}
    counted = first.get("host_dispatch", {}).get("args", {})
    step1 = {"flops_trace_s": first.get("flops_trace", {}).get("dur")}
    step1.update({k: counted.get(k, 0) for k in (
        "jit_trace_s", "jit_lower_s", "backend_compile_s", "cache_load_s",
        "cache_hits", "cache_misses")})
    totals = tracer.totals
    out = {"process_age_s": (setup or {}).get("args", {}).get("process_age_s"),
           "build_s": setup["dur"] if setup else None,
           "phases": phases, "step1": step1,
           "compile": {"programs": totals.get("programs", 0),
                       "seconds": totals.get("backend_compile_s", 0.0),
                       "cache_hits": totals.get("cache_hits", 0),
                       "cache_misses": totals.get("cache_misses", 0)}}
    return _rounded(out)


def _rounded(x):
    if isinstance(x, dict):
        return {k: _rounded(v) for k, v in x.items()}
    return round(x, 6) if isinstance(x, float) else x


def startup_line(summary: dict) -> str:
    """``setup_summary`` as one ``STARTUP`` line in the ``KERNELS`` line's
    style. ``step1=load`` where the step's program came out of the compile
    cache (most of its backend seconds were the retrieval), ``compile``
    where the backend compiled it."""
    def group(d):
        return " ".join(f"{k}={v}" for k, v in d.items())
    s1 = summary["step1"]
    loaded = 2 * s1["cache_load_s"] > s1["backend_compile_s"]
    return (f"STARTUP process_age_s={summary['process_age_s']} "
            f"build_s={summary['build_s']} phases[{group(summary['phases'])}] "
            f"step1={'load' if loaded else 'compile'}[{group(s1)}] "
            f"compile[{group(summary['compile'])}]")


# ---- the profiler window of a trainer's loop ----

class ProfileWindow:
    """``--profile-dir D --profile-steps lo-hi``: a ``jax.profiler`` trace
    over steps lo..hi of a trainer's loop. ``on_step`` goes at the loop's
    head, before ``Tracer.begin_step``, so that the first traced iteration's
    annotation is whole; ``close`` goes in the loop's ``finally``."""

    def __init__(self, profile_dir: str, profile_steps: str):
        self.dir = profile_dir
        self.range = None
        self.active = False
        if profile_dir:
            lo, _, hi = profile_steps.partition("-")
            self.range = (int(lo), int(hi or lo))

    def on_step(self, step: int) -> None:
        if self.range is None:
            return
        lo, hi = self.range
        # Window-membership, not step equality: a resumed run may enter the
        # loop past `lo` (or never reach `hi`).
        if not self.active and lo <= step <= hi:
            jax.profiler.start_trace(self.dir)
            self.active = True
        elif self.active and step > hi:
            self.close()

    def close(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            self.range = None


# ---- ambient tracer (library-layer instrumentation without API churn) ----
_default: Optional[Tracer] = None
_latest: Optional[Tracer] = None
_default_lock = threading.Lock()


def set_default_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install (or clear, with None) the process-wide default tracer used by
    the module-level ``span``. Returns the previous one."""
    global _default, _latest
    with _default_lock:
        prev = _default
        _default = tracer
        if tracer is not None:
            _latest = tracer
    return prev


def get_default_tracer() -> Optional[Tracer]:
    return _default


def latest_tracer() -> Optional[Tracer]:
    """The tracer most recently installed as the default, still reachable
    after its trainer's ``train()`` has restored the previous default: what
    a caller that holds no trainer reads a finished run's spans from."""
    return _latest


@contextmanager
def span(name: str, step: Optional[int] = None, **args):
    """Record into the default tracer; a zero-cost no-op when none is set
    (library code stays importable and fast without telemetry wired up).
    With a tracer installed, yields the span's mutable args dict (see
    Tracer.span); without one, yields None — callers guard with
    ``if sargs is not None``."""
    t = _default
    if t is None:
        yield None
    else:
        with t.span(name, step=step, **args) as sargs:
            yield sargs
