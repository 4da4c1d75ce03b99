"""Where the persistent XLA compile cache lives.

Every entry point calls :func:`enable_compile_cache` before its first
compile. The directory is part of what JAX keys a cached executable on, so
it must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when the
caller's environment sets one (JAX reads that itself; nothing is set here),
and otherwise ``<checkout>/.jax_cache`` with the checkout located from this
file — never from the cwd, a temp name, a pid or the time. JAX's default
thresholds (store a program that took >= 1 s to compile, any size) already
keep every training step; only sub-second programs recompile. Counted by the
listeners below (chip run, PR 49, ``olmoe_s4096_1chip`` through the benchmark
on a cache the run before had filled): of the 7 programs a start compiles or
loads (the trainer's 4: two for the key, the initialiser, the step; the
harness's reference check's 3) 4 are loaded and 3 compile again, and all
seven take 1.63 s of a ``setup_s`` of 32.0, the step's own load 0.41 s of it;
on an empty cache the same seven take 57.6 s of 87.1. What a warm start waits
for is not the sub-second programs.

The key of a cached executable holds its ops' metadata (JAX leaves it out by
default): the step's device scopes (``telemetry/trace.py:device_scope``) live
there and nowhere else, and an executable cached by a program that named
fewer layers computes the same and profiles as that program did. The price
is a recompile when a source line moves under a compiled function.
"""

import os
import threading
import time

import jax
import jax.core
import jax.monitoring

from ps_pytorch_tpu.telemetry.trace import get_default_tracer

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """-> the cache directory in use."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---- every compile counted in the span that caused it ----
# JAX 0.9.0's own events. The three stages of a compile nest (a traced
# function traces the jitted functions it calls, and runs its constants'
# small programs whole), so each is counted net of what ran inside it and the
# keys of one span add up to no more than the span.
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
}
# The part of backend_compile_duration, which fires for a load too, that was
# the retrieval from the cache.
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# Of the keys handed to ``Tracer.add``, the three that also feed a registry
# counter (TRAINING_COUNTERS): what a trainer gives its tracer as ``counters``.
COMPILE_COUNTERS = {"programs": "jax_programs_compiled_total",
                    "cache_misses": "jax_compile_cache_misses_total",
                    "backend_compile_s": "jax_compile_seconds_total"}
# What ``jax.core`` says of a thread on which no trace is open (this module is
# imported at top level).
_NO_TRACE = jax.core.get_opaque_trace_state()
_listening = threading.Lock()
_thread = threading.local()


def _on_duration(name: str, secs: float, **_kw) -> None:
    tracer = get_default_tracer()
    if tracer is None:
        return
    if name == _CACHE_LOAD:
        tracer.add("cache_load_s", secs)
        return
    key = _STAGES.get(name)
    if key is None:
        return
    # The event closes an interval that ends now; the stages this thread
    # counted since the interval opened ran inside it. ``seen`` holds the
    # thread's finished stages that a stage still open may hold, every one of
    # them (a trace of thousands of jitted calls subtracts thousands), for as
    # long as a trace is open and no longer.
    seen = getattr(_thread, "seen", None)
    if seen is None:
        seen = _thread.seen = []
    opened, inside = time.monotonic() - secs, 0.0
    while seen and seen[-1][0] >= opened:
        inside += seen.pop()[1]
    if _no_trace_open():
        seen.clear()    # but this one, which a lowering may have traced
    seen.append((opened, secs))
    tracer.add(key, max(secs - inside, 0.0))
    if key == "backend_compile_s":
        tracer.add("programs", 1)
        # JAX's own cache_misses event fires only for a program it STORES
        # (one that took >= 1 s): a miss here is every program that was not
        # loaded, the sub-second ones a warm start compiles again included.
        if not getattr(_thread, "hit", False):
            tracer.add("cache_misses", 1)
        _thread.hit = False


def _no_trace_open() -> bool:
    """Nothing is being traced on this thread: what has finished on it is
    inside no trace that has yet to close."""
    return jax.core.get_opaque_trace_state() == _NO_TRACE


def _on_event(name: str, **_kw) -> None:
    if name == _CACHE_HIT:
        _thread.hit = True      # told before its backend_compile_duration
        tracer = get_default_tracer()
        if tracer is not None:
            tracer.add("cache_hits", 1)


def count_compiles() -> None:
    """Registers the listeners, once a process however often it is called
    (every trainer's constructor calls it, and nothing else: an entry point
    that builds no trainer has no tracer to count into). Each hands what JAX
    reports to ``Tracer.add`` of the default tracer and does nothing where
    none is installed; nothing runs on a step that compiles nothing."""
    if _listening.acquire(blocking=False):    # never released: once a process
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
