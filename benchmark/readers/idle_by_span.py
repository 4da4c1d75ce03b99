"""Reader: what the host was doing while chip 0 was idle, by program span.

The idle seconds of chip 0 in the steady window of the traced steps
(``trace_reduce.gaps``) are laid over the program's spans (see
``program_spans.py``): each direct child of an iteration's root span takes
the idle time that lies under it, the root's self time takes what lies under
the root and under no child, and the rest lies outside every iteration. The
metric is the share of the idle seconds under no child span, in percent.

The spans get onto the trace's axis in one of two ways. Where the xplane
holds the program's own annotations (the host tracer was on: every span is a
``jax.profiler.TraceAnnotation``), their intervals are taken from the trace
as they are. Where it does not (``host_tracer_level: 0``), each recorded
span's start on the Unix clock (``wall_ns``, from its tree's anchor) is moved
by the xplane's origin: the trace counts from its session's start, which it
keeps as Unix ns in the plane ``Task Environment``, stat
``profile_start_time``. Where a trace has both, ``SPANCLOCK`` says how far
they disagree.

The chip's plane and the host's are not quite on one clock: the profiler sets
a device's events on the host's axis to within a millisecond or so, anew in
every session (chip probe, PR 23: a module that starts 0.93 ms before the
span that dispatches it opens). What the program's spans know bounds the
error: a run of the step program cannot start before the ``dispatch`` span
that launches it opens, nor end after the ``sync`` span that reads its result
closes, and what the device runs next was launched after that span closed.
``DEVCLOCK`` prints the interval of shifts of the chip's events that these
allow over the window's steps. Where it does not hold 0 the trace contradicts
itself, and the idle gaps are moved by the least that mends it; inside the
interval nothing tells one shift from another, so the attribution at the two
ends of a step's device time is only as sure as the interval is narrow.

Earlier lines, through ``run.say``: ``IDLE_BY_SPAN <span> <ms/step> <% of
idle>`` for every child span and for ``train_step(self)``, ``SPANCLOCK`` and
``DEVCLOCK``.
None without a trace, without root spans in the program (a commit before
PR 23), or with neither annotations nor an origin.
"""

import bisect
import os
import statistics

import harness
import trace_reduce
from trace_reduce import clip, subtract, total, union

TASK_PLANE = "Task Environment"
ORIGIN_STAT = "profile_start_time"


def load_profile(run):
    """``ProfileData`` of the run's xplane: the newest under the harness's
    run directories (one process runs one cell), or None."""
    path = trace_reduce.find_xplane(os.path.join(harness.RUNS_DIR, "*", "trace"))
    if not path:
        return None
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def origin_ns(profile):
    """Unix ns at which the xplane's clock reads 0, or None."""
    for plane in profile.planes if profile else ():
        if plane.name == TASK_PLANE:
            return dict(plane.stats).get(ORIGIN_STAT)
    return None


def annotated(trace, names):
    """{name: [(start_s, end_s)]} of the trace's host events that carry a
    span's name; empty unless the root's are among them."""
    out = {}
    for name, s, e in trace.host:
        if name in names:
            out.setdefault(name, []).append((s, e))
    return out if ps().ROOT in out else {}


def anchored(spans, origin):
    """{name: [(start_s, end_s)]} of recorded spans, moved from the Unix
    clock onto the xplane's axis."""
    out = {}
    for s in spans:
        t = (s["wall_ns"] - origin) * 1e-9
        out.setdefault(s["name"], []).append((t, t + s["dur"]))
    return out


def covered(idle, intervals):
    """Seconds of the disjoint cover ``idle`` that ``intervals`` cover."""
    return total(idle) - total(subtract(idle, union(intervals)))


def attribute(idle, by_name, child_names):
    """-> ({child span: idle seconds under it}, under the root alone, outside
    every root). A thread's sibling spans do not overlap, so the children's
    shares add up."""
    per = {n: covered(idle, by_name[n]) for n in sorted(child_names)
           if n in by_name}
    under_root = covered(idle, by_name.get(ps().ROOT, []))
    return per, under_root - sum(per.values()), total(idle) - under_root


def clock_gap_us(ann, anc):
    """|annotation start - nearest anchored start of the same name|, in
    microseconds: (median, worst, count)."""
    gaps = []
    for name, evs in ann.items():
        starts = sorted(s for s, _ in anc.get(name, []))
        for s, _ in evs:
            i = bisect.bisect_left(starts, s)
            near = [abs(s - starts[j]) for j in (i - 1, i)
                    if 0 <= j < len(starts)]
            if near:
                gaps.append(1e6 * min(near))
    if not gaps:
        return None
    return statistics.median(gaps), max(gaps), len(gaps)


def ps():
    """The sibling reader's module (``harness.load_module`` caches it)."""
    return harness.load_module(
        os.path.join(os.path.dirname(__file__), "program_spans.py"))


def device_shift(chip, window, dispatches, syncs):
    """(lo, hi): the seconds by which the chip's events may move on the
    host's axis. For every run of the step program (the module with the most
    device time, as in ``Chip.steady_window``) in the window: it starts after
    its dispatch span opened (lo); it ends before the first sync span after
    that dispatch closed (hi); and, where no other dispatch opened before
    that sync closed, the host launched nothing while it waited, so the next
    module of any program starts after the sync closed (lo). None without
    runs or spans to compare."""
    by_name = {}
    for text, s, e in chip.modules:
        by_name.setdefault(text, []).append((s, e))
    if not by_name or not dispatches or not syncs:
        return None
    runs = [r for r in max(by_name.values(), key=total)
            if window[0] <= r[0] <= window[1]]
    starts = sorted(s for _, s, _ in chip.modules)
    dispatches, syncs = sorted(dispatches), sorted(syncs)
    opened = [s for s, _ in dispatches]
    sync_opened = [s for s, _ in syncs]
    lo, hi = [], []
    for s, e in runs:
        d = min(range(len(dispatches)), key=lambda i: abs(opened[i] - s))
        lo.append(opened[d] - s)
        i = bisect.bisect_left(sync_opened, opened[d])
        if i == len(syncs):
            continue
        closed = syncs[i][1]
        hi.append(closed - e)
        j = bisect.bisect_right(starts, e)
        if j < len(starts) and (d + 1 == len(opened) or opened[d + 1] >= closed):
            lo.append(closed - starts[j])
    return (max(lo), min(hi)) if lo and hi else None


def read(run, dispatch="host_dispatch", sync="metrics_sync"):
    got = ps().tracer_spans()
    steady = run.steady()
    if got is None or not steady:
        return None
    spans, self_times = got
    chip, (lo, hi), periods = steady
    idle = trace_reduce.gaps(chip.busy, lo, hi)
    if not idle:
        return None
    its = ps().iterations(spans, self_times)
    child_names = {c["name"] for it in its for c in it["children"]}
    ann = annotated(run.trace, child_names | {ps().ROOT})
    origin = origin_ns(load_profile(run))
    anc = anchored(spans, origin) if origin is not None else {}
    if ann and anc:
        gap = clock_gap_us(ann, anc)
        if gap:
            run.say(f"SPANCLOCK annotations against anchored spans: median "
                    f"{gap[0]:.1f} us, worst {gap[1]:.1f} us over {gap[2]} "
                    f"events; the annotations are used")
    by_name = ann or anc
    if not by_name:
        return None
    if not ann:
        run.say("SPANCLOCK no annotations in the trace (host tracer off): "
                "anchored spans moved by the xplane's profile_start_time")
    shift = device_shift(chip, (lo, hi), by_name.get(dispatch), by_name.get(sync))
    moved = 0.0
    if shift:
        moved = min(max(0.0, shift[0]), shift[1]) if shift[0] <= shift[1] else 0.0
        run.say(f"DEVCLOCK chip {chip.index}'s events fit the {dispatch} and "
                f"{sync} spans when moved by {1e3 * shift[0]:+.3f} to "
                f"{1e3 * shift[1]:+.3f} ms; moved by {1e3 * moved:+.3f} ms")
    idle = [(s + moved, e + moved) for s, e in idle]
    by_name = {n: clip(v, lo + moved, hi + moved) for n, v in by_name.items()}
    per, root_self, outside = attribute(idle, by_name, child_names)
    idle_s = total(idle)
    rows = sorted(per.items(), key=lambda kv: -kv[1]) + \
        [(f"{ps().ROOT}(self)", root_self), ("outside_every_iteration", outside)]
    for name, sec in rows:
        run.say(f"IDLE_BY_SPAN {name} {1e3 * sec / periods:.4f} ms/step "
                f"{100.0 * sec / idle_s:.2f}% of idle")
    run.say(f"IDLE_BY_SPAN total {1e3 * idle_s / periods:.4f} ms/step idle "
            f"over {periods} steps of chip {chip.index}")
    return 100.0 * (idle_s - sum(per.values())) / idle_s
