"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the per-layer
readers need, with nothing but ``jax.profiler.ProfileData``.

What a trace looks like (one v5e, looked at by hand before this was written):
each chip is a plane named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO op (start and duration on the device's clock, the
event's name is the op's HLO text, ``%fusion.9 = ... kind=kOutput ...``), its
line ``XLA Modules`` one event per executed program, and asynchronous ops
(copies, collectives that overlap compute) sit on ``Async XLA Ops`` from
their start to their done. Host threads are lines of the plane ``/host:CPU``.

Definitions, the same for every PR:

- busy: the union of the intervals of the ``XLA Ops`` events of one chip.
  Asynchronous ops do not count as busy: a DMA that nothing waits for is not
  the TensorCore working.
- window: given by the caller (the host-side trace window has profiler
  start-up in it), or else first op start to last op end of that chip.
- idle share: 1 - busy / window.
- collective time: the union of the intervals of collective ops, wherever they
  sit (``XLA Ops`` or ``Async XLA Ops``); its exposed part is what no
  non-collective ``XLA Ops`` event of the same chip covers.
- idle gaps: the maximal intervals inside the window in which no ``XLA Ops``
  event runs, each attributed to the host event that covers most of it
  (longest overlap; ``unattributed`` when none does). Host and device clocks
  are the profiler's own, already on one axis in the xplane.
"""

import math
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)(-start|-done)?\b")


# ------------------------------------------------------------ intervals --

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given intervals."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted cover ``a`` that ``b`` (same form) leaves
    uncovered."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], clip(busy, lo, hi))


# ---------------------------------------------------------------- names --

def op_name(text: str) -> str:
    """``%fusion.9 = (f32[64]...) fusion(...), kind=kOutput`` -> ``fusion.9``;
    a bare name comes back as it is."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def describe(text: str) -> str:
    """A label for the breakdown: the op's name, what it is (a fusion's kind,
    a custom-call's target, else the opcode) and its widest result, e.g.
    ``fusion.9 kOutput bf16[4096,32,32,64]``."""
    parts = re.match(r"(.*?) = (.*?)\s([a-z][\w\-]*)\(", text)
    if not parts:
        return op_name(text)
    _, results, opcode = parts.groups()
    kind = re.search(r'kind=(\w+)|custom_call_target="([^"]+)"', text)
    shapes = re.findall(r"[a-z]+\d*\[[\d,]*\]", results)
    widest = max(shapes, default="", key=lambda s: math.prod(
        int(d) for d in re.findall(r"\d+", s.split("[", 1)[1])))
    return " ".join(x for x in (
        op_name(text), (kind.group(1) or kind.group(2)) if kind else opcode,
        widest) if x)


def is_collective(text: str) -> bool:
    head = text.split(" = ", 1)
    return bool(COLLECTIVE.search(head[0]) or
                (len(head) > 1 and COLLECTIVE.search(head[1].split("(", 1)[0])))


# ---------------------------------------------------------------- planes --

class Chip:
    """One device plane, already in seconds."""

    def __init__(self, index: int, ops, async_ops, modules):
        self.index = index
        self.ops = ops              # [(text, start_s, end_s)] of XLA Ops
        self.async_ops = async_ops  # same, of Async XLA Ops
        self.modules = modules      # same, of XLA Modules
        self.busy = union((s, e) for _, s, e in ops)

    def steady_window(self) -> Optional[Tuple[float, float, int]]:
        """(lo, hi, periods): from the start of the second run of the step
        program inside the trace to the start of its last, so it holds whole
        step periods and none of the profiler's start and stop. The step
        program is the module with the most device time. None when the
        trace holds fewer than two runs of it."""
        by_name: Dict[str, List[Interval]] = {}
        for text, s, e in self.modules:
            by_name.setdefault(text, []).append((s, e))
        if not by_name:
            return None
        runs = sorted(max(by_name.values(), key=total))
        if len(runs) >= 4:
            runs = runs[1:]
        if len(runs) < 2:
            return None
        return runs[0][0], runs[-1][0], len(runs) - 1

    def span(self) -> Interval:
        if not self.busy:
            return (0.0, 0.0)
        return (self.busy[0][0], self.busy[-1][1])

    def busy_s(self, window: Optional[Interval] = None) -> float:
        lo, hi = window or self.span()
        return total(clip(self.busy, lo, hi))

    def idle_share(self, window: Optional[Interval] = None) -> Optional[float]:
        lo, hi = window or self.span()
        if hi <= lo:
            return None
        return 1.0 - self.busy_s((lo, hi)) / (hi - lo)

    def op_seconds(self, window: Optional[Interval] = None) -> Dict[str, float]:
        """Device seconds by op, under the label ``describe`` gives it."""
        lo, hi = window or self.span()
        out: Dict[str, float] = {}
        for text, s, e in self.ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                name = describe(text)
                out[name] = out.get(name, 0.0) + d
        return out

    def matching_seconds(self, pattern: str,
                         window: Optional[Interval] = None) -> float:
        """Device seconds of ``XLA Ops`` events whose full text matches."""
        rx = re.compile(pattern)
        lo, hi = window or self.span()
        return sum(max(0.0, min(e, hi) - max(s, lo))
                   for text, s, e in self.ops if rx.search(text))

    def collectives(self, window: Optional[Interval] = None):
        """-> (collective seconds, exposed seconds): the union of collective
        intervals, and the part of it no other op of this chip covers."""
        lo, hi = window or self.span()
        coll = union(clip(((s, e) for text, s, e in self.ops + self.async_ops
                           if is_collective(text)), lo, hi))
        other = union(clip(((s, e) for text, s, e in self.ops
                            if not is_collective(text)), lo, hi))
        return total(coll), total(subtract(coll, other))


class Trace:
    def __init__(self, chips: List[Chip], host):
        self.chips = chips
        self.host = host            # [(name, start_s, end_s)], every thread

    def chip(self, index: int = 0) -> Optional[Chip]:
        for c in self.chips:
            if c.index == index:
                return c
        return None

    def attribute_gaps(self, chip: Chip, window: Optional[Interval] = None,
                       top: int = 5) -> List[Tuple[str, float]]:
        """The longest idle gaps of ``chip`` as (what the host was doing,
        seconds): the host event with the longest overlap with the gap, the
        narrowest such event on a tie, ``unattributed`` when none overlaps."""
        lo, hi = window or chip.span()
        longest = sorted(gaps(chip.busy, lo, hi),
                         key=lambda g: g[0] - g[1])[:top]
        out = []
        for gs, ge in longest:
            best, best_key = "unattributed", (0.0, 0.0)
            for name, s, e in self.host:
                ov = min(e, ge) - max(s, gs)
                if ov <= 0:
                    continue
                key = (ov, -(e - s))
                if key > best_key:
                    best, best_key = name, key
            out.append((best, ge - gs))
        return out


def _events(line, scale: float):
    return [(ev.name, ev.start_ns * scale, (ev.start_ns + ev.duration_ns) * scale)
            for ev in line.events]


def from_profile_data(data) -> Trace:
    """``jax.profiler.ProfileData`` (or anything with the same planes /
    lines / events attributes, as the tests build by hand) -> Trace."""
    chips, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            chips.append(Chip(
                int(m.group(1)),
                _events(lines[OPS_LINE], 1e-9),
                _events(lines[ASYNC_LINE], 1e-9) if ASYNC_LINE in lines else [],
                _events(lines[MODULES_LINE], 1e-9) if MODULES_LINE in lines else []))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(_events(line, 1e-9))
    chips.sort(key=lambda c: c.index)
    return Trace(chips, host)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile_data(ProfileData.from_file(path))


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` output directory."""
    import glob
    import os
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None
