"""Reader: the device's time by the program's own scopes.

The program opens a ``jax.named_scope`` where each layer of its jitted step
begins (``ps_pytorch_tpu/telemetry/trace.py``: ``DEVICE_SCOPES``,
``device_scope``), so every op's name stack says which layer it is of, and a
TPU profile keeps that stack on each ``XLA Ops`` event as the stat ``tf_op``
of the event's metadata (``jit(local_step)/jvp(MoETransformerLM)/block_2/
attn_core/...``). This reader takes chip 0's ``XLA Ops`` events inside the
steady window of the traced steps (``run.steady()``) and gives each to

- a scope: the innermost component of its ``tf_op`` that is in the
  vocabulary, a transform that wraps a component taken off
  (``transpose(jvp(loss))`` is ``loss``); ``unscoped`` without one, with no
  ``tf_op``, or where XLA joined several (``a;b``) and the first has none;
- a part: ``recompute`` where the stack holds ``rematted_computation``, else
  ``backward`` where it holds ``transpose(``, else ``forward``.

An event that holds other events (a ``while`` op and its body's ops, a
``conditional`` and its branch's) counts for its own time only: each
nanosecond goes to the innermost event, so the scopes add up to the chip's
busy time in the window. A fused op counts to the scope of the one op whose
metadata the fusion carries. On the v5e (chip runs, PR 33) an output fusion
carries its matmul's or convolution's: the SGD update that XLA fuses behind
a weight gradient reads under the layer (``attn_proj``, ``ffn``, ``head``,
``conv``), ``dlogits`` computed in the head's gradient matmuls under
``head``, and ``optimizer`` holds the updates that are passes of their own
(PERF.md section 3 names the crossings found).

``jax.profiler.ProfileData`` (jax 0.9.0) hands out an event's own stats and
not its metadata's, so the xplane is decoded here from the protobuf wire
format, and only what is needed of it: the planes' names, chip 0's ``XLA
Ops`` line, and that plane's event and stat metadata.

Parameters: ``scope`` (a name, a list of names, or ``all``), ``part``
(``all`` | ``forward`` | ``backward`` | ``recompute``), ``per`` (``step_ms``
| ``busy_share``, percent of the chip's busy time in the window). None
without a trace, where the program has no scopes (a commit before PR 33),
or where nothing ran under the scope asked for. The first call of a run
prints the whole table through ``run.say``, largest first:

    DEVICE_BY_SCOPE <scope>: fwd a.aa bwd b.bb recompute c.cc ms/step, s.s% of busy, n ops, F GFLOP, B MB; top: <its largest op> x.xx
    DEVICE_BY_SCOPE total t.tt of busy u.uu ms/step over p steps of chip 0; read in r.rr s

(the ``unscoped`` line ends ``; n.nn with no tf_op``: the ms a step of ops the
compiler added and gave no name, which no scope of the program can reach)

(``n ops``: the distinct HLO ops; GFLOP and MB a step from the profile's
own ``flops`` and ``bytes_accessed``, ops that hold other ops left out.)

As a script it prints the same table for a profile of any run of
``train.py`` / ``train_lm.py`` (``--profile-dir D --profile-steps 3-6``):

    python3 benchmark/readers/device_scopes.py D
"""

import os
import re
import struct
import sys
import time

if __name__ == "__main__":      # the benchmark's and the program's modules
    _here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [os.path.dirname(_here), os.path.dirname(os.path.dirname(_here))]

import trace_reduce

UNSCOPED = "unscoped"
PARTS = ("forward", "backward", "recompute")


# ------------------------------------------------------------ wire format --

def fields(buf, lo=0, hi=None):
    """(field number, wire type, value) of one protobuf message: an int for
    a varint or a fixed-width field, a ``(lo, hi)`` span of ``buf`` for a
    length-delimited one."""
    hi = len(buf) if hi is None else hi
    while lo < hi:
        key, lo = _varint(buf, lo)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, lo = _varint(buf, lo)
        elif wire == 2:
            n, lo = _varint(buf, lo)
            value, lo = (lo, lo + n), lo + n
        elif wire == 1:
            value, lo = struct.unpack_from("<q", buf, lo)[0], lo + 8
        elif wire == 5:
            value, lo = struct.unpack_from("<i", buf, lo)[0], lo + 4
        else:
            raise ValueError(f"wire type {wire} at byte {lo}")
        yield number, wire, value


def _varint(buf, lo):
    value = shift = 0
    while True:
        b = buf[lo]
        lo += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, lo
        shift += 7


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stats(buf, span, stat_names):
    """(stat name, value) of the XStat message at ``span``; a ``ref_value``
    is the name it refers to."""
    meta, value = 0, None
    for number, wire, v in fields(buf, *span):
        if number == 1:
            meta = v
        elif number == 2:                       # double_value, fixed64 bits
            value = struct.unpack("<d", struct.pack("<q", v))[0]
        elif number in (3, 4):
            value = _signed(v) if number == 4 else v
        elif number in (5, 6):
            value = _text(buf, v)
        elif number == 7:
            value = stat_names.get(v, "")
    return stat_names.get(meta, str(meta)), value


def device_ops(path, chip=0):
    """The ``XLA Ops`` events of ``/device:TPU:<chip>`` in an xplane file:
    [(start_s, end_s, metadata id)], and {metadata id: {"name": the op's HLO
    text, "tf_op", "flops", "bytes_accessed", ...}} of that plane. None where
    the file has no such plane or line."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    want = f"/device:TPU:{chip}"
    for number, _, plane in fields(buf):                    # XSpace.planes
        if number != 1:
            continue
        parts = list(fields(buf, *plane))
        if not any(n == 2 and _text(buf, v) == want for n, _, v in parts):
            continue
        stat_names, metadata, events = {}, {}, None
        for n, _, v in parts:                   # XPlane.stat_metadata first
            if n == 5:
                entry = dict((k, x) for k, _, x in fields(buf, *v))
                sm = dict((k, x) for k, _, x in fields(buf, *entry[2]))
                stat_names[entry.get(1, sm.get(1, 0))] = \
                    _text(buf, sm[2]) if 2 in sm else ""
        for n, _, v in parts:
            if n == 3 and events is None:                   # XPlane.lines
                events = _line_events(buf, v, trace_reduce.OPS_LINE)
        if events is None:
            return None
        for n, _, v in parts:
            if n == 4:                              # XPlane.event_metadata
                entry = dict((k, x) for k, _, x in fields(buf, *v))
                metadata[entry[1]] = _event_metadata(buf, entry[2],
                                                     stat_names)
        return events, metadata
    return None


def _line_events(buf, span, name):
    """[(start_s, end_s, metadata id)] of the XLine at ``span`` if it is
    called ``name``, else None."""
    parts = list(fields(buf, *span))
    if not any(n == 2 and _text(buf, v) == name for n, _, v in parts):
        return None
    t0_ns = next((v for n, _, v in parts if n == 3), 0)
    out = []
    for n, _, v in parts:
        if n != 4:
            continue
        meta = offset_ps = dur_ps = 0
        for k, _, x in fields(buf, *v):
            if k == 1:
                meta = x
            elif k == 2:
                offset_ps = x
            elif k == 3:
                dur_ps = x
        start = (t0_ns + offset_ps * 1e-3) * 1e-9
        out.append((start, start + dur_ps * 1e-12, meta))
    return out


def _event_metadata(buf, span, stat_names):
    out = {"name": ""}
    display = ""
    for n, _, v in fields(buf, *span):
        if n == 2:
            out["name"] = _text(buf, v)
        elif n == 4:
            display = _text(buf, v)
        elif n == 5:
            key, value = _stats(buf, v, stat_names)
            out[key] = value
    # ``ProfileData`` shows the display name where there is one; the HLO
    # text the other readers match is whichever of the two holds " = ".
    if " = " not in out["name"] and " = " in display:
        out["name"] = display
    return out


# ------------------------------------------------------------- attribution --

def scope_of(tf_op, vocabulary):
    """(scope, part) of an op's name stack; see the module's docstring."""
    tf_op = tf_op or ""
    names = [re.sub(r"^(?:[\w\-]+\()+|\)+$", "", c)
             for c in tf_op.split(";")[0].split("/")]
    scopes = [n for n in names if n in vocabulary]
    part = "recompute" if "rematted_computation" in tf_op else \
        "backward" if "transpose(" in tf_op else "forward"
    return (scopes[-1] if scopes else UNSCOPED), part


def self_seconds(events, lo, hi):
    """[(self seconds, has children, metadata id)] of the events clipped to
    the window, each second of the line given to the innermost event that
    covers it."""
    clipped = sorted(((max(s, lo), min(e, hi), m) for s, e, m in events
                      if min(e, hi) > max(s, lo)),
                     key=lambda ev: (ev[0], -ev[1]))
    out, stack = [], []                 # stack: indices into out, open events
    for s, e, m in clipped:
        while stack and out[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[0] -= min(e, parent[3]) - s
            parent[1] = True
        out.append([e - s, False, m, e])
        stack.append(len(out) - 1)
    return [(max(t, 0.0), kids, m) for t, kids, m, _ in out]


def table(events, metadata, window, vocabulary):
    """{scope: {"forward" / "backward" / "recompute": seconds, "ops": the
    distinct ops, "flops", "bytes": summed over the events that hold no
    other, "nameless": seconds of ops with no ``tf_op`` at all (under
    ``unscoped``: what the compiler added, where the rest is the program's to
    name), "top": (seconds, HLO text) of its largest op}} over the window."""
    by_op, rows = {}, {}
    for t, kids, m in self_seconds(events, *window):
        meta = metadata.get(m, {})
        scope, part = scope_of(meta.get("tf_op"), vocabulary)
        row = rows.setdefault(scope, {"forward": 0.0, "backward": 0.0,
                                      "recompute": 0.0, "ops": set(),
                                      "flops": 0.0, "bytes": 0.0,
                                      "nameless": 0.0})
        row[part] += t
        if not meta.get("tf_op"):
            row["nameless"] += t
        row["ops"].add(m)
        if not kids:
            row["flops"] += float(meta.get("flops") or 0)
            row["bytes"] += float(meta.get("bytes_accessed") or 0)
        by_op[scope, m] = by_op.get((scope, m), 0.0) + t
    for (scope, m), t in by_op.items():
        if t > rows[scope].get("top", (0.0, ""))[0]:
            rows[scope]["top"] = (t, metadata.get(m, {}).get("name", ""))
    return rows


def seconds(row, part="all"):
    return sum(row[p] for p in PARTS) if part == "all" else row[part]


def lines(rows, periods, busy_s, chip=0, read_s=None):
    """The ``DEVICE_BY_SCOPE`` lines of a table, largest scope first."""
    ms = 1e3 / periods
    out = []
    for scope, row in sorted(rows.items(), key=lambda kv: -seconds(kv[1])):
        top_s, top = row.get("top", (0.0, ""))
        out.append(
            f"DEVICE_BY_SCOPE {scope}: fwd {row['forward'] * ms:.2f} bwd "
            f"{row['backward'] * ms:.2f} recompute {row['recompute'] * ms:.2f}"
            f" ms/step, {100.0 * seconds(row) / busy_s:.1f}% of busy, "
            f"{len(row['ops'])} ops, {row['flops'] / periods / 1e9:.1f} GFLOP,"
            f" {row['bytes'] / periods / 1e6:.0f} MB; top: "
            f"{trace_reduce.describe(top)} {top_s * ms:.2f}"
            + (f"; {row['nameless'] * ms:.2f} with no tf_op"
               if scope == UNSCOPED else ""))
    total = sum(seconds(r) for r in rows.values())
    out.append(f"DEVICE_BY_SCOPE total {total * ms:.2f} of busy "
               f"{busy_s * ms:.2f} ms/step over {periods} steps of chip "
               f"{chip}" + (f"; read in {read_s:.2f} s" if read_s is not None
                            else ""))
    return out


def vocabulary():
    """The program's scopes, or None where it has none (a commit before
    PR 33)."""
    try:
        from ps_pytorch_tpu.telemetry import trace
    except ImportError:
        return None
    return getattr(trace, "DEVICE_SCOPES", None)


def reduce_file(path, vocab, chip=0):
    """-> (rows, periods, busy seconds) of an xplane file over the steady
    window of its own step program, or None without one."""
    c = trace_reduce.load(path).chip(chip)
    steady = c.steady_window() if c else None
    got = device_ops(path, chip) if steady else None
    if got is None:
        return None
    lo, hi, periods = steady
    return table(*got, (lo, hi), vocab), periods, c.busy_s((lo, hi))


# ------------------------------------------------------------------ reader --

def _of_run(run):
    """The run's table, read once: (rows, periods, busy seconds) or None."""
    if hasattr(run, "device_scopes"):
        return run.device_scopes
    run.device_scopes = None
    vocab, steady = vocabulary(), run.steady()
    if not vocab or not steady:
        return None
    import harness
    path = trace_reduce.find_xplane(
        os.path.join(harness.RUNS_DIR, "*", "trace"))
    if not path:
        return None
    t0 = time.monotonic()
    chip, window, periods = steady
    got = device_ops(path, chip.index)
    if got is None:
        return None
    rows, busy_s = table(*got, window, vocab), chip.busy_s(window)
    if not rows or busy_s <= 0:
        return None
    for line in lines(rows, periods, busy_s, chip.index,
                      time.monotonic() - t0):
        run.say(line)
    run.device_scopes = rows, periods, busy_s
    return run.device_scopes


def read(run, scope, per, part="all"):
    got = _of_run(run)
    if got is None:
        return None
    rows, periods, busy_s = got
    names = list(rows) if scope == "all" else \
        [scope] if isinstance(scope, str) else list(scope)
    t = sum(seconds(rows[n], part) for n in names if n in rows)
    if t <= 0:
        return None
    if per == "step_ms":
        return 1e3 * t / periods
    if per == "busy_share":
        return 100.0 * t / busy_s
    raise ValueError(f"unknown per {per!r}")


def main(argv):
    if len(argv) != 2:
        print(__doc__.split("As a script")[1].split("\n\n")[1].strip(),
              file=sys.stderr)
        return 2
    path = argv[1]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    vocab = vocabulary()
    got = reduce_file(path, vocab) if path and vocab else None
    if got is None:
        print(f"no xplane with two runs of a step program on "
              f"/device:TPU:0 under {argv[1]}", file=sys.stderr)
        return 1
    print("\n".join(lines(*got)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
