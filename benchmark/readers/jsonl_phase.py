"""Reader over the trainers' JSONL ``phases`` (host-clock spans of
``telemetry/trace.py``): median milliseconds per step of one phase over the
window's records. None when no record carries the phase."""

import statistics


def read(run, phase):
    vals = [r["phases"][phase] for r in run.window_records
            if phase in (r.get("phases") or {})]
    return 1e3 * statistics.median(vals) if vals else None
