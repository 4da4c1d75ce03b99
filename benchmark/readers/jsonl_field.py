"""Reader over the trainers' JSONL records: the median over the window's
records of one top-level field (a counter the step returned and the trainer
logged with the loss). None when no record of the window carries the field,
as in a program that does not log it yet."""

import statistics


def read(run, field):
    vals = [r[field] for r in run.window_records if r.get(field) is not None]
    return statistics.median(vals) if vals else None
