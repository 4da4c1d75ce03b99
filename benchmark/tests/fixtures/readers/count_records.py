"""Fixture reader: how many of the window's JSONL records carry ``key``."""


def read(run, key):
    return sum(1 for r in run.window_records if key in r)
