"""Reader: peak device memory of the fullest chip after the window, in GiB:
``peak_bytes_in_use`` plus ``peak_bytes_reserved`` of ``memory_stats()``
(``harness.peak_bytes`` says why both). None where the backend reports none."""

import harness


def read(run):
    peak = max((harness.peak_bytes(s) for s in run.memory_stats), default=0)
    return peak / 2 ** 30 if peak else None
