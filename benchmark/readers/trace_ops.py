"""Reader over the device trace: time of the ops whose HLO text matches
``pattern``, on chip 0, inside the steady window of the traced steps.

``per``: ``step_ms`` (milliseconds per step) or ``busy_share`` (percent of
the chip's busy time). None when there is no trace or nothing matches."""


def read(run, pattern, per):
    steady = run.steady()
    if not steady:
        return None
    chip, window, periods = steady
    t = chip.matching_seconds(pattern, window)
    if t <= 0:
        return None
    if per == "step_ms":
        return 1e3 * t / periods
    if per == "busy_share":
        return 100.0 * t / chip.busy_s(window)
    raise ValueError(f"unknown per {per!r}")
