"""Reader over the device trace: milliseconds per step of collective ops on
chip 0 (``which``: ``total``), or of the part of them during which no other
op ran on that chip (``exposed``). None on a trace without collectives."""


def read(run, which):
    steady = run.steady()
    if not steady:
        return None
    chip, window, periods = steady
    total, exposed = chip.collectives(window)
    if total <= 0:
        return None
    return 1e3 * {"total": total, "exposed": exposed}[which] / periods
