"""Reader over the device trace: the idle share of the steady window of the
traced steps, in percent; the largest over the chips."""


def read(run):
    steady = [run.steady(c.index) for c in (run.trace.chips if run.trace else [])]
    shares = [chip.idle_share(window) for chip, window, _ in filter(None, steady)]
    return 100.0 * max(shares) if shares else None
