"""device_idle_share (device): the share of the traced window in which no
operation ran on the chip, averaged over the cell's chips."""


def read(run):
    if run.trace is None or not run.chips:
        return None
    lo, hi = run.trace.window
    busy = sum(run.trace.busy_ns(c) for c in run.chips) / len(run.chips)
    return 100.0 * (1.0 - busy / (hi - lo))
