"""The whole model step's share of the chip's peak: least time of a
step's work at the peaks (``work/``, ``peaks.json``: the larger of
bytes over HBM bandwidth and flops over peak FLOP/s) over the traced
run's time per step, in percent."""


def read(r):
    if r.trace is None or r.steps <= 0:
        return None
    return 100.0 * r.least_s / (r.window_s / r.steps)
