"""Least time of a step's work (``work/``, ``peaks.json``) over the
device's busy time per step, kernels and glue together, in percent.
Moving work between a kernel and an XLA operation cannot lift it."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * r.least_s / (r.trace.busy_s / r.steps)
