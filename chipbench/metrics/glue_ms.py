"""Device time per step outside the Pallas kernels: busy time less
kernel time in the traced window (gathers, pads, concatenations, the
SDDMM chunks, every other XLA operation)."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return 1e3 * r.trace.glue_s / r.steps
