"""Device time per step of the Pallas kernels (``tpu_custom_call``
events) in the traced window."""


def read(r):
    if r.trace is None or r.trace.kernel_s <= 0:
        return None
    return 1e3 * r.trace.kernel_s / r.steps
