"""Device time per step outside the Pallas kernels in a model's step
(``glue_s`` of the traced window): the projections, dense attention,
the expert layer, the head, and the value and operand staging of the
fused attention calls."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return 1e3 * r.trace.glue_s / r.steps
