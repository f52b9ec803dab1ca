"""Device time per step of the programs launched under the program's
``*.stage_operands`` spans: lane and row pads of the dense operands,
X's row strips, attention's scaled Q and its gather ``q_ext[row_map]``
(``span_reduce``).  None where the trace holds no program span."""


def read(r):
    spans = getattr(r.trace, "spans", None) or {}
    if not any(name != "(none)" for name in spans):
        return None
    return 1e3 * sum(v["device_s"] for name, v in spans.items()
                     if name.endswith(".stage_operands")) / r.steps
