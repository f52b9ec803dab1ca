"""Device time per step of the programs launched under the program's
``*.unpermute`` spans: the output's inverse-permutation gather
``y_ws[inv_perm]`` (``span_reduce``).  None where the trace holds no
program span."""


def read(r):
    spans = getattr(r.trace, "spans", None) or {}
    if not any(name != "(none)" for name in spans):
        return None
    return 1e3 * sum(v["device_s"] for name, v in spans.items()
                     if name.endswith(".unpermute")) / r.steps
