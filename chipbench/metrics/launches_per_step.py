"""Device programs per step launched under any program span (in a
forward, every one of them lies inside the call's ``*.call`` span): the
launches that jitting the eager call path would fold together
(``span_reduce``).  None where the trace holds no program span."""


def read(r):
    spans = getattr(r.trace, "spans", None) or {}
    if not any(name != "(none)" for name in spans):
        return None
    return sum(v["launches"] for name, v in spans.items()
               if name != "(none)") / r.steps
