"""Device time per step of the fused sparse-attention kernel: the
``device_ops`` entries of the traced window whose label starts with
``attn_fused`` (``attn_fused``, ``attn_fused_staged``), summed.  None
where the trace holds no such kernel."""


def read(r):
    if r.trace is None:
        return None
    s = sum(sec for label, sec in r.trace.device_ops
            if label.startswith("attn_fused"))
    return 1e3 * s / r.steps if s > 0 else None
