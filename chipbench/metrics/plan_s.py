"""Host seconds the program's ``ops.BUILD_SECONDS`` counted during set-up
(plan, pack, verify, and a gradient's transposed plan), summed."""


def read(r):
    total = sum(r.build_seconds.values())
    return total if total > 0 else None
