"""Host seconds of the model's sparse-attention set-up: building the
sattn slots' window masks and ``compile_sparse_attention`` of them
(``ops.BUILD_SECONDS["sattn_mask"]``).  None where the program counts
no such build."""


def read(r):
    s = r.build_seconds.get("sattn_mask", 0.0)
    return s if s > 0 else None
