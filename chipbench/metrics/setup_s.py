"""Process start to the first timed step (host clock): structure,
operands, ``compile_*`` (plan, pack, tables), compile-cache loads and
the warm step."""


def read(r):
    return r.setup_s
