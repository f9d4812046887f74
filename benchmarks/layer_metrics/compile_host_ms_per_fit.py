"""Host time of the traced fit spent tracing, lowering, compiling and
reading the compile cache (every ``jit.*`` span under the ``fit`` root;
where they overlap, as an inner function's trace inside its caller's
does, the time counts once)."""
from _spans import host_ms


def read(m):
    return host_ms(m, "compile")
