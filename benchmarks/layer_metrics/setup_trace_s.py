"""Set-up spent tracing: self time of every ``jit.trace`` of the startup
period (an inner function's trace inside its caller's counts once)."""
from _startup import part


def read(m):
    return part(m, "trace")
