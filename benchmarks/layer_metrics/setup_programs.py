"""Programs the process made before its first window: the number of
``jit.cache_read`` and ``jit.backend_compile`` records of the startup
period."""
from _startup import part


def read(m):
    return part(m, "programs")
