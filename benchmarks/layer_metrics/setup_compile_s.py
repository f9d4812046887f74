"""Set-up spent in the backend compiler: self time of every
``jit.backend_compile`` of the startup period (0 in a warm process)."""
from _startup import part


def read(m):
    return part(m, "compile")
