"""Set-up spent reading compiled programs from the persistent cache and
loading them: self time of every ``jit.cache_read`` of the startup
period (0 in a cold process)."""
from _startup import part


def read(m):
    return part(m, "cache_read")
