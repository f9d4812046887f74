"""Set-up spent bringing the backend up: self time of
``runtime.init_backend`` (platform, compile cache, ``jax.devices()``)."""
from _startup import part


def read(m):
    return part(m, "backend")
