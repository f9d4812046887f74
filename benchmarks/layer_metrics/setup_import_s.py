"""Set-up before the backend: from the process's start to the start of
``runtime.init_backend`` (interpreter, ``import jax``, the harness's and
the program's imports)."""
from _startup import part


def read(m):
    return part(m, "import")
