"""Set-up spent in the first fit itself: its wall less the ``jit.*`` time
inside it (making the model, loading programs onto the chip, the steps
themselves)."""
from _startup import part


def read(m):
    return part(m, "first_run")
