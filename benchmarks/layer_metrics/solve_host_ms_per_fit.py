"""Host time of the traced fit in the solve: dispatching ``_bcd_fit``
and waiting for it (span ``fit.solve``), compiles taken out."""
from _spans import host_ms


def read(m):
    return host_ms(m, "solve")
