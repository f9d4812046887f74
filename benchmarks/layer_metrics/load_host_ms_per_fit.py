"""Host time of the traced fit in the loader: drawing (or reading) the
corpus and putting it on the device (spans ``fit.load``, ``fit.h2d``)."""
from _spans import host_ms


def read(m):
    return host_ms(m, "load")
