"""Host time of the traced fit in featurizing: drawing the banks,
dispatching cosine features and scaling, the scaler's moments, the label
indicators and the wait for the device at the phase's end (spans
``fit.featurize*``, ``featurize.*``, ``fit.labels``), compiles taken out."""
from _spans import host_ms


def read(m):
    return host_ms(m, "featurize")
