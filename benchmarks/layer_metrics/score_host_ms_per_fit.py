"""Host time of the traced fit in scoring train and test rows, the
evaluator's read-back included (spans ``fit.score``, ``score.*``),
compiles taken out."""
from _spans import host_ms


def read(m):
    return host_ms(m, "score")
