"""Share of the traced fit's wall that only the ``fit`` root span
covers: host code no layer's span sees yet."""
from _spans import layer_ms, records


def read(m):
    recs = records(m)
    if recs is None:
        return None
    ms = layer_ms(recs)
    return 100.0 * ms["uncovered"] / ms["wall"] if ms["wall"] else None
