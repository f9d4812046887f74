"""Windows the expert layers ran beyond each layer's first, a step:
``extra_windows / steps`` of the traced fit's counters (the program's
``fit.counters`` span), how often the path that drops no routed row
engages where a layer's rows routed here outgrow one window
(``ops/moe.py``)."""
from _laguna import counters


def read(m):
    c = counters(m)
    if c is None or "extra_windows" not in c or not c.get("steps"):
        return None
    return c["extra_windows"] / c["steps"]
