"""Rows the expert layers put through their grouped products over the
rows routed to their held experts: ``dispatch_rows / routed_rows`` of
the traced fit's counters (the program's ``fit.counters`` span). Every
one of the ``tokens x top_k`` rows moves where a layer holds a large
share of the experts; a window of the sorted rows, as many times as the
rows routed here need, where it holds a small one (``ops/moe.py``)."""
from _laguna import counters


def read(m):
    c = counters(m)
    if c is None or not c.get("dispatch_rows") or not c.get("routed_rows"):
        return None
    return c["dispatch_rows"] / c["routed_rows"]
