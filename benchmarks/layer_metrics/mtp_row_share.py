"""Share of the traced fit's token positions that the multi-token
prediction module's loss covered: ``100 x mtp_rows / (steps x batch x
seq)`` of the program's ``fit.counters`` span. 100 while the depth-2
loss reads every position; less where a change trims or drops it. A
program or model without the counter gives nothing."""
from _laguna import counters


def read(m):
    c = counters(m)
    if c is None or not c.get("mtp_rows") or not c.get("steps"):
        return None
    sizes = m.get("sizes") or {}
    if not sizes.get("batch") or not sizes.get("seq"):
        return None
    return 100.0 * c["mtp_rows"] / (c["steps"] * sizes["batch"] * sizes["seq"])
