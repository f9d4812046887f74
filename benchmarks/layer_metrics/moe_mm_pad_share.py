"""Share of the rows the grouped product ran over that are no routed
row: the product visits whole row tiles, so a held expert whose rows
end inside a tile pays for the rest of it. ``100 x (mm_rows -
routed_rows) / mm_rows`` of the traced fit's counters (the program's
``fit.counters`` span)."""
from _laguna import counters


def read(m):
    c = counters(m)
    if c is None or not c.get("mm_rows"):
        return None
    return 100.0 * (c["mm_rows"] - c["routed_rows"]) / c["mm_rows"]
