"""Largest load of a held expert over the mean load, a step, averaged
over the traced fit's steps (the program's ``fit.counters`` span)."""
from _laguna import counters


def read(m):
    c = counters(m)
    return None if c is None else c.get("load_max_over_mean")
