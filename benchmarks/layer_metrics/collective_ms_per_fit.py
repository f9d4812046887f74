"""Device time of the traced fit's all-reduces (self time of every op
whose name holds ``all-reduce``, mean over chips): the sharded fit's
Grams and moments summed over the ``data`` axis. A one-chip trace has
none and gives None."""
from harness import xplane


def read(m):
    t = m["trace"]
    fits = m["facts"].get("traced_fits")
    if not t or not fits:
        return None
    s = xplane.ops_matching(t, "all-reduce")
    return 1e3 * s / fits if s else None
