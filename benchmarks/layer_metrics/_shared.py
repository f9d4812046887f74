"""What several per-layer readers share. ``m`` is the measured run:
``trace`` (harness/xplane.py's reduction, or None), ``facts`` (from
the traffic kind), ``sizes``, ``work`` (the adapter's ``ops_and_bytes``),
``programs`` (the configuration's program names) and ``peaks``. A
reader that finds nothing to read returns None."""

from __future__ import annotations

from harness import xplane


def idle_share(m) -> float | None:
    t = m["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def program_seconds(m, role: str) -> float | None:
    t = m["trace"]
    names = m["programs"].get(role)
    if not t or not names:
        return None
    return xplane.programs_matching(t, names)


def solve_ms_per_fit(m) -> float | None:
    s = program_seconds(m, "solve")
    fits = m["facts"].get("traced_fits")
    if s is None or not fits:
        return None
    return 1e3 * s / fits
