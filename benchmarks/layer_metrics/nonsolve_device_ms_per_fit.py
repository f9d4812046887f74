"""Device busy time outside the solve program, per traced fit: today
featurize, scaling and scoring together, which the trace cannot yet
tell apart."""
from _shared import solve_ms_per_fit


def read(m):
    solve = solve_ms_per_fit(m)
    if solve is None:
        return None
    return 1e3 * m["trace"]["busy_s"] / m["facts"]["traced_fits"] - solve
