"""Device time inside the solve program (``_bcd_fit``), per traced fit."""
from _shared import solve_ms_per_fit as read  # noqa: F401
