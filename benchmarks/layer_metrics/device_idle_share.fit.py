"""Share of the traced fit in which no op ran on the device (mean over chips)."""
from _shared import idle_share as read  # noqa: F401
