"""The scan kernel's share of its roofline: the least time for the
FLOPs the scan needs a position and layer (the chunk's scores at their
causal half, their product with x, the state's update and read-out) and
for reading x, dt, B and C and writing y once a run, at the rows the
traced fit's ``ssm_rows`` counter gives and the forward runs a step
(two under remat), over the device time of the ``ssd_chunk`` kernel.
The same work whatever implements the scan."""
from _laguna import counters, roofline


def read(m):
    c, w = counters(m), m.get("work") or {}
    rows = (c or {}).get("ssm_rows")
    if not rows or "ssm_scan_flops_per_row" not in w:
        return None
    runs = w["ssm_scan_runs"] * rows
    return roofline(
        m, "ssd_chunk",
        runs * w["ssm_scan_flops_per_row"], runs * w["ssm_scan_bytes_per_row"],
    )
