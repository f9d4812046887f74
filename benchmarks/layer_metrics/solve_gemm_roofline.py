"""The least time the chip needs for the BCD's matrix products (FLOPs
from shapes over the bf16 peak) over the solve program's device time.
Compute-bound: at 16 rows per column the gemms' bytes need far less."""
from _shared import solve_ms_per_fit


def read(m):
    solve = solve_ms_per_fit(m)
    if not solve or m["peaks"] is None:
        return None
    least_s = m["work"]["solve_gemm_flops_per_fit"] / m["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / (solve / 1e3)
