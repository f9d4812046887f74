"""The window attention kernel's share of its roofline: the least time
for the score and value products inside the window, once a forward run
(two a layer under remat), and for reading q, K and V and writing the
output, over the device time of the ``attn_window`` kernel."""
from _laguna import roofline, steps


def read(m):
    w, n = m.get("work") or {}, steps(m)
    if not n or "attn_window_kernel_flops_per_step" not in w:
        return None
    return roofline(
        m,
        "attn_window",
        n * w["attn_window_kernel_flops_per_step"],
        n * w["attn_window_kernel_bytes_per_step"],
    )
