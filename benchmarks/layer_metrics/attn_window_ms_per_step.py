"""Device time of the window layers' attention kernel a step: the
Pallas forward (``attn_window``), run twice a layer under remat. The
blockwise backward is XLA fusions without a name to find them by."""
from _laguna import kernel_ms_per_step


def read(m):
    return kernel_ms_per_step(m, "attn_window")
