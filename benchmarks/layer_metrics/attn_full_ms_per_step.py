"""Device time of the full layers' attention kernel a step: the Pallas
forward (``attn_full``), run twice a layer under remat. The blockwise
backward is XLA fusions without a name to find them by."""
from _laguna import kernel_ms_per_step


def read(m):
    return kernel_ms_per_step(m, "attn_full")
