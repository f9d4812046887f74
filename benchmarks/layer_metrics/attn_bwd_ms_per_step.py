"""Device time of the attention backward kernel a step: the Pallas call
named ``attn_bwd`` after its scope, one run a layer. A program whose
backward is no kernel has no such op, and the metric is left out."""
from _laguna import kernel_ms_per_step


def read(m):
    return kernel_ms_per_step(m, "attn_bwd")
