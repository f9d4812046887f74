"""Device time of the state-space scan's forward kernel a step: the
Pallas call named ``ssd_chunk``, two runs a layer under remat. A program
whose scan is no such kernel has no such op, and the metric is left out."""
from _laguna import kernel_ms_per_step


def read(m):
    return kernel_ms_per_step(m, "ssd_chunk")
