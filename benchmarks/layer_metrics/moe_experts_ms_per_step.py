"""Device time of the routed experts' grouped products a step: the
Pallas kernels ``gmm`` (forward, recomputed forward, and the backward's
product with the transposed weights) and ``tgmm`` (the weights'
gradients) of the four expert layers. The gathers into expert order and
back are XLA fusions without a name to find them by."""
from _laguna import kernel_ms_per_step


def read(m):
    return kernel_ms_per_step(m, "gmm")
