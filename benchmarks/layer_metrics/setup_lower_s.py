"""Set-up spent lowering to MLIR: self time of every ``jit.lower`` of the
startup period. The Pallas kernels are lowered to Mosaic here, in
Python, on every process start: the persistent cache is keyed on the
lowered text."""
from _startup import part


def read(m):
    return part(m, "lower")
