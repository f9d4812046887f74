"""Share of the positions the state-space layers scanned in the traced
fit that the ``ssd_chunk`` kernel scanned (``100 x ssm_kernel_rows /
ssm_rows`` of the program's ``fit.counters`` span): 100 where every
layer's scan ran in the kernel on the mixer's own layout, 0 where it
fell back to the ``jax.numpy`` chunks."""
from _laguna import counters


def read(m):
    c = counters(m)
    if c is None or not c.get("ssm_rows") or "ssm_kernel_rows" not in c:
        return None
    return 100.0 * c["ssm_kernel_rows"] / c["ssm_rows"]
