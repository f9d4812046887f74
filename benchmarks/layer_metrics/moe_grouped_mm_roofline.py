"""The grouped product's share of its roofline: the least time for the
rows the traced fit routed to held experts (three d x width products a
row and pass; the passes the kernels ran: forward, the recomputed
forward under remat, and two products of that size backward; each row's
bytes, and every held expert's matrices read once a layer and pass)
over the device time of the ``gmm`` and ``tgmm`` kernels."""
from _laguna import counters, roofline


def read(m):
    c, w = counters(m), m.get("work") or {}
    if c is None or "moe_flops_per_row" not in w:
        return None
    rows, passes = c["routed_rows"], w["moe_passes"]
    weights = c["steps"] * w["moe_layers"] * w["moe_weight_bytes_per_layer"]
    return roofline(
        m,
        "gmm",
        passes * rows * w["moe_flops_per_row"],
        passes * (rows * w["moe_bytes_per_row"] + weights),
    )
