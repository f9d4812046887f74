"""What the ``laguna_xs2`` readers share: device time by kernel name,
the traced fit's steps, and the expert layers' counters of the traced
fit (the program's ``fit.counters`` span). Each gives None where there
is nothing to read: no trace, or a program without the kernel, the span
or the counter.

An op's name in the trace is its HLO line, ``%name = shape op(operands)``
with no metadata (chip run, PR 28): a ``jax.named_scope`` is not in it.
What is: a Pallas custom call is named after the scope or the jitted
function it was called under (``%attn_window.3``, ``%jvp_attn_full_.2``,
``%gmm.7``, ``%tgmm.2``), every other op is ``%fusion.N`` or the like.
So these readers see the kernels, forward and recomputed forward and,
for the grouped product, backward; the attention's blockwise backward
(XLA fusions in a scan) and the gathers around the grouped product
carry no name to find them by. Only the op's own name is matched: its
operands name the ops it reads, a kernel among them."""

from __future__ import annotations

from _spans import records


def steps(m) -> int | None:
    return m["work"].get("steps") if m.get("work") else None


def kernel_seconds(m, kernel: str) -> float | None:
    """Self time of every op whose own name holds ``kernel``, over the
    traced fit."""
    t = m["trace"]
    if not t:
        return None
    return sum(
        s for line, s in t["ops_s"].items() if kernel in line.split(" = ")[0]
    ) or None


def kernel_ms_per_step(m, kernel: str) -> float | None:
    s, n = kernel_seconds(m, kernel), steps(m)
    if s is None or not n:
        return None
    return 1e3 * s / n


def counters(m) -> dict | None:
    recs = records(m)
    found = [r for r in recs or () if r["name"] == "fit.counters"]
    return found[-1] if found else None


def roofline(m, kernel: str, flops: float, bytes_: float) -> float | None:
    """The least time the chip needs (the larger of operations over the
    bf16 peak and bytes over the HBM peak) over the kernel's device time."""
    s = kernel_seconds(m, kernel)
    if s is None or m["peaks"] is None:
        return None
    least = max(
        flops / m["peaks"]["bf16_flops_per_s"],
        bytes_ / m["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / s
