"""Cost attachment: join the plan IR against operator profiles.

Two sources, in preference order (KeystoneML samples operator profiles
at runtime; the TPU compiler hands most of that over statically):

1. The observe cost-profile registry
   (:mod:`keystone_tpu.observe.cost`) — profiles recorded by an earlier
   instrumented run of the same pipeline, keyed by the shared node
   label.
2. A sampled profiling pass: apply each node to a small probe slice,
   measuring wall time and asking the compiled program for
   ``cost_analysis()`` / ``memory_analysis()``. Bounded by the probe
   size; the probe feeds forward so every node is costed on the shapes
   it actually sees.

All figures are normalized per input row so a plan sampled on 256 rows
prices a 1M-row execution.
"""

from __future__ import annotations

import time
from typing import Any, NamedTuple

import jax
import numpy as np

from keystone_tpu.observe import cost as _cost
from keystone_tpu.plan.ir import NodeCost, PlanNode

class DevicePeaks(NamedTuple):
    """One device kind's published peaks, per chip."""

    flops: float  # bf16 MXU FLOP/s
    hbm_bw: float  # HBM bytes/s
    h2d_bw: float  # host→device bytes/s over PCIe
    ici_bw: float  # collective bytes/s over ICI
    int8_ops: float  # int8 MXU OP/s
    # Mosaic scoped-VMEM limit the Pallas kernels request; None keeps
    # the compiler's default (16 MiB)
    vmem_limit: int | None = None


# Roofline peaks per device kind — THE single home (``observe/report.py``
# and ``plan/ir.py`` re-export from here, so the report's vs_peak column
# and the planner's recompute/transfer estimates can never quote
# different chips), keyed by a ``device_kind`` substring. Source of the
# TPU rows: Google Cloud TPU documentation, system-architecture pages
# ("TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s HBM, 1,600
# Gbit/s ICI; "TPU v4": 275 TFLOP/s bf16 or int8, 1,200 GB/s; "TPU v5p":
# 459 TFLOP/s bf16, 918 TOP/s int8, 2,765 GB/s). The one-chip v5e
# reports ``device_kind`` "TPU v5 lite" (chip run, PR 21); only that row
# has been checked against hardware. The f32 MXU rate is lower, so f32
# workloads report conservative MFU. The "cpu" row is coarse: the
# planner only compares relative magnitudes there, and nothing prints a
# utilization against it (``peak_flops_for`` returns None off-TPU). A
# device that is not in the table is an error, never priced as a CPU.
DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "cpu": DevicePeaks(5e10, 2e10, 2e10, 2e10, 5e10),
    "v4": DevicePeaks(2.75e14, 1.2e12, 3.2e10, 3e11, 2.75e14),
    "v5 lite": DevicePeaks(
        1.97e14, 8.19e11, 3.2e10, 2e11, 3.93e14, vmem_limit=96 << 20
    ),
    "v5p": DevicePeaks(4.59e14, 2.765e12, 3.2e10, 4.8e11, 9.18e14),
}


def device_peaks(device_kind: str | None = None) -> DevicePeaks:
    """The peaks row for a jax ``device_kind`` string (substring match,
    case-insensitive); ``None`` means the device this process runs on.
    Raises for a kind the table does not hold — add its row with the
    source of the figures."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    for key, peaks in DEVICE_PEAKS.items():
        if key in kind:
            return peaks
    raise ValueError(
        f"device kind {device_kind!r} is not in "
        "keystone_tpu.plan.costs.DEVICE_PEAKS; add a row with its "
        "published peaks and their source"
    )


def int8_gram_speedup(device_kind: str | None = None) -> float:
    """int8-vs-bf16 MXU rate for a ``device_kind`` — the Gram-operator
    selection's cost basis (plan/fused_fit.py): 2× on v5e/v5p, 1× on v4
    and the CPU, so the planner never chooses the quantized Gram where
    it cannot win."""
    peaks = device_peaks(device_kind)
    return peaks.int8_ops / peaks.flops


def peak_flops_for(device_kind: str | None) -> float | None:
    """bf16 peak FLOP/s for an accelerator ``device_kind``, or None for
    the CPU and for a run that recorded no kind — the report's roofline
    basis."""
    if not device_kind or "cpu" in device_kind.lower():
        return None
    return device_peaks(device_kind).flops


def _rows(batch: Any) -> int:
    leaves = jax.tree_util.tree_leaves(batch)
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape:
            return int(shape[0])
    return 1


def _out_bytes(out: Any) -> float:
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(out):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None:
            size = getattr(leaf, "size", 0)
            itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
            nbytes = size * itemsize
        total += float(nbytes)
    return total


def cost_from_profile(profile: dict, rows: int) -> NodeCost:
    """A :class:`NodeCost` from one observe cost-registry profile entry
    (``cost_profiles.json`` schema), normalized per row."""
    rows = max(rows, 1)
    if not profile or "error" in profile:
        return NodeCost()
    return NodeCost(
        flops=float(profile.get("flops", 0.0)) / rows,
        bytes_accessed=float(profile.get("bytes_accessed", 0.0)) / rows,
        output_bytes=float(profile.get("output_bytes", 0.0)) / rows,
        peak_bytes=float(profile.get("peak_bytes", 0.0)) / rows,
        input_bytes=float(profile.get("input_bytes", 0.0)) / rows,
        collective_bytes=float(profile.get("collective_bytes", 0.0)) / rows,
        source="profile",
    )


def _profile_rows(profile: dict) -> int | None:
    """Rows the profile was recorded on, parsed from its input shapes
    (``"float32[2048, 784]"``) so normalization uses the profile's own
    batch size, not the planner's probe size."""
    shapes = profile.get("input_shapes") or []
    for s in shapes:
        lb = s.find("[")
        if lb < 0:
            continue
        head = s[lb + 1 :].split(",")[0].rstrip("]").strip()
        if head.isdigit():
            return int(head)
    return None


def from_registry(chain: list[PlanNode], rows: int) -> int:
    """Fill chain costs from the process cost registry where labels
    match; returns how many nodes were costed."""
    registry = _cost.get_cost_registry()
    hit = 0
    for pn in chain:
        profile = registry.get(pn.label)
        if profile and "error" not in profile:
            pn.cost = cost_from_profile(
                profile, _profile_rows(profile) or rows
            )
            hit += 1
    return hit


def sample_chain(chain: list[PlanNode], probe: Any) -> Any:
    """Sampled profiling pass: cost every un-costed node of ``chain`` on
    ``probe`` (feeding each node's output forward), measuring eager wall
    time and attaching the compiler's FLOPs/bytes. Returns the final
    output so multi-branch callers can keep feeding suffix chains.

    A node the sample can't run (host-side op on a probe it rejects)
    keeps its default cost rather than aborting the plan — the planner
    then simply has no basis to prefer rewriting/caching it.
    """
    rows = max(_rows(probe), 1)
    for pn in chain:
        if pn.cost.source != "default":
            # registry-costed already: only advance the probe — no
            # compile/cost-analysis pass for nodes the registry covers
            try:
                probe = pn.op(probe)
            except Exception:  # noqa: BLE001 — can't feed further nodes
                return probe
            continue
        in_bytes = _out_bytes(probe) / rows
        try:
            profile = _cost.analyze(lambda n, b: n(b), pn.op, probe)
            t0 = time.perf_counter()
            out = jax.block_until_ready(pn.op(probe))
            wall = time.perf_counter() - t0
        except Exception:  # noqa: BLE001 — uncostable node, keep defaults
            return probe
        pn.cost = cost_from_profile(profile, rows)
        pn.cost.wall_s = wall / rows
        pn.cost.source = "sampled"
        if not pn.cost.output_bytes:
            pn.cost.output_bytes = _out_bytes(out) / rows
        # the node's input is the probe it just consumed — for the
        # chain's first node that is the host batch crossing PCIe, the
        # basis of the staging pass's transfer-vs-compute comparison
        pn.cost.input_bytes = in_bytes
        probe = out
    return probe


def attach(
    chain: list[PlanNode], sample: Any | None, rows_hint: int | None = None
) -> None:
    """Cost a chain: registry profiles first, sampled pass for the rest."""
    rows = rows_hint or (_rows(sample) if sample is not None else 1)
    from_registry(chain, rows)
    if sample is not None and any(
        pn.cost.source == "default" for pn in chain
    ):
        sample_chain(chain, sample)


def slice_probe(data: Any, rows: int = 256) -> Any:
    """A bounded probe slice of ``data`` for the sampling pass."""
    n = _rows(data)
    if n <= rows:
        return data
    if isinstance(data, (np.ndarray, jax.Array)):
        return data[:rows]
    return jax.tree_util.tree_map(lambda leaf: leaf[:rows], data)
