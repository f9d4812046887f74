"""Plan IR — the small DAG the cost-based planner optimizes and executes.

KeystoneML's optimizer works on the pipeline's operator DAG with a
sampled per-operator profile attached (time, memory, output size); the
TPU-native analog here is a list of :class:`PlanNode` — one per pipeline
node, carrying a :class:`NodeCost` taken from the observe cost-profile
registry or a sampled profiling pass — plus the branch structure of a
multi-consumer fit (several estimators riding one featurization prefix).

The IR is deliberately tiny: a fitted ``Pipeline`` is already a flat,
inspectable node tuple (see :mod:`keystone_tpu.core.pipeline`), so the
plan only needs to add what the tuple can't express — costs, reuse
counts, materialization decisions, and applied rewrites. The optimizer
passes in :mod:`.passes` mutate these flags; :mod:`.executor` runs the
result.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from keystone_tpu.core.pipeline import Cacher, Pipeline, Transformer
from keystone_tpu.observe import events as _events

# The roofline table (peak FLOP/s, HBM B/s, PCIe B/s, ICI B/s per device
# kind) lives in ONE place: :data:`keystone_tpu.plan.costs.DEVICE_PEAKS`
# (the observe report prices its vs_peak column off the same rows).
# ``costs`` imports this module at module level, so the hop back is
# function-local; the module ``__getattr__`` below keeps the historical
# ``plan.ir.DEVICE_PEAKS`` / ``plan.ir.device_peaks`` names importable.
def _device_peaks(device_kind: str | None):
    from keystone_tpu.plan.costs import device_peaks

    return device_peaks(device_kind)


def __getattr__(name: str):
    if name in ("DEVICE_PEAKS", "device_peaks"):
        from keystone_tpu.plan import costs as _costs

        return getattr(_costs, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass
class NodeCost:
    """Per-node cost estimate, normalized per input row.

    ``wall_s`` is a measured per-row apply time when the estimate came
    from a sampled profiling pass (the strongest signal); ``flops`` /
    ``bytes_accessed`` come from the compiler's ``cost_analysis()`` and
    back the roofline fallback when no measurement exists. ``source``
    records where the numbers came from (``profile`` — the observe cost
    registry; ``sampled`` — a fresh profiling pass; ``default`` — no
    information, conservative zeros).

    The comms terms: ``input_bytes`` is what the node reads from its
    predecessor — for the chain's FIRST node that is the host batch that
    must cross PCIe per chunk; ``collective_bytes`` is what a sharded
    execution of the node moves over ICI in collectives (``psum`` of
    partial products etc. — zero for purely row-wise maps, which need
    no cross-shard communication at all).
    """

    flops: float = 0.0
    bytes_accessed: float = 0.0
    output_bytes: float = 0.0
    peak_bytes: float = 0.0
    input_bytes: float = 0.0
    collective_bytes: float = 0.0
    wall_s: float | None = None
    source: str = "default"

    def recompute_s(self, rows: float, device_kind: str | None = None) -> float:
        """Estimated seconds to (re)compute this node over ``rows`` rows."""
        if self.wall_s is not None:
            return self.wall_s * rows
        peaks = _device_peaks(device_kind)
        return max(
            self.flops * rows / peaks.flops,
            self.bytes_accessed * rows / peaks.hbm_bw,
        )

    def h2d_s(self, rows: float, device_kind: str | None = None) -> float:
        """Estimated seconds to move this node's input host→device
        (PCIe) for ``rows`` rows — the staging transfer the executor
        tries to hide behind compute."""
        return self.input_bytes * rows / _device_peaks(device_kind).h2d_bw

    def collective_s(
        self, rows: float, device_kind: str | None = None
    ) -> float:
        """Estimated seconds this node spends in cross-shard collectives
        (ICI psum) when executed sharded over ``rows`` rows."""
        return (
            self.collective_bytes * rows / _device_peaks(device_kind).ici_bw
        )


@dataclasses.dataclass
class PlanNode:
    """One pipeline node inside a plan."""

    label: str
    op: Any  # Transformer (apply nodes) or Estimator (the fit sink)
    cost: NodeCost = dataclasses.field(default_factory=NodeCost)
    reuse: int = 1  # number of downstream consumers of this node's output
    materialize: bool = False  # planner-chosen cache point after this node
    rewritten_from: tuple[str, ...] = ()  # labels the rewrite replaced


@dataclasses.dataclass
class Plan:
    """A planned pipeline: optimized chain + branch structure + decisions.

    ``prefix`` is the (possibly shared) node chain; ``branches`` holds
    per-consumer suffix chains for a multi-branch fit (empty for a plain
    linear pipeline). ``decisions`` is the observable record — every
    rewrite, cache insertion, and chunk choice lands there AND in the
    metrics/event sinks, so a run report shows what the planner did.
    """

    prefix: list[PlanNode]
    branches: list[list[PlanNode]] = dataclasses.field(default_factory=list)
    share_prefix: bool = True
    chunk_size: int | None = None
    prefetch: int = 2
    budget_bytes: int = 0
    device_kind: str | None = None
    rows: int = 0  # rows the costs were normalized against (sample size)
    mesh: Any = None  # jax Mesh for sharded dispatch (None — single device)
    shard: bool = False  # planner chose data-axis sharded dispatch
    stage_depth: int = 2  # staged host→device chunks kept in flight
    fit: Any = None  # FitPlanInfo for a fused streaming fit (fused_fit)
    decisions: list[dict] = dataclasses.field(default_factory=list)

    def decide(self, action: str, **fields: Any) -> dict:
        rec = {"action": action, **fields}
        self.decisions.append(rec)
        return rec

    def pipeline(self) -> Pipeline:
        """The optimized linear chain as a plain ``Pipeline`` (rewrites
        applied, planner cache points as explicit :class:`Cacher` nodes).
        Only valid for single-chain plans."""
        if self.branches:
            raise ValueError("multi-branch plan has no single pipeline form")
        nodes: list[Transformer] = []
        for pn in self.prefix:
            nodes.append(pn.op)
            if pn.materialize and not isinstance(pn.op, Cacher):
                nodes.append(Cacher(name=pn.label))
        return Pipeline.of(*nodes)

    def execute(self, data):
        from keystone_tpu.plan import executor

        return executor.run_plan(self, data)

    def explain(self) -> str:
        """Human-readable plan dump (the ``plan`` CLI renders this)."""
        lines = [
            f"plan: {len(self.prefix)} node(s)"
            + (f" + {len(self.branches)} branch(es)" if self.branches else ""),
            f"  budget: {self.budget_bytes / 2**20:.0f} MiB"
            + (f"  chunk: {self.chunk_size}" if self.chunk_size else "  chunk: -")
            + f"  device: {self.device_kind or 'unknown'}"
            + (
                f"  shard: {dict(self.mesh.shape).get('data', '?')}x data"
                if self.shard and self.mesh is not None
                else ""
            )
            + f"  stage_depth: {self.stage_depth}",
            f"  {'#':>2} {'node':<28} {'flops/row':>10} {'out B/row':>10}"
            f" {'est s':>9} {'reuse':>5} {'cache':>5}",
        ]

        def row(i, pn):
            est = pn.cost.recompute_s(max(self.rows, 1), self.device_kind)
            lines.append(
                f"  {i:>2} {pn.label:<28} {pn.cost.flops:>10.3g}"
                f" {pn.cost.output_bytes:>10.3g} {est:>9.2g}"
                f" {pn.reuse:>5} {'yes' if pn.materialize else '-':>5}"
            )

        if self.fit is not None:
            f = self.fit
            lines.insert(
                1,
                f"  fit: {'fused streaming' if f.fused else 'materialized'}"
                + (
                    f"  d={f.d} k={f.k} gram={f.gram}"
                    if f.fused
                    else f"  ({f.reason or 'see decisions'})"
                ),
            )
        for i, pn in enumerate(self.prefix):
            row(i, pn)
        for b, branch in enumerate(self.branches):
            lines.append(f"  branch {b}:")
            for i, pn in enumerate(branch):
                row(i, pn)
        if self.decisions:
            lines.append("  decisions:")
            for d in self.decisions:
                fields = ", ".join(
                    f"{k}={v}" for k, v in d.items() if k != "action"
                )
                lines.append(f"    - {d['action']}: {fields}")
        else:
            lines.append("  decisions: none (plan == input pipeline)")
        return "\n".join(lines)


def nodes_of(pipe: Transformer) -> list[Transformer]:
    """Flat node list of a Pipeline, or the single transformer itself."""
    if isinstance(pipe, Pipeline):
        return list(pipe.nodes)
    return [pipe]


def chain_from(pipe: Transformer) -> list[PlanNode]:
    """Lift a (fitted) pipeline into an uncosted PlanNode chain."""
    return [
        PlanNode(label=_events.node_label(node, i), op=node)
        for i, node in enumerate(nodes_of(pipe))
    ]
