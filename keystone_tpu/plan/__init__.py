"""Cost-based pipeline planner (the KeystoneML whole-pipeline optimizer,
TPU-native).

The paper's headline loop — estimate per-operator costs, choose physical
implementations, cache reused intermediates under a memory budget, then
execute — lands here as four small modules:

- :mod:`.ir` — plan IR: node chain + branches with per-node costs,
- :mod:`.costs` — cost attachment from the observe cost-profile
  registry or a sampled profiling pass on a small slice,
- :mod:`.passes` — registered rewrite rules (operator selection,
  generalizing ``core/fusion.py``), greedy automatic materialization
  under ``KEYSTONE_PLAN_BUDGET_MB``, chunk-size selection,
- :mod:`.executor` — jitted segments between materialization points,
  bounded in-flight chunked dispatch, shared-prefix fits.

Entry points::

    plan = plan_pipeline(fitted_pipe, sample=probe)   # build + optimize
    out  = plan.execute(batch)                        # plan-aware run
    out  = execute(fitted_pipe, batch)                # one-shot form
    fitted = fit_shared([chainA, chainB], data, y)    # prefix paid once
    fitted = fit_streaming(chained_est, x, y)         # fused streaming
                                                      # normal-eq fit

Env knobs: ``KEYSTONE_PLAN=1`` opts model entry points into planned
execution; ``KEYSTONE_PLAN_BUDGET_MB`` caps resident cached
intermediates (default 1024); ``KEYSTONE_STAGE_DEPTH`` overrides the
double-buffered host→device staging depth (0 = synchronous);
``KEYSTONE_GRAM_OP`` / ``KEYSTONE_GRAM_INT8_MAX_ERR`` steer the fused
fit's Gram-operator selection (:mod:`.fused_fit`). Every decision is
observable: ``optimize`` events in the run log plus ``plan_*`` /
``plan_transfer_*`` / ``plan_shard_*`` metrics counters.
"""

from __future__ import annotations

import os
from typing import Any, Sequence

import jax

from keystone_tpu.core.pipeline import Transformer
from keystone_tpu.observe import events as _events
from keystone_tpu.plan import costs as _costs
from keystone_tpu.plan import executor as _executor
from keystone_tpu.plan import passes as _passes
from keystone_tpu.plan.ir import NodeCost, Plan, PlanNode, chain_from
from keystone_tpu.plan.executor import apply_shared, fit_shared, run_plan
from keystone_tpu.plan.fused_fit import fit_streaming, plan_fit

ENV_ENABLE = "KEYSTONE_PLAN"
ENV_BUDGET_MB = "KEYSTONE_PLAN_BUDGET_MB"
_DEFAULT_BUDGET_BYTES = 1 << 30

__all__ = [
    "Plan",
    "PlanNode",
    "NodeCost",
    "plan_pipeline",
    "plan_fit",
    "execute",
    "fit_shared",
    "fit_streaming",
    "apply_shared",
    "run_plan",
    "enabled",
    "default_budget_bytes",
]


def enabled() -> bool:
    """The ``KEYSTONE_PLAN`` gate: models route through the planner when
    truthy (unset/0/false/off → the classic paths, bit-for-bit)."""
    return os.environ.get(ENV_ENABLE, "").strip().lower() not in (
        "",
        "0",
        "false",
        "off",
        "no",
    )


def default_budget_bytes() -> int:
    """Residency budget for cached intermediates: the env override, else
    the device's reported memory limit, else 1 GiB."""
    mb = os.environ.get(ENV_BUDGET_MB, "").strip()
    if mb:
        try:
            return max(int(float(mb) * 2**20), 0)
        except ValueError:
            pass
    try:
        stats = jax.devices()[0].memory_stats() or {}
        limit = int(stats.get("bytes_limit", 0))
        if limit > 0:
            return limit
    except Exception:  # noqa: BLE001 — backend without memory stats
        pass
    return _DEFAULT_BUDGET_BYTES


def _device_kind() -> str:
    return jax.devices()[0].device_kind


def plan_pipeline(
    pipe: Transformer,
    sample: Any | None = None,
    *,
    budget_bytes: int | None = None,
    chunk_size: int | None = None,
    n_rows: int | None = None,
    prefetch: int = 2,
    mesh: Any = None,
    stage_depth: int | None = None,
) -> Plan:
    """Build and optimize a plan for a fitted (apply) pipeline.

    ``sample`` drives the profiling pass for nodes the cost registry
    doesn't already know (a bounded slice is taken — pass the real batch
    freely). ``chunk_size`` forces the executor's chunking; otherwise
    the planner picks one from cost estimates when ``n_rows`` (the
    expected execution size) warrants it. ``mesh`` (default: the ambient
    :func:`keystone_tpu.parallel.mesh.use_mesh` mesh) opts the executor
    into data-axis sharded dispatch; the staging pass then also sizes
    the double-buffered host→device transfer depth (``stage_depth`` /
    ``KEYSTONE_STAGE_DEPTH`` override it).

    Self-tuning (both env-gated no-ops by default): with a plan store
    configured (``KEYSTONE_PLAN_STORE``, :mod:`.store`) the learned
    record for this (pipeline fingerprint, device kind) seeds chunk
    size and stage depth — the run starts where the last one converged;
    with the autotuner active (``KEYSTONE_TUNE=1``, :mod:`.tune`) its
    live ``chunk_rows`` knob takes precedence over the store, the
    chosen chunk becomes the knob's seed, and the tuner is bound to the
    store identity so committed improvements persist. Priority:
    explicit argument > live autotuner > stored record > cost model,
    with every seeding recorded as a plan decision (``source=``).
    """
    from keystone_tpu.parallel.mesh import current_mesh
    from keystone_tpu.plan import store as _plan_store
    from keystone_tpu.plan import tune as _tune

    chain = chain_from(pipe)
    fp = _plan_store.fingerprint([pn.label for pn in chain])
    device_kind = _device_kind()
    learned = None
    if _plan_store.store_dir():
        try:
            learned = _plan_store.load(fp, device_kind=device_kind)
        except _plan_store.PlanStoreError as e:
            # refusal is loud but not fatal: plan untuned
            from keystone_tpu.core.logging import get_logger

            get_logger("keystone_tpu.plan").warning("%s", e)
    tuner = _tune.active()
    chunk_req, chunk_source = chunk_size, "requested"
    if chunk_req is None and tuner is not None:
        # fingerprint-scoped: only the pipeline that bound the chunk
        # knob reads it back — another pipeline must not inherit a
        # chunk tuned for a different working set
        live = tuner.chunk_value_for(fp)
        if live:
            chunk_req, chunk_source = int(live), "autotuner"
    if chunk_req is None and learned is not None:
        stored = (learned.get("plan") or {}).get("chunk_size")
        if stored:
            chunk_req, chunk_source = int(stored), "store"
    depth_req, depth_source = stage_depth, "requested"
    if (
        depth_req is None
        and not os.environ.get("KEYSTONE_STAGE_DEPTH", "").strip()
        and learned is not None
    ):
        stored = (learned.get("knobs") or {}).get("stage_depth")
        if stored is None:
            stored = (learned.get("plan") or {}).get("stage_depth")
        if stored is not None:
            depth_req, depth_source = int(stored), "store"
    probe = _costs.slice_probe(sample) if sample is not None else None
    _costs.attach(chain, probe)
    plan = Plan(
        prefix=chain,
        budget_bytes=(
            default_budget_bytes() if budget_bytes is None else budget_bytes
        ),
        device_kind=device_kind,
        rows=_costs._rows(probe) if probe is not None else 0,
        prefetch=prefetch,
        mesh=mesh if mesh is not None else current_mesh(),
    )
    _passes.select_operators(plan)
    # budget decisions are priced at the REAL execution size, not the
    # profiling-sample size — resident bytes scale with rows
    _passes.choose_materialization(plan, rows=n_rows)
    if chunk_req is not None or n_rows is not None:
        _passes.choose_chunk_size(
            plan,
            n_rows or 0,
            requested=chunk_req,
            source=chunk_source,
            shards=_shards(plan),
        )
    _passes.choose_staging(
        plan,
        n_rows or 0,
        requested_depth=depth_req,
        depth_source=depth_source,
    )
    if learned is not None:
        plan.decide(
            "learned",
            fingerprint=fp,
            run=(learned.get("provenance") or {}).get("run"),
            saved_ts=learned.get("saved_ts"),
        )
    if tuner is not None:
        if plan.chunk_size:
            tuner.bind_chunk(plan.chunk_size, fingerprint=fp)
        tuner.bind_store(
            fp,
            device_kind,
            {
                "chunk_size": plan.chunk_size,
                "stage_depth": plan.stage_depth,
                "nodes": [pn.label for pn in plan.prefix],
            },
            # the store was already consulted above — pass the payload
            # through so the hit/mismatch counters count real loads
            record=learned,
        )
    _passes.emit_plan(plan)
    return plan


def _shards(plan: Plan) -> int:
    from keystone_tpu.parallel.mesh import data_axis_size

    return data_axis_size(plan.mesh)


def execute(
    pipe: Transformer,
    data: Any,
    *,
    sample: Any | None = None,
    budget_bytes: int | None = None,
    chunk_size: int | None = None,
    prefetch: int = 2,
    mesh: Any = None,
    stage_depth: int | None = None,
) -> Any:
    """One-shot planned execution: plan ``pipe`` (sampling costs on a
    slice of ``data`` unless a separate ``sample`` is given) and run it —
    sharded over ``mesh``'s data axis when one is given/installed."""
    plan = plan_pipeline(
        pipe,
        sample=data if sample is None else sample,
        budget_bytes=budget_bytes,
        chunk_size=chunk_size,
        n_rows=_costs._rows(data),
        prefetch=prefetch,
        mesh=mesh,
        stage_depth=stage_depth,
    )
    return run_plan(plan, data)


def _assemble_fit_plan(
    chains: Sequence[Any],
    sample: Any | None = None,
    budget_bytes: int | None = None,
    n_rows: int | None = None,
) -> tuple[Plan, list[Any]]:
    """Plan a multi-branch fit: shared-prefix nodes (reuse = number of
    chains on the tail) plus one branch per chain holding its remaining
    prefix nodes. The materialization pass then decides whether the
    shared intermediate earns residency."""
    shared = _executor.shared_prefix_nodes(chains)
    prefix = [
        PlanNode(label=_events.node_label(node, i), op=node)
        for i, node in enumerate(shared)
    ]
    if prefix:
        prefix[-1].reuse = len(chains)
    branches = []
    for chain in chains:
        rest = _executor._prefix_nodes(chain)[len(shared) :]
        branches.append(
            [
                PlanNode(label=_events.node_label(node, len(shared) + i), op=node)
                for i, node in enumerate(rest)
            ]
        )
    probe = _costs.slice_probe(sample) if sample is not None else None
    if probe is not None and prefix:
        out = _costs.sample_chain(prefix, probe)
        for branch in branches:
            _costs.sample_chain(branch, out)
    plan = Plan(
        prefix=prefix,
        branches=branches,
        budget_bytes=(
            default_budget_bytes() if budget_bytes is None else budget_bytes
        ),
        device_kind=_device_kind(),
        rows=_costs._rows(probe) if probe is not None else 0,
    )
    _passes.choose_materialization(plan, rows=n_rows)
    _passes.emit_plan(plan)
    return plan, shared
