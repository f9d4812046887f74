"""The single registry of every structured event kind this codebase emits.

Every ``event:`` kind that can appear in a run's ``events.jsonl`` is
declared here — the schema README documents, the report/top renderers
switch on, and the drift test (``tests/test_spans.py``) greps emit sites
against. Adding an emit site with a new kind and forgetting to declare
it fails CI, so the consumer surfaces (report, top, jq pipelines) can
rely on this table being the whole vocabulary.

Stdlib-only and import-free on purpose: :mod:`.events` calls
:func:`note` on every emit (one set lookup; unknown kinds warn once per
process, they are never dropped — observability must degrade, not
censor).
"""

from __future__ import annotations

#: kind → one-line description (the contract; see each emitter's module)
EVENT_KINDS: dict[str, str] = {
    "run_start": "run activation bracket open (events.py)",
    "run_end": "run bracket close, wall + ok/failed status (events.py)",
    "node": "one pipeline-node call: phase, wall, status (pipeline "
    "hooks, observe/instrument.py)",
    "span": "one log_time bracket: label + wall (core/logging.py); "
    "causal trace spans live in spans.jsonl, not here",
    "phase": "coarse run phase wall (model mains)",
    "optimize": "a planner / fusion / staging decision (plan/passes.py, "
    "core/fusion.py, core/staging.py)",
    "resilience": "a survived resilience decision: fault, retry, guard, "
    "preemption (resilience/emit.py); fleet routing/failover/breaker/"
    "restart decisions ride the same kind with action=fleet_* "
    "(serve/fleet.py)",
    "cluster": "a membership decision: heartbeat, verdict, re-mesh "
    "(resilience/cluster.py)",
    "serve": "serving lifecycle: start/stop, model, port "
    "(serve/server.py)",
    "device_memory": "per-device HBM watermark sample "
    "(observe/devices.py)",
    "trace_window": "a programmatic profiler window opened/closed "
    "(observe/tracing.py)",
    "metrics_rollup": "multihost metrics merge completed "
    "(parallel/multihost.py)",
    "alert": "an anomaly-monitor verdict: step-time drift, loss spike, "
    "HBM growth, deadline miss / shed rate, feature drift "
    "(observe/health.py); SLO burn-rate firing/cleared transitions "
    "with trace exemplars (observe/slo.py, phase=slo)",
    "model_swap": "online-learning model lifecycle: hot-swap with "
    "old/new version ids, rollback of a failed candidate, shadow "
    "start/stop (learn/swap.py, serve/server.py)",
    "refit": "a refit-daemon decision: chunk folded/skipped, versioned "
    "model published, reload notify (learn/refit.py)",
    "tune": "an autotuner decision: knob adjust/commit/revert/hold/load "
    "with the current knob snapshot and window goodput (plan/tune.py)",
    "collector": "a fleet-collector cycle summary: targets scraped/"
    "failed, points ingested, run dirs tailed, SLO verdicts firing "
    "(observe/collector.py); SLO burn-rate transitions ride the "
    "'alert' kind with phase=slo (observe/slo.py)",
    "chaos": "a chaos-campaign lifecycle record: campaign_start with "
    "the compiled fault schedule, process-level chaos_action steps, "
    "and the final verdict with per-invariant PASS/FAIL "
    "(resilience/chaos.py)",
}

_warned: set[str] = set()


def declared() -> frozenset[str]:
    """Every registered event kind (the drift test's ground truth)."""
    return frozenset(EVENT_KINDS)


def note(kind: str) -> bool:
    """Record that ``kind`` is being emitted; warns ONCE per unknown
    kind per process and returns whether it is declared. Never raises —
    an undeclared kind is schema drift to fix, not a reason to lose the
    record."""
    if kind in EVENT_KINDS:
        return True
    if kind not in _warned:
        _warned.add(kind)
        from keystone_tpu.core.logging import get_logger

        get_logger("keystone_tpu.observe").warning(
            "event kind %r is not declared in observe/schema.py — "
            "add it to EVENT_KINDS (schema drift)",
            kind,
        )
    return False
