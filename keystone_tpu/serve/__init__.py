"""Online serving: a fitted pipeline (or LM) becomes a service.

Everything else in the framework is batch — KeystoneML's fitted
pipelines stop at ``apply`` (PAPER.md §1). This package is the request
path the ROADMAP's "heavy traffic" north star needs, built on the
substrate the earlier subsystems laid down:

- :mod:`.export` — a fitted pipeline or LM as an **AOT-compiled**
  apply: plan-optimized (``plan/`` operator selection), lowered and
  compiled per batch *bucket* ahead of traffic, warm-started from the
  persistent compilation cache (``core/runtime.py``) so a server
  cold-starts in seconds, not minutes.
- :mod:`.queue` — an async request queue with **SLO-aware
  micro-batching**: requests coalesce up to a latency deadline
  (``KEYSTONE_SERVE_DEADLINE_MS``), pad to the nearest compiled bucket,
  and dispatch as one program. The clock is injectable, so every
  batching decision unit-tests without sleeping (the
  ``resilience/retry.py`` discipline).
- :mod:`.decode_loop` — **continuous batching** for LM generation: a
  fixed slot pool where finished sequences retire and queued prompts
  join *per decode step*, so aggregate tokens/s scales with concurrency
  instead of serializing streams (the multiplier on the int8-Pallas
  single-stream decode rate).
- :mod:`.server` — a minimal stdlib HTTP/JSON front end
  (``python -m keystone_tpu serve <model> [--port N]``) wired into the
  resilience fault sites (``serve.drop`` / ``serve.slow_request``), a
  request-path watchdog, and ``observe/`` per-request telemetry
  (latency percentiles via the Timer reservoir, queue-depth /
  batch-fill gauges, a serving panel in ``observe top``).
- :mod:`.fleet` — the **fault-tolerant tier** over N such servers
  (``python -m keystone_tpu fleet``): health-aware least-loaded
  routing, per-request failover + circuit breakers + optional hedging,
  bounded admission with load shedding, replica supervision with
  relaunch, and zero-downtime rolling restarts over the SIGTERM-drain
  contract (``fleet restart``).
"""

from __future__ import annotations

from keystone_tpu.serve.decode_loop import DecodeLoop
from keystone_tpu.serve.export import ExportedApply, export_lm, export_pipeline
from keystone_tpu.serve.queue import MicroBatcher, RequestShed, ServeFuture

__all__ = [
    "DecodeLoop",
    "ExportedApply",
    "MicroBatcher",
    "RequestShed",
    "ServeFuture",
    "export_lm",
    "export_pipeline",
]
