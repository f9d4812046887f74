"""Live training telemetry — the per-step stream next to the event log.

The event log (:mod:`.events`) records *what ran*; this module records
*how fast it is running, right now*: one JSON line per training step (or
per planned chunk stream) in ``<run-dir>/steps.jsonl``, beside
``events.jsonl``. The LM train loop and the plan executor feed it; the
``observe top`` dashboard (:mod:`.top`) and :mod:`.report` consume it.

Activation mirrors the event log exactly: a :class:`StepLog` exists only
while an event sink is active, and :func:`active_step_log` is ONE global
read (``events.active()``) returning None on the disabled path — the
per-step hot path pays nothing when observability is off.

Step record schema (one JSON object per line; extra fields free-form):

==================  ====================================================
``ts``              unix time (float, seconds)
``run``             run id (same id as the run's events)
``source``          ``train`` (LM loop) | ``plan`` (chunked executor) |
                    ``solver`` (fused streaming fits) | ``serve``
``step``            step index (1-based, the completed step)
``loss``            host-read scalar loss
``wall_s``          wall-clock of the bracket the rates derive from
``tokens``          tokens this step → ``tokens_per_s``
``flops``           modeled FLOPs → ``tflops_per_s`` and ``mfu``
``mfu``             achieved / peak FLOPs, priced off
                    :data:`keystone_tpu.plan.costs.DEVICE_PEAKS`
``hbm_peak_bytes``  device-memory watermark (when the backend has stats)
==================  ====================================================
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any

from keystone_tpu.observe import events as _events
from keystone_tpu.observe import metrics as _metrics

STEPS_FILE = "steps.jsonl"

# in-memory mirror cap — enough for percentile summaries and the
# dashboard's sparkline window without growing with run length
_MAX_MEMORY_STEPS = 4096

_bind_lock = threading.Lock()
_peak_cache: list = []  # [peak_total_flops | None] memo


def _peak_flops_total() -> float | None:
    """Cluster-visible peak FLOP/s: per-device peak from the planner's
    roofline table × local device count. Memoized. None on the CPU (a
    CPU run records no MFU); an accelerator the table does not hold
    raises rather than being priced as some other device."""
    if _peak_cache:
        return _peak_cache[0]
    import jax

    from keystone_tpu.plan.costs import peak_flops_for

    devs = jax.devices()
    peak = peak_flops_for(devs[0].device_kind)
    if peak is not None:
        peak *= len(devs)
    _peak_cache.append(peak)
    return peak


class StepLog:
    """One run's per-step telemetry sink: ``steps.jsonl`` plus a bounded
    in-memory mirror (the ``--once`` dashboard reads it).

    ``run_dir=None`` gives a memory-only stream. Thread-safe; a failing
    disk write disables the file sink with one warning, same degrade
    rule as :class:`keystone_tpu.observe.events.EventLog`.
    """

    def __init__(self, run_dir: str | None = None, run_id: str | None = None):
        self.run_id = run_id
        self.records: collections.deque = collections.deque(
            maxlen=_MAX_MEMORY_STEPS
        )
        self._lock = threading.Lock()
        self._sink: _events.JsonlSink | None = None
        if run_dir:
            try:
                # size-rotated under KEYSTONE_OBSERVE_MAX_MB: a
                # million-step run must not grow steps.jsonl unbounded
                self._sink = _events.JsonlSink(
                    os.path.join(run_dir, STEPS_FILE), "step telemetry"
                )
            except OSError as e:
                from keystone_tpu.core.logging import get_logger

                get_logger("keystone_tpu.observe").warning(
                    "cannot open %s under %s (%r); step telemetry is "
                    "memory-only for this run",
                    STEPS_FILE,
                    run_dir,
                    e,
                )

    def record(self, source: str, **fields: Any) -> dict:
        rec: dict[str, Any] = {"ts": time.time(), "source": source}
        if self.run_id:
            rec["run"] = self.run_id
        rec.update(fields)
        with self._lock:
            self.records.append(rec)
            if self._sink is not None:
                self._sink.write(rec)
        return rec

    def step(
        self,
        *,
        step: int,
        loss: float | None = None,
        tokens: int | None = None,
        wall_s: float | None = None,
        flops: float | None = None,
        hbm_peak_bytes: int | None = None,
        source: str = "train",
        **extra: Any,
    ) -> dict:
        """Record one completed step, deriving the rate fields the
        dashboard renders: ``tokens_per_s`` from tokens/wall and ``mfu``
        as achieved-vs-peak FLOPs (roofline table in
        :mod:`keystone_tpu.plan.costs`)."""
        fields: dict[str, Any] = {"step": int(step), **extra}
        if loss is not None:
            fields["loss"] = float(loss)
        if wall_s is not None:
            fields["wall_s"] = round(float(wall_s), 6)
        if tokens is not None:
            fields["tokens"] = int(tokens)
            if wall_s:
                fields["tokens_per_s"] = round(tokens / wall_s, 3)
        if flops is not None and wall_s:
            fields["tflops_per_s"] = round(flops / wall_s / 1e12, 6)
            peak = _peak_flops_total()
            if peak:
                fields["mfu"] = round(flops / wall_s / peak, 6)
        if hbm_peak_bytes is not None:
            fields["hbm_peak_bytes"] = int(hbm_peak_bytes)
        reg = _metrics.get_registry()
        reg.gauge("telemetry_last_step", source=source).set(float(step))
        if "tokens_per_s" in fields:
            reg.gauge("telemetry_tokens_per_s", source=source).set(
                fields["tokens_per_s"]
            )
        if "mfu" in fields:
            reg.gauge("telemetry_mfu", source=source).set(fields["mfu"])
        if wall_s is not None:
            reg.timer("telemetry_step_seconds", source=source).observe(
                float(wall_s)
            )
        rec = self.record(source, **fields)
        if source == "train":
            # the anomaly monitor rides the live stream: NaN/spiked
            # loss, step-time drift, HBM growth → `alert` events. Only
            # reachable while a sink is active, so the telemetry-off
            # hot path still pays exactly one global read.
            from keystone_tpu.observe import health as _health

            _health.get_monitor().note_step(
                step=int(step),
                loss=loss,
                wall_s=wall_s,
                hbm_peak_bytes=hbm_peak_bytes,
            )
        return rec

    def close(self) -> None:
        with self._lock:
            if self._sink is not None:
                self._sink.close()
                self._sink = None


def active_step_log() -> StepLog | None:
    """The :class:`StepLog` riding the active event sink, or None.

    The ONLY check the per-step hot paths make: with no sink active this
    is exactly one global read (``events.active()``) and constructs
    nothing — the acceptance bar for telemetry-off overhead."""
    log = _events.active()
    if log is None:
        return None
    sl = log.__dict__.get("_steplog")
    if sl is None:
        with _bind_lock:
            sl = log.__dict__.get("_steplog")
            if sl is None:
                sl = StepLog(log.run_dir, log.run_id)
                log._steplog = sl
    return sl


def reset_peak_cache() -> None:
    """Drop the memoized device peak (tests that fake the backend)."""
    _peak_cache.clear()
