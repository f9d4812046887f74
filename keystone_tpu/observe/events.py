"""Structured run-event log — the per-operator execution record.

KeystoneML's optimizer is driven by per-operator profiles sampled during
execution; Spark's event log + UI is where those observations live. The
TPU-native analog is this module: every pipeline node call (and coarse
run phase) becomes one JSON line in ``<dir>/<run-id>/events.jsonl`` so a
cost model, a report renderer, or plain ``jq`` can consume the run.

Activation is env-gated and near-zero cost when off:

- ``KEYSTONE_OBSERVE_DIR=/path`` — every process that touches the
  pipeline DSL appends events under a fresh run directory there.
- :func:`run` — explicit, scoped activation (the CLI launcher and
  tests use this); restores the previous sink on exit.
- disabled — :func:`active` is one module-global read returning None,
  and the pipeline hooks take their plain fast path.

Event schema (one JSON object per line; fields beyond these are free-form):

==============  =========================================================
``ts``          unix time (float, seconds)
``run``         run id (shared by all events of one run)
``event``       ``run_start`` | ``run_end`` | ``node`` | ``span`` |
                ``phase`` | ``optimize``
``node``        node label (``node`` events), e.g. ``01:BlockLinearMapper``
``phase``       ``fit`` | ``apply`` | ``compile`` (first traced call)
``wall_s``      wall-clock duration of the bracket
``status``      ``ok`` | ``failed`` (+ ``error`` repr when failed)
==============  =========================================================
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
import uuid
from typing import Any, Iterator

from keystone_tpu.observe.schema import note as _schema_note

ENV_DIR = "KEYSTONE_OBSERVE_DIR"
ENV_MAX_MB = "KEYSTONE_OBSERVE_MAX_MB"
EVENTS_FILE = "events.jsonl"

# in-memory mirror cap: a runaway loop must not grow the host heap
# without bound just because observability is on
_MAX_MEMORY_RECORDS = 100_000


def max_bytes_from_env() -> int | None:
    """Size cap for the high-rate JSONL streams (``steps.jsonl`` /
    ``spans.jsonl``): ``KEYSTONE_OBSERVE_MAX_MB`` megabytes per file
    before rotation, None = unbounded (the default — events.jsonl is
    never rotated, a report needs its run_start/run_end brackets)."""
    raw = os.environ.get(ENV_MAX_MB, "").strip()
    if raw:
        try:
            mb = float(raw)
            if mb > 0:
                return int(mb * 2**20)
        except ValueError:
            pass
    return None


def node_label(node: Any, index: int | None = None) -> str:
    """Stable display label for a pipeline node.

    Shared by the pipeline hooks, :mod:`.instrument`, and :mod:`.cost` so
    wall-time events and cost profiles join on the same key. The index
    prefix keeps two like-typed nodes at different positions distinct.
    """
    name = getattr(node, "name", None)
    if not name or not isinstance(name, str):
        name = type(node).__name__
    return f"{index:02d}:{name}" if index is not None else name


def _encode(rec: dict) -> str | None:
    """One record → one JSONL line (``default=repr``: a non-JSON field
    is a per-record problem, stringify it rather than losing the
    record; a circular reference skips the record → None)."""
    try:
        return json.dumps(rec, default=repr)
    except ValueError:  # circular reference: skip this record
        return None


def write_record(fh, rec: dict, sink_name: str):
    """Serialize ``rec`` and append it to JSONL sink ``fh`` — the ONE
    home of the write-or-degrade contract shared by the event log and
    the per-record streams (an OSError disables the sink with one
    warning). Returns ``fh``, or None when the sink must be disabled.
    The caller holds its own lock."""
    line = _encode(rec)
    if line is None:
        return fh
    try:
        fh.write(line + "\n")
    except OSError as e:
        from keystone_tpu.core.logging import get_logger

        get_logger("keystone_tpu.observe").warning(
            "%s write failed (%r); file sink disabled", sink_name, e
        )
        return None
    return fh


class JsonlSink:
    """An append-only JSONL file with write-or-degrade semantics and
    size-based rotation — the sink behind the high-rate streams
    (``steps.jsonl``, ``spans.jsonl``), which otherwise grow without
    bound on long runs.

    When a write would push the file past ``max_bytes``
    (``KEYSTONE_OBSERVE_MAX_MB``; None = unbounded), the current file
    is renamed to ``<path>.1`` (replacing the previous generation) and
    a fresh file is started — so on-disk usage is bounded by ~2x the
    cap, and a reader always sees the newest records. The incremental
    tailer (:class:`keystone_tpu.observe.top.Tail`) detects the
    truncation and restarts; the tolerant reader (:func:`read_jsonl`)
    already survives any torn seam. NOT thread-safe — the owning log
    holds its own lock around :meth:`write`."""

    def __init__(
        self, path: str, sink_name: str, max_bytes: int | None = None
    ):
        self.path = path
        self.sink_name = sink_name
        self.max_bytes = (
            max_bytes_from_env() if max_bytes is None else max_bytes
        )
        self._fh = open(path, "a", buffering=1)  # noqa: SIM115 — run-lifetime
        self._size = self._fh.tell()

    def _rotate(self) -> None:
        try:
            self._fh.close()
            os.replace(self.path, self.path + ".1")
            self._fh = open(  # noqa: SIM115 — run-lifetime
                self.path, "a", buffering=1
            )
            self._size = 0
        except OSError as e:
            from keystone_tpu.core.logging import get_logger

            get_logger("keystone_tpu.observe").warning(
                "%s rotation failed (%r); file sink disabled",
                self.sink_name,
                e,
            )
            self._fh = None

    def write(self, rec: dict) -> None:
        if self._fh is None:
            return
        line = _encode(rec)
        if line is None:
            return
        # size in encoded BYTES (the unit the cap and tell() use) — a
        # code-point count under-measures multi-byte records and would
        # rotate late
        nbytes = len(line.encode("utf-8")) + 1
        if (
            self.max_bytes
            and self._size
            and self._size + nbytes > self.max_bytes
        ):
            self._rotate()
            if self._fh is None:
                return
        try:
            self._fh.write(line + "\n")
            self._size += nbytes
        except OSError as e:
            from keystone_tpu.core.logging import get_logger

            get_logger("keystone_tpu.observe").warning(
                "%s write failed (%r); file sink disabled",
                self.sink_name,
                e,
            )
            self._fh = None

    def close(self) -> None:
        if self._fh is not None:
            try:
                self._fh.close()
            except OSError:
                pass
            self._fh = None


class EventLog:
    """A single run's event sink: JSONL file plus an in-memory mirror.

    ``base_dir=None`` gives a memory-only log. All methods are
    thread-safe; a failing disk write disables the file sink with one
    warning rather than taking down the run.
    """

    def __init__(self, base_dir: str | None = None, run_id: str | None = None):
        self.run_id = run_id or (
            time.strftime("%Y%m%d-%H%M%S") + "-" + uuid.uuid4().hex[:6]
        )
        self.records: list[dict] = []
        self.dropped = 0
        self._lock = threading.Lock()
        self._fh = None
        self.run_dir: str | None = None
        if base_dir:
            self.run_dir = os.path.join(base_dir, self.run_id)
            os.makedirs(self.run_dir, exist_ok=True)
            self._fh = open(  # noqa: SIM115 — held for the run's lifetime
                os.path.join(self.run_dir, EVENTS_FILE), "a", buffering=1
            )

    def emit(self, event: str, **fields: Any) -> dict:
        # schema drift check: every kind must be declared in ONE place
        # (observe/schema.py); unknown kinds warn once, never drop
        _schema_note(event)
        rec = {"ts": time.time(), "run": self.run_id, "event": event}
        rec.update(fields)
        with self._lock:
            if len(self.records) < _MAX_MEMORY_RECORDS:
                self.records.append(rec)
            else:
                self.dropped += 1
            if self._fh is not None:
                self._fh = write_record(self._fh, rec, "event log")
        return rec

    @contextlib.contextmanager
    def node(self, node: str, phase: str = "apply", **fields: Any) -> Iterator[None]:
        """Bracket one node call: emits a ``node`` event with wall time
        and status, re-raising any exception."""
        t0 = time.perf_counter()
        try:
            yield
        except BaseException as e:
            self.emit(
                "node",
                node=node,
                phase=phase,
                wall_s=time.perf_counter() - t0,
                status="failed",
                error=repr(e),
                **fields,
            )
            raise
        self.emit(
            "node",
            node=node,
            phase=phase,
            wall_s=time.perf_counter() - t0,
            status="ok",
            **fields,
        )

    def close(self) -> None:
        # the per-step telemetry stream (observe/telemetry.py) and the
        # span trace stream (observe/spans.py) bind their sinks to this
        # log's lifetime — close them with the run
        for bound in ("_steplog", "_spanlog"):
            sub = self.__dict__.pop(bound, None)
            if sub is not None:
                sub.close()
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
                self._fh = None


# Lazy three-state active sink: _UNINIT → (EventLog | None) on first use,
# so a process launched under KEYSTONE_OBSERVE_DIR self-activates and a
# process without it pays one `is` check per pipeline call.
_UNINIT: Any = object()
_active: Any = _UNINIT
_state_lock = threading.Lock()


def active() -> EventLog | None:
    """The currently active event log, or None. The ONLY check the hot
    pipeline hooks make — keep it a plain read when initialized."""
    global _active
    log = _active
    if log is _UNINIT:
        with _state_lock:
            if _active is _UNINIT:
                base = os.environ.get(ENV_DIR)
                try:
                    _active = EventLog(base) if base else None
                except OSError as e:
                    # unwritable/full observe dir: observability must
                    # degrade, not crash the pipeline at its first hook
                    _active = None
                    from keystone_tpu.core.logging import get_logger

                    get_logger("keystone_tpu.observe").warning(
                        "cannot open event log under %s (%r); "
                        "observability disabled for this process",
                        base,
                        e,
                    )
                if _active is not None:
                    _active.emit("run_start", source="env", argv=sys.argv)
                    _close_at_exit(_active)
            log = _active
    return log


def _close_at_exit(log: EventLog) -> None:
    """Env-activated logs have no scoping context manager, so bracket
    them at process exit: emit run_end (wall measured from activation)
    and close the file — otherwise a report can't tell a completed run
    from a crashed one. An uncaught exception is observed via a chained
    ``sys.excepthook`` so the run_end carries status=failed. Known
    limitation: CPython never invokes the excepthook for ``SystemExit``,
    so env-activated runs aborted that way record status=ok — scoped
    activation (:func:`run`, used by the launcher) brackets those
    correctly."""
    import atexit

    t0 = time.perf_counter()
    state: dict = {"status": "ok"}
    prev_hook = sys.excepthook

    def hook(tp, val, tb):
        state["status"] = "failed"
        state["error"] = f"{tp.__name__}: {val}"
        prev_hook(tp, val, tb)

    sys.excepthook = hook

    def _finish() -> None:
        try:
            log.emit(
                "run_end",
                wall_s=time.perf_counter() - t0,
                **state,
            )
            log.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    atexit.register(_finish)


def reset() -> None:
    """Drop the active sink and re-arm env detection (tests)."""
    global _active
    with _state_lock:
        if isinstance(_active, EventLog):
            _active.close()
        _active = _UNINIT


@contextlib.contextmanager
def run(
    base_dir: str | None = None, run_id: str | None = None, **meta: Any
) -> Iterator[EventLog]:
    """Scoped activation: install a fresh :class:`EventLog` as the active
    sink, bracket it with ``run_start``/``run_end`` events, and restore
    the previous sink (including the lazy-env sentinel) on exit.

    ``base_dir=None`` falls back to ``KEYSTONE_OBSERVE_DIR``; if that is
    unset too, the log is memory-only (still yielded, still active).
    """
    global _active
    if base_dir is None:
        base_dir = os.environ.get(ENV_DIR) or None
    try:
        log = EventLog(base_dir, run_id)
    except OSError as e:
        # same degrade invariant as env activation: a broken observe dir
        # must not abort the run — continue with a memory-only log
        from keystone_tpu.core.logging import get_logger

        get_logger("keystone_tpu.observe").warning(
            "cannot open event log under %s (%r); continuing memory-only",
            base_dir,
            e,
        )
        log = EventLog(None, run_id)
    with _state_lock:
        prev = _active
        _active = log
    # a new scoped run means new baselines: without this, the anomaly
    # monitor would carry a previous run's frozen step-wall p95 / loss
    # EMA into an unrelated workload and mis-alert (a process may run
    # several training loops of different sizes)
    from keystone_tpu.observe.health import reset_monitor

    reset_monitor()
    log.emit("run_start", **meta)
    t0 = time.perf_counter()
    try:
        yield log
    except BaseException as e:
        log.emit(
            "run_end",
            wall_s=time.perf_counter() - t0,
            status="failed",
            error=repr(e),
        )
        raise
    else:
        log.emit("run_end", wall_s=time.perf_counter() - t0, status="ok")
    finally:
        with _state_lock:
            _active = prev
        log.close()


def resolve_run_dir(path: str) -> str:
    """Accept either a run directory (contains ``events.jsonl``) or a
    base observe directory (pick the newest run under it)."""
    if os.path.isfile(os.path.join(path, EVENTS_FILE)):
        return path
    candidates = [
        os.path.join(path, d)
        for d in os.listdir(path)
        if os.path.isfile(os.path.join(path, d, EVENTS_FILE))
    ]
    if not candidates:
        raise FileNotFoundError(f"no {EVENTS_FILE} under {path!r}")
    return max(candidates, key=os.path.getmtime)


def read_events(path: str) -> list[dict]:
    """Parse a run's ``events.jsonl``. Unparseable records — above all
    the torn FINAL line a crashed or SIGKILLed writer leaves mid-record
    — are skipped with one warning naming the line(s), so the run stays
    readable and the loss stays visible."""
    run_dir = resolve_run_dir(path)
    return read_jsonl(os.path.join(run_dir, EVENTS_FILE))


def read_jsonl_rotated(file_path: str) -> list[dict]:
    """Like :func:`read_jsonl`, but stitches the rotated generation a
    :class:`JsonlSink` may have left (``<path>.1`` first, then the
    current file — oldest→newest). The ONE reader for the size-capped
    streams (``steps.jsonl``, ``spans.jsonl``): a consumer that read
    only the current file would silently drop the run's earliest
    records — exactly the baseline window the drift checks freeze on."""
    out: list[dict] = []
    for path in (file_path + ".1", file_path):
        if os.path.isfile(path):
            out.extend(read_jsonl(path))
    return out


def read_jsonl(file_path: str) -> list[dict]:
    """Tolerant JSONL reader shared by the event log and the step
    telemetry stream (same crash-torn-tail failure mode)."""
    out: list[dict] = []
    bad: list[int] = []
    with open(file_path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                bad.append(lineno)
    if bad:
        from keystone_tpu.core.logging import get_logger

        get_logger("keystone_tpu.observe").warning(
            "%s: skipped %d unparseable record(s) at line(s) %s — torn "
            "final line from a killed writer, or corruption",
            file_path,
            len(bad),
            bad[:5],
        )
    return out
