"""End-to-end span tracing: the causal layer of observe/.

The event log records *what ran* and the step stream *how fast*; this
module records *what caused what*: every hop a unit of work takes —
request → micro-batch → plan segment → staged chunk → device — becomes
one span record in ``<run-dir>/spans.jsonl``, linked by
``(trace, span, parent)`` ids that survive thread boundaries. A served
request, a train step, or a planned pass can then be rendered as a tree
(``python -m keystone_tpu observe trace <dir>``) and its wall decomposed
into *where the time went* buckets — the per-stage stall/goodput signal
the self-tuning planner (ROADMAP item 3) needs.

Activation: spans are on while an event sink is active (``--observe
DIR`` / ``KEYSTONE_OBSERVE_DIR``; the run's :class:`SpanLog` writes
``spans.jsonl``) **or while a ``jax.profiler`` session is on**
(``--profile DIR``, :class:`~keystone_tpu.observe.tracing.StepTracer`,
anyone's ``jax.profiler.start_trace``). In the second case they go to a
memory-only :class:`SpanLog` that :func:`profiled_spans` still returns
after the session stops; a new session starts a new list (the profiler
has no public session id, so "new" means: seen on after it was seen off
— see :func:`profiled_spans`). With neither, :func:`active_span_log` /
:func:`span` cost three reads (the event sink,
``TraceAnnotation.is_enabled()``, the startup period), build nothing and
yield None.

The startup period is the third, bounded, activation: what a process
does before its first window. It opens when
``core/runtime.py::init_backend`` is first entered (:func:`open_startup`;
a process that never calls it, as a test process, sees none of this) and
closes, for good, at the end of the process's first unit of work: the
first parentless :func:`span` named in :data:`_STARTUP_UNITS` (a ``fit``
root, a served request). Two bounds close it earlier, whichever is
first: a unit that is still stepping after :data:`_STARTUP_STEPS`
``train.step`` is a training run and no warm-up (``closed_by``
``steps``: a trainer's steady steps are neither recorded nor waited
for), and a period holds at most :data:`_MAX_STARTUP_SPANS` records
(``records``). While it is open and neither a sink nor a session is on,
:func:`active_span_log` returns its memory-only :class:`SpanLog`, so the
first fit is recorded as a traced fit is; every span that asked for no
parent (a ``plan.segment`` before the fit closes nothing), and a
``jit.*`` record made outside any span, parent on the ``process`` root,
whose ids are made when the period opens and which is emitted when it
closes: ``t0_ns`` back-dated to the process's start
(``t0_source`` ``proc``: ``/proc/self/stat`` and the boot clock; else
``import``: when this module was loaded), ``t1_ns`` the end of that
first unit of work. :func:`startup_spans` returns the records,
:func:`startup_summary` reduces them to the one ``startup {json}`` line
``init_backend`` logs when the period closes.

The shared clock: while a profiler session is on, a live :func:`span`
also enters ``jax.profiler.TraceAnnotation(name, span=, parent=,
trace=)``, so the span lands in the ``/host:CPU`` plane of the same
``.xplane.pb`` as the device planes, under its own name with its ids as
stats. Host spans and device ops are then on one timeline
(``python -m keystone_tpu observe idle <profile-dir>`` reads it), and
``t0_ns`` of a record maps onto it through the offset of any span that
both hold.

Which call recompiled: the first time spans come on, one
``jax.monitoring`` duration listener is registered (the program's only
one: it also keeps the counts behind the ``compile {json}`` exit line,
:func:`compile_counts`). While spans are on, every jaxpr trace, lowering
and backend compile becomes a ``jit.trace`` / ``jit.lower`` /
``jit.backend_compile`` child (attr ``fun``) of whatever span is
ambient; a backend compile that the persistent cache answered is
recorded as ``jit.cache_read`` instead (jax times the cache read inside
the backend-compile bracket: one request, one span). Outside any span
nothing is recorded, a child needs a parent, except while the startup
period is open: ``process`` is the parent then.

Span record schema (one JSON object per line; extra fields free-form):

==============  ========================================================
``ts``          unix time at emission (float, seconds)
``run``         run id (same id as the run's events)
``trace``       trace id — all spans of one causal unit share it
``span``        this span's id
``parent``      parent span id (absent for roots)
``name``        span name, dotted by subsystem (``serve.queue_wait``,
                ``plan.segment``, ``staging.h2d``, ``train.step``,
                ``fit`` / ``fit.load`` / ``fit.h2d`` / ``fit.featurize``
                / ``featurize.cosine`` / ``fit.solve`` / ``fit.score``
                of the classic fit path, ``jit.backend_compile``)
``wall_s``      wall-clock duration
``t0_ns``       start and end on ``time.perf_counter_ns()``: one clock
``t1_ns``       per process, so spans of a run order and nest by these
``bucket``      goodput bucket (see :data:`BUCKETS`), absent on
                structural spans whose children carry the time
``status``      ``failed`` when the bracket raised (absent = ok)
==============  ========================================================

Thread boundaries: the ambient span context rides a ``contextvars``
variable, which does NOT flow into an already-running worker thread —
so the micro-batcher captures :func:`current` at submit time, the
staging engine at stream creation, and the decode loop at prompt
submit, then records spans with that explicit parent. That is the whole
propagation protocol; there is no global registry of live spans.

Goodput buckets (:data:`BUCKETS`) classify a span's wall:

==============  ========================================================
``queue``       admitted but waiting for coalescing/capacity
``wait_host``   host-side input production + host→device transfer
``wait_device`` blocked on device results (``block_until_ready``)
``compute``     dispatched device work (incl. the queued dispatch wall)
``collective``  cross-host barriers / merges
``checkpoint``  checkpoint save/restore
==============  ========================================================

Spans can overlap (staging overlaps compute by design), so bucket
shares are reported over the *classified* wall, not the run wall.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import os
import threading
import time
import uuid
from typing import Any, Iterator, NamedTuple

import jax
from jax.profiler import TraceAnnotation as _TraceAnnotation

from keystone_tpu.observe import events as _events

SPANS_FILE = "spans.jsonl"

# when this module was loaded, on the span clock: what a startup record
# dates the process from where /proc cannot say
_T_IMPORT_NS = time.perf_counter_ns()

#: the goodput taxonomy — every classified span names one of these
BUCKETS = (
    "queue",
    "wait_host",
    "wait_device",
    "compute",
    "collective",
    "checkpoint",
)

# in-memory mirror cap — enough for the goodput summaries and the
# trace renderer without growing with run length
_MAX_MEMORY_SPANS = 8192

_bind_lock = threading.Lock()
_UNSET: Any = object()


class SpanContext(NamedTuple):
    """The ids a child span needs from its parent — pass this across
    thread boundaries explicitly (contextvars stop at threads)."""

    trace: str
    span: str


def _new_id() -> str:
    """12 hex characters, the first a letter: an id must never read as a
    number, because the profiler guesses the type of a TraceAnnotation's
    stats and hands ``68401e457669`` back as ``inf`` and ``000123456789``
    as ``123456789`` (one id in 250 is of those forms)."""
    h = uuid.uuid4().hex
    return "abcdef"[int(h[11], 16) % 6] + h[:11]


def make_context(
    parent: SpanContext | None = None, trace: str | None = None
) -> SpanContext:
    """Pre-allocate a span's ids so children recorded earlier (e.g. a
    prefill recorded at admit, inside a generation span recorded at
    retire) can parent on it before it is emitted."""
    t = trace or (parent.trace if parent is not None else _new_id())
    return SpanContext(t, _new_id())


# the ambient span: what a nested `span()` parents on when no explicit
# parent is given. Context-local, so concurrent requests never cross.
_current: contextvars.ContextVar[SpanContext | None] = contextvars.ContextVar(
    "keystone_span", default=None
)


def current() -> SpanContext | None:
    """The ambient span context (None outside any span). A plain
    context-local read — safe on any hot path."""
    return _current.get()


def force(x):
    """``jax.block_until_ready(x)`` when, and only when, a span is being
    recorded around the caller; returns ``x``. A phase boundary calls it
    on the phase's outputs so that a recorded span ends when its device
    work has, while an untraced run keeps its asynchronous dispatch. A
    span that outlives what recorded it (the ``fit`` root of a trainer
    whose startup period a bound closed) waits for nothing more."""
    if _current.get() is not None and active_span_log() is not None:
        jax.block_until_ready(x)
    return x


class SpanLog:
    """One run's span sink: ``spans.jsonl`` (size-rotated under
    ``KEYSTONE_OBSERVE_MAX_MB``) plus a bounded in-memory mirror.

    ``run_dir=None`` gives a memory-only log. Thread-safe; disk-write
    failure degrades with one warning, same rule as the event log.
    """

    def __init__(
        self,
        run_dir: str | None = None,
        run_id: str | None = None,
        max_records: int = _MAX_MEMORY_SPANS,
    ):
        self.run_id = run_id
        self.records: collections.deque = collections.deque(maxlen=max_records)
        self._lock = threading.Lock()
        self._sink: _events.JsonlSink | None = None
        if run_dir:
            try:
                self._sink = _events.JsonlSink(
                    os.path.join(run_dir, SPANS_FILE), "span log"
                )
            except OSError as e:
                from keystone_tpu.core.logging import get_logger

                get_logger("keystone_tpu.observe").warning(
                    "cannot open %s under %s (%r); span tracing is "
                    "memory-only for this run",
                    SPANS_FILE,
                    run_dir,
                    e,
                )

    def record_span(
        self,
        name: str,
        *,
        wall_s: float,
        end_ns: int | None = None,
        bucket: str | None = None,
        parent: SpanContext | None = None,
        trace: str | None = None,
        ctx: SpanContext | None = None,
        status: str | None = None,
        **attrs: Any,
    ) -> SpanContext:
        """Emit one already-measured span and return its context.

        ``end_ns`` is when it ended on ``time.perf_counter_ns()`` (now,
        if not given); the start is that less ``wall_s``. ``ctx`` reuses
        pre-allocated ids (:func:`make_context`); otherwise the trace
        comes from ``trace``, else the ``parent``, else a fresh one (a
        root)."""
        if end_ns is None:
            end_ns = time.perf_counter_ns()
        if ctx is None:
            ctx = make_context(parent, trace)
        rec: dict[str, Any] = {
            "ts": time.time(),
            "trace": ctx.trace,
            "span": ctx.span,
            "name": name,
            "wall_s": round(float(wall_s), 6),
            "t0_ns": end_ns - int(round(float(wall_s) * 1e9)),
            "t1_ns": end_ns,
        }
        if self.run_id:
            rec["run"] = self.run_id
        if parent is not None:
            rec["parent"] = parent.span
        if bucket is not None:
            rec["bucket"] = bucket
        if status is not None:
            rec["status"] = status
        for k, v in attrs.items():
            if v is not None:
                rec[k] = v
        with self._lock:
            self.records.append(rec)
            if self._sink is not None:
                self._sink.write(rec)
        return ctx

    def close(self) -> None:
        with self._lock:
            if self._sink is not None:
                self._sink.close()
                self._sink = None


# the memory-only log of the newest profiler session, and whether that
# session is still the one that is on
_session_log: SpanLog | None = None
_session_open = False


def _profiler_span_log() -> SpanLog | None:
    """The memory-only :class:`SpanLog` of the ``jax.profiler`` session
    that is on, or None. ``TraceAnnotation.is_enabled()`` is the one
    public, static way to ask, and it names no session: one seen on
    after it was seen off (here or by :func:`profiled_spans`) is a new
    one and gets a new list. Two sessions with no such look between
    them share a list."""
    global _session_log, _session_open
    if not _TraceAnnotation.is_enabled():
        _session_open = False
        return None
    if not _session_open:
        with _bind_lock:
            if not _session_open:
                _session_log = SpanLog()
                _session_open = True
                _listen_for_compiles()
    return _session_log


def profiled_spans() -> list[dict]:
    """The span records of the newest profiler session, during it and
    after it stopped ([] if none ran, or if an event sink was active:
    the records then went to that run's ``spans.jsonl``). The one way
    into the session's spans from outside this module (the benchmark's
    per-layer readers use it).

    Call it (or any :func:`span`) between two sessions: that look while
    the profiler is off is what makes the next session's list a new one.
    Without it the second session appends to the first's records."""
    global _session_open
    if not _TraceAnnotation.is_enabled():
        _session_open = False
    sl = _session_log
    return list(sl.records) if sl is not None else []


# ------------------------------------------------------ the startup period

# what a unit of work is: the first span of these names under no other
# span of this process closes the period when it ends. Any other
# parentless span of the period (``plan.segment``, ``serve.stream``,
# ``fleet.request`` ...) is a child of ``process`` and closes nothing
_STARTUP_UNITS = frozenset({"fit", "serve.request"})
# a unit still stepping after this many ``train.step`` is a training run
# (its ``fit`` root is the whole of it) and no warm-up: the period closes
# at that step's end, and the steps after it are neither recorded nor
# waited for (:func:`force`). A cell's warm-up fit makes 8
_STARTUP_STEPS = 16
# a period that holds this many records closes. A first fit of the LM
# makes 3 000 to 9 000: jax times every inner jitted function it traces
# inside the step, some 600 ``jit.trace`` a layer
_MAX_STARTUP_SPANS = 32768


class _Startup:
    """One process's startup period: the ``process`` root's ids and
    start, the memory-only log, and what ``init_backend`` gave to say of
    the root (``attrs()``) and to hear when the period closes
    (``report(summary)``)."""

    def __init__(self, attrs, report):
        # twice the bound: the spans in flight when it closes the period
        # still end in this log, and push nothing out
        self.log = SpanLog(max_records=2 * _MAX_STARTUP_SPANS)
        self.ctx = make_context()
        self.t0_ns, self.t0_source = _process_start_ns()
        self.attrs, self.report = attrs, report
        self.unit: str | None = None  # the span whose end closes the period
        self.steps = 0  # `train.step` spans ended so far
        # span id -> the record so far of a span that has not ended: what
        # the summary says of a unit that a bound cut short
        self.in_flight: dict[str, dict] = {}

    def enter(
        self, name: str, ctx: SpanContext, parent: SpanContext, t0_ns: int, unit: bool
    ) -> None:
        self.in_flight[ctx.span] = {
            "name": name,
            "trace": ctx.trace,
            "span": ctx.span,
            "parent": parent.span,
            "t0_ns": t0_ns,
        }
        if unit:
            with _bind_lock:
                if self.unit is None:
                    self.unit = ctx.span

    def leave(self, name: str, ctx: SpanContext, t1_ns: int) -> None:
        self.in_flight.pop(ctx.span, None)
        if ctx.span == self.unit:
            _close_startup(self, t1_ns, "unit")
        elif name == "train.step":
            with _bind_lock:
                self.steps += 1
                enough = self.steps >= _STARTUP_STEPS
            if enough:
                _close_startup(self, t1_ns, "steps")


# the process's period, kept for good once opened (startup_spans), and
# the same object while it is open: the one read a steady span() adds
_startup: _Startup | None = None
_startup_open: _Startup | None = None


def _process_start_ns() -> tuple[int, str]:
    """When this process started, on ``time.perf_counter_ns()``, and how
    that is known: ``proc`` (field 22 of ``/proc/self/stat``, ticks
    since boot, against the boot clock now) or ``import`` (when this
    module was loaded)."""
    now = time.perf_counter_ns()
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command, which may hold spaces
            ticks = int(f.read().rpartition(")")[2].split()[19])
        age = time.clock_gettime_ns(time.CLOCK_BOOTTIME) - (
            ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        )
        if age >= now - _T_IMPORT_NS:  # a start after the import is no start
            return now - age, "proc"
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return _T_IMPORT_NS, "import"


def open_startup(attrs, report) -> SpanContext:
    """Open the startup period (module docstring) and return the
    ``process`` root's context; a later call returns the same context
    and opens nothing. ``attrs()`` gives the root's attributes when the
    period closes, ``report(summary)`` hears :func:`startup_summary` of
    its records then. ``core/runtime.py::init_backend`` is the caller."""
    global _startup, _startup_open
    with _bind_lock:
        if _startup is None:
            _startup = _startup_open = _Startup(attrs, report)
            _listen_for_compiles()
    return _startup.ctx


def _close_startup(st: _Startup, end_ns: int, closed_by: str) -> None:
    """Close the period, once: emit the ``process`` root into the log
    that is in use and report the summary of what lies under it."""
    global _startup_open
    with _bind_lock:
        if _startup_open is not st:
            return
        _startup_open = None
    sl = active_span_log() or st.log
    sl.record_span(
        "process",
        wall_s=(end_ns - st.t0_ns) / 1e9,
        end_ns=end_ns,
        ctx=st.ctx,
        t0_source=st.t0_source,
        closed_by=closed_by,
        **st.attrs(),
    )
    # with a sink or a session on, part of the period lies in its log
    recs = list(st.log.records)
    if sl is not st.log:
        recs += list(sl.records)
    # what a bound cut short (the unit, its phase) counts up to here; it
    # records itself when it ends
    recs += [{**r, "t1_ns": end_ns} for r in list(st.in_flight.values())]
    st.report(startup_summary(recs))


def startup_spans() -> list[dict]:
    """The records of the startup period, during it and after it closed
    ([] if ``init_backend`` never ran, or for what went to an active
    sink's ``spans.jsonl`` or a profiler session's list instead). The
    one way into them from outside this module, beside
    :func:`profiled_spans` (the benchmark's ``setup_*`` readers use it)."""
    st = _startup
    return list(st.log.records) if st is not None else []


def active_span_log() -> SpanLog | None:
    """The :class:`SpanLog` riding the active event sink, else the one
    of the profiler session that is on, else the startup period's while
    it is open, else None.

    The ONLY check the hot paths make: with none of them this is one
    global read (``events.active()``), one static call
    (``TraceAnnotation.is_enabled()``) and one more global read (the
    startup period) and constructs nothing — the same overhead contract
    as :func:`keystone_tpu.observe.telemetry.active_step_log`."""
    log = _events.active()
    if log is None:
        sl = _profiler_span_log()
        if sl is None:
            st = _startup_open
            if st is not None:
                if len(st.log.records) < _MAX_STARTUP_SPANS:
                    return st.log
                _close_startup(st, time.perf_counter_ns(), "records")
        return sl
    sl = log.__dict__.get("_spanlog")
    if sl is None:
        with _bind_lock:
            sl = log.__dict__.get("_spanlog")
            if sl is None:
                sl = SpanLog(log.run_dir, log.run_id)
                log._spanlog = sl
                _listen_for_compiles()
    return sl


# ------------------------------------------------- which call recompiled

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    _BACKEND_COMPILE_EVENT: "jit.backend_compile",
}
# emitted on a persistent-cache hit only, inside the backend-compile
# bracket of the same request and before that bracket's own event
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_listening = False
_cache_hit = threading.local()
# counted from the first time the listeners are on, spans or no spans
_compiles = {"backend_compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}


def compile_counts() -> dict:
    """Backend compile seconds and persistent-cache hits and misses of
    this process since its listeners were registered (``init_backend``
    registers them): the ``compile {json}`` exit line."""
    return dict(_compiles)


def _on_jit_event(event: str, **_kw: Any) -> None:
    key = _CACHE_COUNT_EVENTS.get(event)
    if key is not None:
        _compiles[key] += 1


def _on_jit_duration(event: str, duration: float, **kw: Any) -> None:
    """The ``jax.monitoring`` duration listener: counts backend compile
    seconds always; inside a recorded span, or under ``process`` while
    the startup period is open, one post-hoc child span per compile step
    (start = now - duration); else nothing more (two reads)."""
    if event != _CACHE_READ_EVENT and event not in _JIT_EVENTS:
        return
    if event == _BACKEND_COMPILE_EVENT:
        _compiles["backend_compile_s"] += duration
    parent = _current.get()
    if parent is None:
        st = _startup_open
        if st is None:  # spans off, or no span to parent on
            return
        parent = st.ctx
    sl = active_span_log()
    if sl is None:
        return
    if event == _CACHE_READ_EVENT:
        _cache_hit.pending = True
        return
    name = _JIT_EVENTS[event]
    if name == "jit.backend_compile" and getattr(_cache_hit, "pending", False):
        _cache_hit.pending = False
        name = "jit.cache_read"
    sl.record_span(name, wall_s=duration, parent=parent, fun=kw.get("fun_name"))


def _listen_for_compiles() -> None:
    """Register the compile listeners, once per process (jax keeps
    listeners for good, hence the gate inside the callback)."""
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_jit_duration)
        jax.monitoring.register_event_listener(_on_jit_event)


@contextlib.contextmanager
def span(
    name: str,
    *,
    bucket: str | None = None,
    parent: Any = _UNSET,
    trace: str | None = None,
    log: Any = _UNSET,
    **attrs: Any,
) -> Iterator[SpanContext | None]:
    """Bracket a block as one span: measures wall, parents on the
    ambient context (or an explicit ``parent``), installs itself as the
    ambient context for the duration, and emits on exit (``status:
    failed`` rides a raised exception out). An attribute given as a
    callable is called on exit: the way to say what only the block finds
    out (a cache's hits).

    While a profiler session is on the block is also bracketed by a
    ``TraceAnnotation`` of the same name (module docstring). With no
    sink, no session and no startup period this yields None after three
    reads — pass ``log=``
    (a :class:`SpanLog` or None) to skip even those when the caller
    already looked it up once for a whole batch/stream.
    """
    sl = active_span_log() if log is _UNSET else log
    if sl is None:
        yield None
        return
    ambient = _current.get()
    pctx = ambient if parent is _UNSET else parent
    st = _startup_open
    if st is not None:
        # a unit of work: asked for no parent, or brought one from
        # another process (a request behind the fleet's router)
        unit = name in _STARTUP_UNITS and (pctx is None or ambient is None)
        if pctx is None:
            pctx = st.ctx  # no parent, in the startup period: `process`
    ctx = make_context(pctx, trace)
    token = _current.set(ctx)
    twin = None
    if _TraceAnnotation.is_enabled():
        # the span's twin on the profiler's clock: same name, ids as stats
        ids = {"span": ctx.span, "trace": ctx.trace}
        if pctx is not None:
            ids["parent"] = pctx.span
        twin = _TraceAnnotation(name, **ids)
        twin.__enter__()
    t0 = time.perf_counter_ns()
    if st is not None:
        st.enter(name, ctx, pctx, t0, unit)
    status = None
    try:
        yield ctx
    except BaseException:
        status = "failed"
        raise
    finally:
        t1 = time.perf_counter_ns()
        if twin is not None:
            twin.__exit__(None, None, None)
        _current.reset(token)
        sl.record_span(
            name,
            wall_s=(t1 - t0) / 1e9,
            end_ns=t1,
            bucket=bucket,
            parent=pctx,
            ctx=ctx,
            status=status,
            **{k: v() if callable(v) else v for k, v in attrs.items()},
        )
        if st is not None:
            st.leave(name, ctx, t1)


# --------------------------------------------------------------- analysis


def read_spans(run_dir: str) -> list[dict]:
    """A run's span records, rotated generation first (so order is
    oldest→newest); [] when the run recorded none."""
    run_dir = _events.resolve_run_dir(run_dir)
    return _events.read_jsonl_rotated(os.path.join(run_dir, SPANS_FILE))


def read_spans_all(base_dir: str) -> list[dict]:
    """EVERY run's span records under a base observe directory, merged
    and sorted by emission time — the cross-process view. A fleet is
    several processes (router + N replicas) each writing its own run
    dir; one request's causal tree spans them (the router's
    ``X-Keystone-Trace`` hop header carries the ids across), so the
    trace renderer must read them together to show router queue →
    replica queue → device compute as one tree."""
    if os.path.isfile(os.path.join(base_dir, SPANS_FILE)):
        dirs = [base_dir]
    else:
        dirs = [
            os.path.join(base_dir, d)
            for d in (
                os.listdir(base_dir) if os.path.isdir(base_dir) else ()
            )
            if os.path.isfile(os.path.join(base_dir, d, SPANS_FILE))
        ]
    out: list[dict] = []
    for d in sorted(dirs):
        out.extend(
            _events.read_jsonl_rotated(os.path.join(d, SPANS_FILE))
        )
    out.sort(key=lambda r: float(r.get("ts") or 0.0))
    return out


def build_trees(spans: list[dict]) -> dict[str, list[dict]]:
    """Group spans into per-trace trees: trace id → list of root nodes,
    each node ``{"rec": span, "children": [nodes...]}`` (children in
    emission order). A span whose parent never got emitted (crashed
    writer) is promoted to a root rather than dropped."""
    by_trace: dict[str, list[dict]] = {}
    nodes: dict[str, dict] = {}
    for rec in spans:
        sid = rec.get("span")
        if not sid:
            continue
        nodes[sid] = {"rec": rec, "children": []}
    for node in nodes.values():
        rec = node["rec"]
        parent = nodes.get(rec.get("parent"))
        if parent is not None and parent is not node:
            parent["children"].append(node)
        else:
            by_trace.setdefault(str(rec.get("trace")), []).append(node)
    return by_trace


def critical_path(node: dict) -> float:
    """Critical-path seconds through one span node: its own wall, or
    its children's critical paths summed when they account for more
    (children measured on other threads can exceed the parent's
    bracket)."""
    own = float(node["rec"].get("wall_s") or 0.0)
    if not node["children"]:
        return own
    return max(own, sum(critical_path(c) for c in node["children"]))


def trace_critical_path(roots: list[dict]) -> float:
    return sum(critical_path(r) for r in roots)


def goodput_summary(spans: list[dict]) -> dict[str, Any]:
    """The per-run "where the time went" report: wall per goodput
    bucket with its share of the classified total, plus trace count and
    summed critical-path length. Structural spans (no ``bucket``) are
    skipped — their time lives in their classified children — so the
    shares never double-count a parent bracket."""
    walls: dict[str, float] = {}
    for rec in spans:
        bucket = rec.get("bucket")
        if not bucket:
            continue
        walls[bucket] = walls.get(bucket, 0.0) + float(
            rec.get("wall_s") or 0.0
        )
    total = sum(walls.values())
    trees = build_trees(spans)
    cp = sum(trace_critical_path(roots) for roots in trees.values())
    return {
        "total_s": round(total, 6),
        "buckets": {
            b: {
                "wall_s": round(w, 6),
                "share": round(w / total, 4) if total else 0.0,
            }
            for b, w in sorted(
                walls.items(), key=lambda kv: -kv[1]
            )
        },
        "traces": len(trees),
        "spans": len(spans),
        "critical_path_s": round(cp, 6),
    }


def self_ns(recs: list[dict], root: dict) -> dict[str, int]:
    """span id -> nanoseconds of ``root``'s wall that belong to that
    span, for ``root`` and every record that leads to it by its parents:
    every instant of ``[root.t0_ns, root.t1_ns]`` goes to the innermost
    span over it (deepest under ``root``, then the one that started
    last), so a span keeps its wall less what its children cover, an
    inner ``jit.trace`` is not counted again in the outer one that holds
    it, and the values add up to the root's wall, to the nanosecond. The
    one sweep behind :func:`startup_summary` and the benchmark's
    ``setup_*`` readers."""
    by_id = {r["span"]: r for r in recs if "t0_ns" in r}
    by_id[root["span"]] = root

    def depth_of(sid: str) -> int | None:
        """Steps up to the root; None where the parents do not lead there."""
        seen: set[str] = set()
        while sid != root["span"]:
            if sid not in by_id or sid in seen:
                return None
            seen.add(sid)
            sid = by_id[sid].get("parent")
        return len(seen)

    depth = {sid: d for sid in by_id if (d := depth_of(sid)) is not None}
    lo, hi = root["t0_ns"], root["t1_ns"]
    edges = []
    for sid in depth:
        r = by_id[sid]
        t0, t1 = min(max(r["t0_ns"], lo), hi), min(max(r["t1_ns"], lo), hi)
        if t1 > t0:
            edges += [(t0, 1, sid), (t1, 0, sid)]
    edges.sort()
    out = dict.fromkeys(depth, 0)
    over: set[str] = set()
    at = lo
    for t, opens, sid in edges:
        if t > at:
            inner = max(over, key=lambda i: (depth[i], by_id[i]["t0_ns"]))
            out[inner] += t - at
            at = t
        (over.add if opens else over.discard)(sid)
    return out


def startup_summary(recs: list[dict]) -> dict | None:
    """The ``startup {json}`` line of a closed startup period: seconds
    from the process's start to the backend's (``import_s``), in
    ``init_backend`` (``backend_s``), tracing, lowering, reading the
    compile cache and compiling (self time of every ``jit.*`` record
    under ``process``), in the first unit of work less those
    (``first_run_s``), the programs made (cache reads + compiles), the
    root's wall, and the three ``fun`` with the most ``jit.*`` time.
    None without a ``process`` root."""
    roots = [r for r in recs if r.get("name") == "process" and "parent" not in r]
    if not roots:
        return None
    root = roots[-1]
    mine = {sid: ns / 1e9 for sid, ns in self_ns(recs, root).items()}
    by_id = {r["span"]: r for r in recs if r.get("span") in mine}
    parts = {
        "runtime.init_backend": "backend_s",
        "jit.trace": "trace_s",
        "jit.lower": "lower_s",
        "jit.cache_read": "cache_read_s",
        "jit.backend_compile": "compile_s",
    }
    out = dict.fromkeys(("import_s", *parts.values(), "first_run_s"), 0.0)
    funs: dict[str, float] = {}
    for sid, s in mine.items():
        r = by_id[sid]
        if r is root:
            continue
        out[parts.get(r["name"], "first_run_s")] += s
        if r["name"].startswith("jit."):
            fun = str(r.get("fun") or "?")
            if fun.startswith("jit(") and fun.endswith(")"):
                fun = fun[4:-1]  # the compile's name for what was traced
            funs[fun] = funs.get(fun, 0.0) + s
    backend = [r["t0_ns"] for r in by_id.values() if r["name"] == "runtime.init_backend"]
    if backend:
        out["import_s"] = (min(backend) - root["t0_ns"]) / 1e9
    top = sorted(funs.items(), key=lambda kv: -kv[1])[:3]
    return {
        **{k: round(v, 3) for k, v in out.items()},
        "programs": sum(
            r["name"] in ("jit.cache_read", "jit.backend_compile")
            for r in by_id.values()
        ),
        "total_s": round((root["t1_ns"] - root["t0_ns"]) / 1e9, 3),
        "top": [{"fun": f, "s": round(s, 3)} for f, s in top],
    }


# -------------------------------------------------------------- rendering


def _render_node(node: dict, depth: int, lines: list[str]) -> None:
    rec = node["rec"]
    wall = float(rec.get("wall_s") or 0.0)
    extras = []
    if rec.get("bucket"):
        extras.append(rec["bucket"])
    if rec.get("status") == "failed":
        extras.append("FAILED")
    for key in (
        "rid", "step", "rows", "requests", "bucket_size", "tokens", "cached"
    ):
        if key in rec:
            extras.append(f"{key}={rec[key]}")
    # what an LM fit says of its layers and its step's counters, where
    # the model has such layers
    for key in (
        "ssm_layers", "cca_layers", "mtp_depth", "moe_latent", "routed_rows",
        "mm_rows", "dispatch_rows", "extra_windows", "load_max_over_mean",
        "router_gate_mean", "ssm_rows", "cca_rows", "mtp_rows",
    ):
        # no extra window is worth saying where the layers dispatched
        if rec.get(key) or (key == "extra_windows" and rec.get("dispatch_rows")):
            value = rec[key]
            extras.append(
                f"{key}={value:.4g}" if isinstance(value, float) else f"{key}={value}"
            )
    tag = f"  [{', '.join(extras)}]" if extras else ""
    lines.append(
        f"{'  ' * depth}{rec.get('name', '?'):{max(34 - 2 * depth, 8)}} "
        f"{wall * 1e3:9.3f} ms{tag}"
    )
    for child in node["children"]:
        _render_node(child, depth + 1, lines)


def _trace_matches_request(roots: list[dict], rid: str) -> bool:
    return any(str(r["rec"].get("rid")) == rid for r in roots)


def render_traces(
    spans: list[dict], request: str | None = None, limit: int = 20
) -> str:
    """The ``observe trace`` body: per-trace span trees (newest first)
    with a critical-path summary line each. ``request`` filters to
    traces whose root carries that ``rid`` — and follows their
    ``batch_trace`` links so the underlying micro-batch's segment/chunk
    tree renders beneath the request's own."""
    trees = build_trees(spans)
    if not trees:
        return "(no spans recorded — spans.jsonl absent or empty)"
    order = sorted(
        trees,
        key=lambda t: max(
            float(r["rec"].get("ts") or 0.0) for r in trees[t]
        ),
        reverse=True,
    )
    selected: list[str] = []
    if request is not None:
        selected = [t for t in order if _trace_matches_request(trees[t], request)]
        if not selected:
            return f"(no trace with a root span rid={request!r})"
        # follow request → batch links: the batch trace carries the
        # segment/staging tree the request's dispatch rode through
        linked: list[str] = []
        for t in selected:
            stack = list(trees[t])
            while stack:
                node = stack.pop()
                bt = node["rec"].get("batch_trace")
                if bt and bt in trees and bt not in selected + linked:
                    linked.append(str(bt))
                stack.extend(node["children"])
        selected.extend(linked)
    else:
        selected = order[:limit]
    lines: list[str] = []
    for t in selected:
        roots = trees[t]
        cp = trace_critical_path(roots)
        names = sorted(
            (
                (critical_path(r), r["rec"].get("name", "?"))
                for r in roots
            ),
            reverse=True,
        )
        head = names[0][1] if names else "?"
        lines.append(
            f"trace {t}  ({sum(1 for _ in _walk(roots))} span(s), "
            f"critical path {cp * 1e3:.3f} ms, root {head})"
        )
        for root in roots:
            _render_node(root, 1, lines)
        lines.append("")
    if request is None and len(order) > limit:
        lines.append(f"... {len(order) - limit} more trace(s); --limit N")
    return "\n".join(lines).rstrip()


def _walk(roots: list[dict]) -> Iterator[dict]:
    stack = list(roots)
    while stack:
        node = stack.pop()
        yield node
        stack.extend(node["children"])


def render_goodput(summary: dict[str, Any]) -> list[str]:
    """Text lines for the goodput section shared by ``observe trace``
    and the run report."""
    lines = [
        f"goodput (where the time went — {summary['spans']} span(s), "
        f"{summary['traces']} trace(s), classified "
        f"{summary['total_s']:.3f}s, critical path "
        f"{summary['critical_path_s']:.3f}s):"
    ]
    for bucket, row in summary["buckets"].items():
        bar = "#" * int(round(row["share"] * 30))
        lines.append(
            f"  {bucket:12} {row['wall_s']:9.3f}s  "
            f"{row['share'] * 100:5.1f}%  {bar}"
        )
    if not summary["buckets"]:
        lines.append("  (no classified spans)")
    return lines


def main(argv: list[str] | None = None) -> None:
    """``python -m keystone_tpu observe trace <run-dir> [--request ID]
    [--limit N]``."""
    import sys

    argv = list(sys.argv[1:] if argv is None else argv)
    request = None
    if "--request" in argv:
        i = argv.index("--request")
        if i + 1 >= len(argv):
            raise SystemExit("--request needs an id argument")
        request = argv[i + 1]
        del argv[i : i + 2]
    limit = 20
    if "--limit" in argv:
        i = argv.index("--limit")
        if i + 1 >= len(argv):
            raise SystemExit("--limit needs a count argument")
        try:
            limit = int(argv[i + 1])
        except ValueError:
            raise SystemExit(
                f"--limit: bad count {argv[i + 1]!r}"
            ) from None
        del argv[i : i + 2]
    if not argv or argv[0] in ("-h", "--help"):
        raise SystemExit(
            "usage: python -m keystone_tpu observe trace <run-dir> "
            "[--request ID] [--limit N]\n"
            "<run-dir> is a directory containing spans.jsonl, or a base\n"
            "KEYSTONE_OBSERVE_DIR (the newest run under it is rendered)"
        )
    try:
        if request is not None:
            # a request id is a cross-process question: the fleet
            # router and its replicas each wrote their own run dir
            # under the base — merge them so the tree crosses the hop
            spans = read_spans_all(argv[0])
            if not spans:
                spans = read_spans(argv[0])
        else:
            spans = read_spans(argv[0])
    except OSError as e:
        raise SystemExit(str(e)) from None
    print(render_traces(spans, request=request, limit=limit))
    print()
    print("\n".join(render_goodput(goodput_summary(spans))))
