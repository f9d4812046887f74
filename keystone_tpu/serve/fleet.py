"""Fault-tolerant serving fleet: ``python -m keystone_tpu fleet``.

PR 7 built one server on one chip; this module makes that server a
*tier*: a front-end HTTP router supervising N replica ``serve``
processes so the fleet survives any single-replica failure with zero
failed client requests. The pieces:

**Replica lifecycle** — every replica walks ``starting → up →
draining → down`` (and back through ``starting`` on relaunch), driven
by two detectors: active ``/healthz`` polls every
``KEYSTONE_FLEET_POLL_S`` (which also pick up the replica's reported
p95 and queue depth, and its ``draining`` flag the moment a SIGTERM
drain begins), and passive per-request failure detection (a connection
error or 5xx on a routed request). A per-replica **circuit breaker**
trips after ``KEYSTONE_FLEET_BREAKER_FAILS`` consecutive failures,
holds routing off for ``KEYSTONE_FLEET_BREAKER_COOLDOWN_S``, then
half-opens: probe traffic is allowed through, one success closes it,
one failure re-opens. The breaker clock is injectable, so the full
trip/half-open/recover schedule unit-tests with zero sleeps.

**Routing** — least-loaded SLO-aware: among ``up`` replicas whose
breaker admits traffic, pick the lowest ``(router-side in-flight,
reported queue depth, reported p95)``. Idempotent ``/predict`` /
``/generate`` requests that hit a dead or failing replica are
**failed over** — retried on a different replica under a
:class:`~keystone_tpu.resilience.retry.RetryPolicy` (injectable
clock/sleep — the failover matrix tests never sleep). With
``KEYSTONE_FLEET_HEDGE=1`` a request that has burned half its
``KEYSTONE_FLEET_DEADLINE_MS`` budget on one replica is **hedged**:
a second copy dispatches to another replica, the first success wins,
and the loser's response is discarded.

**Graceful degradation** — admission is bounded
(``KEYSTONE_FLEET_MAX_INFLIGHT``): past the bound the router sheds
with ``503 + Retry-After`` instead of queueing without bound, so a
degraded fleet degrades instead of collapsing.

**Rolling restart** — ``python -m keystone_tpu fleet restart`` (or
``POST /admin/restart``) restarts the tier one replica at a time over
the PR-7 SIGTERM-drain contract: mark draining (routing stops
immediately), SIGTERM (the replica finishes queued work and exits 0),
relaunch on the same port, wait for ``/healthz`` ok, then gate on a
**one-row probe** through ``/predict`` before the next replica
begins — deploys and PR-11 model rollouts are zero-downtime by
construction.

**Supervision** — replica processes are children of the router
process (the ``supervise`` machinery's command-template substitution
and SIGTERM→SIGKILL teardown phases, reused per replica): a replica
that dies is relaunched on its port up to ``--max-restarts`` times,
warm-started by the shared compile cache so cold start is seconds.

Every routing / failover / breaker / restart decision emits a
``resilience``-schema event (``action="fleet_*"``) plus ``fleet_*``
metrics counters, rendered by the ``observe top`` fleet panel and the
run report. The router injects ``X-Keystone-Trace`` on every hop so a
request's span tree crosses into the replica's
(``observe trace --request ID`` merges the per-process span files).

Deterministic chaos drills ride the fault plan: ``fleet.replica_kill``
(SIGKILL the routed replica mid-request), ``fleet.slow_replica``
(tail latency → hedge), ``fleet.conn_reset`` (failover) — all keyed by
router request id, replayable from a seed like every other site.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import os
import queue as _queue
import signal
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Sequence

from keystone_tpu.core.logging import get_logger
from keystone_tpu.observe import metrics as _metrics
from keystone_tpu.observe import spans as _spans
from keystone_tpu.resilience import faults as _faults
from keystone_tpu.resilience.emit import decision as _decision
from keystone_tpu.resilience.retry import RetryExhausted, RetryPolicy
from keystone_tpu.resilience.supervisor import _free_port, _substitute

logger = get_logger("keystone_tpu.serve.fleet")

ENV_REPLICAS = "KEYSTONE_FLEET_REPLICAS"
ENV_POLL_S = "KEYSTONE_FLEET_POLL_S"
ENV_BREAKER_FAILS = "KEYSTONE_FLEET_BREAKER_FAILS"
ENV_BREAKER_COOLDOWN_S = "KEYSTONE_FLEET_BREAKER_COOLDOWN_S"
ENV_MAX_INFLIGHT = "KEYSTONE_FLEET_MAX_INFLIGHT"
ENV_DEADLINE_MS = "KEYSTONE_FLEET_DEADLINE_MS"
ENV_HEDGE = "KEYSTONE_FLEET_HEDGE"

DEFAULT_REPLICAS = 3
DEFAULT_POLL_S = 0.5
DEFAULT_BREAKER_FAILS = 3
DEFAULT_BREAKER_COOLDOWN_S = 2.0
DEFAULT_MAX_INFLIGHT = 64
DEFAULT_DEADLINE_MS = 2000.0

#: replica lifecycle states (the fleet panel renders these verbatim)
STATES = ("starting", "up", "draining", "down")


def _env_num(name: str, default: float, cast=float, low=0.0):
    raw = os.environ.get(name, "").strip()
    if raw:
        try:
            val = cast(raw)
            if val > low:
                return val
        except ValueError:
            pass
    return cast(default)


def replicas_from_env() -> int:
    return _env_num(ENV_REPLICAS, DEFAULT_REPLICAS, int)


def hedge_from_env() -> bool:
    return os.environ.get(ENV_HEDGE, "").strip() in ("1", "true", "on")


class FleetShed(RuntimeError):
    """Admission refused: the router's bounded queue is full (503 +
    Retry-After — the graceful-degradation path)."""

    def __init__(self, msg: str, retry_after_s: int = 1):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class ReplicaUnavailable(ConnectionError):
    """One routed dispatch failed (connection error or replica 5xx) —
    transient by the retry classifier, so the policy fails the request
    over to a different replica."""


class NoReplicaAvailable(ConnectionError):
    """No replica is currently routable (all down/draining/tripped).
    Transient too: a relaunching replica may be seconds away."""


class ReplicaHTTPError(RuntimeError):
    """A replica answered a NON-retryable status (4xx): the request
    itself is bad — passed through to the client, never failed over."""

    def __init__(self, status: int, payload: dict):
        super().__init__(f"replica answered {status}")
        self.status = status
        self.payload = payload


class RestartInProgress(RuntimeError):
    """A rolling restart already holds the tier (409 — the tier must
    never drain two replicas at once)."""


class DeadlineExceeded(RuntimeError):
    """The request burned its whole fleet deadline budget (the 504
    path). Deliberately NOT an OSError/TimeoutError: the retry
    classifier treats those as transient, and retrying a request whose
    budget is gone only delays the inevitable answer."""


# ------------------------------------------------------------------ breaker


class CircuitBreaker:
    """Per-replica trip switch: ``fails`` consecutive failures open it,
    ``cooldown_s`` later it half-opens (traffic allowed as probes), one
    probe success closes it, one probe failure re-opens. The clock is
    injectable so the whole schedule unit-tests with zero sleeps;
    thread-safe (router worker threads record from many requests)."""

    def __init__(
        self,
        fails: int | None = None,
        cooldown_s: float | None = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.fails = (
            _env_num(ENV_BREAKER_FAILS, DEFAULT_BREAKER_FAILS, int)
            if fails is None
            else fails
        )
        self.cooldown_s = (
            _env_num(ENV_BREAKER_COOLDOWN_S, DEFAULT_BREAKER_COOLDOWN_S)
            if cooldown_s is None
            else cooldown_s
        )
        self.clock = clock
        self._lock = threading.Lock()
        self.state = "closed"
        self._consecutive = 0
        self._opened_at = 0.0

    def allow(self) -> bool:
        """May a request route here now? Open → False until the
        cooldown elapses, then the breaker half-opens and admits probe
        traffic (non-consuming: every request during half-open is a
        probe — the first verdict decides)."""
        with self._lock:
            if self.state == "open":
                if self.clock() - self._opened_at < self.cooldown_s:
                    return False
                self.state = "half_open"
            return True

    def record_success(self) -> None:
        with self._lock:
            if self.state == "open":
                # a success from a dispatch that was already in flight
                # when the breaker tripped says nothing about recovery —
                # only a half-open PROBE verdict may close the breaker,
                # after the cooldown has been served
                return
            was = self.state
            self.state = "closed"
            self._consecutive = 0
        if was == "half_open":
            _decision(
                "fleet_breaker_close", counter="fleet_breaker_close"
            )

    def record_failure(self) -> None:
        with self._lock:
            self._consecutive += 1
            if self.state == "half_open" or (
                self.state == "closed" and self._consecutive >= self.fails
            ):
                self.state = "open"
                self._opened_at = self.clock()
                tripped = True
            else:
                tripped = False
        if tripped:
            _decision(
                "fleet_breaker_open",
                counter="fleet_breaker_open",
                consecutive=self._consecutive,
            )

    def reset(self) -> None:
        """A fresh incarnation of the replica starts with a clean
        breaker (the old process's failures say nothing about it)."""
        with self._lock:
            self.state = "closed"
            self._consecutive = 0


# ------------------------------------------------------------------ replica


@dataclasses.dataclass
class Replica:
    """One replica server: lifecycle state, health snapshot, breaker,
    and (when the fleet manages processes) the child handle."""

    rid: int
    port: int
    host: str = "127.0.0.1"
    state: str = "starting"
    proc: subprocess.Popen | None = None
    breaker: CircuitBreaker = dataclasses.field(default_factory=CircuitBreaker)
    inflight: int = 0  # router-side concurrent dispatches
    queue_depth: float = 0.0  # replica-reported
    p95_ms: float = 0.0  # replica-reported queue p95
    draining: bool = False
    restarts: int = 0  # total fresh incarnations (crash + deploy)
    crash_restarts: int = 0  # relaunches after a CRASH — the budgeted kind
    poll_fails: int = 0
    routed: int = 0
    restarting: bool = False  # rolling restart owns the proc right now
    gave_up: bool = False  # relaunch budget exhausted (proc is None)
    last_exit: int | None = None

    def snapshot(self) -> dict:
        return {
            "rid": self.rid,
            "port": self.port,
            "state": self.state,
            "inflight": self.inflight,
            "queue_depth": self.queue_depth,
            "p95_ms": self.p95_ms,
            "breaker": self.breaker.state,
            "restarts": self.restarts,
            "routed": self.routed,
        }


def http_transport(
    replica: Replica,
    method: str,
    path: str,
    body: dict | None = None,
    timeout: float = 5.0,
    headers: dict | None = None,
) -> tuple[int, dict]:
    """The default dispatch: one HTTP request to the replica, JSON in
    and out. Connection-level failures raise OSError (the failover
    classifier's bread and butter); an unparseable body is a replica
    failure too, surfaced as :class:`ReplicaUnavailable`. A shed 503's
    ``Retry-After`` header lands in the payload as ``retry_after_s`` so
    the failover policy can honor the replica's explicit back-off
    (injected test transports emulate it by putting the key in the
    payload directly; 4xx answers pass through to the client untouched,
    so a 429's header would have nobody to honor it)."""
    conn = http.client.HTTPConnection(
        replica.host, replica.port, timeout=timeout
    )
    try:
        payload = None if body is None else json.dumps(body).encode()
        hdrs = {"Content-Type": "application/json"}
        if headers:
            hdrs.update(headers)
        conn.request(method, path, body=payload, headers=hdrs)
        resp = conn.getresponse()
        data = resp.read()
        try:
            parsed = json.loads(data) if data else {}
        except ValueError as e:
            raise ReplicaUnavailable(
                f"replica {replica.rid} answered unparseable JSON"
            ) from e
        if resp.status == 503:
            ra = resp.getheader("Retry-After")
            if ra is not None:
                try:
                    parsed.setdefault("retry_after_s", float(ra))
                except (ValueError, AttributeError):
                    pass
        return resp.status, parsed
    finally:
        conn.close()


# -------------------------------------------------------------------- fleet


class Fleet:
    """N replicas + the routing/supervision brain behind the router.

    ``cmd`` is the replica command template (``{port}`` / ``{rid}`` /
    ``{restart}`` placeholders, substituted per replica per incarnation
    — the ``supervise`` substitution rules); ``cmd=None`` gives an
    unmanaged fleet over externally-run servers on ``ports`` (the
    fake-transport unit tests and bring-your-own-orchestrator setups).
    ``transport`` / ``clock`` / ``retry_sleep`` are injectable so every
    routing, breaker, and failover decision tests without processes or
    sleeps.
    """

    def __init__(
        self,
        cmd: Sequence[str] | None = None,
        n: int | None = None,
        ports: Sequence[int] | None = None,
        host: str = "127.0.0.1",
        env: dict | None = None,
        transport: Callable[..., tuple[int, dict]] = http_transport,
        clock: Callable[[], float] = time.monotonic,
        retry_sleep: Callable[[float], None] = time.sleep,
        poll_s: float | None = None,
        grace_s: float = 15.0,
        boot_timeout_s: float = 180.0,
        max_restarts: int = 3,
        max_inflight: int | None = None,
        deadline_ms: float | None = None,
        hedge: bool | None = None,
        breaker_fails: int | None = None,
        breaker_cooldown_s: float | None = None,
        probe: tuple[str, dict] | None = None,
    ):
        self.cmd = list(cmd) if cmd else None
        n = replicas_from_env() if n is None else n
        if ports is not None:
            ports = list(ports)
        else:
            ports = [_free_port() for _ in range(n)]
        if n != len(ports):
            raise ValueError(f"{n} replicas but {len(ports)} ports")
        self.transport = transport
        self.clock = clock
        self.retry_sleep = retry_sleep
        self.poll_s = (
            _env_num(ENV_POLL_S, DEFAULT_POLL_S) if poll_s is None else poll_s
        )
        self.grace_s = grace_s
        self.boot_timeout_s = boot_timeout_s
        self.max_restarts = max_restarts
        self.max_inflight = (
            _env_num(ENV_MAX_INFLIGHT, DEFAULT_MAX_INFLIGHT, int)
            if max_inflight is None
            else max_inflight
        )
        self.deadline_s = (
            _env_num(ENV_DEADLINE_MS, DEFAULT_DEADLINE_MS)
            if deadline_ms is None
            else deadline_ms
        ) / 1e3
        self.hedge = hedge_from_env() if hedge is None else hedge
        self._env = dict(os.environ if env is None else env)
        self.replicas = [
            Replica(
                rid=i,
                port=p,
                host=host,
                breaker=CircuitBreaker(
                    breaker_fails, breaker_cooldown_s, clock=clock
                ),
            )
            for i, p in enumerate(ports)
        ]
        self._next_rid = 0
        self._lock = threading.Lock()
        # (next_rid below is the public view — request-keyed drills
        # key their fault specs off it instead of reaching into the
        # private counter)
        self._inflight = 0
        self._stop = threading.Event()
        self._restart_lock = threading.Lock()
        # the one-row probe the rolling restart gates on: configured, or
        # captured from the first successful routed request
        self._probe = probe
        self._threads: list[threading.Thread] = []
        self._stats_emitted: dict | None = None

    @property
    def next_rid(self) -> int:
        """The id the next admitted request will receive — the key
        surface for request-keyed chaos drills (``fleet.*:@k`` specs)."""
        with self._lock:
            return self._next_rid

    # ------------------------------------------------------------ lifecycle

    def start(self, wait_up: int = 0, timeout: float | None = None) -> None:
        """Spawn every managed replica (no-op for unmanaged) and start
        the poll + supervisor threads. ``wait_up=k`` blocks until at
        least k replicas reach ``up`` (or ``timeout``, default the boot
        timeout)."""
        _decision(
            "fleet_start",
            counter="fleet_starts",
            replicas=len(self.replicas),
            ports=[r.port for r in self.replicas],
        )
        if self.cmd is not None:
            for r in self.replicas:
                self._spawn(r)
        for name, target in (
            ("fleet-poll", self._poll_loop),
            ("fleet-supervisor", self._monitor_loop),
        ):
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        if wait_up:
            self.wait_up(wait_up, timeout)

    def wait_up(self, k: int, timeout: float | None = None) -> None:
        deadline = time.monotonic() + (
            self.boot_timeout_s if timeout is None else timeout
        )
        while time.monotonic() < deadline:
            if sum(1 for r in self.replicas if r.state == "up") >= k:
                return
            if self.cmd is not None and all(
                r.gave_up for r in self.replicas
            ):
                raise RuntimeError(
                    f"every replica failed to boot (exits: "
                    f"{[r.last_exit for r in self.replicas]})"
                )
            time.sleep(0.05)
        raise TimeoutError(
            f"fewer than {k} replicas up after {timeout or self.boot_timeout_s}s: "
            f"{[(r.rid, r.state) for r in self.replicas]}"
        )

    def _spawn(self, r: Replica) -> None:
        if self._stop.is_set():
            raise RuntimeError("fleet is shutting down")
        args = [
            _substitute(
                a,
                {"port": r.port, "rid": r.rid, "restart": r.restarts},
            )
            for a in self.cmd
        ]
        env = dict(self._env)
        env["KEYSTONE_FLEET_REPLICA"] = str(r.rid)
        r.proc = subprocess.Popen(args, env=env)
        r.poll_fails = 0
        r.gave_up = False
        r.draining = False
        r.breaker.reset()
        self._set_state(r, "starting")

    def _set_state(self, r: Replica, state: str) -> None:
        if r.state == state:
            return
        r.state = state
        _decision(
            "fleet_replica_state",
            counter="fleet_replica_transitions",
            counter_labels={"state": state},
            replica=r.rid,
            state=state,
            port=r.port,
            restarts=r.restarts,
        )

    def shutdown(self, grace_s: float | None = None) -> None:
        """Tear the tier down: SIGTERM every replica (drain), SIGKILL
        stragglers after the grace — the supervise teardown phases, per
        replica."""
        self._stop.set()
        grace = self.grace_s if grace_s is None else grace_s
        # serialize against a rolling restart: an in-flight _restart_one
        # aborts at its next _spawn/_wait_healthy stop check, and only
        # then do we snapshot the child list — no freshly spawned
        # replica can slip past the teardown as an orphan
        with self._restart_lock:
            procs = [r.proc for r in self.replicas if r.proc is not None]
        for p in procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + grace
        for p in procs:
            left = max(deadline - time.monotonic(), 0.0)
            try:
                p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                try:
                    p.kill()
                except OSError:
                    pass
                p.wait()
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = []
        _decision("fleet_stop", counter="fleet_stops")

    # ------------------------------------------------------- health polling

    def _poll_loop(self) -> None:
        while not self._stop.wait(self.poll_s):
            for r in self.replicas:
                if not self._stop.is_set():
                    self.poll_replica(r)
            self._emit_stats()

    def poll_replica(self, r: Replica) -> None:
        """One active health check: adopt the replica's reported p95 /
        queue depth, and drive the lifecycle — ``draining: true`` pulls
        it out of rotation the moment its SIGTERM drain begins, a
        healthy answer promotes ``starting``/``down`` to ``up``, and
        repeated poll failures on an ``up`` replica demote it."""
        if r.restarting:
            return  # the rolling restart owns this replica's lifecycle
        try:
            status, payload = self.transport(
                r, "GET", "/healthz", timeout=max(self.poll_s, 0.25)
            )
        except OSError:
            status, payload = 0, {}
        if status == 200:
            r.poll_fails = 0
            r.queue_depth = float(payload.get("queue_depth") or 0.0)
            r.p95_ms = float(payload.get("queue_p95_ms") or 0.0)
            r.draining = bool(payload.get("draining")) or (
                payload.get("status") == "draining"
            )
            if r.draining:
                if r.state in ("starting", "up"):
                    self._set_state(r, "draining")
            elif r.state in ("starting", "down"):
                self._set_state(r, "up")
        else:
            r.poll_fails += 1
            if r.state == "up" and r.poll_fails >= 3:
                self._set_state(r, "down")

    def _emit_stats(self) -> None:
        """A ``fleet_stats`` event whenever the counters moved — the
        file-tailing dashboards' (observe top) live numbers; the
        in-process registry has them continuously."""
        snap = _metrics.get_registry().snapshot()
        stats = {
            "routed": int(snap.get("fleet_routed", 0)),
            "shed": int(snap.get("fleet_shed", 0)),
            "failover": int(snap.get("fleet_failover", 0)),
            "hedges": int(snap.get("fleet_hedges", 0)),
            "replicas": {
                str(r.rid): r.state for r in self.replicas
            },
        }
        if stats != self._stats_emitted:
            self._stats_emitted = stats
            _decision("fleet_stats", **stats)

    # ----------------------------------------------------------- supervision

    def _monitor_loop(self) -> None:
        while not self._stop.wait(0.2):
            if self.cmd is None:
                continue
            for r in self.replicas:
                if (
                    self._stop.is_set()  # shutdown owns the children now
                    or r.proc is None
                    or r.restarting
                    or r.proc.poll() is None
                ):
                    continue
                rc = r.proc.returncode
                self._set_state(r, "down")
                if r.crash_restarts >= self.max_restarts:
                    # the budget counts CRASH relaunches only — routine
                    # rolling restarts must never spend it down
                    _decision(
                        "fleet_replica_giveup",
                        counter="fleet_replica_giveup",
                        replica=r.rid,
                        exit=rc,
                        restarts=r.crash_restarts,
                    )
                    r.last_exit = rc
                    r.gave_up = True
                    r.proc = None
                    continue
                r.last_exit = rc
                r.restarts += 1
                r.crash_restarts += 1
                _decision(
                    "fleet_replica_relaunch",
                    counter="fleet_replica_restarts",
                    replica=r.rid,
                    exit=rc,
                    restart=r.restarts,
                )
                logger.warning(
                    "replica %d (port %d) exited %s; relaunching "
                    "(crash restart %d/%d)",
                    r.rid, r.port, rc, r.crash_restarts,
                    self.max_restarts,
                )
                self._spawn(r)

    # -------------------------------------------------------------- routing

    def pick(self, exclude: Sequence[int] = ()) -> Replica | None:
        """Least-loaded SLO-aware choice among routable replicas:
        ``up``, not excluded, breaker admitting — minimize (router-side
        in-flight, reported queue depth, reported p95)."""
        candidates = [
            r
            for r in self.replicas
            if r.state == "up"
            and r.rid not in exclude
            and r.breaker.allow()
        ]
        if not candidates:
            return None
        return min(
            candidates,
            key=lambda r: (r.inflight, r.queue_depth, r.p95_ms, r.rid),
        )

    def _dispatch(
        self,
        r: Replica,
        path: str,
        body: dict,
        timeout: float,
        rid: int,
        parent: Any,
        drills: set[str],
        fails: list[int],
    ) -> dict:
        """One routed attempt on one replica: run the chaos drills
        scheduled for this request (first attempt only — ``drills`` is
        consumed), forward with the trace header, classify the answer.
        Success/failure lands on the replica's breaker either way;
        ``fails`` tallies this request's failed dispatches (the
        failover accounting — a hedge alone is not a failover)."""
        if "fleet.replica_kill" in drills:
            drills.discard("fleet.replica_kill")
            self.kill_replica(r)
        if "fleet.slow_replica" in drills:
            drills.discard("fleet.slow_replica")
            _metrics.get_registry().counter("fleet_slowed").inc()
            from keystone_tpu.serve.server import _slow_s

            time.sleep(_slow_s())
        sl = _spans.active_span_log()
        headers = None
        fctx = None
        if sl is not None:
            # pre-allocate the forward span's ids so the replica's
            # serve.request span (recorded in ITS process) can parent on
            # them — the router injects, server.py adopts
            fctx = _spans.make_context(parent)
            headers = {"X-Keystone-Trace": f"{fctx.trace}:{fctx.span}"}
        with self._lock:
            r.inflight += 1
        t0 = time.perf_counter()
        status_txt = None
        try:
            if "fleet.conn_reset" in drills:
                drills.discard("fleet.conn_reset")
                raise ConnectionResetError(
                    f"injected fault at 'fleet.conn_reset' "
                    f"(request {rid} → replica {r.rid})"
                )
            status, payload = self.transport(
                r, "POST", path, body, timeout=timeout, headers=headers
            )
            if status >= 500:
                # classified below (after the span records): the hop
                # span must say failed for a 5xx answer too
                status_txt = "failed"
        except OSError as e:
            status_txt = "failed"
            fails[0] += 1
            r.breaker.record_failure()
            raise ReplicaUnavailable(
                f"replica {r.rid} (port {r.port}): {e!r}"
            ) from e
        finally:
            with self._lock:
                r.inflight -= 1
            if sl is not None:
                sl.record_span(
                    "fleet.forward",
                    wall_s=time.perf_counter() - t0,
                    ctx=fctx,
                    parent=parent,
                    status=status_txt,
                    replica=r.rid,
                    rid=rid,
                )
        if status >= 500:
            fails[0] += 1
            r.breaker.record_failure()
            err = ReplicaUnavailable(
                f"replica {r.rid} answered {status}: "
                f"{payload.get('error', '')!r}"
            )
            ra = payload.get("retry_after_s")
            if isinstance(ra, (int, float)) and ra > 0:
                # an admission-shed 503's explicit back-off: the retry
                # policy waits AT LEAST this long before the next
                # failover attempt (the thundering-herd fix — N eager
                # retries against an overloaded tier re-create the
                # overload that shed them)
                err.retry_after_s = float(ra)
            raise err
        r.breaker.record_success()
        if status >= 400:
            raise ReplicaHTTPError(status, payload)
        r.routed += 1
        _metrics.get_registry().counter(
            "fleet_routed", replica=str(r.rid)
        ).inc()
        _metrics.get_registry().counter("fleet_routed").inc()
        return payload

    def _remaining(self, t0: float) -> float:
        left = self.deadline_s - (self.clock() - t0)
        if left <= 0:
            raise DeadlineExceeded(
                f"request exceeded its {self.deadline_s:.3f}s fleet "
                "deadline budget"
            )
        return left

    def _attempt(
        self,
        path: str,
        body: dict,
        rid: int,
        t0: float,
        tried: set[int],
        parent: Any,
        drills: set[str],
        fails: list[int],
    ) -> dict:
        """One failover attempt: pick a replica not yet tried (all
        tried → start over; a relaunched replica may be back), dispatch
        — hedged when enabled."""
        r = self.pick(exclude=tried)
        if r is None and tried:
            tried.clear()
            r = self.pick()
        if r is None:
            raise NoReplicaAvailable(
                "no routable replica (all down, draining, or tripped)"
            )
        tried.add(r.rid)
        if not self.hedge:
            return self._dispatch(
                r, path, body, self._remaining(t0), rid, parent,
                drills, fails,
            )
        return self._hedged(
            r, path, body, rid, t0, tried, parent, drills, fails
        )

    def _hedged(
        self,
        primary: Replica,
        path: str,
        body: dict,
        rid: int,
        t0: float,
        tried: set[int],
        parent: Any,
        drills: set[str],
        fails: list[int],
    ) -> dict:
        """Dispatch with a hedge: if the primary hasn't answered by the
        time the request has burned HALF its deadline budget, fire the
        same (idempotent) request at a second replica; first success
        wins, the loser's eventual answer is discarded."""
        outcome: _queue.SimpleQueue = _queue.SimpleQueue()
        reg = _metrics.get_registry()

        def run(rep: Replica, which: str) -> None:
            try:
                outcome.put(
                    (
                        which,
                        None,
                        self._dispatch(
                            rep, path, body, self._remaining(t0),
                            rid, parent, drills, fails,
                        ),
                    )
                )
            except BaseException as e:  # noqa: BLE001 — reported below
                outcome.put((which, e, None))

        threading.Thread(
            target=run, args=(primary, "primary"), daemon=True
        ).start()
        hedged = False
        half_wait = max(t0 + self.deadline_s / 2 - self.clock(), 0.0)
        try:
            which, err, payload = outcome.get(timeout=half_wait)
        except _queue.Empty:
            hedge_rep = self.pick(exclude=tried)
            if hedge_rep is None:
                try:
                    which, err, payload = outcome.get(
                        timeout=self._remaining(t0)
                    )
                except _queue.Empty:
                    raise DeadlineExceeded(
                        "request deadline elapsed waiting on its only "
                        "routable replica"
                    ) from None
            else:
                tried.add(hedge_rep.rid)
                hedged = True
                reg.counter("fleet_hedges").inc()
                _decision(
                    "fleet_hedge",
                    rid=rid,
                    primary=primary.rid,
                    hedge=hedge_rep.rid,
                )
                threading.Thread(
                    target=run, args=(hedge_rep, "hedge"), daemon=True
                ).start()
                failures: list[BaseException] = []
                while True:
                    try:
                        which, err, payload = outcome.get(
                            timeout=max(
                                t0 + self.deadline_s - self.clock(), 0.01
                            )
                        )
                    except _queue.Empty:
                        raise DeadlineExceeded(
                            "hedged request: neither replica answered "
                            "within the deadline budget"
                        ) from None
                    if err is None:
                        break
                    failures.append(err)
                    if len(failures) == 2:
                        raise failures[0]
        if err is not None:
            raise err
        if hedged:
            # only a race that actually ran counts a winner — the loser's
            # eventual answer (still in flight on the other thread) is
            # simply never read
            reg.counter("fleet_hedge_wins", which=which).inc()
        return payload

    def forward(self, path: str, body: dict, kind: str = "predict") -> dict:
        """Route one client request through the fleet: bounded
        admission, chaos-drill sites, then failover attempts under the
        retry policy. Returns the winning replica's payload; raises
        :class:`FleetShed` (503), :class:`ReplicaHTTPError` (pass the
        4xx through), or :class:`DeadlineExceeded` (504)."""
        reg = _metrics.get_registry()
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            if self._inflight >= self.max_inflight:
                reg.counter("fleet_shed").inc()
                _decision("fleet_shed", rid=rid, inflight=self._inflight)
                raise FleetShed(
                    f"router at capacity ({self.max_inflight} in flight); "
                    "retry shortly"
                )
            self._inflight += 1
        # the chaos drills scheduled for THIS request, evaluated exactly
        # once at admission: a failover retry of the same request must
        # not re-fire replica_kill (it would cascade through the fleet,
        # killing every replica the retry lands on)
        drills = {
            site
            for site in (
                "fleet.replica_kill",
                "fleet.slow_replica",
                "fleet.conn_reset",
            )
            if _faults.fire(site, rid)
        }
        t0 = self.clock()
        tried: set[int] = set()
        fails = [0]  # dispatches that actually failed for this request
        policy = RetryPolicy(
            max_attempts=max(len(self.replicas) + 1, 2),
            base_delay_s=0.02,
            max_delay_s=0.25,
            deadline_s=self.deadline_s,
            sleep=self.retry_sleep,
            monotonic=self.clock,
        )
        t_wall = time.perf_counter()
        try:
            with _spans.span("fleet.request", rid=rid, kind=kind) as ctx:
                try:
                    payload = policy.call(
                        lambda: self._attempt(
                            path, body, rid, t0, tried, ctx, drills, fails
                        ),
                        label="fleet.forward",
                    )
                except RetryExhausted as e:
                    raise FleetShed(
                        f"request {rid}: every failover attempt failed "
                        f"({e})",
                        retry_after_s=2,
                    ) from e
            if fails[0]:
                # the request survived an actual dispatch failure on
                # another replica — a hedge that merely raced two
                # healthy replicas is NOT a failover
                reg.counter("fleet_failover").inc()
                _decision(
                    "fleet_failover",
                    rid=rid,
                    tried=sorted(tried),
                    failed_dispatches=fails[0],
                )
            self._maybe_capture_probe(path, body)
            return payload
        finally:
            reg.timer("fleet_request_seconds").observe(
                time.perf_counter() - t_wall
            )
            with self._lock:
                self._inflight -= 1

    def _maybe_capture_probe(self, path: str, body: dict) -> None:
        """Remember a one-row version of the first successful request —
        the rolling restart's readiness gate (a replica that answers it
        provably serves real traffic, not just /healthz)."""
        if self._probe is not None:
            return
        probe = None
        if path == "/predict" and body.get("rows"):
            probe = (path, {"rows": body["rows"][:1]})
        elif path == "/generate" and body.get("prompt") is not None:
            probe = (path, {"prompt": body["prompt"], "max_new": 1})
        if probe is not None:
            self._probe = probe

    # ------------------------------------------------------- chaos drilling

    def kill_replica(self, r: Replica) -> None:
        """SIGKILL one replica — the ``fleet.replica_kill`` drill: no
        drain, no cleanup, exactly a machine dying mid-request. The
        monitor relaunches it; the in-flight request fails over."""
        _decision(
            "fleet_replica_kill",
            counter="fleet_replica_kills",
            replica=r.rid,
            port=r.port,
        )
        if r.proc is not None and r.proc.poll() is None:
            try:
                r.proc.kill()
            except OSError:
                pass

    # -------------------------------------------------------- rolling restart

    def rolling_restart(self, probe: tuple[str, dict] | None = None) -> dict:
        """Restart the tier one replica at a time with zero client
        impact: drain (routing stops immediately, the replica finishes
        queued work under the PR-7 SIGTERM contract), relaunch on the
        same port, wait healthy, pass the one-row probe — only then the
        next replica begins. Raises RuntimeError when a restart is
        already running (the tier must never drain two at once)."""
        if self.cmd is None:
            raise RuntimeError("unmanaged fleet: nothing to restart")
        if not self._restart_lock.acquire(blocking=False):
            raise RestartInProgress(
                "a rolling restart is already in progress"
            )
        probe = probe or self._probe
        done: list[int] = []
        t0 = time.monotonic()
        _decision(
            "fleet_restart",
            counter="fleet_rolling_restarts",
            stage="begin",
            replicas=len(self.replicas),
        )
        try:
            for r in list(self.replicas):
                self._restart_one(r, probe)
                done.append(r.rid)
            _decision(
                "fleet_restart",
                stage="done",
                replicas=done,
                wall_s=round(time.monotonic() - t0, 3),
            )
            return {
                "restarted": done,
                "wall_s": round(time.monotonic() - t0, 3),
            }
        except BaseException as e:
            _decision(
                "fleet_restart", stage="failed", replicas=done,
                error=repr(e),
            )
            raise
        finally:
            self._restart_lock.release()

    def _restart_one(self, r: Replica, probe: tuple[str, dict] | None) -> None:
        r.restarting = True  # the monitor must not race the relaunch
        try:
            _decision(
                "fleet_restart", stage="drain", replica=r.rid, port=r.port
            )
            self._set_state(r, "draining")
            old = r.proc
            if old is not None and old.poll() is None:
                try:
                    old.terminate()  # SIGTERM: drain queued work, exit 0
                except OSError:
                    pass
                try:
                    old.wait(timeout=self.grace_s)
                except subprocess.TimeoutExpired:
                    try:
                        old.kill()
                    except OSError:
                        pass
                    old.wait()
            r.restarts += 1
            self._spawn(r)
            self._wait_healthy(r)
            if probe is not None:
                path, body = probe
                status, payload = self.transport(
                    r, "POST", path, body, timeout=30.0
                )
                if status != 200:
                    raise RuntimeError(
                        f"replica {r.rid} failed its post-restart probe "
                        f"({path} → {status}: {payload})"
                    )
            self._set_state(r, "up")
            # a probed fresh deploy starts with a clean crash budget —
            # whatever the previous incarnation burned says nothing
            # about this one
            r.crash_restarts = 0
            _decision(
                "fleet_restart",
                stage="replica_up",
                replica=r.rid,
                restart=r.restarts,
                probed=probe is not None,
            )
        finally:
            r.restarting = False

    def _wait_healthy(self, r: Replica) -> None:
        deadline = time.monotonic() + self.boot_timeout_s
        while time.monotonic() < deadline:
            if self._stop.is_set():
                raise RuntimeError("fleet is shutting down")
            if r.proc is not None and r.proc.poll() is not None:
                raise RuntimeError(
                    f"replica {r.rid} exited {r.proc.returncode} during "
                    "restart boot"
                )
            try:
                status, payload = self.transport(
                    r, "GET", "/healthz", timeout=1.0
                )
            except OSError:
                status, payload = 0, {}
            if status == 200 and not payload.get("draining"):
                return
            time.sleep(0.1)
        raise TimeoutError(
            f"replica {r.rid} not healthy {self.boot_timeout_s}s after "
            "restart"
        )

    # --------------------------------------------------------------- health

    def snapshot(self) -> dict:
        """The router's /healthz body: tier status + per-replica rows +
        the routed/shed/failover counters."""
        snap = _metrics.get_registry().snapshot()
        up = sum(1 for r in self.replicas if r.state == "up")
        # status keys off ROUTABLE replicas: an `up` replica whose
        # breaker is open takes no traffic — a fleet of those is an
        # outage and must not report ok to a monitor
        routable = sum(
            1
            for r in self.replicas
            if r.state == "up" and r.breaker.state != "open"
        )
        t = snap.get("fleet_request_seconds") or {}
        out = {
            "status": (
                "ok"
                if routable == len(self.replicas)
                else ("degraded" if routable else "down")
            ),
            "replicas_up": up,
            "replicas_routable": routable,
            "replicas": [r.snapshot() for r in self.replicas],
            "routed": snap.get("fleet_routed", 0),
            "shed": snap.get("fleet_shed", 0),
            "failover": snap.get("fleet_failover", 0),
            "hedges": snap.get("fleet_hedges", 0),
            # the collector's discovery hook: the router advertises
            # every replica's scrape endpoint (down ones included — a
            # gap in a known series is signal, an unknown replica is
            # not), re-read by `observe collect --router` each cycle so
            # relaunches and rolling restarts surface automatically
            "scrape_targets": [
                f"http://{r.host}:{r.port}/metrics" for r in self.replicas
            ],
        }
        if t.get("count"):
            out["request_p50_ms"] = round(t.get("p50_s", 0.0) * 1e3, 3)
            out["request_p95_ms"] = round(t.get("p95_s", 0.0) * 1e3, 3)
        return out


# -------------------------------------------------------------- HTTP router


def _handler_for(fleet: Fleet):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # noqa: D102 — metrics are the record
            pass

        def _send(
            self, code: int, payload: dict, headers: dict | None = None
        ) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):  # noqa: N802 — stdlib API
            if self.path == "/healthz":
                return self._send(200, fleet.snapshot())
            if self.path == "/admin/fleet":
                return self._send(200, fleet.snapshot())
            if self.path == "/metrics":
                from keystone_tpu.serve.server import (
                    write_metrics_response,
                )

                return write_metrics_response(self)
            return self._send(
                404,
                {
                    "error": f"unknown path {self.path}",
                    "paths": [
                        "/predict", "/generate", "/healthz", "/metrics",
                        "/admin/fleet", "/admin/restart",
                    ],
                },
            )

        def do_POST(self):  # noqa: N802 — stdlib API
            try:
                n = int(self.headers.get("Content-Length") or 0)
                body = json.loads(self.rfile.read(n) or b"{}")
            except ValueError:
                return self._send(400, {"error": "invalid JSON body"})
            if self.path == "/admin/restart":
                try:
                    return self._send(200, fleet.rolling_restart())
                except RestartInProgress as e:
                    return self._send(409, {"error": str(e)})
                except Exception as e:  # noqa: BLE001 — must answer
                    # a mid-restart failure (failed probe, boot crash)
                    # is a server-side 500, NOT a retry-worthy 409
                    return self._send(500, {"error": repr(e)})
            if self.path not in ("/predict", "/generate"):
                return self._send(
                    404, {"error": f"unknown path {self.path}"}
                )
            kind = self.path.lstrip("/")
            try:
                payload = fleet.forward(self.path, body, kind=kind)
            except FleetShed as e:
                return self._send(
                    503,
                    {"error": str(e)},
                    headers={"Retry-After": str(e.retry_after_s)},
                )
            except ReplicaHTTPError as e:
                return self._send(e.status, e.payload)
            except (DeadlineExceeded, TimeoutError) as e:
                return self._send(504, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 — must answer
                logger.warning("fleet request failed: %r", e)
                return self._send(500, {"error": repr(e)})
            self._send(200, payload)

    return Handler


# --------------------------------------------------------------------- CLI


USAGE = """usage: python -m keystone_tpu fleet <model> [options] [-- serve-args...]
       python -m keystone_tpu fleet restart [--url URL]

<model> is anything `serve` accepts (a checkpoint path | mnist | lm);
everything after `--` is forwarded verbatim to every replica's serve
command (plus a per-replica --port).

options:
  --replicas N      replica servers (default KEYSTONE_FLEET_REPLICAS=3)
  --port P          router listen port (default 8200; 0 = OS-assigned)
  --host H          router bind address (default 127.0.0.1)
  --grace S         drain grace per teardown phase (default 15)
  --max-restarts R  relaunch budget per replica (default 3)
  --hedge           hedge a request at half its deadline budget
                    (default KEYSTONE_FLEET_HEDGE)
  --max-inflight N  admission bound before 503 + Retry-After
                    (default KEYSTONE_FLEET_MAX_INFLIGHT=64)
  --deadline-ms F   per-request fleet budget (default
                    KEYSTONE_FLEET_DEADLINE_MS=2000)
  --poll-s S        /healthz poll cadence (default KEYSTONE_FLEET_POLL_S=0.5)

`fleet restart` posts /admin/restart to a running router (default
--url http://127.0.0.1:8200) and waits for the rolling restart to
finish — one replica at a time, drain + relaunch + one-row probe.
"""


def _cli_restart(argv: list[str]) -> None:
    url = "http://127.0.0.1:8200"
    if "--url" in argv:
        i = argv.index("--url")
        if i + 1 >= len(argv):
            raise SystemExit("--url needs a value")
        url = argv[i + 1].rstrip("/")
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        url + "/admin/restart",
        data=b"{}",
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=600) as resp:
            payload = json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as e:
        detail = e.read().decode(errors="replace")[:500]
        raise SystemExit(
            f"rolling restart failed: {e.code} {detail}"
        ) from None
    except OSError as e:
        raise SystemExit(f"cannot reach router at {url}: {e}") from None
    print(
        f"rolling restart complete: replicas {payload.get('restarted')} "
        f"in {payload.get('wall_s')}s"
    )


def main(argv: list[str] | None = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        raise SystemExit(USAGE)
    if argv[0] == "restart":
        return _cli_restart(argv[1:])
    target = argv[0]
    args: dict = {}
    passthrough: list[str] = []
    flags = {"--hedge": "hedge"}
    valued = {
        "--replicas": "replicas", "--port": "port", "--host": "host",
        "--grace": "grace", "--max-restarts": "max_restarts",
        "--max-inflight": "max_inflight", "--deadline-ms": "deadline_ms",
        "--poll-s": "poll_s",
    }
    i = 1
    while i < len(argv):
        a = argv[i]
        if a == "--":
            passthrough = argv[i + 1 :]
            break
        if a in flags:
            args[flags[a]] = True
            i += 1
        elif a in valued:
            if i + 1 >= len(argv):
                raise SystemExit(f"{a} needs a value")
            args[valued[a]] = argv[i + 1]
            i += 2
        else:
            raise SystemExit(f"unknown option {a!r}\n{USAGE}")
    n = int(args.get("replicas", replicas_from_env()))
    # replicas inherit this environment: the platform the launcher
    # selected, and the compile cache every incarnation shares (the
    # variable if set, else the in-checkout directory each replica
    # resolves for itself — core/runtime.py), so a relaunch is warm
    env = dict(os.environ)
    if n > 1 and env.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        # a chip belongs to one process and every replica is a process
        # that takes the default device: replica 2 of a one-chip machine
        # cannot have the chip, and on a larger host all N would contend
        # for chip 0. Refuse, don't balance a live replica against N-1
        # crash-looping ones.
        raise SystemExit(
            f"fleet: --replicas {n} needs one accelerator per replica "
            "process, and this router assigns none (a chip belongs to "
            "one process). Run --replicas 1 on the chip, or ask for the "
            "CPU with JAX_PLATFORMS=cpu for a routing/failover drill. "
            "Replicas as devices of ONE process is the ROADMAP item "
            "that lifts this."
        )
    cmd = [
        sys.executable, "-m", "keystone_tpu", "serve", target,
        "--port", "{port}", *passthrough,
    ]
    fleet = Fleet(
        cmd=cmd,
        n=n,
        env=env,
        grace_s=float(args.get("grace", 15.0)),
        max_restarts=int(args.get("max_restarts", 3)),
        max_inflight=(
            int(args["max_inflight"]) if "max_inflight" in args else None
        ),
        deadline_ms=(
            float(args["deadline_ms"]) if "deadline_ms" in args else None
        ),
        hedge=True if args.get("hedge") else None,
        poll_s=float(args["poll_s"]) if "poll_s" in args else None,
    )
    host = str(args.get("host", "127.0.0.1"))
    port = int(args.get("port", 8200))
    httpd = ThreadingHTTPServer((host, port), _handler_for(fleet))
    port = httpd.server_address[1]
    t0 = time.perf_counter()
    try:
        fleet.start()
        print(
            f"fleet: router on http://{host}:{port}, {n} replica(s) on "
            f"ports {[r.port for r in fleet.replicas]} — booting",
            flush=True,
        )
        fleet.wait_up(1)
    except BaseException:
        # a failed or interrupted boot (timeout, Ctrl-C before the
        # signal handlers below exist) must not strand N replica
        # processes holding their ports with no supervisor
        fleet.shutdown(grace_s=5.0)
        httpd.server_close()
        raise
    print(
        f"fleet: first replica up after {time.perf_counter() - t0:.1f}s "
        f"(states: {[r.state for r in fleet.replicas]})",
        flush=True,
    )

    def _term(signum, frame):
        logger.info("signal %d: draining the fleet", signum)

        def stop():
            fleet.shutdown()
            httpd.shutdown()

        threading.Thread(target=stop, daemon=True).start()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    try:
        httpd.serve_forever(poll_interval=0.1)
    finally:
        httpd.server_close()
    logger.info("fleet router stopped cleanly")


if __name__ == "__main__":
    main()
