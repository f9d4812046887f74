"""serve/fleet tests: circuit breaker with injected clock (zero
sleeps), least-loaded SLO-aware routing, per-request failover under the
retry policy, bounded admission (503 + Retry-After), hedged dispatch at
half-deadline, the three fleet chaos-drill fault sites, the observe-top
fleet panel — and the process-level drills: SIGKILL a replica mid-burst
with zero client failures + supervisor relaunch, and a rolling restart
under a threaded burst with zero dropped requests, against both the
stdlib stub replica (fast) and the real mnist serve replicas (the
full-stack acceptance drill, incl. the cross-process trace tree)."""

import json
import os
import pathlib
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from keystone_tpu.observe import events as observe_events
from keystone_tpu.observe import metrics as observe_metrics
from keystone_tpu.observe import spans as observe_spans
from keystone_tpu.resilience import faults
from keystone_tpu.serve.fleet import (
    CircuitBreaker,
    Fleet,
    FleetShed,
    NoReplicaAvailable,
    ReplicaHTTPError,
    _handler_for,
)

STUB = str(pathlib.Path(__file__).parent / "fleet_replica_worker.py")


def _counter(name: str) -> float:
    return observe_metrics.get_registry().snapshot().get(name, 0)


def _counter_sum(prefix: str) -> float:
    snap = observe_metrics.get_registry().snapshot()
    return sum(
        v
        for k, v in snap.items()
        if k.startswith(prefix) and isinstance(v, (int, float))
    )


class Clock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _ok_transport(payload=None):
    payload = payload or {"predictions": [[1.0]]}

    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        if method == "GET":
            return 200, {"draining": False, "queue_depth": 0.0}
        return 200, {**payload, "replica": replica.rid}

    return transport


def _unit_fleet(n=3, transport=None, **kw):
    """An unmanaged fleet over a fake transport: no processes, no
    threads, no sleeps (retry backoff is swallowed)."""
    kw.setdefault("deadline_ms", 500.0)
    kw.setdefault("hedge", False)
    kw.setdefault("max_inflight", 16)
    fleet = Fleet(
        cmd=None,
        n=n,
        transport=transport or _ok_transport(),
        retry_sleep=lambda s: None,
        **kw,
    )
    for r in fleet.replicas:
        r.state = "up"
    return fleet


# ---------------------------------------------------------------------------
# circuit breaker: trip / half-open / recover, injected clock, zero sleeps


def test_breaker_trips_half_opens_and_recovers_with_injected_clock():
    clock = Clock()
    b = CircuitBreaker(fails=3, cooldown_s=5.0, clock=clock)
    assert b.allow() and b.state == "closed"
    b.record_failure()
    b.record_failure()
    assert b.state == "closed" and b.allow()
    b.record_failure()  # third consecutive: trip
    assert b.state == "open" and not b.allow()
    # a stale success from a dispatch already in flight at trip time
    # must NOT bypass the cooldown — only a half-open probe may close
    b.record_success()
    assert b.state == "open" and not b.allow()
    clock.t = 4.99
    assert not b.allow()
    clock.t = 5.0  # cooldown over: half-open, probe traffic admitted
    assert b.allow() and b.state == "half_open"
    b.record_failure()  # the probe failed: re-open for a fresh cooldown
    assert b.state == "open" and not b.allow()
    clock.t = 9.0
    assert not b.allow()
    clock.t = 10.0
    assert b.allow() and b.state == "half_open"
    b.record_success()  # the probe succeeded: closed, counters reset
    assert b.state == "closed"
    b.record_failure()
    b.record_failure()
    b.record_success()  # success resets the consecutive count
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"


def test_breaker_success_mid_streak_prevents_trip():
    clock = Clock()
    b = CircuitBreaker(fails=2, cooldown_s=1.0, clock=clock)
    for _ in range(5):
        b.record_failure()
        b.record_success()
    assert b.state == "closed"


# ---------------------------------------------------------------------------
# routing: least-loaded SLO-aware pick


def test_pick_least_loaded_and_skips_unroutable():
    fleet = _unit_fleet(n=3)
    r0, r1, r2 = fleet.replicas
    r0.inflight, r1.queue_depth, r2.p95_ms = 1, 5.0, 2.0
    assert fleet.pick().rid == 2  # lowest (inflight, queue, p95)
    r2.state = "draining"  # draining replicas take no new work
    assert fleet.pick().rid == 1  # inflight 0 beats inflight 1
    r1.breaker.state = "open"
    r1.breaker._opened_at = time.monotonic() + 1e6  # stays open
    assert fleet.pick().rid == 0
    r0.state = "down"
    assert fleet.pick() is None
    assert fleet.pick(exclude=(0, 1, 2)) is None


def test_pick_excludes_already_tried():
    fleet = _unit_fleet(n=2)
    assert fleet.pick(exclude=(0,)).rid == 1
    assert fleet.pick(exclude=(1,)).rid == 0


# ---------------------------------------------------------------------------
# failover: a dead replica's request is retried on a different one


def test_forward_fails_over_to_healthy_replica_zero_sleeps():
    calls = []

    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        calls.append(replica.rid)
        if replica.rid == 0:
            raise ConnectionRefusedError("replica 0 is dead")
        return 200, {"predictions": [[2.0]], "replica": replica.rid}

    fleet = _unit_fleet(n=3, transport=transport)
    failover0 = _counter("fleet_failover")
    t0 = time.perf_counter()
    out = fleet.forward("/predict", {"rows": [[1.0]]})
    assert time.perf_counter() - t0 < 1.0  # injected sleep: no backoff wait
    assert out["replica"] != 0
    assert calls[0] == 0  # the preferred replica was tried first
    assert _counter("fleet_failover") == failover0 + 1
    # passive detection landed on the breaker
    assert fleet.replicas[0].breaker._consecutive >= 1


def test_forward_replica_5xx_fails_over():
    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        if replica.rid == 0:
            return 500, {"error": "device fell over"}
        return 200, {"ok": True, "replica": replica.rid}

    fleet = _unit_fleet(n=2, transport=transport)
    out = fleet.forward("/predict", {"rows": [[1.0]]})
    assert out["replica"] == 1


def test_forward_4xx_passes_through_without_failover():
    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        return 400, {"error": "row shape"}

    fleet = _unit_fleet(n=2, transport=transport)
    failover0 = _counter("fleet_failover")
    with pytest.raises(ReplicaHTTPError) as exc:
        fleet.forward("/predict", {"rows": [[1.0]]})
    assert exc.value.status == 400
    assert _counter("fleet_failover") == failover0
    # a 4xx is the CLIENT's fault: the replica answered, stays healthy
    assert fleet.replicas[0].breaker.state == "closed"


def test_forward_all_replicas_down_sheds_as_retryable():
    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        raise ConnectionRefusedError("nobody home")

    fleet = _unit_fleet(n=2, transport=transport)
    with pytest.raises(FleetShed):
        fleet.forward("/predict", {"rows": [[1.0]]})


def test_deadline_exceeded_is_not_retried_and_maps_to_504():
    """A request whose fleet budget is gone must answer 504, not spin
    through the retry policy: DeadlineExceeded is deliberately NOT in
    the transient family (TimeoutError would be — it is an OSError)."""
    from keystone_tpu.resilience.retry import is_transient
    from keystone_tpu.serve.fleet import DeadlineExceeded

    clock = Clock()
    fleet = _unit_fleet(n=1, clock=clock, deadline_ms=100.0)
    t0 = clock()
    assert fleet._remaining(t0) == pytest.approx(0.1)
    clock.t = 0.2
    with pytest.raises(DeadlineExceeded) as exc:
        fleet._remaining(t0)
    assert not is_transient(exc.value)


def test_no_replica_available_when_all_draining():
    fleet = _unit_fleet(n=2)
    for r in fleet.replicas:
        r.state = "draining"
    with pytest.raises((FleetShed, NoReplicaAvailable)):
        fleet.forward("/predict", {"rows": [[1.0]]})


# ---------------------------------------------------------------------------
# chaos-drill fault sites


def test_fleet_fault_sites_registered_and_validate():
    for site in ("fleet.replica_kill", "fleet.slow_replica", "fleet.conn_reset"):
        assert site in faults.SITES
    specs = faults.parse_spec(
        "fleet.replica_kill:@10:0,fleet.conn_reset:@3:1,"
        "fleet.slow_replica:0.5:7"
    )
    assert [s.site for s in specs] == [
        "fleet.replica_kill", "fleet.conn_reset", "fleet.slow_replica",
    ]


def test_conn_reset_drill_fails_over_exactly_the_keyed_request():
    calls = []

    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        calls.append(replica.rid)
        return 200, {"ok": True, "replica": replica.rid}

    fleet = _unit_fleet(n=2, transport=transport)
    faults.configure("fleet.conn_reset:@1:0")
    try:
        fleet.forward("/predict", {"rows": [[1.0]]})  # rid 0: clean
        assert len(calls) == 1
        out = fleet.forward("/predict", {"rows": [[1.0]]})  # rid 1: reset
        # the reset consumed the first attempt; the retry landed on the
        # OTHER replica and succeeded
        assert out["ok"] is True
        assert len(calls) == 2  # reset raised before transport ran
    finally:
        faults.reset()


def test_replica_kill_drill_fires_once_never_on_the_failover_retry():
    """The cascade guard: a request whose first dispatch killed its
    replica must NOT re-fire the kill on the retry — otherwise one
    keyed drill would put down every replica the failover walks."""
    killed = []

    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        if replica.rid in killed:
            raise ConnectionResetError(f"replica {replica.rid} is dead")
        return 200, {"ok": True, "replica": replica.rid}

    fleet = _unit_fleet(n=3, transport=transport)
    fleet.kill_replica = lambda r: killed.append(r.rid)  # no real procs
    faults.configure("fleet.replica_kill:@0:0")
    try:
        out = fleet.forward("/predict", {"rows": [[1.0]]})
        assert len(killed) == 1  # exactly one kill, despite the retry
        assert out["replica"] not in killed
    finally:
        faults.reset()


# ---------------------------------------------------------------------------
# bounded admission: shed with Retry-After instead of collapsing


def test_admission_bound_sheds_with_retry_after():
    gate = threading.Event()

    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        gate.wait(timeout=10.0)
        return 200, {"ok": True}

    fleet = _unit_fleet(n=1, transport=transport, max_inflight=1)
    shed0 = _counter("fleet_shed")
    results = {}

    def first():
        results["first"] = fleet.forward("/predict", {"rows": [[1.0]]})

    t = threading.Thread(target=first)
    t.start()
    deadline = time.time() + 5.0
    while fleet._inflight < 1 and time.time() < deadline:
        time.sleep(0.005)
    with pytest.raises(FleetShed) as exc:
        fleet.forward("/predict", {"rows": [[1.0]]})
    assert exc.value.retry_after_s >= 1
    gate.set()
    t.join(timeout=10.0)
    assert results["first"]["ok"] is True
    assert _counter("fleet_shed") == shed0 + 1


# ---------------------------------------------------------------------------
# hedged dispatch: fire at half-deadline, first success wins


def test_hedge_fires_at_half_deadline_and_winner_is_the_fast_replica():
    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        if replica.rid == 0:
            time.sleep(0.5)  # the slow primary
        return 200, {"replica": replica.rid}

    fleet = _unit_fleet(n=2, transport=transport, hedge=True, deadline_ms=400.0)
    hedges0 = _counter("fleet_hedges")
    wins0 = _counter_sum("fleet_hedge_wins")
    t0 = time.perf_counter()
    out = fleet.forward("/predict", {"rows": [[1.0]]})
    wall = time.perf_counter() - t0
    # the hedge won: answered well before the slow primary's 0.5s, and
    # the primary's eventual answer was discarded
    assert out["replica"] == 1
    assert wall < 0.45
    assert _counter("fleet_hedges") == hedges0 + 1
    assert _counter_sum("fleet_hedge_wins") == wins0 + 1


def test_hedge_does_not_fire_for_a_fast_primary():
    fleet = _unit_fleet(n=2, transport=_ok_transport(), hedge=True,
                        deadline_ms=2000.0)
    hedges0 = _counter("fleet_hedges")
    wins0 = _counter_sum("fleet_hedge_wins")
    fleet.forward("/predict", {"rows": [[1.0]]})
    assert _counter("fleet_hedges") == hedges0
    assert _counter_sum("fleet_hedge_wins") == wins0


def test_slow_replica_drill_triggers_the_hedge(monkeypatch):
    monkeypatch.setenv("KEYSTONE_SERVE_SLOW_MS", "500")

    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        return 200, {"replica": replica.rid}

    fleet = _unit_fleet(n=2, transport=transport, hedge=True, deadline_ms=300.0)
    faults.configure("fleet.slow_replica:@0:0")
    try:
        hedges0 = _counter("fleet_hedges")
        out = fleet.forward("/predict", {"rows": [[1.0]]})
        # the injected 500ms on the primary burned the 150ms half-budget:
        # the hedge fired and won on the other replica
        assert out["replica"] == 1
        assert _counter("fleet_hedges") == hedges0 + 1
    finally:
        faults.reset()


# ---------------------------------------------------------------------------
# lifecycle: the health poll drives starting → up → draining → down


def test_poll_replica_drives_the_lifecycle():
    answers = {"status": 200, "payload": {"draining": False,
                                          "queue_depth": 2.0,
                                          "queue_p95_ms": 3.5}}

    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        if answers["status"] == 0:
            raise ConnectionRefusedError("poll failed")
        return answers["status"], answers["payload"]

    fleet = Fleet(cmd=None, n=1, transport=transport,
                  retry_sleep=lambda s: None)
    (r,) = fleet.replicas
    assert r.state == "starting"
    fleet.poll_replica(r)
    assert r.state == "up"
    assert r.queue_depth == 2.0 and r.p95_ms == 3.5
    # the moment the replica reports draining, routing stops — long
    # before its socket ever closes
    answers["payload"] = {"draining": True}
    fleet.poll_replica(r)
    assert r.state == "draining"
    assert fleet.pick() is None
    # back healthy (e.g. restart relaunched it)
    answers["payload"] = {"draining": False}
    r.state = "starting"
    fleet.poll_replica(r)
    assert r.state == "up"
    # repeated poll failures on an up replica demote it
    answers["status"] = 0
    for _ in range(3):
        fleet.poll_replica(r)
    assert r.state == "down"


def test_serve_healthz_reports_draining_the_moment_drain_begins():
    """The PR-7 server satellite: the ``draining`` flag flips on the
    stop event itself — the router's poll sees it while the batcher is
    still draining, before any connection failure."""
    from keystone_tpu.serve.server import ServeApp

    class _Noop:
        buckets = (1,)

        def __call__(self, batch):
            return batch

    app = ServeApp(exported=_Noop(), deadline_ms=1.0)
    try:
        assert app.health()["draining"] is False
        app._stop.set()
        health = app.health()
        assert health["draining"] is True
        assert health["status"] == "draining"
    finally:
        app.shutdown()


# ---------------------------------------------------------------------------
# trace propagation: the router injects, the replica adopts


def test_router_injects_trace_header_and_serve_adopts_parent(tmp_path):
    seen = {}

    def transport(replica, method, path, body=None, timeout=5.0, headers=None):
        seen["headers"] = headers
        return 200, {"ok": True}

    fleet = _unit_fleet(n=1, transport=transport)
    with observe_events.run(base_dir=str(tmp_path)):
        fleet.forward("/predict", {"rows": [[1.0]]})
    raw = (seen["headers"] or {}).get("X-Keystone-Trace")
    assert raw and ":" in raw
    trace_id, _, span_id = raw.partition(":")
    recs = observe_spans.read_spans(str(tmp_path))
    by_name = {r["name"]: r for r in recs}
    # the hop span carries exactly the ids the header advertised, under
    # the request's root trace
    assert by_name["fleet.forward"]["trace"] == trace_id
    assert by_name["fleet.forward"]["span"] == span_id
    assert by_name["fleet.request"]["trace"] == trace_id
    # and a replica-side serve.request span parented on those ids joins
    # the same tree (server.py's header adoption, exercised in-process)
    from keystone_tpu.observe.spans import SpanContext

    with observe_events.run(base_dir=str(tmp_path)) as log:
        sl = observe_spans.active_span_log()
        sl.record_span(
            "serve.request",
            wall_s=0.001,
            parent=SpanContext(trace_id, span_id),
        )
        merged = observe_spans.read_spans_all(str(tmp_path))
    trees = observe_spans.build_trees(
        [r for r in merged if r.get("trace") == trace_id]
    )
    roots = trees[trace_id]
    names = set()
    stack = list(roots)
    while stack:
        node = stack.pop()
        names.add(node["rec"]["name"])
        stack.extend(node["children"])
    assert {"fleet.request", "fleet.forward", "serve.request"} <= names
    # one tree: serve.request is NOT a root (it hangs off the hop span)
    assert all(r["rec"]["name"] == "fleet.request" for r in roots)


# ---------------------------------------------------------------------------
# observe top: the fleet panel


def test_observe_top_fleet_panel(tmp_path):
    from keystone_tpu.observe import top

    events = [
        {"ts": 1.0, "event": "resilience", "action": "fleet_replica_state",
         "replica": 0, "state": "up", "port": 8101, "restarts": 0},
        {"ts": 1.1, "event": "resilience", "action": "fleet_replica_state",
         "replica": 1, "state": "up", "port": 8102, "restarts": 0},
        {"ts": 2.0, "event": "resilience", "action": "fleet_replica_state",
         "replica": 1, "state": "down", "port": 8102, "restarts": 1},
        {"ts": 2.5, "event": "resilience", "action": "fleet_failover",
         "rid": 7, "tried": [1, 0]},
        {"ts": 3.0, "event": "resilience", "action": "fleet_stats",
         "routed": 40, "shed": 2, "failover": 1, "hedges": 0,
         "replicas": {"0": "up", "1": "down"}},
        {"ts": 3.5, "event": "resilience", "action": "retry", "label": "x"},
    ]
    state = top.summarize([], events)
    fl = state["fleet"]
    assert fl["routed"] == 40 and fl["shed"] == 2 and fl["failover"] == 1
    assert fl["replicas"]["0"]["state"] == "up"
    assert fl["replicas"]["1"]["state"] == "down"
    assert fl["replicas"]["1"]["restarts"] == 1
    assert fl["events"] == {"fleet_failover": 1}
    # fleet actions stay OUT of the generic resilience counter line
    assert state["resilience"] == {"retry": 1}
    screen = top.render(state, str(tmp_path))
    assert "fleet: 1/2 up  routed=40  shed=2  failover=1" in screen
    assert "r0 :8101  up" in screen
    assert "r1 :8102  down  restarts=1" in screen


def test_report_renders_fleet_section(tmp_path):
    from keystone_tpu.observe import report

    with observe_events.run(base_dir=str(tmp_path)) as log:
        log.emit("resilience", phase="resilience",
                 action="fleet_replica_state", replica=0, state="up")
        log.emit("resilience", phase="resilience", action="fleet_failover",
                 rid=3, tried=[0, 1])
        log.emit("resilience", phase="resilience", action="fleet_restart",
                 phase_name="done")
    text = report.render(str(tmp_path))
    assert "serving fleet (router / replica lifecycle):" in text
    assert "failover=1" in text
    assert "fleet_failover: rid=3" in text


# ---------------------------------------------------------------------------
# the HTTP router surface


@pytest.fixture
def http_router(free_tcp_port):
    from http.server import ThreadingHTTPServer

    fleet = _unit_fleet(n=2, transport=_ok_transport({"predictions": [[3.0]]}))
    httpd = ThreadingHTTPServer(("127.0.0.1", free_tcp_port), _handler_for(fleet))
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield fleet, f"http://127.0.0.1:{free_tcp_port}"
    httpd.shutdown()
    httpd.server_close()


def _post(url, body, timeout=10.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_router_http_predict_healthz_metrics(http_router):
    fleet, base = http_router
    status, payload = _post(base + "/predict", {"rows": [[1.0, 2.0]]})
    assert status == 200 and payload["predictions"] == [[3.0]]
    with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
        health = json.loads(r.read())
    assert health["status"] == "ok" and health["replicas_up"] == 2
    assert {row["state"] for row in health["replicas"]} == {"up"}
    assert health["routed"] >= 1
    with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    assert "fleet_routed" in text


def test_router_http_shed_answers_503_with_retry_after(http_router):
    fleet, base = http_router
    fleet.max_inflight = 0  # everything sheds
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(base + "/predict", {"rows": [[1.0]]})
    assert exc.value.code == 503
    assert int(exc.value.headers["Retry-After"]) >= 1


def test_fleet_cli_help_and_restart_url_error():
    from keystone_tpu.serve import fleet as fleet_mod

    with pytest.raises(SystemExit) as exc:
        fleet_mod.main(["--help"])
    assert "fleet" in str(exc.value)
    # restart against a dead router: a clean error, not a traceback
    with pytest.raises(SystemExit, match="cannot reach router"):
        fleet_mod.main(["restart", "--url", "http://127.0.0.1:9"])


# ---------------------------------------------------------------------------
# process drills against the stdlib stub replica (seconds, no jax boot)


@pytest.fixture
def stub_fleet(tmp_path):
    env = {**os.environ, "STUB_DRAIN_S": "0.1"}
    fleet = Fleet(
        cmd=[sys.executable, STUB, "--port", "{port}"],
        n=3,
        env=env,
        poll_s=0.1,
        grace_s=5.0,
        boot_timeout_s=30.0,
        deadline_ms=5000.0,
        max_inflight=64,
        breaker_fails=3,
        breaker_cooldown_s=0.5,
    )
    try:
        fleet.start(wait_up=3, timeout=30.0)
        yield fleet
    finally:
        fleet.shutdown(grace_s=5.0)


def _stub_pids(fleet):
    out = {}
    for r in fleet.replicas:
        status, payload = fleet.transport(r, "GET", "/healthz", timeout=5.0)
        assert status == 200
        out[r.rid] = payload["pid"]
    return out


def _burst(fleet, stop, errors, ok):
    while not stop.is_set():
        try:
            payload = fleet.forward("/predict", {"rows": [[1.0, 2.0]]})
            assert payload["predictions"] == [[2.0, 4.0]]
            ok.append(1)
        except Exception as e:  # noqa: BLE001 — the assertion IS the tally
            errors.append(repr(e))
        time.sleep(0.005)


def test_stub_fleet_sigkill_failover_and_relaunch(stub_fleet):
    """SIGKILL one replica under load: zero client failures (failover
    absorbs the death) and the supervisor relaunches it back to up."""
    fleet = stub_fleet
    pids0 = _stub_pids(fleet)
    stop, errors, ok = threading.Event(), [], []
    threads = [
        threading.Thread(target=_burst, args=(fleet, stop, errors, ok))
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    try:
        time.sleep(0.2)
        victim = fleet.replicas[1]
        fleet.kill_replica(victim)
        # the supervisor must bring it back to `up` with a fresh pid
        deadline = time.time() + 60.0
        while time.time() < deadline and not (
            victim.state == "up" and victim.restarts >= 1
        ):
            time.sleep(0.05)
        time.sleep(0.3)  # keep the burst running on the healed tier
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
    assert errors == []
    assert len(ok) >= 20
    assert victim.state == "up" and victim.restarts >= 1
    assert victim.crash_restarts >= 1  # a crash spends the crash budget
    assert _stub_pids(fleet)[victim.rid] != pids0[victim.rid]


def test_stub_fleet_rolling_restart_under_load_zero_errors(stub_fleet):
    """The zero-downtime deploy: a full rolling restart while a
    threaded burst runs — every replica gets a fresh process, gated on
    the one-row probe, and not one client request fails."""
    fleet = stub_fleet
    pids0 = _stub_pids(fleet)
    stop, errors, ok = threading.Event(), [], []
    threads = [
        threading.Thread(target=_burst, args=(fleet, stop, errors, ok))
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    try:
        time.sleep(0.1)  # traffic first, so the probe is captured
        assert fleet._probe is not None
        result = fleet.rolling_restart()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
    assert errors == []
    assert sorted(result["restarted"]) == [0, 1, 2]
    pids1 = _stub_pids(fleet)
    assert all(pids1[rid] != pids0[rid] for rid in pids0)
    assert all(r.state == "up" and r.restarts >= 1 for r in fleet.replicas)
    # a deliberate deploy restart never spends the CRASH-relaunch
    # budget — routine rolling restarts must not degrade the tier's
    # ability to survive real crashes later
    assert all(r.crash_restarts == 0 for r in fleet.replicas)
    # the probe really hit each fresh incarnation before it took traffic
    for r in fleet.replicas:
        status, payload = fleet.transport(r, "GET", "/healthz", timeout=5.0)
        assert payload["requests"] >= 1


def test_stub_fleet_restart_cli_roundtrip(stub_fleet, free_tcp_port, capsys):
    """`python -m keystone_tpu fleet restart --url ...` drives a real
    router's /admin/restart end to end."""
    from http.server import ThreadingHTTPServer

    from keystone_tpu.serve import fleet as fleet_mod

    fleet = stub_fleet
    fleet.forward("/predict", {"rows": [[1.0]]})  # capture the probe
    httpd = ThreadingHTTPServer(
        ("127.0.0.1", free_tcp_port), _handler_for(fleet)
    )
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        fleet_mod.main(
            ["restart", "--url", f"http://127.0.0.1:{free_tcp_port}"]
        )
    finally:
        httpd.shutdown()
        httpd.server_close()
    out = capsys.readouterr().out
    assert "rolling restart complete" in out
    assert all(r.restarts >= 1 for r in fleet.replicas)


# ---------------------------------------------------------------------------
# the full-stack acceptance drill: real mnist serve replicas


@pytest.fixture(scope="module")
def mnist_fleet(tmp_path_factory):
    base = tmp_path_factory.mktemp("mnist_fleet")
    obs = base / "obs"
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "KEYSTONE_OBSERVE_DIR": str(obs),
        "KEYSTONE_SERVE_DEADLINE_MS": "5",
    }
    fleet = Fleet(
        cmd=[
            sys.executable, "-m", "keystone_tpu", "serve", "mnist",
            "--port", "{port}", "--synthetic", "96", "--num-ffts", "2",
            "--buckets", "1,4",
        ],
        n=3,
        env=env,
        poll_s=0.2,
        grace_s=20.0,
        boot_timeout_s=240.0,
        deadline_ms=20000.0,
        max_inflight=64,
    )
    try:
        fleet.start(wait_up=3, timeout=240.0)
        yield fleet, obs
    finally:
        fleet.shutdown(grace_s=10.0)


def _mnist_burst(fleet, n, kill_at=None):
    """n /predict requests across worker threads; returns (ok, errors)."""
    import concurrent.futures

    if kill_at is not None:
        faults.configure(f"fleet.replica_kill:@{kill_at}:0")
    rows = np.zeros((1, 784), np.float32).tolist()

    def one(_):
        return fleet.forward("/predict", {"rows": rows})

    ok, errors = 0, []
    try:
        with concurrent.futures.ThreadPoolExecutor(max_workers=6) as pool:
            for fut in [pool.submit(one, i) for i in range(n)]:
                try:
                    payload = fut.result(timeout=120.0)
                    assert len(payload["predictions"]) == 1
                    ok += 1
                except Exception as e:  # noqa: BLE001 — tallied
                    errors.append(repr(e))
    finally:
        faults.reset()
    return ok, errors


def test_mnist_fleet_kill_drill_zero_failures(mnist_fleet):
    """THE chaos acceptance drill: 3 real serve replicas under a
    threaded burst, `fleet.replica_kill` SIGKILLs one mid-burst —
    every client request still succeeds (failover > 0, zero errors)
    and the supervisor relaunches the replica back to `up`."""
    fleet, _obs = mnist_fleet
    failover0 = _counter("fleet_failover")
    kill_at = next(iter([10]))  # the 11th routed request pulls the trigger
    ok, errors = _mnist_burst(fleet, 24, kill_at=kill_at)
    assert errors == [], errors
    assert ok == 24
    assert _counter("fleet_failover") > failover0
    assert _counter("fleet_replica_kills") >= 1
    # the burst outruns the 0.2s supervision cadence: give the monitor
    # time to detect the SIGKILLed child, relaunch it, and poll it up
    deadline = time.time() + 180.0
    while time.time() < deadline and not any(
        r.restarts >= 1 for r in fleet.replicas
    ):
        time.sleep(0.1)
    victims = [r for r in fleet.replicas if r.restarts >= 1]
    assert victims, "no replica was relaunched"
    while time.time() < deadline and any(
        r.state != "up" for r in fleet.replicas
    ):
        time.sleep(0.25)
    assert [r.state for r in fleet.replicas] == ["up", "up", "up"]
    # the healed tier serves cleanly again
    ok, errors = _mnist_burst(fleet, 6)
    assert errors == [] and ok == 6


def test_mnist_fleet_cross_process_trace_tree(mnist_fleet, capsys):
    """One request's causal tree crosses the router→replica hop: the
    router injects X-Keystone-Trace, the replica process adopts it, and
    `observe trace --request ID` over the shared base dir renders
    router hop → replica queue wait → dispatch as ONE tree."""
    fleet, obs = mnist_fleet
    rows = np.zeros((1, 784), np.float32).tolist()
    with observe_events.run(base_dir=str(obs)):
        fleet.forward("/predict", {"rows": rows})
    # the replica's batcher thread records its queue/dispatch spans just
    # AFTER resolving the response future — poll briefly for the full tree
    root, in_trace, names = None, [], set()
    deadline = time.time() + 10.0
    while time.time() < deadline:
        merged = observe_spans.read_spans_all(str(obs))
        roots = [r for r in merged if r.get("name") == "fleet.request"]
        if roots:
            root = roots[-1]
            in_trace = [
                r for r in merged if r.get("trace") == root["trace"]
            ]
            names = {r["name"] for r in in_trace}
            if {"fleet.forward", "serve.request"} <= names and (
                "serve.queue_wait" in names
            ):
                break
        time.sleep(0.2)
    assert root is not None, "router recorded no fleet.request span"
    rid = root["rid"]
    # router-side hop AND replica-side request path share the trace id
    assert {"fleet.request", "fleet.forward", "serve.request"} <= names
    assert "serve.queue_wait" in names or "serve.dispatch" in names
    # the replica's serve.request hangs off the router's forward span
    serve_req = [r for r in in_trace if r["name"] == "serve.request"][-1]
    forward = [r for r in in_trace if r["name"] == "fleet.forward"][-1]
    assert serve_req["parent"] == forward["span"]
    # and the CLI renders it as one tree for the request id
    observe_spans.main([str(obs), "--request", str(rid)])
    out = capsys.readouterr().out
    assert "fleet.request" in out
    assert "serve.request" in out


def test_mnist_fleet_rolling_restart_under_load(mnist_fleet):
    """The acceptance pin for `fleet restart`: a full rolling restart
    of the real tier under a threaded burst, zero dropped/5xx
    requests, every replica on a fresh process gated through the
    one-row probe."""
    fleet, _obs = mnist_fleet
    assert fleet._probe is not None  # captured from the earlier bursts
    restarts0 = {r.rid: r.restarts for r in fleet.replicas}
    stop, errors, ok = threading.Event(), [], []

    def burst():
        rows = np.zeros((1, 784), np.float32).tolist()
        while not stop.is_set():
            try:
                payload = fleet.forward("/predict", {"rows": rows})
                assert len(payload["predictions"]) == 1
                ok.append(1)
            except Exception as e:  # noqa: BLE001 — tallied
                errors.append(repr(e))
            time.sleep(0.02)

    threads = [threading.Thread(target=burst) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        result = fleet.rolling_restart()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
    assert errors == [], errors
    assert len(ok) >= 10
    assert sorted(result["restarted"]) == [0, 1, 2]
    assert all(
        r.restarts == restarts0[r.rid] + 1 for r in fleet.replicas
    )
    assert all(r.state == "up" for r in fleet.replicas)
    assert _counter("fleet_rolling_restarts") >= 1
