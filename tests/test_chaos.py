"""Chaos campaign engine (resilience/chaos.py) + the PR-15 satellites.

Covers:

- the campaign spec layer: load/validate/compile, loud refusal of
  unknown sites/invariants, the machine-readable `faults --list
  --json` catalog the specs validate against;
- THE acceptance drills: the canned fleet game day end to end (3 stub
  replicas, replica_kill + conn_reset + slow_replica mid-24-request
  burst → every invariant PASS, zero client failures, failover ≥ 1,
  availability alert fired-and-cleared) and its replay determinism
  (same JSON + same seed → identical fault schedule); an intentionally
  broken invariant makes `chaos run` exit nonzero naming it; the refit
  game day; the train game day (supervised relaunch + disk-full save +
  digest-verified bit-exact resume);
- the new fault sites: `ckpt.disk_full` (atomic_write crash window:
  ENOSPC discards the temp, the committed artifact survives; the train
  loop's periodic save degrades loudly and keeps training) and
  `kv.partition` (a fully partitioned non-coordinator concludes host 0
  is gone — the verdict protocol with zero network, zero sleeps);
- the Retry-After satellite: a shed 503's explicit back-off stretches
  the failover retry delay (injected clock — the thundering-herd fix);
- the registry-wide "no site rots" sweep: EVERY registered fault site,
  forced on its first check against its smallest host harness, must
  degrade with a resilience event + faults_fired counter and never an
  unhandled crash — and a site added without a harness fails here.
"""

from __future__ import annotations

import errno
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from keystone_tpu.observe import events as observe_events
from keystone_tpu.observe import metrics as observe_metrics
from keystone_tpu.resilience import chaos, faults
from keystone_tpu.resilience.chaos import (
    CampaignError,
    compile_schedule,
    load_campaign,
    run_campaign,
    validate_campaign,
)


def _counter(name: str) -> float:
    return observe_metrics.get_registry().snapshot().get(name, 0)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# spec layer


def test_compile_schedule_is_pure_and_covers_all_forms():
    spec = {
        "name": "x",
        "seed": 7,
        "target": "fleet",
        "steps": [
            {"fault": "fleet.replica_kill", "at": 10},
            {"fault": "fleet.conn_reset", "window": [3, 5]},
            {"fault": "tar.read", "p": 0.25, "max": 2},
            {"fault": "train.nan", "at": 1, "seed": 99},
            {"action": "sigkill", "index": 0},
        ],
        "invariants": [{"check": "zero_client_failures"}],
    }
    want = (
        "fleet.replica_kill:@10:7,fleet.conn_reset:@3:7,"
        "fleet.conn_reset:@4:7,tar.read:0.25:7:2,train.nan:@1:99"
    )
    assert compile_schedule(spec) == want
    assert compile_schedule(spec) == want  # pure: same spec, same text
    # and the compiled text parses under the real grammar
    parsed = faults.parse_spec(compile_schedule(spec))
    assert len(parsed) == 5


def test_validate_refuses_unknown_site_loudly():
    spec = {
        "name": "bad",
        "target": "fleet",
        "steps": [{"fault": "fleet.nope", "at": 0}],
        "invariants": [{"check": "zero_client_failures"}],
    }
    with pytest.raises(CampaignError, match="unknown fault site"):
        validate_campaign(spec)
    with pytest.raises(CampaignError, match="faults --list --json"):
        validate_campaign(spec)


def test_validate_refuses_unknown_invariant_and_bad_target():
    base = {
        "name": "x",
        "target": "fleet",
        "steps": [],
        "invariants": [{"check": "definitely_not_a_check"}],
    }
    with pytest.raises(CampaignError, match="unknown check"):
        validate_campaign(base)
    with pytest.raises(CampaignError, match="target"):
        validate_campaign({**base, "target": "warehouse"})
    with pytest.raises(CampaignError, match="no invariants"):
        validate_campaign({**base, "invariants": []})
    # one step is one thing: a merged fault+action step would silently
    # drop its action half past validation
    with pytest.raises(CampaignError, match="both 'fault' and 'action'"):
        validate_campaign(
            {
                "name": "x",
                "target": "fleet",
                "steps": [
                    {"fault": "fleet.conn_reset", "at": 1,
                     "action": "sigkill", "index": 0}
                ],
                "invariants": [{"check": "zero_client_failures"}],
            }
        )
    # actions drive fleet replicas only
    with pytest.raises(CampaignError, match="actions"):
        validate_campaign(
            {
                "name": "x",
                "target": "train",
                "steps": [{"action": "sigkill", "index": 0}],
                "invariants": [{"check": "workload_completed"}],
            }
        )


def test_validate_refuses_unknown_replica_kind():
    """A typo'd workload.replica is an invalid spec, refused before any
    process spawns — never reported as a failed game day."""
    spec = load_campaign("fleet_game_day")
    spec["workload"]["replica"] = "mnits"
    with pytest.raises(CampaignError, match="workload.replica"):
        validate_campaign(spec)
    with pytest.raises(CampaignError):
        run_campaign(spec)


def test_validate_refuses_typoed_invariant_params_and_empty_windows():
    """A typo'd parameter ('mins' for 'min') or an empty window would
    silently weaken the gate to always-PASS — both are refused at load
    time instead."""
    base = {
        "name": "x",
        "target": "fleet",
        "steps": [],
        "invariants": [
            {"check": "event_count", "action": "fault", "mins": 1}
        ],
    }
    with pytest.raises(CampaignError, match="unknown key"):
        validate_campaign(base)
    base["invariants"] = [{"check": "event_count", "action": "fault"}]
    with pytest.raises(CampaignError, match="vacuously"):
        validate_campaign(base)
    base["invariants"] = [{"check": "counter_bounds", "min": 1}]
    with pytest.raises(CampaignError, match="needs 'counter'"):
        validate_campaign(base)
    base["invariants"] = [{"check": "zero_client_failures"}]
    base["steps"] = [{"fault": "fleet.conn_reset", "window": [16, 14]}]
    with pytest.raises(CampaignError, match="empty"):
        validate_campaign(base)
    # the key registry must cover every registered invariant, or a new
    # check becomes un-validatable
    assert set(chaos.INVARIANT_KEYS) == set(chaos.INVARIANTS)


def test_validate_refuses_max_on_keyed_steps():
    """'max' only means something on probability clauses; on an
    at/window step it would be silently dropped — refuse instead."""
    spec = {
        "name": "x",
        "target": "fleet",
        "steps": [{"fault": "fleet.conn_reset", "window": [0, 20], "max": 2}],
        "invariants": [{"check": "zero_client_failures"}],
    }
    with pytest.raises(CampaignError, match="'max' caps probability"):
        validate_campaign(spec)


def test_validate_round_trips_the_compiled_schedule():
    """A clause value the grammar rejects (p outside (0,1]) must be
    refused at load time as a CampaignError, not crash mid-campaign."""
    spec = {
        "name": "x",
        "target": "fleet",
        "steps": [{"fault": "fleet.conn_reset", "p": 1.5}],
        "invariants": [{"check": "zero_client_failures"}],
    }
    with pytest.raises(CampaignError, match="compiled fault schedule"):
        validate_campaign(spec)


def test_counter_bounds_event_fallback_uses_declared_action():
    """Cross-process counters fall back to the event record; when the
    emit site's action differs from the counter name the spec names it
    explicitly (counter ckpt_save_failures rides ckpt_save_failed)."""
    ctx = {
        "snap_before": {},
        "snap_after": {},
        "events": [
            {"event": "resilience", "action": "ckpt_save_failed", "step": 4}
        ],
        "spans": [],
        "workload": {},
    }
    v = chaos.INVARIANTS["counter_bounds"](
        {
            "counter": "ckpt_save_failures",
            "action": "ckpt_save_failed",
            "min": 1,
        },
        ctx,
    )
    assert v["ok"], v


def test_canned_campaigns_all_validate():
    canned = chaos.canned_campaigns()
    assert {"fleet_game_day", "train_game_day", "refit_game_day"} <= set(
        canned
    )
    for name in canned:
        spec = load_campaign(name)
        validate_campaign(spec)
        assert compile_schedule(spec)  # every canned day injects faults


def test_faults_list_json_is_the_machine_readable_registry(capsys):
    faults.main(["--list", "--json"])
    payload = json.loads(capsys.readouterr().out)
    names = {row["name"] for row in payload["sites"]}
    assert names == set(faults.SITES)
    by_name = {row["name"]: row for row in payload["sites"]}
    # new sites registered, with their natural keys declared
    assert "ckpt.disk_full" in names and "kv.partition" in names
    assert by_name["train.nan"]["key"] == "step index"
    assert all("description" in row for row in payload["sites"])
    # the key registry is structural and must cover the site registry
    # exactly — a site added to SITES without declaring its key (or a
    # stale key entry) is registry drift
    assert set(faults.SITE_KEYS) == set(faults.SITES)


def test_chaos_cli_list_and_validate(capsys):
    chaos.main(["list"])
    out = capsys.readouterr().out
    assert "fleet_game_day" in out and "refit_game_day" in out
    chaos.main(["validate", "fleet_game_day"])
    out = capsys.readouterr().out
    assert "ok: fleet_game_day" in out
    assert "fleet.replica_kill:@10:0" in out
    with pytest.raises(SystemExit, match="chaos"):
        chaos.main(["--help"])
    with pytest.raises(SystemExit, match="unknown chaos command"):
        chaos.main(["frobnicate"])


def test_chaos_validate_cli_refuses_unknown_site(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(
        json.dumps(
            {
                "target": "fleet",
                "steps": [{"fault": "no.such_site", "at": 0}],
                "invariants": [{"check": "zero_client_failures"}],
            }
        )
    )
    with pytest.raises(SystemExit, match="unknown fault site"):
        chaos.main(["validate", str(bad)])


# ---------------------------------------------------------------------------
# new fault sites + durability satellites


def test_atomic_write_disk_full_keeps_old_artifact(tmp_path):
    """THE crash-window drill: ENOSPC inside atomic_write discards the
    temp file and never touches the committed artifact — a reader
    during or after the failure sees the old complete file."""
    from keystone_tpu.core.serialization import atomic_write

    path = tmp_path / "artifact.bin"
    with atomic_write(str(path)) as f:
        f.write(b"generation-1")
    faults.configure("ckpt.disk_full:1:0")
    with pytest.raises(OSError) as exc:
        with atomic_write(str(path)) as f:
            f.write(b"generation-2-partial")
    assert exc.value.errno == errno.ENOSPC
    faults.reset()
    assert path.read_bytes() == b"generation-1"
    assert list(tmp_path.glob("*.tmp.*")) == []  # temp cleaned up
    # and the next write (disk freed) commits normally
    with atomic_write(str(path)) as f:
        f.write(b"generation-2")
    assert path.read_bytes() == b"generation-2"


def test_enospc_is_not_transient():
    from keystone_tpu.resilience.retry import is_transient

    faults.configure("ckpt.disk_full:1:0")
    with pytest.raises(OSError) as exc:
        faults.maybe_disk_full(note="probe")
    assert exc.value.errno == errno.ENOSPC
    assert not is_transient(exc.value)
    # plain injected IO faults stay transient (the retry family)
    assert is_transient(faults.InjectedFault("flaky read"))


def test_retry_policy_honors_retry_after_with_injected_clock():
    """The thundering-herd fix: an error carrying retry_after_s
    stretches the backoff to at least the server's explicit ask —
    verified against the recorded sleep schedule, zero real sleeping."""
    from keystone_tpu.resilience.retry import RetryPolicy

    sleeps: list[float] = []
    clock = {"t": 0.0}

    def sleep(s):
        sleeps.append(s)
        clock["t"] += s

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            err = ConnectionError("shed")
            err.retry_after_s = 3.0
            raise err
        return "ok"

    policy = RetryPolicy(
        max_attempts=4,
        base_delay_s=0.02,
        jitter=0.0,
        sleep=sleep,
        monotonic=lambda: clock["t"],
    )
    assert policy.call(flaky) == "ok"
    assert len(sleeps) == 2
    assert all(s >= 3.0 for s in sleeps), sleeps
    # without the header the schedule is the policy's own
    calls["n"], sleeps[:] = 0, []

    def flaky_plain():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("shed")
        return "ok"

    assert policy.call(flaky_plain) == "ok"
    assert all(s < 1.0 for s in sleeps), sleeps


def test_fleet_failover_honors_replica_retry_after():
    """An admission-shed 503 from a replica (Retry-After surfaced by
    the transport as payload retry_after_s) makes the failover policy
    wait at least that long before the next attempt."""
    from keystone_tpu.serve.fleet import Fleet

    sleeps: list[float] = []
    clock = {"t": 0.0}

    def transport(r, method, path, body=None, timeout=5.0, headers=None):
        if method == "GET":
            return 200, {"status": "ok"}
        if r.rid == 0:
            return 503, {"error": "at capacity", "retry_after_s": 2.5}
        return 200, {"predictions": [[1.0]]}

    fleet = Fleet(
        cmd=None,
        n=2,
        transport=transport,
        clock=lambda: clock["t"],
        retry_sleep=lambda s: (sleeps.append(s), clock.update(t=clock["t"] + s)),
        deadline_ms=60000.0,
    )
    for r in fleet.replicas:
        r.state = "up"
    # replica 0 is the least-loaded first pick (rid tiebreak)
    out = fleet.forward("/predict", {"rows": [[1.0]]})
    assert out["predictions"] == [[1.0]]
    assert sleeps and sleeps[0] >= 2.5, sleeps


def test_http_transport_surfaces_retry_after_header():
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from keystone_tpu.serve.fleet import Replica, http_transport

    class H(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def do_POST(self):  # noqa: N802
            body = json.dumps({"error": "shed"}).encode()
            self.send_response(503)
            self.send_header("Retry-After", "7")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        r = Replica(rid=0, port=httpd.server_address[1])
        status, payload = http_transport(r, "POST", "/predict", {})
        assert status == 503
        assert payload["retry_after_s"] == 7.0
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_kv_partition_drives_the_verdict_protocol_zero_sleeps():
    """A fully partitioned non-coordinator cannot publish for a whole
    timeout window → it concludes host 0 is gone (the
    coordinator_unreachable verdict), with zero network and zero
    sleeping — the kv.partition drill."""
    from keystone_tpu.resilience.cluster import ClusterMonitor, LocalKV

    faults.configure("kv.partition:1:0")
    clock = {"t": 0.0}
    mon = ClusterMonitor(
        LocalKV(),
        process_id=1,
        num_processes=2,
        interval_s=1.0,
        timeout_s=5.0,
        clock=lambda: clock["t"],
        abort=lambda code: None,
    )
    assert mon.beat_once() is False  # dropped, transport-down noted
    assert mon.check() is None
    clock["t"] = 10.0  # a full timeout later, still partitioned
    assert mon.beat_once() is False
    assert mon.check() == (0,)
    # a healthy monitor with no partition publishes fine
    faults.reset()
    kv = LocalKV()
    mon2 = ClusterMonitor(
        kv, process_id=1, num_processes=2, interval_s=1.0, timeout_s=5.0,
        clock=lambda: 0.0, abort=lambda code: None,
    )
    assert mon2.beat_once() is True
    assert kv.dir("keystone/cluster/heartbeat/")


def test_ckpt_disk_full_mid_train_save_degrades_and_resumes(tmp_path):
    """THE acceptance drill for the new site: ENOSPC at the second
    periodic save (ckpt.disk_full:@4 — keyed by the save step) leaves
    training running, emits the ckpt_save_failed resilience trail, and
    every checkpoint that IS on disk restores digest-verified
    bit-exact."""
    import jax

    from keystone_tpu.models import lm_transformer as lm
    from keystone_tpu.models.lm.train import train

    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=17, max_seq=8, dim=8, depth=1, num_heads=2
    )
    corpus = lm.synthetic_corpus(1_000, 17, seed=0)
    faults.configure("ckpt.disk_full:@4:0")
    try:
        with observe_events.run() as log:
            model, losses = train(
                model, corpus, steps=6, batch=2, seq=8, lr=1e-3, seed=0,
                checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2,
            )
    finally:
        faults.reset()
    assert len(losses) == 6  # the run survived the failed save
    fails = [
        r
        for r in log.records
        if r.get("event") == "resilience"
        and r.get("action") == "ckpt_save_failed"
    ]
    assert len(fails) == 1 and fails[0]["step"] == 4
    assert "ENOSPC" in fails[0]["error"] or "No space" in fails[0]["error"]
    # the verifier's own invariant: everything on disk is bit-exact
    verdict = chaos.INVARIANTS["resume_bit_exact"](
        {"dir": str(tmp_path / "ck")},
        {"workload": {}, "events": [], "spans": []},
    )
    assert verdict["ok"], verdict
    assert 2 in verdict["evidence"]["verified_steps"]


# ---------------------------------------------------------------------------
# the registry-wide "no site rots" sweep

SLOW_ENV = {"KEYSTONE_SERVE_SLOW_MS": "1"}


def _h_raise(site):
    def run():
        with pytest.raises(faults.InjectedFault):
            faults.maybe_raise(site)

    return run


def _h_fire(site, key=0):
    def run():
        assert faults.fire(site, key)

    return run


def _h_disk_full():
    with pytest.raises(OSError) as exc:
        faults.maybe_disk_full(note="sweep")
    assert exc.value.errno == errno.ENOSPC


def _h_poison():
    out = faults.poison("batch.nan", np.ones((2, 3), np.float32))
    assert np.isnan(out).any()


def _h_accel_drop():
    with pytest.raises(faults.AcceleratorDrop, match="UNAVAILABLE"):
        faults.maybe_drop_accelerator()


def _h_preempt():
    with pytest.raises(faults.SimulatedPreemption):
        faults.maybe_preempt(key=0)


def _h_heartbeat_drop():
    from keystone_tpu.resilience.cluster import ClusterMonitor, LocalKV

    kv = LocalKV()
    mon = ClusterMonitor(
        kv, 0, 1, interval_s=1.0, timeout_s=5.0,
        clock=lambda: 0.0, abort=lambda c: None,
    )
    assert mon.beat_once() is False  # beat 0 eaten by the drill
    assert not kv.dir("keystone/cluster/heartbeat/")


def _h_kv_partition():
    from keystone_tpu.resilience.cluster import ClusterMonitor, LocalKV

    kv = LocalKV()
    mon = ClusterMonitor(
        kv, 0, 1, interval_s=1.0, timeout_s=5.0,
        clock=lambda: 0.0, abort=lambda c: None,
    )
    assert mon.beat_once() is False  # publish dropped at the transport
    assert not kv.dir("keystone/cluster/heartbeat/")


def _h_fleet(site):
    def run():
        from keystone_tpu.serve.fleet import Fleet

        calls = {"n": 0}

        def transport(r, method, path, body=None, timeout=5.0, headers=None):
            calls["n"] += 1
            return 200, {"predictions": [[1.0]]}

        fleet = Fleet(
            cmd=None, n=2, transport=transport,
            retry_sleep=lambda s: None, deadline_ms=60000.0,
        )
        for r in fleet.replicas:
            r.state = "up"
        os.environ.update(SLOW_ENV)  # slow_replica sleeps 1 ms, not 100
        try:
            out = fleet.forward("/predict", {"rows": [[1.0]]})
        finally:
            os.environ.pop("KEYSTONE_SERVE_SLOW_MS", None)
        assert out["predictions"] == [[1.0]]  # drill absorbed, client ok

    return run


def _h_refit_corrupt():
    # the real call site keys by chunk file name — any key must hit the
    # p=1 clause
    assert faults.fire("refit.corrupt_chunk", key="chunk_000.npz")


def _h_state_digest():
    import tempfile

    from keystone_tpu.learn.merge import (
        FitStateError,
        load_fit_state,
        save_fit_state,
    )
    from keystone_tpu.ops.linear import LinearMapEstimator

    est = LinearMapEstimator(lam=0.1)
    state = est.fit_stats_init(3, 2)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "s.ksts")
        save_fit_state(state, path, est=est)
        with pytest.raises(FitStateError):
            load_fit_state(path)  # drill reports a digest mismatch


#: site → its smallest host harness. EVERY registered site must appear
#: here — a new site without a sweep harness fails the test below, so
#: the registry can't silently rot. Harnesses either exercise the real
#: smallest consumer (atomic_write, the cluster monitor, the fleet
#: router, fit-state load) or, for sites whose only effect is killing a
#: process / a heavyweight rig drilled by its own dedicated test, the
#: site's public decision helper.
SITE_HARNESSES: dict[str, tuple[str, object]] = {
    "tar.read": ("tar.read:@0:0", _h_raise("tar.read")),
    "idx.read": ("idx.read:@0:0", _h_raise("idx.read")),
    "batch.nan": ("batch.nan:@0:0", _h_poison),
    "accel.fit": ("accel.fit:@0:0", _h_accel_drop),
    "ckpt.save": ("ckpt.save:@0:0", _h_raise("ckpt.save")),
    "ckpt.restore": ("ckpt.restore:@0:0", _h_raise("ckpt.restore")),
    "ckpt.disk_full": ("ckpt.disk_full:@0:0", _h_disk_full),
    "train.nan": ("train.nan:@0:0", _h_fire("train.nan")),
    "train.preempt": ("train.preempt:@0:0", _h_preempt),
    "train.sigterm": ("train.sigterm:@0:0", _h_fire("train.sigterm")),
    "cluster.host_kill": (
        "cluster.host_kill:@0:0",
        _h_fire("cluster.host_kill"),
    ),
    "cluster.heartbeat_drop": (
        "cluster.heartbeat_drop:@0:0",
        _h_heartbeat_drop,
    ),
    "kv.partition": ("kv.partition:@0:0", _h_kv_partition),
    "serve.drop": ("serve.drop:@0:0", _h_fire("serve.drop")),
    "serve.slow_request": (
        "serve.slow_request:@0:0",
        _h_fire("serve.slow_request"),
    ),
    "serve.swap_fail": ("serve.swap_fail:@0:0", _h_fire("serve.swap_fail")),
    "refit.corrupt_chunk": ("refit.corrupt_chunk:1:0:1", _h_refit_corrupt),
    "refit.state_digest": ("refit.state_digest:1:0:1", _h_state_digest),
    "fleet.replica_kill": (
        "fleet.replica_kill:@0:0",
        _h_fleet("fleet.replica_kill"),
    ),
    "fleet.slow_replica": (
        "fleet.slow_replica:@0:0",
        _h_fleet("fleet.slow_replica"),
    ),
    "fleet.conn_reset": (
        "fleet.conn_reset:@0:0",
        _h_fleet("fleet.conn_reset"),
    ),
    "tune.bad_knob": ("tune.bad_knob:@0:0", _h_fire("tune.bad_knob")),
    "collector.scrape_fail": (
        "collector.scrape_fail:@0:0",
        _h_fire("collector.scrape_fail"),
    ),
}


def test_every_registered_site_has_a_sweep_harness():
    """The registry-wide guard: registering a site without adding its
    sweep harness fails CI — no site rots."""
    assert set(SITE_HARNESSES) == set(faults.SITES), (
        "fault registry and sweep harnesses drifted: "
        f"missing harness for {set(faults.SITES) - set(SITE_HARNESSES)}, "
        f"stale harness for {set(SITE_HARNESSES) - set(faults.SITES)}"
    )


@pytest.mark.parametrize("site", sorted(faults.SITES))
def test_site_sweep_degrades_with_event_and_counter(site):
    """Every site, forced on its first check against its smallest host
    harness: the fault fires exactly as scheduled, lands a resilience
    event + faults_fired counter, and nothing crashes unhandled."""
    spec, harness = SITE_HARNESSES[site]
    key = f"faults_fired{{site={site}}}"
    before = _counter(key)
    faults.configure(spec)
    try:
        with observe_events.run() as log:
            harness()
    finally:
        faults.reset()
    assert _counter(key) - before >= 1, f"{site}: counter did not move"
    fired = [
        r
        for r in log.records
        if r.get("event") == "resilience"
        and r.get("action") == "fault"
        and r.get("site") == site
    ]
    assert fired, f"{site}: no resilience fault event recorded"


# ---------------------------------------------------------------------------
# campaigns end to end


def test_fleet_game_day_e2e_and_replay_identical(tmp_path):
    """THE acceptance drill: the canned fleet game day (3 stub
    replicas, replica_kill + conn_reset + slow_replica mid-24-request
    burst) passes every invariant — zero client failures, failover ≥ 1,
    availability alert fired-and-cleared — and a replay with the same
    seed produces the identical fault schedule."""
    r1 = run_campaign("fleet_game_day", report_dir=str(tmp_path / "a"))
    assert r1["passed"], chaos.render_report(r1)
    byname = {v["name"]: v for v in r1["invariants"]}
    assert byname["zero_client_failures"]["ok"]
    assert r1["workload"]["client_failures"] == 0
    assert r1["workload"]["client_ok"] == 24
    assert byname["failover_fired"]["ok"]
    assert byname["failover_fired"]["evidence"]["failover"] >= 1
    assert byname["alert_fired_and_cleared(availability)"]["ok"]
    # the evidence exemplars resolve through the span substrate
    ev = byname["alert_fired_and_cleared(availability)"]["evidence"]
    assert ev.get("rid") is not None and ev.get("trace")
    from keystone_tpu.observe import spans as observe_spans

    spans = observe_spans.read_spans_all(str(tmp_path / "a"))
    assert any(s.get("trace") == ev["trace"] for s in spans)
    # report artifacts exist and agree
    verdict = json.loads(
        (tmp_path / "a" / "chaos_verdict.json").read_text()
    )
    assert verdict["passed"] is True
    assert "PASS" in (tmp_path / "a" / "chaos_report.txt").read_text()

    # replay into the SAME report dir: identical compiled schedule AND
    # identical fired set — the second verdict is scoped to its own run
    # dirs, so the first game day's events must not leak in (a reused
    # --report DIR would otherwise double every fault and failover)
    r2 = run_campaign("fleet_game_day", report_dir=str(tmp_path / "a"))
    assert r2["passed"], chaos.render_report(r2)
    assert r2["schedule"] == r1["schedule"]
    assert r2["fired"] == r1["fired"]
    assert [s for s, _ in r1["fired"]] == [
        "fleet.conn_reset", "fleet.replica_kill", "fleet.slow_replica",
    ]


def test_broken_invariant_fails_the_campaign_and_names_it(tmp_path):
    """An intentionally impossible invariant (failover_fired >= 5
    against a 1-kill campaign) must fail the run, name the invariant in
    the report, and exit nonzero through the CLI."""
    spec = load_campaign("fleet_game_day")
    spec["workload"]["requests"] = 12
    spec["workload"]["settle_s"] = 5
    for inv in spec["invariants"]:
        if inv["check"] == "failover_fired":
            inv["min"] = 5
    # drop the SLO invariant to keep the negative drill fast/focused
    spec["invariants"] = [
        i
        for i in spec["invariants"]
        if i["check"] != "alert_fired_and_cleared"
    ]
    path = tmp_path / "broken.json"
    path.write_text(json.dumps(spec))
    with pytest.raises(SystemExit) as exc:
        chaos.main(
            ["run", str(path), "--report", str(tmp_path / "rep")]
        )
    assert "failover_fired" in str(exc.value)
    report = (tmp_path / "rep" / "chaos_report.txt").read_text()
    assert "FAIL" in report and "failover_fired" in report


def test_refit_game_day_e2e(tmp_path):
    """The online-learning loop under fire: corrupt chunk skipped
    loudly, injected swap failure rolled back, zero failed live
    requests, no torn artifact anywhere."""
    r = run_campaign("refit_game_day", report_dir=str(tmp_path))
    assert r["passed"], chaos.render_report(r)
    w = r["workload"]
    assert w["client_failures"] == 0 and w["client_ok"] > 0
    assert w["chunks_skipped"] == 1 and w["chunks_folded"] == 2
    assert w["swap_failures"] == 1 and w["swaps_committed"] == 1
    byname = {v["name"]: v for v in r["invariants"]}
    assert byname["no_torn_artifacts"]["evidence"]["checked"] >= 3


@pytest.mark.slow
def test_train_game_day_e2e(tmp_path):
    """Supervised host-kill + disk-full save + heartbeat drop: the
    supervisor relaunches, the resumed run restores digest-verified,
    and the full event trail is on record. Marked slow: two jax child
    boots under the supervisor."""
    r = run_campaign("train_game_day", report_dir=str(tmp_path))
    assert r["passed"], chaos.render_report(r)
    assert r["workload"]["exit"] == 0
    assert r["workload"]["relaunched"]
    byname = {v["name"]: v for v in r["invariants"]}
    assert byname["resume_bit_exact"]["evidence"]["verified_steps"]
    fired_sites = {s for s, _ in r["fired"]}
    assert {
        "cluster.host_kill", "ckpt.disk_full", "cluster.heartbeat_drop",
    } <= fired_sites


def test_chaos_event_kind_declared():
    from keystone_tpu.observe import schema

    assert "chaos" in schema.declared()


def test_chaos_run_cli_smoke_subprocess(tmp_path):
    """`python -m keystone_tpu chaos run` through the real launcher:
    exit 0, PASS report on stdout, verdict artifact on disk."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("KEYSTONE_FAULTS", None)
    r = subprocess.run(
        [
            sys.executable, "-m", "keystone_tpu", "chaos", "run",
            "fleet_game_day", "--report", str(tmp_path),
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASS" in r.stdout
    assert (tmp_path / "chaos_verdict.json").exists()
