"""Chaos campaign engine: composed multi-fault game days with
declarative invariants and automated verdicts.

Every fault site in :mod:`keystone_tpu.resilience.faults` is drilled
somewhere by a bespoke test — but real incidents are *composed*: a
replica dies while the disk fills during a checkpoint while a client
burst is in flight. This module turns the existing registry into
repeatable, verdict-producing game days::

    python -m keystone_tpu chaos run fleet_game_day --report DIR
    python -m keystone_tpu chaos run my_campaign.json --target train
    python -m keystone_tpu chaos list
    python -m keystone_tpu chaos validate my_campaign.json

A **campaign** is a declarative JSON spec:

- ``steps`` — a seeded schedule: each step is either a **registry
  fault** (validated against ``faults.SITES`` — ``faults --list
  --json`` is the machine-readable catalog — and compiled into the
  existing ``KEYSTONE_FAULTS`` grammar, so every decision stays a pure
  function of ``(seed, site, key)`` and a replayed campaign produces
  an identical fault schedule) or a **process-level action**
  (SIGKILL / SIGSTOP+SIGCONT a replica at a wall-clock offset);
- ``workload`` — the traffic the runner itself drives against the
  target: a threaded request burst through the fleet router
  (``target: fleet``), a supervised LM train run (``target: train``),
  or a refit-daemon feed under live serving traffic
  (``target: refit``);
- ``invariants`` — declarative checks evaluated **purely from the
  observe substrate** after the campaign: the merged events/spans
  JSONL of every participating process, metrics-counter deltas, the
  collector's time-series store, and the SLO burn-rate engine (see
  :data:`INVARIANTS`). Every verdict carries evidence — exemplar
  request/trace ids that resolve via
  ``observe trace <report-dir> --request <rid>``.

The runner emits one ``chaos`` verdict event, writes a human-readable
PASS/FAIL report plus a JSON verdict into the report directory, and
exits nonzero when any invariant fails — the game day is a gate, not a
demo. Three canned campaigns ship under ``resilience/campaigns/``
(fleet / train / refit game days); the fleet one runs on CPU-pinned
stub replicas.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Callable

from keystone_tpu.resilience.faults import SITES

CAMPAIGN_DIR = os.path.join(os.path.dirname(__file__), "campaigns")
TARGETS = ("fleet", "train", "refit")
ACTION_KINDS = ("sigkill", "sigterm", "sigstop")

#: invariant catalog: check name → evaluator. Each evaluator takes
#: (inv spec, verification context) and returns a verdict dict
#: {"ok": bool, "detail": str, "evidence": {...}}.
INVARIANTS: dict[str, Callable[[dict, dict], dict]] = {}


class CampaignError(ValueError):
    """The campaign spec is invalid — unknown site/invariant/action,
    missing fields, or a target the spec cannot drive. Loud at load
    time, before any process is spawned."""


#: allowed parameter keys per invariant check (beyond "check") — a key
#: outside this set is refused at validate time, because a typo'd
#: parameter ("mins" for "min") would otherwise silently weaken the
#: gate to always-PASS
INVARIANT_KEYS: dict[str, frozenset[str]] = {
    "zero_client_failures": frozenset(),
    "workload_completed": frozenset(),
    "counter_bounds": frozenset(
        {"counter", "min", "max", "where", "event", "action"}
    ),
    "failover_fired": frozenset({"min"}),
    "event_count": frozenset({"event", "action", "where", "min", "max"}),
    "resume_bit_exact": frozenset({"dir"}),
    "no_torn_artifacts": frozenset({"dirs"}),
    "alert_fired_and_cleared": frozenset(
        {
            "objective",
            "target",
            "threshold_ms",
            "min_points",
            "factor",
            "short_s",
            "long_s",
        }
    ),
}


def _invariant(name: str):
    def register(fn):
        INVARIANTS[name] = fn
        return fn

    return register


# ------------------------------------------------------------------- spec


def canned_campaigns() -> dict[str, str]:
    """name → path of the campaigns shipped with the package."""
    out = {}
    for path in sorted(glob.glob(os.path.join(CAMPAIGN_DIR, "*.json"))):
        out[os.path.splitext(os.path.basename(path))[0]] = path
    return out


def load_campaign(ref: str | dict) -> dict:
    """Load a campaign spec from a dict, a JSON file path, or a canned
    campaign name (``chaos list``)."""
    if isinstance(ref, dict):
        return json.loads(json.dumps(ref))  # defensive copy
    path = ref
    if not os.path.isfile(path):
        canned = canned_campaigns()
        if ref in canned:
            path = canned[ref]
        else:
            raise CampaignError(
                f"no campaign file {ref!r} and no canned campaign by "
                f"that name (canned: {', '.join(sorted(canned)) or 'none'})"
            )
    try:
        with open(path) as f:
            spec = json.load(f)
    except ValueError as e:
        raise CampaignError(f"{path}: not valid JSON ({e})") from e
    if not isinstance(spec, dict):
        raise CampaignError(f"{path}: campaign must be a JSON object")
    spec.setdefault("name", os.path.splitext(os.path.basename(path))[0])
    return spec


def validate_campaign(spec: dict) -> None:
    """Refuse a bad spec loudly: unknown fault sites (against the live
    ``faults.SITES`` registry), unknown invariant checks, unknown
    action kinds, bad targets. Raises :class:`CampaignError` naming
    the offending clause and the valid vocabulary."""
    target = spec.get("target")
    if target not in TARGETS:
        raise CampaignError(
            f"campaign {spec.get('name')!r}: target {target!r} must be "
            f"one of {TARGETS}"
        )
    if target == "fleet":
        kind = (spec.get("workload") or {}).get("replica", "stub")
        if kind not in ("stub", "mnist") and not isinstance(kind, list):
            raise CampaignError(
                f"workload.replica {kind!r}: 'stub', 'mnist', or a "
                "command list"
            )
    for i, step in enumerate(spec.get("steps") or []):
        if not isinstance(step, dict):
            raise CampaignError(f"step {i}: must be an object")
        if "fault" in step and "action" in step:
            raise CampaignError(
                f"step {i}: carries both 'fault' and 'action' — one "
                "step is one thing; split them (a merged step would "
                "silently drop the action half)"
            )
        if "fault" in step:
            site = step["fault"]
            if site not in SITES:
                known = ", ".join(sorted(SITES))
                raise CampaignError(
                    f"step {i}: unknown fault site {site!r} — not in "
                    f"the registry (`python -m keystone_tpu faults "
                    f"--list --json`). Known sites: {known}"
                )
            if ("at" in step) + ("p" in step) + ("window" in step) != 1:
                raise CampaignError(
                    f"step {i} ({site}): exactly one of 'at' (keyed "
                    "fire), 'p' (probability), or 'window' ([start, "
                    "end) keyed range) is required"
                )
            if "max" in step and "p" not in step:
                raise CampaignError(
                    f"step {i} ({site}): 'max' caps probability "
                    "clauses only — keyed 'at'/'window' steps fire "
                    "exactly once per key, so a cap would be silently "
                    "meaningless"
                )
            if "window" in step:
                try:
                    a, b = (int(x) for x in step["window"])
                except (TypeError, ValueError) as e:
                    raise CampaignError(
                        f"step {i} ({site}): window must be a "
                        f"[start, end) pair of ints ({e})"
                    ) from e
                if b <= a:
                    raise CampaignError(
                        f"step {i} ({site}): window [{a}, {b}) is "
                        "empty — the step would compile to zero "
                        "clauses and silently inject nothing"
                    )
        elif "action" in step:
            if step["action"] not in ACTION_KINDS:
                raise CampaignError(
                    f"step {i}: unknown action {step['action']!r} "
                    f"(known: {ACTION_KINDS})"
                )
            if target != "fleet":
                raise CampaignError(
                    f"step {i}: process-level actions drive fleet "
                    f"replicas; the {target!r} target injects process "
                    "death via its registry sites (cluster.host_kill)"
                )
        else:
            raise CampaignError(
                f"step {i}: needs either 'fault' (a registry site) or "
                "'action' (a process-level step)"
            )
    for i, inv in enumerate(spec.get("invariants") or []):
        check = (inv or {}).get("check")
        if check not in INVARIANTS:
            raise CampaignError(
                f"invariant {i}: unknown check {check!r} (known: "
                f"{', '.join(sorted(INVARIANTS))})"
            )
        unknown = set(inv) - {"check"} - INVARIANT_KEYS[check]
        if unknown:
            raise CampaignError(
                f"invariant {i} ({check}): unknown key(s) "
                f"{sorted(unknown)} — a typo'd parameter (e.g. 'mins' "
                f"for 'min') would silently weaken the gate; allowed: "
                f"{sorted(INVARIANT_KEYS[check]) or 'none'}"
            )
        if check in ("counter_bounds", "event_count") and not (
            inv.get("min") is not None or inv.get("max") is not None
        ):
            raise CampaignError(
                f"invariant {i} ({check}): needs 'min' and/or 'max' — "
                "without a bound the check is vacuously true"
            )
        if check == "counter_bounds" and not inv.get("counter"):
            raise CampaignError(
                f"invariant {i} (counter_bounds): needs 'counter'"
            )
    if not spec.get("invariants"):
        raise CampaignError(
            f"campaign {spec.get('name')!r}: no invariants — a game "
            "day without a verdict is a demo, not a drill"
        )
    # round-trip the compiled schedule through the real grammar so a
    # bad clause value (p outside (0,1], a non-numeric seed) is refused
    # HERE, not as a raw traceback after the campaign already started
    from keystone_tpu.resilience.faults import parse_spec

    try:
        parse_spec(compile_schedule(spec))
    except ValueError as e:
        raise CampaignError(
            f"campaign {spec.get('name')!r}: compiled fault schedule "
            f"is invalid ({e})"
        ) from e


def compile_schedule(spec: dict) -> str:
    """The campaign's fault steps compiled into one ``KEYSTONE_FAULTS``
    value — a pure function of the spec (campaign seed included), so
    the same JSON always produces the identical schedule and every
    decision replays from ``(seed, site, key)``."""
    seed = int(spec.get("seed", 0))
    clauses: list[str] = []
    for step in spec.get("steps") or []:
        if "fault" not in step:
            continue
        site = step["fault"]
        s = int(step.get("seed", seed))
        if "at" in step:
            clauses.append(f"{site}:@{int(step['at'])}:{s}")
        elif "window" in step:
            a, b = (int(x) for x in step["window"])
            clauses.extend(f"{site}:@{k}:{s}" for k in range(a, b))
        else:
            p = float(step["p"])
            clause = f"{site}:{p:g}:{s}"
            if step.get("max") is not None:
                clause += f":{int(step['max'])}"
            clauses.append(clause)
    return ",".join(clauses)


# -------------------------------------------------------------- workloads


def _burst(
    forward: Callable[[int], Any],
    requests: int,
    threads: int,
    gap_s: float,
) -> dict:
    """Drive exactly ``requests`` calls through ``forward`` from a
    thread pool, tallying outcomes — the client's-eye view every fleet
    invariant judges."""
    import queue as _q

    todo: _q.SimpleQueue = _q.SimpleQueue()
    for i in range(requests):
        todo.put(i)
    ok: list[float] = []
    failures: list[str] = []
    lock = threading.Lock()

    def worker():
        while True:
            try:
                i = todo.get_nowait()
            except _q.Empty:
                return
            t0 = time.perf_counter()
            try:
                forward(i)
                with lock:
                    ok.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 — the tally IS the test
                with lock:
                    failures.append(f"request {i}: {e!r}")
            if gap_s:
                time.sleep(gap_s)

    t0 = time.perf_counter()
    pool = [
        threading.Thread(target=worker, daemon=True)
        for _ in range(max(int(threads), 1))
    ]
    for t in pool:
        t.start()
    for t in pool:
        t.join(timeout=600.0)
    wall = time.perf_counter() - t0
    with lock:
        # snapshot under the lock: a worker that outlived its join
        # timeout must not mutate the tallies the verdict reads, and a
        # request it never accounted for is a LOST request — the
        # zero-failure invariant counts it against the campaign rather
        # than letting a hang pass the gate
        lat = sorted(ok)
        errs = list(failures)
    lost = requests - len(lat) - len(errs)

    def pct(p: float) -> float:
        if not lat:
            return 0.0
        return lat[min(int(p * (len(lat) - 1)), len(lat) - 1)]

    return {
        "client_ok": len(lat),
        "client_failures": len(errs) + max(lost, 0),
        "client_lost": max(lost, 0),
        "errors": errs[:5]
        + ([f"{lost} request(s) never completed"] if lost > 0 else []),
        "wall_s": round(wall, 3),
        "request_p50_ms": round(pct(0.5) * 1e3, 2),
        "request_p95_ms": round(pct(0.95) * 1e3, 2),
    }


def _schedule_actions(spec: dict, fleet) -> list[threading.Timer]:
    """Arm the campaign's process-level steps as wall-clock timers
    against the fleet's replica processes: SIGKILL/SIGTERM at
    ``after_s``, SIGSTOP at ``after_s`` + SIGCONT ``duration_s``
    later — the wedged-replica drill the fault grammar can't express."""
    import signal as _signal

    from keystone_tpu.resilience.emit import decision as _decision

    timers: list[threading.Timer] = []
    signums = {
        "sigkill": _signal.SIGKILL,
        "sigterm": _signal.SIGTERM,
        "sigstop": _signal.SIGSTOP,
    }

    def fire(action: str, index: int, signum: int) -> None:
        try:
            r = fleet.replicas[index % len(fleet.replicas)]
        except (IndexError, ZeroDivisionError):
            return
        # deliver FIRST, then record what actually happened — the event
        # is evidence, and an action against an already-dead replica
        # must say so rather than claim a signal that was never sent.
        # (proc snapshotted once: the fleet supervisor thread can null
        # or replace r.proc concurrently with this timer thread)
        delivered = False
        proc = r.proc
        if proc is not None and proc.poll() is None:
            try:
                os.kill(proc.pid, signum)
                delivered = True
            except OSError:
                pass
        _decision(
            "chaos_action",
            counter="chaos_actions" if delivered else "chaos_actions_missed",
            counter_labels={"action": action},
            event_kind="chaos",
            action_kind=action,
            replica=r.rid,
            delivered=delivered,
        )

    for step in spec.get("steps") or []:
        action = step.get("action")
        if action not in ACTION_KINDS:
            continue
        index = int(step.get("index", 0))
        after = max(float(step.get("after_s", 0.0)), 0.0)
        t = threading.Timer(
            after, fire, args=(action, index, signums[action])
        )
        t.daemon = True
        t.start()
        timers.append(t)
        if action == "sigstop":
            dur = max(float(step.get("duration_s", 0.5)), 0.0)
            t2 = threading.Timer(
                after + dur, fire, args=("sigcont", index, _signal.SIGCONT)
            )
            t2.daemon = True
            t2.start()
            timers.append(t2)
    return timers


def _run_fleet(
    spec: dict, report_dir: str, schedule: str, work_dir: str
) -> dict:
    """The fleet game day: boot a router + N replica processes, run the
    campaign's request burst through :meth:`Fleet.forward` (the fault
    sites key off the router's request ids, so ``at`` steps hit exact
    requests), let the tier settle (supervisor relaunches), tear down."""
    from keystone_tpu.serve.fleet import Fleet

    wl = dict(spec.get("workload") or {})
    replicas = int(wl.get("replicas", 3))
    requests = int(wl.get("requests", 24))
    threads = int(wl.get("threads", 4))
    kind = wl.get("replica", "stub")
    env = dict(os.environ)
    env["KEYSTONE_OBSERVE_DIR"] = report_dir
    if schedule:
        env["KEYSTONE_FAULTS"] = schedule
    boot_timeout = float(wl.get("boot_timeout_s", 120.0))
    if kind == "stub":
        # spawn the stub by FILE path, not -m: the module is stdlib-only
        # by design, and `-m keystone_tpu...` would import the package
        # __init__ (and jax) into every replica boot — a ~5x boot-time
        # regression for a process drill whose whole point is no jax
        cmd = [
            sys.executable,
            os.path.join(os.path.dirname(__file__), "chaos_stub.py"),
            "--port", "{port}",
        ]
        rows = wl.get("rows") or [[1.0, 2.0]]
        env.setdefault("STUB_DRAIN_S", "0.1")
    elif kind == "mnist":
        import numpy as np

        # N replica processes cannot share one chip, and what a game day
        # exercises (routing, failover, relaunch) is host-side: the
        # replicas are pinned to the CPU on purpose, whatever the host
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [
            sys.executable, "-m", "keystone_tpu", "serve", "mnist",
            "--port", "{port}",
            "--synthetic", str(int(wl.get("synthetic", 96))),
            "--num-ffts", str(int(wl.get("num_ffts", 2))),
            "--buckets", "1,4,8",
        ]
        rows = (
            np.random.default_rng(int(spec.get("seed", 0)))
            .normal(size=(1, 784))
            .astype(np.float32)
            .tolist()
        )
        boot_timeout = float(wl.get("boot_timeout_s", 300.0))
    elif isinstance(kind, list):
        cmd = [str(a) for a in kind]
        rows = wl.get("rows") or [[1.0, 2.0]]
    else:
        raise CampaignError(
            f"workload.replica {kind!r}: 'stub', 'mnist', or a command "
            "list"
        )
    fleet = Fleet(
        cmd=cmd,
        n=replicas,
        env=env,
        poll_s=float(wl.get("poll_s", 0.1)),
        grace_s=float(wl.get("grace_s", 10.0)),
        boot_timeout_s=boot_timeout,
        deadline_ms=float(wl.get("deadline_ms", 10000.0)),
        max_inflight=int(wl.get("max_inflight", 64)),
        hedge=bool(wl.get("hedge", False)),
    )
    timers: list[threading.Timer] = []
    try:
        fleet.start(wait_up=replicas, timeout=boot_timeout)
        timers = _schedule_actions(spec, fleet)
        out = _burst(
            lambda i: fleet.forward("/predict", {"rows": rows}),
            requests,
            threads,
            float(wl.get("gap_ms", 5.0)) / 1e3,
        )
        # let the tier heal before teardown: the supervisor's relaunch
        # of a killed replica (and its state events) are part of the
        # story the verifier reads
        settle = float(wl.get("settle_s", 10.0))
        deadline = time.monotonic() + settle
        while time.monotonic() < deadline:
            if all(
                r.state == "up" or r.gave_up for r in fleet.replicas
            ):
                break
            time.sleep(0.1)
        out.update(
            kind="fleet",
            ok=True,
            replicas=replicas,
            requests=requests,
            replica_kind="stub" if kind == "stub" else str(kind),
            # said plainly in the verdict: mnist replicas are CPU-pinned
            # by the campaign, stubs and custom commands compute nothing
            # the campaign knows of
            replica_platform="cpu" if kind == "mnist" else None,
            replica_states=[r.state for r in fleet.replicas],
            artifact_dirs=[],
        )
        return out
    finally:
        for t in timers:
            t.cancel()
        if timers:
            # a fired sigstop whose SIGCONT timer we just cancelled (or
            # that outlived the burst) would leave a replica frozen —
            # unable to drain, eating the full shutdown grace. SIGCONT
            # is a no-op for running processes, so resume everyone.
            import signal as _signal

            for r in fleet.replicas:
                if r.proc is not None and r.proc.poll() is None:
                    try:
                        os.kill(r.proc.pid, _signal.SIGCONT)
                    except OSError:
                        pass
        fleet.shutdown(grace_s=float(wl.get("grace_s", 10.0)))


def _run_train(
    spec: dict, report_dir: str, schedule: str, work_dir: str
) -> dict:
    """The train game day: a supervised LM train run in a child process
    tree (``supervise`` owns the relaunch protocol), with the
    campaign's faults armed in the child environment — host kills,
    disk-full saves, heartbeat drops all fire inside the real loop."""
    wl = dict(spec.get("workload") or {})
    # artifacts live under THIS campaign's work dir (the runner's run
    # dir): a reused --report DIR must not hand this run a previous
    # campaign's checkpoints to resume from
    ckpt_dir = os.path.join(work_dir, "ckpt")
    out_npz = os.path.join(work_dir, "train_out.npz")
    env = dict(os.environ)
    env["KEYSTONE_OBSERVE_DIR"] = report_dir
    env["JAX_PLATFORMS"] = "cpu"
    if schedule:
        env["KEYSTONE_FAULTS"] = schedule
    worker = [
        sys.executable, "-m", "keystone_tpu.resilience.chaos",
        "train-worker",
        "--out", out_npz,
        "--ckpt", ckpt_dir,
        "--steps", str(int(wl.get("steps", 12))),
        "--every", str(int(wl.get("every", 2))),
        "--batch", str(int(wl.get("batch", 4))),
        "--seq", str(int(wl.get("seq", 16))),
        "--dim", str(int(wl.get("dim", 16))),
        "--depth", str(int(wl.get("depth", 1))),
        "--vocab", str(int(wl.get("vocab", 31))),
        "--seed", str(int(spec.get("seed", 0))),
    ]
    cmd = [
        sys.executable, "-m", "keystone_tpu", "supervise",
        "--procs", "1",
        "--max-restarts", str(int(wl.get("max_restarts", 2))),
        "--grace", "5",
        "--", *worker,
    ]
    t0 = time.perf_counter()
    r = subprocess.run(
        cmd,
        env=env,
        capture_output=True,
        text=True,
        timeout=float(wl.get("timeout_s", 900.0)),
    )
    return {
        "kind": "train",
        "ok": r.returncode == 0,
        "exit": r.returncode,
        "wall_s": round(time.perf_counter() - t0, 3),
        "checkpoint_dir": ckpt_dir,
        "artifact_dirs": [ckpt_dir],
        "relaunched": "relaunching" in (r.stderr or ""),
        "stderr_tail": (r.stderr or "")[-800:],
    }


def _run_refit(
    spec: dict, report_dir: str, schedule: str, work_dir: str
) -> dict:
    """The refit game day: a live in-process serving app takes traffic
    while the refit daemon folds labeled chunks (one injected-corrupt)
    and hot-swaps published models (one injected swap failure) — the
    online-learning loop under composed failure."""
    import numpy as np

    from keystone_tpu.core.pipeline import ChainedLabelEstimator, Identity
    from keystone_tpu.learn import refit as refit_mod
    from keystone_tpu.learn.swap import ModelSwapper, SwapError
    from keystone_tpu.ops.linear import LinearMapEstimator
    from keystone_tpu.serve.export import export_pipeline
    from keystone_tpu.serve.server import ServeApp

    wl = dict(spec.get("workload") or {})
    rows_n = int(wl.get("rows", 150))
    chunk_rows = int(wl.get("chunk_rows", 40))
    chunks = int(wl.get("chunks", 3))
    dim = int(wl.get("dim", 8))
    out_dim = int(wl.get("labels", 3))
    seed = int(spec.get("seed", 0))
    art = os.path.join(work_dir, "refit")
    watch = os.path.join(art, "chunks")
    os.makedirs(watch, exist_ok=True)

    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(dim, out_dim)).astype(np.float32)

    def make(n: int):
        a = rng.normal(size=(n, dim)).astype(np.float32)
        b = (a @ w_true + 0.01 * rng.normal(size=(n, out_dim))).astype(
            np.float32
        )
        return a, b

    a0, b0 = make(rows_n)
    state_path = os.path.join(art, "state.ksts")
    chain = ChainedLabelEstimator(
        prefix=Identity(), est=LinearMapEstimator(lam=0.2)
    )
    pipe, _state = refit_mod.bootstrap_state(chain, a0, b0, state_path)
    for i in range(chunks):
        a, b = make(chunk_rows)
        np.savez(
            os.path.join(watch, f"chunk_{i:03d}.npz"), data=a, labels=b
        )

    exported = export_pipeline(pipe, a0[:1])
    app = ServeApp(exported=exported, model_version="v0")
    app.swapper = ModelSwapper(
        app, source_path=os.path.join(art, refit_mod.CURRENT_MODEL)
    )
    stop = threading.Event()
    tally = {"ok": 0, "failures": []}
    probe = a0[:4]
    lock = threading.Lock()

    def traffic():
        while not stop.is_set():
            try:
                app.predict(probe)
                with lock:
                    tally["ok"] += 1
            except Exception as e:  # noqa: BLE001 — the tally IS the test
                with lock:
                    tally["failures"].append(repr(e))
            time.sleep(0.002)

    threads = [
        threading.Thread(target=traffic, daemon=True)
        for _ in range(int(wl.get("traffic_threads", 2)))
    ]
    t0 = time.perf_counter()
    summary: dict = {}
    swaps_committed = swap_failures = 0
    try:
        for t in threads:
            t.start()
        daemon = refit_mod.RefitDaemon(state_path, watch, out_dir=art)
        summary = daemon.run_once()
        for _ in range(int(wl.get("swaps", 2))):
            try:
                app.swapper.swap_to_path()
                swaps_committed += 1
            except SwapError:
                # rollback-by-not-committing: the incumbent keeps
                # serving — the traffic tally proves it
                swap_failures += 1
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        app.shutdown()
    return {
        "kind": "refit",
        "ok": True,
        "wall_s": round(time.perf_counter() - t0, 3),
        "client_ok": tally["ok"],
        "client_failures": len(tally["failures"]),
        "errors": tally["failures"][:5],
        "chunks_folded": summary.get("chunks_folded", 0),
        "chunks_skipped": summary.get("chunks_skipped", 0),
        "swaps_committed": swaps_committed,
        "swap_failures": swap_failures,
        "model_version": app.model_version,
        "artifact_dirs": [art],
    }


WORKLOADS = {"fleet": _run_fleet, "train": _run_train, "refit": _run_refit}


# -------------------------------------------------------------- verifier


def _campaign_run_dirs(
    report_dir: str, pre_existing: frozenset[str]
) -> list[str]:
    """The run directories THIS campaign created under the report dir
    — the runner's own plus each child replica/trainer's. Entries that
    predate the campaign are excluded, so a reused ``--report DIR``
    never leaks a previous game day's events/spans into this one's
    verdict evidence."""
    out = []
    for name in sorted(os.listdir(report_dir)):
        if name in pre_existing:
            continue
        path = os.path.join(report_dir, name)
        if os.path.isdir(path) and (
            os.path.isfile(os.path.join(path, "events.jsonl"))
            or os.path.isfile(os.path.join(path, "spans.jsonl"))
        ):
            out.append(path)
    return out


def _events_all(run_dirs: list[str]) -> list[dict]:
    """Every participating process's events, merged across the
    campaign's run dirs."""
    from keystone_tpu.observe import events as _events

    out: list[dict] = []
    for d in run_dirs:
        path = os.path.join(d, _events.EVENTS_FILE)
        if os.path.isfile(path):
            out.extend(_events.read_jsonl(path))
    out.sort(key=lambda r: float(r.get("ts") or 0.0))
    return out


def _counter_delta(ctx: dict, name: str) -> tuple[float, bool]:
    """Delta of one registry counter across the campaign (exact key
    first; the summed labeled variants only when no plain key exists —
    counters that bump both would double-count)."""

    def total(snap: dict) -> tuple[float, bool]:
        if name in snap and isinstance(snap[name], (int, float)):
            return float(snap[name]), True
        t, found = 0.0, False
        for k, v in snap.items():
            if k.startswith(name + "{") and isinstance(v, (int, float)):
                t += float(v)
                found = True
        return t, found

    after, found = total(ctx["snap_after"])
    before, _ = total(ctx["snap_before"])
    return after - before, found


def _count_events(ctx: dict, kind: str, action: str | None, where: dict):
    hits = []
    for ev in ctx["events"]:
        if ev.get("event") != kind:
            continue
        if action is not None and ev.get("action") != action:
            continue
        if any(ev.get(k) != v for k, v in (where or {}).items()):
            continue
        hits.append(ev)
    return hits


def _request_exemplar(ctx: dict, failed: bool | None = None) -> dict:
    """A concrete (rid, trace) pair from the campaign's request spans —
    the id the report tells the operator to feed ``observe trace
    --request``."""
    for rec in reversed(ctx["spans"]):
        if rec.get("name") not in ("fleet.request", "serve.request"):
            continue
        if failed is not None and (
            (rec.get("status") == "failed") != failed
        ):
            continue
        if rec.get("rid") is None:
            continue
        return {"rid": rec.get("rid"), "trace": rec.get("trace")}
    return {}


@_invariant("zero_client_failures")
def _inv_zero_client_failures(inv: dict, ctx: dict) -> dict:
    w = ctx["workload"]
    ok_n = int(w.get("client_ok", 0))
    bad_n = int(w.get("client_failures", 0))
    # closed-loop workloads declare how many requests they issued —
    # every single one must come back ok (a lost request is a failure
    # the tally can't see, so the count is part of the contract)
    issued = w.get("requests")
    complete = issued is None or ok_n == int(issued)
    evidence = {"client_ok": ok_n, "client_failures": bad_n}
    if issued is not None:
        evidence["requests_issued"] = int(issued)
    evidence.update(_request_exemplar(ctx))
    if w.get("errors"):
        evidence["errors"] = w["errors"]
    return {
        "ok": bad_n == 0 and ok_n > 0 and complete,
        "detail": f"{ok_n}/{issued if issued is not None else ok_n + bad_n} "
        "client requests succeeded",
        "evidence": evidence,
    }


@_invariant("workload_completed")
def _inv_workload_completed(inv: dict, ctx: dict) -> dict:
    w = ctx["workload"]
    return {
        "ok": bool(w.get("ok")),
        "detail": (
            f"workload {'completed' if w.get('ok') else 'FAILED'}"
            + (
                f" (exit {w['exit']})"
                if w.get("exit") is not None
                else ""
            )
        ),
        "evidence": {
            k: w[k]
            for k in ("exit", "relaunched", "stderr_tail")
            if k in w
        },
    }


@_invariant("counter_bounds")
def _inv_counter_bounds(inv: dict, ctx: dict) -> dict:
    name = inv.get("counter") or ""
    lo = inv.get("min")
    hi = inv.get("max")
    value, found = _counter_delta(ctx, name)
    if not found:
        # cross-process counters never reach the runner's registry —
        # fall back to the event record of the same decision. Counter
        # and event-action names can differ at an emit site (counter
        # 'ckpt_save_failures' rides action 'ckpt_save_failed'), so the
        # spec may name the action explicitly; default to the counter
        # name for sites where they coincide.
        hits = _count_events(
            ctx,
            inv.get("event", "resilience"),
            inv.get("action", name),
            inv.get("where"),
        )
        value, found = float(len(hits)), bool(hits)
    ok = True
    if lo is not None and value < float(lo):
        ok = False
    if hi is not None and value > float(hi):
        ok = False
    bounds = f"[{lo if lo is not None else '-inf'}, {hi if hi is not None else 'inf'}]"
    return {
        "ok": ok,
        "detail": f"{name} = {value:g}, required {bounds}",
        "evidence": {"counter": name, "value": value},
    }


@_invariant("failover_fired")
def _inv_failover_fired(inv: dict, ctx: dict) -> dict:
    lo = int(inv.get("min", 1))
    value, _ = _counter_delta(ctx, "fleet_failover")
    hits = _count_events(ctx, "resilience", "fleet_failover", None)
    value = max(value, float(len(hits)))
    evidence: dict = {"failover": value}
    if hits:
        evidence["rids"] = [h.get("rid") for h in hits[:4]]
        ex = _request_exemplar(ctx, failed=None)
        evidence.update(ex)
    return {
        "ok": value >= lo,
        "detail": f"failover fired {value:g} time(s), required >= {lo}",
        "evidence": evidence,
    }


@_invariant("event_count")
def _inv_event_count(inv: dict, ctx: dict) -> dict:
    kind = inv.get("event", "resilience")
    action = inv.get("action")
    hits = _count_events(ctx, kind, action, inv.get("where") or {})
    lo = inv.get("min")
    hi = inv.get("max")
    ok = True
    if lo is not None and len(hits) < int(lo):
        ok = False
    if hi is not None and len(hits) > int(hi):
        ok = False
    label = f"{kind}" + (f"/{action}" if action else "")
    return {
        "ok": ok,
        "detail": (
            f"{len(hits)} {label} event(s)"
            + (f", required >= {lo}" if lo is not None else "")
            + (f", required <= {hi}" if hi is not None else "")
        ),
        "evidence": {
            "count": len(hits),
            "sample": [
                {
                    k: h.get(k)
                    for k in ("action", "site", "key", "step", "rid")
                    if h.get(k) is not None
                }
                for h in hits[:4]
            ],
        },
    }


@_invariant("resume_bit_exact")
def _inv_resume_bit_exact(inv: dict, ctx: dict) -> dict:
    """Every digest sidecar in the checkpoint directory verifies
    against the leaves actually on disk — the post-restart params a
    relaunch restored are bit-identical to what the pre-kill
    incarnation committed (the PR-6 digest protocol, re-proven from
    the artifacts alone)."""
    ckpt_dir = inv.get("dir") or ctx["workload"].get("checkpoint_dir")
    if not ckpt_dir or not os.path.isdir(ckpt_dir):
        return {
            "ok": False,
            "detail": f"no checkpoint directory at {ckpt_dir!r}",
            "evidence": {},
        }
    from keystone_tpu.core import checkpoint as _ckpt

    digest_files = sorted(
        glob.glob(os.path.join(ckpt_dir, "digests_*.json"))
    )
    if not digest_files:
        return {
            "ok": False,
            "detail": f"{ckpt_dir}: no digest sidecars to verify "
            "(KEYSTONE_CKPT_DIGEST disabled?)",
            "evidence": {},
        }
    mgr = _ckpt._manager(ckpt_dir)
    verified: list[int] = []
    mismatches: list[str] = []
    try:
        on_disk = {int(s) for s in mgr.all_steps()}
        for df in digest_files:
            step = int(os.path.basename(df).split("_")[1].split(".")[0])
            if step not in on_disk:
                continue  # sidecar outlived a GC'd step — not a tear
            with open(df) as f:
                want = json.load(f).get("leaves") or []
            try:
                restored = mgr.restore(step)
            except Exception:  # noqa: BLE001 — orbax API variance
                import orbax.checkpoint as ocp

                restored = mgr.restore(
                    step, args=ocp.args.StandardRestore()
                )
            leaves = restored["leaves"]
            got = [_ckpt.leaf_digest(x) for x in leaves]
            if got != list(want):
                mismatches.append(f"step {step}")
            else:
                verified.append(step)
    finally:
        mgr.close()
    restore_events = _count_events(ctx, "resilience", "fault", {
        "site": "cluster.host_kill"
    })
    return {
        "ok": bool(verified) and not mismatches,
        "detail": (
            f"steps {verified} digest-verified bit-exact on disk"
            + (f"; MISMATCH at {mismatches}" if mismatches else "")
        ),
        "evidence": {
            "verified_steps": verified,
            "mismatches": mismatches,
            "host_kills_survived": len(restore_events),
        },
    }


@_invariant("no_torn_artifacts")
def _inv_no_torn_artifacts(inv: dict, ctx: dict) -> dict:
    """Every persisted artifact the campaign touched re-loads through
    its own integrity gate: ``.kst`` pipelines through the spec check,
    fit states through their sha256 digest, npz chunks and JSON
    sidecars through their parsers. A file that fails IS the torn
    write the atomic-write contract promises can't exist."""
    dirs = list(ctx["workload"].get("artifact_dirs") or [])
    dirs.extend(inv.get("dirs") or [])
    checked: list[str] = []
    torn: list[str] = []
    for base in dirs:
        for root, _dirs, files in os.walk(base):
            for fname in sorted(files):
                path = os.path.join(root, fname)
                try:
                    with open(path, "rb") as f:
                        magic = f.read(6)
                except OSError as e:
                    torn.append(f"{path}: {e!r}")
                    continue
                try:
                    if magic in (b"KSTF1\n", b"KSTP1\n"):
                        from keystone_tpu.core.serialization import (
                            load_pipeline,
                        )

                        load_pipeline(path)
                    elif magic == b"KSTS1\n":
                        from keystone_tpu.learn.merge import load_fit_state

                        load_fit_state(path)
                    elif fname.endswith(".npz"):
                        import numpy as np

                        with np.load(path) as z:
                            _ = list(z.files)
                    elif fname.endswith(".json"):
                        with open(path) as jf:
                            json.load(jf)
                    else:
                        continue
                    checked.append(path)
                except Exception as e:  # noqa: BLE001 — torn = any loader
                    # refusing its own artifact
                    torn.append(f"{path}: {e!r}")
    return {
        "ok": not torn and bool(checked),
        "detail": (
            f"{len(checked)} artifact(s) re-loaded through their "
            "digest/spec gates"
            + (f"; TORN: {torn[:3]}" if torn else "")
        ),
        "evidence": {"checked": len(checked), "torn": torn[:5]},
    }


@_invariant("alert_fired_and_cleared")
def _inv_alert_fired_and_cleared(inv: dict, ctx: dict) -> dict:
    """Replay the campaign's request outcomes through the PR-14 SLO
    burn-rate engine with windows scaled to the campaign wall: the
    named objective must FIRE while the injected failures are in-window
    and CLEAR once they slide out — the paging story, verified from
    the store alone, with the firing alert's trace exemplar as
    evidence."""
    from keystone_tpu.observe import slo as _slo
    from keystone_tpu.observe.collector import Collector

    objective = inv.get("objective", "availability")
    # the collector's store and tail cursors live under THIS campaign's
    # runner run dir, and only this campaign's run dirs are tailed — a
    # reused report dir must never replay a previous game day's request
    # outcomes through the burn engine
    col = Collector(
        os.path.join(ctx["run_dir"], "collector"),
        targets=[],
        watch=list(ctx["run_dirs"]),
    )
    try:
        col.tail_once()
        pts = col.store.query(
            _slo.REQUEST_SERIES, start=0.0, end=time.time() + 60.0
        )
        return _slo_replay(inv, objective, col.store, pts)
    finally:
        col.close()


def _slo_replay(inv: dict, objective: str, store, pts: list[dict]) -> dict:
    from keystone_tpu.observe import slo as _slo

    if not pts:
        return {
            "ok": False,
            "detail": "no request samples reached the time-series store",
            "evidence": {},
        }
    ts = [float(p["ts"]) for p in pts if isinstance(p.get("ts"), (int, float))]
    t0, t1 = min(ts), max(ts)
    wall = max(t1 - t0, 0.5)
    # floors, not trust: the replay advances in short/4 steps, so a
    # zero/negative override would spin the loop forever
    short = max(float(inv.get("short_s", max(wall / 2.0, 0.5))), 0.05)
    long_w = max(
        float(inv.get("long_s", max(wall * 2.0, short * 2.0))),
        short * 2.0,
    )
    window = _slo.BurnWindow(
        "campaign", short, long_w, float(inv.get("factor", 1.0))
    )
    kind = "latency" if objective == "latency" else "availability"
    obj = _slo.Objective(
        objective,
        kind,
        target=float(inv.get("target", 0.99)),
        threshold_s=(
            float(inv.get("threshold_ms", 250.0)) / 1e3
            if kind == "latency"
            else None
        ),
        min_points=int(inv.get("min_points", 2)),
    )
    engine = _slo.SLOEngine(
        store, _slo.SLOConfig([obj], [window]), emit=True
    )
    t = t0 + short / 4.0
    end = t1 + long_w + short
    while t <= end:
        engine.evaluate(now=t)
        t += short / 4.0
    fired = [a for a in engine.alerts if a["state"] == "firing"]
    cleared = [a for a in engine.alerts if a["state"] == "cleared"]
    evidence: dict = {
        "transitions": [
            {"state": a["state"], "burn_short": a.get("burn_short")}
            for a in engine.alerts
        ],
        "samples": len(pts),
    }
    if fired:
        if fired[0].get("exemplar_rid") is not None:
            evidence["rid"] = fired[0]["exemplar_rid"]
        if fired[0].get("exemplar_trace"):
            evidence["trace"] = fired[0]["exemplar_trace"]
    return {
        "ok": bool(fired) and bool(cleared),
        "detail": (
            f"{objective} burn alert "
            + (
                "fired and cleared"
                if fired and cleared
                else (
                    "fired but never cleared"
                    if fired
                    else "never fired"
                )
            )
            + f" over {len(pts)} request sample(s)"
        ),
        "evidence": evidence,
    }


def verify(spec: dict, ctx: dict) -> list[dict]:
    """Evaluate every invariant, returning one verdict row per spec
    entry: ``{"name", "ok", "detail", "evidence"}``."""
    out = []
    for inv in spec.get("invariants") or []:
        name = inv["check"]
        label = name
        for k in ("counter", "objective", "event", "action"):
            if inv.get(k):
                label = f"{name}({inv[k]})"
                break
        try:
            verdict = INVARIANTS[name](inv, ctx)
        except Exception as e:  # noqa: BLE001 — a crashed check is a FAIL
            # with the crash as its evidence, never a crashed campaign
            verdict = {
                "ok": False,
                "detail": f"invariant check crashed: {e!r}",
                "evidence": {},
            }
        verdict["name"] = label
        verdict["spec"] = inv
        out.append(verdict)
    return out


# ---------------------------------------------------------------- runner


def run_campaign(
    ref: str | dict,
    target: str | None = None,
    report_dir: str | None = None,
) -> dict:
    """Run one campaign end to end: validate, compile the fault
    schedule, drive the workload under a scoped observe run, verify the
    invariants from the observe substrate, emit the ``chaos`` verdict
    event, and write the report. Returns the result dict
    (``result["passed"]`` is the gate)."""
    from keystone_tpu.observe import events as _events
    from keystone_tpu.observe import metrics as _metrics
    from keystone_tpu.observe import spans as _spans
    from keystone_tpu.resilience import faults as _faults

    spec = load_campaign(ref)
    if target:
        spec["target"] = target
    validate_campaign(spec)
    name = spec.get("name", "campaign")
    if report_dir is None:
        report_dir = tempfile.mkdtemp(prefix=f"keystone-chaos-{name}-")
    os.makedirs(report_dir, exist_ok=True)
    # snapshot what was already there: a reused --report DIR keeps its
    # old runs on disk for the operator, but THIS campaign's evidence
    # is scoped to the run dirs created from here on — a verdict must
    # never judge a previous game day's events
    pre_existing = frozenset(os.listdir(report_dir))
    schedule = compile_schedule(spec)
    snap_before = _metrics.get_registry().snapshot()
    t0 = time.perf_counter()
    with _events.run(report_dir, chaos=name, target=spec["target"]) as log:
        log.emit(
            "chaos",
            action="campaign_start",
            campaign=name,
            target=spec["target"],
            seed=int(spec.get("seed", 0)),
            schedule=schedule,
        )
        _faults.configure(schedule or None)
        work_dir = log.run_dir or tempfile.mkdtemp(
            prefix=f"keystone-chaos-{name}-work-"
        )
        try:
            workload = WORKLOADS[spec["target"]](
                spec, report_dir, schedule, work_dir
            )
        except CampaignError:
            # a spec-level problem a workload driver only notices at
            # run time (an unknown replica kind) is an invalid
            # campaign, not a failed game day — refuse loudly like
            # validate would, never report it as a recovery regression
            raise
        except Exception as e:  # noqa: BLE001 — a crashed workload is a
            # failed campaign with the crash on record, not a traceback
            workload = {
                "kind": spec["target"],
                "ok": False,
                "client_ok": 0,
                "client_failures": 0,
                "error": repr(e),
                "artifact_dirs": [],
            }
        finally:
            _faults.reset()
        run_dirs = _campaign_run_dirs(report_dir, pre_existing)
        ctx = {
            "spec": spec,
            "report_dir": report_dir,
            "run_dir": log.run_dir or work_dir,
            "run_dirs": run_dirs,
            "workload": workload,
            "snap_before": snap_before,
            "snap_after": _metrics.get_registry().snapshot(),
            "events": _events_all(run_dirs),
            "spans": [
                rec
                for d in run_dirs
                for rec in _events.read_jsonl_rotated(
                    os.path.join(d, _spans.SPANS_FILE)
                )
            ],
        }
        ctx["spans"].sort(key=lambda r: float(r.get("ts") or 0.0))
        invariants = verify(spec, ctx)
        # a crashed workload fails the campaign even when no invariant
        # happens to notice (the invariants judge outcomes; a workload
        # that never ran produced none)
        passed = (
            all(v["ok"] for v in invariants)
            and workload.get("error") is None
        )
        fired = sorted(
            (str(ev.get("site")), str(ev.get("key")))
            for ev in ctx["events"]
            if ev.get("event") == "resilience"
            and ev.get("action") == "fault"
        )
        result = {
            "campaign": name,
            "target": spec["target"],
            "seed": int(spec.get("seed", 0)),
            "passed": passed,
            "schedule": schedule,
            "fired": fired,
            "workload": workload,
            "invariants": invariants,
            "wall_s": round(time.perf_counter() - t0, 3),
            "report_dir": report_dir,
            "run_dir": log.run_dir,
        }
        log.emit(
            "chaos",
            action="verdict",
            campaign=name,
            passed=passed,
            schedule=schedule,
            wall_s=result["wall_s"],
            invariants=[
                {"name": v["name"], "ok": v["ok"], "detail": v["detail"]}
                for v in invariants
            ],
        )
        _metrics.get_registry().counter(
            "chaos_campaigns", verdict="pass" if passed else "fail"
        ).inc()
        _write_report(result, report_dir)
    return result


def _write_report(result: dict, report_dir: str) -> None:
    from keystone_tpu.core.serialization import atomic_write

    try:
        with atomic_write(os.path.join(report_dir, "chaos_verdict.json")) as f:
            f.write(json.dumps(result, indent=1, default=repr).encode())
        with atomic_write(os.path.join(report_dir, "chaos_report.txt")) as f:
            f.write(render_report(result).encode())
    except OSError as e:
        from keystone_tpu.core.logging import get_logger

        get_logger("keystone_tpu.resilience").warning(
            "chaos: report write under %s failed (%r)", report_dir, e
        )


def render_report(result: dict) -> str:
    """The human-readable PASS/FAIL body: one line per invariant with
    its evidence, plus the exact ``observe trace`` command that resolves
    the cited exemplars."""
    inv = result["invariants"]
    n_ok = sum(1 for v in inv if v["ok"])
    lines = [
        f"chaos campaign {result['campaign']!r} — "
        f"{'PASS' if result['passed'] else 'FAIL'} "
        f"({n_ok}/{len(inv)} invariants) in {result['wall_s']:.1f}s",
        f"  target {result['target']}  seed {result['seed']}",
        f"  schedule: {result['schedule'] or '(no registry faults)'}",
    ]
    w = result.get("workload") or {}
    if w.get("kind") == "fleet":
        lines.append(
            f"  workload: {w.get('requests')} requests over "
            f"{w.get('replicas')} replica(s): {w.get('client_ok')} ok, "
            f"{w.get('client_failures')} failed "
            f"(p50 {w.get('request_p50_ms')}ms "
            f"p95 {w.get('request_p95_ms')}ms)"
        )
    elif w.get("kind") == "train":
        lines.append(
            f"  workload: supervised train exit {w.get('exit')}"
            + (" after relaunch" if w.get("relaunched") else "")
        )
    elif w.get("kind") == "refit":
        lines.append(
            f"  workload: refit fold ({w.get('chunks_folded')} folded, "
            f"{w.get('chunks_skipped')} skipped) + "
            f"{w.get('swaps_committed')} swap(s) "
            f"({w.get('swap_failures')} rolled back) under "
            f"{w.get('client_ok')} live request(s), "
            f"{w.get('client_failures')} failed"
        )
    if w.get("error"):
        lines.append(f"  workload ERROR: {w['error']}")
    if result.get("fired"):
        lines.append(
            "  faults fired: "
            + ", ".join(f"{s}@{k}" for s, k in result["fired"][:12])
        )
    exemplars = []
    for v in inv:
        mark = "PASS" if v["ok"] else "FAIL"
        ev = v.get("evidence") or {}
        tail = ""
        bits = []
        if ev.get("rid") is not None:
            bits.append(f"rid={ev['rid']}")
            exemplars.append(str(ev["rid"]))
        if ev.get("trace"):
            bits.append(f"trace={ev['trace']}")
        if bits:
            tail = f"  [exemplar {' '.join(bits)}]"
        lines.append(f"  [{mark}] {v['name']}: {v['detail']}{tail}")
        if not v["ok"] and ev:
            lines.append(f"         evidence: {json.dumps(ev, default=repr)[:300]}")
    if exemplars:
        lines.append(
            f"  resolve evidence: python -m keystone_tpu observe trace "
            f"{result['report_dir']} --request {exemplars[0]}"
        )
    lines.append(f"  report dir: {result['report_dir']}")
    return "\n".join(lines)


# ----------------------------------------------------------- train worker


def _train_worker(argv: list[str]) -> None:
    """The supervised train-game-day child: a small LM train run with
    checkpointing, the full fault surface, and a LocalKV membership
    monitor so heartbeat-layer sites (``cluster.heartbeat_drop``,
    ``kv.partition``) have a live publisher to bite. Run under
    ``python -m keystone_tpu supervise`` so ``cluster.host_kill``
    relaunches resume from the last intact checkpoint."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    args: dict[str, str] = {}
    i = 0
    while i + 1 < len(argv):
        if argv[i].startswith("--"):
            args[argv[i][2:]] = argv[i + 1]
        i += 2
    import jax
    import numpy as np

    from keystone_tpu.models import lm_transformer as lm
    from keystone_tpu.models.lm.train import train
    from keystone_tpu.resilience import cluster as _cluster

    seed = int(args.get("seed", 0))
    seq = int(args.get("seq", 16))
    vocab = int(args.get("vocab", 31))
    model = lm.TransformerLM.create(
        jax.random.key(seed),
        vocab=vocab,
        max_seq=seq,
        dim=int(args.get("dim", 16)),
        depth=int(args.get("depth", 1)),
        num_heads=2,
    )
    corpus = lm.synthetic_corpus(4_000, vocab, seed=seed)
    monitor = _cluster.start_monitor(
        process_id=0,
        num_processes=1,
        kv=_cluster.LocalKV(),
        interval_s=0.1,
        timeout_s=30.0,
    )
    try:
        model, losses = train(
            model,
            corpus,
            steps=int(args.get("steps", 12)),
            batch=int(args.get("batch", 4)),
            seq=seq,
            lr=1e-3,
            seed=seed,
            checkpoint_dir=args["ckpt"],
            checkpoint_every=int(args.get("every", 2)),
        )
    finally:
        if monitor is not None:
            _cluster.stop_monitor()
    from keystone_tpu.core.checkpoint import leaf_digest

    params_digest = [
        leaf_digest(x) for x in jax.tree_util.tree_leaves(model)
    ][:4]
    np.savez(
        args["out"],
        losses=np.asarray(losses),
        params_digest=np.asarray(params_digest),
    )


# --------------------------------------------------------------------- CLI


USAGE = """usage: python -m keystone_tpu chaos run <campaign> [--target fleet|train|refit] [--report DIR]
       python -m keystone_tpu chaos list [--json]
       python -m keystone_tpu chaos validate <campaign>

<campaign> is a JSON spec file or a canned campaign name (`chaos
list`). `run` drives the campaign's workload with its seeded fault
schedule armed, verifies the declarative invariants from the observe
substrate, prints the PASS/FAIL report, and exits nonzero on any
failed invariant. `validate` checks the spec against the live fault
registry (`faults --list --json`) and prints the compiled
KEYSTONE_FAULTS schedule without running anything.
"""


def main(argv: list[str] | None = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        raise SystemExit(USAGE)
    cmd, rest = argv[0], argv[1:]
    if cmd == "train-worker":
        return _train_worker(rest)
    if cmd == "list":
        canned = canned_campaigns()
        if "--json" in rest:
            out = []
            for name, path in canned.items():
                spec = load_campaign(path)
                out.append(
                    {
                        "name": name,
                        "target": spec.get("target"),
                        "description": spec.get("description", ""),
                        "path": path,
                    }
                )
            print(json.dumps({"campaigns": out}, indent=1))
            return
        for name, path in canned.items():
            spec = load_campaign(path)
            print(
                f"{name:<18} [{spec.get('target')}] "
                f"{spec.get('description', '')}"
            )
        return
    if cmd == "validate":
        if not rest:
            raise SystemExit("chaos validate needs a campaign argument")
        try:
            spec = load_campaign(rest[0])
            validate_campaign(spec)
        except CampaignError as e:
            raise SystemExit(f"invalid campaign: {e}") from None
        print(f"ok: {spec['name']} (target {spec['target']})")
        print(f"schedule: {compile_schedule(spec) or '(none)'}")
        return
    if cmd != "run":
        raise SystemExit(f"unknown chaos command {cmd!r}\n{USAGE}")
    if not rest:
        raise SystemExit("chaos run needs a campaign argument")
    target = None
    report_dir = None
    campaign = rest[0]
    rest = rest[1:]
    while rest:
        a = rest.pop(0)
        if a == "--target":
            if not rest:
                raise SystemExit("--target needs a value")
            target = rest.pop(0)
        elif a == "--report":
            if not rest:
                raise SystemExit("--report needs a directory argument")
            report_dir = rest.pop(0)
        else:
            raise SystemExit(f"unknown option {a!r}\n{USAGE}")
    try:
        result = run_campaign(campaign, target=target, report_dir=report_dir)
    except CampaignError as e:
        raise SystemExit(f"invalid campaign: {e}") from None
    print(render_report(result))
    if not result["passed"]:
        failing = [v["name"] for v in result["invariants"] if not v["ok"]]
        raise SystemExit(
            f"chaos: campaign {result['campaign']!r} FAILED "
            f"(invariants: {', '.join(failing) or 'workload error'})"
        )


if __name__ == "__main__":
    main()
