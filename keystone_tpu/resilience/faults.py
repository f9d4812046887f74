"""Deterministic, env-gated fault injection.

The Spark substrate the reference ran on made faults routine (lineage
recompute, straggler re-execution); this rebuild is a single process, so
the failures preemptible TPUs and flaky storage actually produce —
truncated tars, dropped accelerators, NaN'd batches, preemption — must
be *injectable* to be survivable-by-construction. Every injection is
derived from a seed, never from wall clock or live RNG state, so any
failure a CI run produces reproduces exactly on replay.

Activation mirrors :mod:`keystone_tpu.observe.events`: one env var,
one global read on the hot path when off.

Spec grammar (``KEYSTONE_FAULTS``, comma-separated)::

    site:p:seed[:max]   # fire with probability p per check (0 < p <= 1)
    site:@k:seed        # fire exactly when the check key equals k

``site`` is a registered injection point (``python -m keystone_tpu
faults --list``). Checks are keyed: call sites that have a natural
stable key (the train loop's step index) pass it explicitly, so the
schedule is a pure function of ``(seed, site, key)`` and survives a
process restart — a resumed run re-derives the same decisions for the
steps it replays and never re-fires a fault whose key is behind it.
Sites without a natural key use a per-site invocation counter (reset at
process start — deterministic for serial ingestion). ``max`` caps total
fires in one process (default unlimited).

Example — one transient tar error, a NaN batch at step 7, and one
preemption after step 12::

    KEYSTONE_FAULTS="tar.read:@0:0,train.nan:@7:0,train.preempt:@12:0"
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
from typing import Any

ENV_FAULTS = "KEYSTONE_FAULTS"

#: Registered injection sites — the contract between specs and call
#: sites. A spec naming an unregistered site fails at parse time so a
#: typo'd CI matrix is caught offline (``faults --validate``).
SITES: dict[str, str] = {
    "tar.read": "raise IOError opening/reading a tar archive "
    "(loaders/streaming.py, loaders/image_loaders.py)",
    "idx.read": "raise IOError reading an IDX (MNIST ubyte) file "
    "(loaders/idx.py)",
    "batch.nan": "poison a float batch with NaNs before a chained "
    "pipeline fit (core/pipeline.py)",
    "accel.fit": "drop the accelerator mid-fit: raise AcceleratorDrop "
    "from the chained-fit bracket (core/pipeline.py)",
    "ckpt.save": "raise IOError inside a checkpoint save "
    "(core/checkpoint.py)",
    "ckpt.restore": "raise IOError inside a checkpoint restore "
    "(core/checkpoint.py)",
    "train.nan": "NaN the LM train loss+grads at the keyed step "
    "(models/lm/train.py; key = step index)",
    "train.preempt": "simulate preemption AFTER the keyed train step "
    "completes (models/lm/train.py; key = step index)",
    "train.sigterm": "deliver a real SIGTERM to this process after the "
    "keyed train step (models/lm/train.py; key = step index)",
    "cluster.heartbeat_drop": "skip publishing this host's membership "
    "heartbeat at the keyed beat (resilience/cluster.py; key = beat "
    "index)",
    "cluster.host_kill": "SIGKILL this process after the keyed train "
    "step — a sudden host death: no checkpoint, no cleanup "
    "(models/lm/train.py; key = step index; `supervise` strips this "
    "site on relaunch so the survivor set doesn't replay the kill)",
    "serve.drop": "shed the keyed request at admission — the serving "
    "front end answers 503 (serve/server.py; key = request id)",
    "serve.slow_request": "inject KEYSTONE_SERVE_SLOW_MS of extra "
    "latency into the keyed request before dispatch — the tail-latency "
    "drill (serve/server.py; key = request id)",
    "refit.corrupt_chunk": "fail reading the keyed labeled chunk in the "
    "refit daemon — the chunk is skipped with a counter and the stream "
    "continues (learn/refit.py; key = chunk file name)",
    "refit.state_digest": "report a fit-state digest mismatch on load — "
    "the refit daemon must refuse the corrupt base loudly "
    "(learn/merge.py; key = state path)",
    "serve.swap_fail": "fail a model hot-swap after the candidate "
    "compiled but before commit — the server must keep serving the "
    "prior version and say so (learn/swap.py; key = swap index)",
    "fleet.replica_kill": "SIGKILL the replica the keyed router request "
    "is about to dispatch to — the sudden-replica-death drill: the "
    "router must fail the request over and the fleet supervisor must "
    "relaunch the replica (serve/fleet.py; key = router request id; "
    "checked once per request, never on failover retries)",
    "fleet.slow_replica": "inject KEYSTONE_SERVE_SLOW_MS of extra "
    "latency into the keyed router request's first dispatch — the "
    "hedged-dispatch drill (serve/fleet.py; key = router request id)",
    "fleet.conn_reset": "reset the connection of the keyed router "
    "request's first dispatch (ConnectionResetError before any bytes "
    "reach the replica) — the failover drill (serve/fleet.py; key = "
    "router request id)",
    "tune.bad_knob": "force an autotuner knob to its worst bound at the "
    "keyed evaluation window — the revert-guard drill: the next "
    "window's goodput regression must walk the knob back "
    "(plan/tune.py; key = evaluation index)",
    "collector.scrape_fail": "fail the keyed collector scrape attempt — "
    "a replica dying mid-scrape: the store keeps a gap for that target "
    "and cycle and collector_scrape_fail increments; the collector must "
    "never crash or tear a segment (observe/collector.py; key = scrape "
    "attempt index)",
    "ckpt.disk_full": "raise ENOSPC (disk full) at the keyed artifact "
    "write — inside core/serialization.atomic_write (the temp file is "
    "discarded, the committed artifact is never touched) and the orbax "
    "train-save bracket (core/checkpoint.py, where the train loop "
    "degrades loudly with a ckpt_save_failed event and keeps the "
    "previous checkpoint); key = save step at checkpoint saves, "
    "artifact file name inside atomic_write — disjoint domains, so a "
    "keyed @step campaign never aliases onto an unrelated write",
    "kv.partition": "drop a coordination-service KV publish/read in the "
    "cluster membership monitor — a network partition without a "
    "network: a partitioned publisher counts it as transport loss and "
    "a fully partitioned non-coordinator concludes host 0 is gone "
    "(resilience/cluster.py; key = beat index for publishes, "
    "'read:N' counter for reads — disjoint domains, so a keyed "
    "@beat step never also eats a detector/poll read)",
}


#: the natural key each site is checked under — declared structurally
#: (not parsed out of the description prose) because ``faults --list
#: --json`` is a published contract campaign specs build against.
#: ``None`` = per-site invocation counter (deterministic for serial
#: call sites). A site registered in :data:`SITES` without an entry
#: here fails the registry-consistency test.
SITE_KEYS: dict[str, str | None] = {
    "tar.read": None,
    "idx.read": None,
    "batch.nan": None,
    "accel.fit": None,
    "ckpt.save": None,
    "ckpt.restore": None,
    "ckpt.disk_full": "save step (checkpoint saves) / artifact file "
    "name (atomic_write)",
    "train.nan": "step index",
    "train.preempt": "step index",
    "train.sigterm": "step index",
    "cluster.heartbeat_drop": "beat index",
    "cluster.host_kill": "step index",
    "kv.partition": "beat index (publishes) / 'read:N' counter (reads)",
    "serve.drop": "request id",
    "serve.slow_request": "request id",
    "refit.corrupt_chunk": "chunk file name",
    "refit.state_digest": "state path",
    "serve.swap_fail": "swap index",
    "fleet.replica_kill": "router request id",
    "fleet.slow_replica": "router request id",
    "fleet.conn_reset": "router request id",
    "tune.bad_knob": "evaluation index",
    "collector.scrape_fail": "scrape attempt index",
}


def site_catalog() -> list[dict]:
    """Machine-readable registry rows: name, description, and the
    natural key the site is checked under (:data:`SITE_KEYS`; None =
    per-site invocation counter). The ``faults --list --json`` body —
    what campaign specs (``resilience/chaos.py``) validate against."""
    return [
        {
            "name": site,
            "description": SITES[site],
            "key": SITE_KEYS.get(site),
        }
        for site in sorted(SITES)
    ]


class InjectedFault(IOError):
    """An injected transient IO failure. Subclasses IOError so the
    retry classifier treats it exactly like the real thing."""


class AcceleratorDrop(RuntimeError):
    """An injected accelerator loss, shaped like the runtime error a
    dead device link produces (message carries UNAVAILABLE so transient
    classifiers see it the way they'd see the real XlaRuntimeError)."""

    def __init__(self, site: str):
        super().__init__(
            f"UNAVAILABLE: accelerator lost (injected fault at {site!r})"
        )


class SimulatedPreemption(RuntimeError):
    """An injected preemption between train steps. The train loop's
    ``finally`` checkpoint path must run before this propagates — that
    is the behavior under test."""


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One parsed ``site:p:seed[:max]`` clause."""

    site: str
    p: float | None  # probability per check, or None when keyed by `at`
    at: int | None  # exact key to fire on (the `@k` form)
    seed: int
    max_fires: int | None = None


def parse_spec(text: str) -> list[FaultSpec]:
    """Parse a ``KEYSTONE_FAULTS`` value; raises ValueError with the
    offending clause on any grammar or unknown-site error."""
    specs: list[FaultSpec] = []
    for clause in text.split(","):
        clause = clause.strip()
        if not clause:
            continue
        parts = clause.split(":")
        if len(parts) not in (2, 3, 4):
            raise ValueError(
                f"fault spec {clause!r}: expected site:p[:seed[:max]]"
            )
        site = parts[0]
        if site not in SITES:
            known = ", ".join(sorted(SITES))
            raise ValueError(
                f"fault spec {clause!r}: unknown site {site!r} "
                f"(known: {known})"
            )
        p: float | None = None
        at: int | None = None
        if parts[1].startswith("@"):
            at = int(parts[1][1:])
        else:
            p = float(parts[1])
            if not 0.0 < p <= 1.0:
                raise ValueError(
                    f"fault spec {clause!r}: p={p} outside (0, 1]"
                )
        seed = int(parts[2]) if len(parts) > 2 else 0
        max_fires = int(parts[3]) if len(parts) > 3 else None
        specs.append(
            FaultSpec(site=site, p=p, at=at, seed=seed, max_fires=max_fires)
        )
    return specs


def unit_hash(seed: int, site: str, key: Any) -> float:
    """Deterministic uniform in [0, 1) from (seed, site, key) — the
    whole schedule is this pure function, so every CI failure replays.
    Shared seed-derivation primitive of the resilience package (the
    retry jitter uses it too)."""
    digest = hashlib.sha256(f"{seed}|{site}|{key}".encode()).digest()
    return int.from_bytes(digest[:8], "big") / 2**64


class FaultPlan:
    """The active set of fault specs plus per-site counters/fire caps."""

    def __init__(self, specs: list[FaultSpec]):
        self.specs = specs
        self._by_site: dict[str, list[FaultSpec]] = {}
        for s in specs:
            self._by_site.setdefault(s.site, []).append(s)
        self._lock = threading.Lock()
        self._counters: dict[str, int] = {}
        self._fired: dict[int, int] = {}  # id(spec) -> fire count

    def has_site(self, site: str) -> bool:
        """True when any spec targets ``site`` (callers that must build
        a different program when a site is armed check this once)."""
        return site in self._by_site

    def should_fire(self, site: str, key: Any | None = None) -> bool:
        specs = self._by_site.get(site)
        if not specs:
            return False
        with self._lock:
            if key is None:
                key = self._counters.get(site, 0)
                self._counters[site] = key + 1
            for spec in specs:
                if spec.at is not None:
                    hit = key == spec.at
                else:
                    hit = unit_hash(spec.seed, site, key) < spec.p
                if not hit:
                    continue
                n = self._fired.get(id(spec), 0)
                if spec.max_fires is not None and n >= spec.max_fires:
                    continue
                self._fired[id(spec)] = n + 1
                self._observe(site, key)
                return True
        return False

    def _observe(self, site: str, key: Any) -> None:
        from keystone_tpu.resilience.emit import decision

        decision(
            "fault",
            counter="faults_fired",
            counter_labels={"site": site},
            site=site,
            key=key,
        )


# Lazy three-state plan, the events.active() idiom: _UNINIT → parse env
# once → (FaultPlan | None). The hot path with no faults configured is
# one module-global read.
_UNINIT: Any = object()
_plan: Any = _UNINIT
_state_lock = threading.Lock()


def active() -> FaultPlan | None:
    global _plan
    plan = _plan
    if plan is _UNINIT:
        with _state_lock:
            if _plan is _UNINIT:
                text = os.environ.get(ENV_FAULTS)
                _plan = FaultPlan(parse_spec(text)) if text else None
            plan = _plan
    return plan


def configure(spec: str | None) -> None:
    """Install a fault plan programmatically (tests); ``None`` disables."""
    global _plan
    with _state_lock:
        _plan = FaultPlan(parse_spec(spec)) if spec else None


def reset() -> None:
    """Drop the plan and re-arm env detection."""
    global _plan
    with _state_lock:
        _plan = _UNINIT


def fire(site: str, key: Any | None = None) -> bool:
    """True when the active plan schedules a fault here. ONE global read
    when no plan is configured — safe on per-batch paths."""
    plan = active()
    if plan is None:
        return False
    return plan.should_fire(site, key)


def maybe_raise(
    site: str, key: Any | None = None, note: str = ""
) -> None:
    """Raise an :class:`InjectedFault` (IOError) when scheduled."""
    if fire(site, key):
        raise InjectedFault(
            f"injected fault at {site!r}"
            + (f" ({note})" if note else "")
        )


def maybe_disk_full(key: Any | None = None, note: str = "") -> None:
    """Raise an :class:`InjectedFault` carrying ``errno.ENOSPC`` when
    the ``ckpt.disk_full`` site is scheduled — the shape a full disk
    actually produces, so classifiers that key off errno (the retry
    policy deliberately treats ENOSPC as non-transient: a full disk
    does not heal on a 100 ms backoff) see the real thing."""
    if fire("ckpt.disk_full", key):
        import errno

        raise InjectedFault(
            errno.ENOSPC,
            "No space left on device (injected fault at 'ckpt.disk_full'"
            + (f": {note}" if note else "")
            + ")",
        )


def maybe_drop_accelerator(site: str = "accel.fit", key: Any | None = None) -> None:
    if fire(site, key):
        raise AcceleratorDrop(site)


def maybe_preempt(key: Any | None = None) -> None:
    if fire("train.preempt", key):
        raise SimulatedPreemption(
            f"injected preemption after train step {key}"
        )


def poison(site: str, batch, key: Any | None = None):
    """Return ``batch`` with its first row NaN-poisoned when scheduled.
    Non-float, scalar, and empty batches pass through untouched (the
    fire is still recorded — the schedule is the schedule)."""
    if not fire(site, key):
        return batch
    import numpy as np

    view = np.asarray(batch)
    if (
        not np.issubdtype(view.dtype, np.floating)
        or view.ndim == 0
        or view.shape[0] == 0
    ):
        return batch
    arr = np.array(view, copy=True)
    arr.reshape(arr.shape[0], -1)[0, :] = np.nan
    return arr


def main(argv: list[str] | None = None) -> None:
    """``python -m keystone_tpu faults --list|--validate SPEC``."""
    import sys

    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        raise SystemExit(
            "usage: python -m keystone_tpu faults --list [--json]\n"
            "       python -m keystone_tpu faults --validate SPEC\n"
            "spec grammar: site:p:seed[:max] | site:@k:seed  "
            "(comma-separated; see KEYSTONE_FAULTS)\n"
            "--list --json prints the machine-readable site registry "
            "(name, description, natural key) that chaos campaign "
            "specs validate against"
        )
    if argv[0] == "--list":
        try:
            if "--json" in argv:
                import json

                print(json.dumps({"sites": site_catalog()}, indent=1))
                return
            width = max(len(s) for s in SITES)
            for site in sorted(SITES):
                print(f"{site:<{width}}  {SITES[site]}")
        except BrokenPipeError:  # | head closed the pipe — fine
            sys.stderr.close()
        return
    if argv[0] == "--validate":
        if len(argv) < 2:
            raise SystemExit("--validate needs a spec argument")
        try:
            specs = parse_spec(argv[1])
        except ValueError as e:
            raise SystemExit(f"invalid: {e}")
        for s in specs:
            when = f"@{s.at}" if s.at is not None else f"p={s.p}"
            cap = "" if s.max_fires is None else f" max={s.max_fires}"
            print(f"ok: {s.site} {when} seed={s.seed}{cap}")
        return
    raise SystemExit(f"unknown option {argv[0]!r}; try --list")
