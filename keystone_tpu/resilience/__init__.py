"""Resilience subsystem: deterministic fault injection, retry/backoff,
numerical health guards, and hang watchdogs.

KeystoneML inherited fault tolerance from Spark (lineage recompute,
straggler re-execution); the TPU rebuild is one process, so surviving
the faults preemptible TPUs and flaky storage actually produce is
an explicit subsystem here (ROADMAP north star: heavy production
traffic). The degrade-don't-crash default follows tf.data's treatment
of ingest-level skip/retry as a framework concern:

- :mod:`.faults` — env-gated (``KEYSTONE_FAULTS``) seed-deterministic
  fault injection; every CI failure replays exactly.
- :mod:`.retry` — :class:`~keystone_tpu.resilience.retry.RetryPolicy`
  (exponential backoff + jitter + deadline + transient classifier),
  applied to tar/idx ingestion and checkpoint IO.
- :mod:`.guards` — non-finite/spike loss guards for the LM train loop
  (donation-safe in-program skip, one host sync per interval) and the
  opt-in pipeline output guard (``KEYSTONE_GUARD_OUTPUTS``).
- :mod:`.watchdog` — step-time stall detection with thread-stack
  diagnostics, optionally escalating a wedged loop to a hard abort.
- :mod:`.cluster` — elastic-multihost membership: coordination-service
  heartbeats, host-loss detection, coordinated-checkpoint barriers, and
  the exit-code protocol :mod:`.supervisor` (``python -m keystone_tpu
  supervise``) drives to relaunch a job on the surviving host set.
- :mod:`.chaos` — the campaign engine on top of all of it: composed
  multi-fault game days (``python -m keystone_tpu chaos run``) whose
  declarative invariants are verdicted from the observe substrate.

All of them are stdlib-light at import (jax loads lazily inside
functions) so the loaders and core pipeline can depend on them without
widening their import graph. Every retry/skip/guard/watchdog decision
emits through :mod:`keystone_tpu.observe` (events tagged
``phase="resilience"`` + metrics counters), so a run report shows
exactly what was survived.
"""

from __future__ import annotations

from keystone_tpu.resilience import (  # noqa: F401
    chaos,
    cluster,
    faults,
    guards,
    retry,
    watchdog,
)
from keystone_tpu.resilience.cluster import (  # noqa: F401
    EXIT_HOST_LOST,
    EXIT_WEDGED,
    ClusterBarrierError,
    ClusterError,
    ClusterMonitor,
    HostLostError,
)
from keystone_tpu.resilience.faults import (  # noqa: F401
    AcceleratorDrop,
    InjectedFault,
    SimulatedPreemption,
)
from keystone_tpu.resilience.guards import (  # noqa: F401
    GuardConfig,
    LossGuard,
    NumericalHealthError,
)
from keystone_tpu.resilience.retry import (  # noqa: F401
    CHECKPOINT_POLICY,
    IO_POLICY,
    RetryExhausted,
    RetryPolicy,
    is_transient,
)
from keystone_tpu.resilience.watchdog import Watchdog  # noqa: F401
