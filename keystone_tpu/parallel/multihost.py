"""Multi-host (multi-process) execution helpers.

The reference scales out with spark-submit + EC2 provisioning scripts
(``bin/pipelines-ec2.sh``); the TPU-native equivalent is JAX multi-process:
every host runs the same program, ``jax.distributed.initialize`` wires the
processes into one runtime, and global arrays are assembled from
process-local shards. Collectives ride ICI within a slice and DCN across
slices — the mesh construction in :mod:`keystone_tpu.parallel.mesh` is
unchanged because ``jax.devices()`` spans all hosts after initialization.

Typical launch (the SAME command on every host, e.g. via ``gcloud compute
tpus ... ssh --worker=all``; ``initialize()`` must run inside the process
that executes the pipeline, which is what the launcher flag does):

    python -m keystone_tpu --multihost <pipeline> ...
"""

from __future__ import annotations

import itertools
import json
import os

import jax
import numpy as np

from keystone_tpu.core.logging import get_logger

logger = get_logger("keystone_tpu.parallel.multihost")

#: merged cluster metrics written by :func:`rollup_metrics` on host 0,
#: rendered by ``python -m keystone_tpu observe <run-dir>``
CLUSTER_METRICS_FILE = "metrics_cluster.json"

# per-process roll-up sequence: every host calls rollup_metrics in the
# same program order (SPMD), so the counter yields matching KV keys and
# barrier ids without any extra coordination
_rollup_seq = itertools.count()

#: env override for :func:`initialize`'s ``init_timeout_s``.
ENV_INIT_TIMEOUT = "KEYSTONE_INIT_TIMEOUT_S"
_DEFAULT_INIT_TIMEOUT_S = 300.0


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    init_timeout_s: float | None = None,
) -> None:
    """Join this process into the multi-host runtime.

    With TPU VMs all arguments are discovered from the environment
    (``jax.distributed.initialize()`` no-arg form); explicit values support
    CPU/GPU test rigs. Under the run supervisor (``python -m keystone_tpu
    supervise``) the per-generation wiring arrives as
    ``KEYSTONE_COORDINATOR`` / ``KEYSTONE_PROCESS_ID`` /
    ``KEYSTONE_NUM_PROCESSES`` — consumed here as defaults, so
    ``supervise -- python -m keystone_tpu --multihost ...`` needs no
    placeholder plumbing; explicit arguments still win.

    ``init_timeout_s`` (default ``KEYSTONE_INIT_TIMEOUT_S``, else 300)
    bounds the join: a missing peer or dead coordinator fails in
    seconds with the coordinator address in the message instead of
    hanging the launch forever — on a preempted slice rejoin, the
    hang IS the failure mode. Non-coordinator
    processes preflight the coordinator's TCP port under this timeout
    (a clean, catchable RuntimeError names the address); the in-barrier
    wait is then bounded by jax's own ``initialization_timeout``, whose
    expiry the jax client escalates to a fatal process exit — bounded
    either way, never a silent hang.
    """
    if init_timeout_s is None:
        init_timeout_s = float(
            os.environ.get(ENV_INIT_TIMEOUT, "") or _DEFAULT_INIT_TIMEOUT_S
        )
    if coordinator_address is None and os.environ.get("KEYSTONE_COORDINATOR"):
        # the run supervisor's per-generation wiring (recomputed on
        # every relaunch — a stale value can't leak across generations
        # because the supervisor rewrites all three per child)
        coordinator_address = os.environ["KEYSTONE_COORDINATOR"]
        missing = [
            name
            for arg, name in (
                (num_processes, "KEYSTONE_NUM_PROCESSES"),
                (process_id, "KEYSTONE_PROCESS_ID"),
            )
            if arg is None and name not in os.environ
        ]
        if missing:
            raise RuntimeError(
                "KEYSTONE_COORDINATOR is set "
                f"({coordinator_address!r}) but {' and '.join(missing)} "
                "is not — the three variables wire one cluster together "
                "and must be set as a group (the run supervisor exports "
                "all of them; a manual launch must too). Unset "
                "KEYSTONE_COORDINATOR to use jax's own environment "
                "discovery instead."
            )
        if num_processes is None:
            num_processes = int(os.environ["KEYSTONE_NUM_PROCESSES"])
        if process_id is None:
            process_id = int(os.environ["KEYSTONE_PROCESS_ID"])
    kwargs = {"initialization_timeout": max(int(init_timeout_s), 1)}
    if coordinator_address is not None:
        kwargs.update(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        if process_id not in (None, 0):
            _preflight_coordinator(
                coordinator_address, init_timeout_s, process_id
            )
    try:
        jax.distributed.initialize(**kwargs)
    except Exception as e:  # noqa: BLE001 — re-raised with diagnosis
        addr = (
            coordinator_address
            or os.environ.get("JAX_COORDINATOR_ADDRESS")
            or "<auto-discovered>"
        )
        raise RuntimeError(
            f"multihost initialize failed (timeout {init_timeout_s:.0f}s, "
            f"coordinator {addr}, process_id={process_id}, "
            f"num_processes={num_processes}): every host must run the "
            "same command and reach the coordinator; check that no "
            f"worker died or was preempted. Underlying error: {e!r}"
        ) from e
    logger.info(
        "multihost: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )
    # every multihost worker start warm-starts from the persistent XLA
    # cache (core/runtime.py): a relaunched/rejoining host's
    # cold-start cost is compilation, and the supervisor's whole loss
    # budget assumes rejoin takes seconds, not minutes
    from keystone_tpu.core.runtime import enable_compilation_cache

    cache = enable_compilation_cache()
    if cache:
        logger.info("multihost: persistent compilation cache at %s", cache)


def _preflight_coordinator(
    addr: str, timeout_s: float, process_id: int
) -> None:
    """Bounded poll of the coordinator's TCP port before handing the
    process to ``jax.distributed.initialize``. The jax client reacts to
    its own init deadline with a FATAL process exit (no Python
    exception to catch), so the reachable-at-all check must happen out
    here where a dead coordinator can fail cleanly, fast, and with the
    address in the message."""
    import socket
    import time

    host, _, port = addr.rpartition(":")
    host = host.strip("[]")  # bracketed IPv6
    if not host or not port.isdigit():
        # unparseable address: let jax.distributed do the validating —
        # the preflight exists to diagnose reachability, not syntax
        return
    deadline = time.monotonic() + timeout_s
    last: Exception | None = None
    while True:
        # at least ONE attempt even when timeout_s is 0/tiny — a live
        # coordinator must never be reported unreachable unprobed
        try:
            with socket.create_connection((host, int(port)), timeout=1.0):
                return
        except OSError as e:
            last = e
        if time.monotonic() >= deadline:
            break
        time.sleep(0.2)
    raise RuntimeError(
        f"multihost initialize: coordinator {addr} unreachable after "
        f"{timeout_s:.0f}s (process_id={process_id}); the coordinator "
        "(process 0) must be running and reachable before workers join. "
        f"Last error: {last!r}"
    )


def merge_metric_dumps(dumps: list[dict]) -> dict:
    """Merge per-host kind-tagged metric dumps
    (:meth:`keystone_tpu.observe.metrics.MetricsRegistry.dump`) into
    cluster totals: counters sum, gauges take the max (watermark
    semantics — the cluster's HBM peak is the worst host's peak), timers
    pool count/total/min/max and recompute percentiles from the pooled
    reservoirs rather than averaging per-host quantiles.

    Returns a snapshot-shaped dict (series key → number, or summary dict
    for timers) ready for a report to render.
    """
    from keystone_tpu.observe.metrics import percentiles

    acc: dict[str, dict] = {}
    for dump in dumps:
        for key, entry in (dump or {}).items():
            if not isinstance(entry, dict):
                continue
            kind = entry.get("kind", "counter")
            cur = acc.get(key)
            if cur is None:
                cur = dict(entry)
                if kind == "timer":
                    cur["samples"] = list(entry.get("samples") or [])
                acc[key] = cur
                continue
            if kind == "counter":
                cur["value"] = cur.get("value", 0) + entry.get("value", 0)
            elif kind == "gauge":
                cur["value"] = max(
                    cur.get("value", 0.0), entry.get("value", 0.0)
                )
            else:  # timer
                n_cur, n_new = cur.get("count", 0), entry.get("count", 0)
                cur["count"] = n_cur + n_new
                cur["total_s"] = cur.get("total_s", 0.0) + entry.get(
                    "total_s", 0.0
                )
                mins = [
                    d["min_s"]
                    for d, n in ((cur, n_cur), (entry, n_new))
                    if n and "min_s" in d
                ]
                if mins:
                    cur["min_s"] = min(mins)
                cur["max_s"] = max(
                    cur.get("max_s", 0.0), entry.get("max_s", 0.0)
                )
                cur["samples"].extend(entry.get("samples") or [])
    out: dict[str, object] = {}
    for key, entry in acc.items():
        if entry.get("kind") == "timer":
            samples = entry.pop("samples", [])
            entry.pop("kind", None)
            if entry.get("count"):
                entry["mean_s"] = entry["total_s"] / entry["count"]
            for pkey in ("p50_s", "p95_s", "p99_s"):
                entry.pop(pkey, None)
            if samples:
                p = percentiles(samples, (50, 95, 99))
                entry.update(p50_s=p[50], p95_s=p[95], p99_s=p[99])
            out[key] = entry
        else:
            out[key] = entry.get("value")
    return out


def _coordination_client():
    """The jax coordination-service KV client for this process, or None
    when ``jax.distributed`` was never initialized. Private jax surface
    (``jax._src.distributed``) by necessity — there is no public KV API
    — so every caller treats None/AttributeError as "transport
    unavailable" and degrades."""
    try:
        from jax._src import distributed as _dist

        return getattr(_dist.global_state, "client", None)
    except Exception:  # noqa: BLE001 — jax refactor moved the module
        return None


def _gather_dumps(
    payload: str, pid: int, nprocs: int, timeout_s: float
) -> list[dict] | None:
    """Gather every host's serialized metrics dump onto host 0. Primary
    transport: the coordination-service KV store (works wherever
    ``jax.distributed`` init works — including CPU test rigs whose XLA
    build has no multiprocess collectives). Fallback: a padded uint8
    ``process_allgather`` over device collectives. Returns the dump list
    on host 0, None on other hosts and on total transport failure."""
    client = _coordination_client()
    seq = next(_rollup_seq)
    if client is not None:
        # No cross-path fallback here: whether a coordination-service
        # client exists IS cluster-consistent (jax.distributed init), but
        # a mid-path failure on one host is not — if host 0 alone fell
        # through to the collective below after the barrier passed, it
        # would block forever in an allgather no other host joins.
        # Degrading to per-host metrics is the safe failure.
        try:
            client.key_value_set(f"keystone/metrics/{seq}/{pid}", payload)
            client.wait_at_barrier(
                f"keystone_metrics_rollup_{seq}", int(timeout_s * 1000)
            )
            if pid != 0:
                return None
            dumps = [
                json.loads(
                    client.blocking_key_value_get(
                        f"keystone/metrics/{seq}/{i}",
                        int(timeout_s * 1000),
                    )
                )
                for i in range(nprocs)
            ]
            try:
                # reclaim the payloads: a long-lived job rolling up
                # periodically must not grow the coordinator's memory
                # by one dump per host per call
                client.key_value_delete(f"keystone/metrics/{seq}/")
            except Exception:  # noqa: BLE001 — older jaxlib, best-effort
                pass
            return dumps
        except Exception as e:  # noqa: BLE001 — degraded, never fatal
            logger.warning(
                "metrics roll-up over the coordination service failed "
                "(%r); each host keeps only its own metrics",
                e,
            )
            return None
    try:
        from jax.experimental import multihost_utils

        blob = np.frombuffer(payload.encode(), np.uint8)
        lens = np.asarray(
            multihost_utils.process_allgather(
                np.array([blob.size], np.int32)
            )
        ).reshape(nprocs)
        padded = np.zeros(int(lens.max()), np.uint8)
        padded[: blob.size] = blob
        gathered = np.asarray(multihost_utils.process_allgather(padded))
        if pid != 0:
            return None
        return [
            json.loads(bytes(gathered[i, : int(lens[i])]).decode())
            for i in range(nprocs)
        ]
    except Exception as e:  # noqa: BLE001 — degraded, never fatal
        logger.warning(
            "metrics roll-up failed (%r); each host keeps only its own "
            "metrics",
            e,
        )
        return None


def rollup_metrics(
    out_dir: str | None = None, timeout_s: float = 60.0
) -> dict | None:
    """Cluster-wide metrics roll-up: every host serializes its metrics
    registry dump, host 0 gathers and merges them (counters summed,
    gauge watermarks maxed, timer reservoirs pooled) so a run report
    shows cluster totals instead of host-0-only numbers.

    ALL hosts must call this (it synchronizes at a barrier) — the
    launcher does so after a ``--multihost`` pipeline returns. Host 0
    writes ``metrics_cluster.json`` under ``out_dir`` (when given) and
    emits a ``metrics_rollup`` event; it returns the merged dict. Other
    hosts return None. Transport failure degrades to a warning and None
    — observability must not take down the run it watches."""
    from keystone_tpu.observe import events as _events
    from keystone_tpu.observe import metrics as _metrics

    try:
        nprocs = jax.process_count()
        pid = jax.process_index()
    except Exception:  # noqa: BLE001 — backend init failure
        nprocs, pid = 1, 0
    local = {"process": pid, "metrics": _metrics.get_registry().dump()}
    if nprocs == 1:
        dumps: list[dict] | None = [local]
    else:
        # the gather is a real cross-host collective: its wall is
        # classified (bucket="collective") in the goodput report
        from keystone_tpu.observe import spans as _spans

        with _spans.span(
            "multihost.rollup_gather", bucket="collective", hosts=nprocs
        ):
            dumps = _gather_dumps(json.dumps(local), pid, nprocs, timeout_s)
        if dumps is None:
            return None
    merged = {
        "hosts": nprocs,
        "metrics": merge_metric_dumps([d.get("metrics", {}) for d in dumps]),
    }
    if out_dir:
        try:
            path = os.path.join(out_dir, CLUSTER_METRICS_FILE)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(merged, f, indent=1)
            os.replace(tmp, path)
        except OSError as e:
            logger.warning(
                "cannot write %s under %s (%r)",
                CLUSTER_METRICS_FILE,
                out_dir,
                e,
            )
    log = _events.active()
    if log is not None:
        log.emit(
            "metrics_rollup",
            hosts=nprocs,
            series=len(merged["metrics"]),
        )
    return merged


def global_batch_from_local(local_batch: np.ndarray, mesh, ndim: int | None = None):
    """Assemble a global data-sharded array from this process's local rows
    (the successor of per-executor RDD partitions; wraps
    ``jax.make_array_from_process_local_data``)."""
    from keystone_tpu.parallel.mesh import data_sharding

    sharding = data_sharding(mesh, ndim or local_batch.ndim)
    return jax.make_array_from_process_local_data(sharding, local_batch)
