"""The refit daemon: fold live labeled chunks, re-finalize, republish.

``python -m keystone_tpu refit <state.ksts> --watch <dir>`` tails a
directory of labeled chunks (``*.npz`` files with ``data`` and
``labels`` arrays — the producer drops them in ATOMICALLY, writing a
temp name then renaming to ``*.npz``; the daemon never deletes them)
and closes the online-learning loop:

- each NEW chunk is folded through the SAME fused featurize+accumulate
  segment the original fit used
  (:func:`keystone_tpu.plan.executor.fit_stream` with the persisted
  state as ``init_state``) — old rows are never re-featurized, the
  per-chunk cost is O(chunk·D²) however much history the state holds;
- re-finalize is the estimator's ``fit_stats_finalize`` — O(D³),
  N-independent — and the result is published as a **versioned**
  fitted pipeline (``model_v000042.kst`` plus an atomically-replaced
  ``current.kst`` pointer) via
  :func:`keystone_tpu.core.serialization.save_fitted`, ready for the
  server's ``/admin/reload`` hot-swap (``--notify URL`` posts the
  reload automatically);
- offsets persist **in the state file's own meta** (the ``processed``
  chunk list rides the digest-checked artifact), so delivery is
  at-least-once with no double counting: a crash after folding but
  before the state save lands resumes from the last durable state and
  re-folds exactly the unacked chunks;
- a chunk that won't read — truncated producer write, or the
  ``refit.corrupt_chunk`` drill — is skipped loudly (counter + a
  ``refit`` event) and the stream continues; a state file that fails
  its digest (``refit.state_digest`` drill) refuses to start at all.

The daemon is single-process; across hosts, accumulate per-host states
and combine with :func:`keystone_tpu.learn.merge.allmerge_fit_state`
(the merge IS the multihost reduction).
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import sys
import threading
import time
from typing import Any

import numpy as np

from keystone_tpu.core.logging import get_logger
from keystone_tpu.learn.merge import (
    FitStateError,
    load_fit_state,
    save_fit_state,
)
from keystone_tpu.observe import events as _events
from keystone_tpu.observe import metrics as _metrics
from keystone_tpu.resilience import faults as _faults

logger = get_logger("keystone_tpu.learn.refit")

#: the atomically-replaced pointer to the newest published model —
#: what a server's reload endpoint and SIGHUP re-read
CURRENT_MODEL = "current.kst"


def bootstrap_state(
    chain: Any, data: Any, labels: Any, state_path: str, **meta: Any
):
    """Fit the initial corpus through the fused streaming path and
    persist the accumulated state — the artifact the refit daemon
    resumes from. Returns ``(fitted_pipeline, state)``; the state file
    carries the estimator, prefix, block widths, a 1-row input sample
    (so ``serve`` can export reloads without ``--input-dim``), and the
    row count."""
    from keystone_tpu.core.pipeline import Pipeline
    from keystone_tpu.plan import executor as _executor
    from keystone_tpu.plan.fused_fit import plan_fit

    plan = plan_fit(chain, data, labels)
    if not plan.fit or not plan.fit.fused:
        raise FitStateError(
            "bootstrap needs a fully fusable streaming-fit chain "
            f"(fallback reason recorded in the plan decisions: "
            f"{[d for d in plan.decisions if d.get('rule') == 'fit_fallback']})"
        )
    state = _executor.fit_stream(plan, data, labels)
    model = chain.est.fit_stats_finalize(state, widths=plan.fit.widths)
    from keystone_tpu.plan.executor import _prefix_nodes

    prefix = tuple(_prefix_nodes(chain))
    save_fit_state(
        state,
        state_path,
        est=chain.est,
        prefix=prefix,
        widths=plan.fit.widths,
        sample=np.asarray(data[:1]),
        rows=int(np.asarray(data).shape[0]),
        version=0,
        processed=[],
        **meta,
    )
    return Pipeline.of(chain.prefix, model), state


class RefitDaemon:
    """One watch loop over a labeled-chunk directory. Construction
    loads (and digest-verifies) the state; :meth:`run_once` folds every
    new chunk and republishes when anything changed; :meth:`run` loops
    with a poll interval until SIGTERM/SIGINT."""

    def __init__(
        self,
        state_path: str,
        watch_dir: str,
        *,
        out_dir: str | None = None,
        notify_url: str | None = None,
    ):
        self.state_path = state_path
        self.watch_dir = watch_dir
        self.out_dir = out_dir or os.path.dirname(
            os.path.abspath(state_path)
        )
        self.notify_url = notify_url
        self.fs = load_fit_state(state_path)  # loud on digest mismatch
        if self.fs.est is None:
            raise FitStateError(
                f"{state_path} carries no estimator — it was saved "
                "without est=; the refit daemon cannot re-finalize it"
            )
        self.state = self.fs.state
        self.processed: set[str] = set(
            self.fs.meta.get("processed") or ()
        )
        self.version = int(self.fs.meta.get("version") or 0)
        self.rows_total = int(self.fs.meta.get("rows") or 0)
        self._plan = None
        self._stop = threading.Event()
        os.makedirs(self.out_dir, exist_ok=True)

    # ------------------------------------------------------------- scan

    def pending(self) -> list[str]:
        """New chunk files in arrival (name) order — producers name
        chunks monotonically (timestamps, sequence numbers) so sorted
        order is stream order."""
        try:
            names = os.listdir(self.watch_dir)
        except OSError:
            return []
        return sorted(
            f
            for f in names
            if f.endswith(".npz") and f not in self.processed
        )

    # ------------------------------------------------------------- fold

    def _label_width(self) -> int:
        s = self.state
        if hasattr(s, "atb"):
            return int(np.asarray(s.atb).shape[-1])
        return int(np.asarray(s.at_labels).shape[-1])

    def _ensure_plan(self, first_chunk: Any = None):
        """The fused fit plan, built ONCE from the state's own saved
        input sample (falling back to the first chunk when an old state
        carries none) and reused for every fold. Probing off the STATE
        — not off whatever chunk happens to arrive — is what separates
        failure classes: a plan that won't fuse here is a
        daemon/config-level problem and raises :class:`FitStateError`
        (the daemon halts loudly, chunks stay pending), while a
        malformed individual chunk fails later inside the fold step and
        is skipped without touching its neighbors."""
        if self._plan is not None:
            return self._plan
        from keystone_tpu.core.pipeline import (
            ChainedLabelEstimator,
            Identity,
            Pipeline,
        )
        from keystone_tpu.plan.fused_fit import _DEFAULT_FIT_CHUNK, plan_fit

        probe = self.fs.meta.get("sample")
        if probe is None:
            probe = first_chunk
        if probe is None:
            raise FitStateError(
                f"{self.state_path} carries no input sample and no "
                "chunk is available to probe the plan with"
            )
        probe = np.asarray(probe)[:1]
        labels_probe = np.zeros((1, self._label_width()), np.float32)
        prefix = (
            Pipeline(nodes=tuple(self.fs.prefix))
            if self.fs.prefix
            else Identity()
        )
        chain = ChainedLabelEstimator(prefix=prefix, est=self.fs.est)
        # probe is 1 row, so the planner has no row count to bound the
        # chunk size with — pin the default so an oversized chunk file
        # still streams instead of staging whole
        plan = plan_fit(
            chain, probe, labels_probe, chunk_size=_DEFAULT_FIT_CHUNK
        )
        if not plan.fit or not plan.fit.fused:
            raise FitStateError(
                "refit does not plan as a fused streaming fit "
                "(non-row-wise prefix or state over budget) — refusing "
                "to fold through a different code path than the state "
                "was accumulated on"
            )
        d_state = int(np.asarray(self.state.ata).shape[0])
        if plan.fit.d != d_state:
            raise FitStateError(
                f"the state's sample featurizes to d={plan.fit.d} but "
                f"its statistics accumulate d={d_state} — stale or "
                "mismatched state file"
            )
        self._plan = plan
        return plan

    def fold(self, fname: str) -> int:
        """Fold one chunk file into the state; returns rows folded (0
        when the chunk was skipped — unreadable, corrupt, or
        malformed: wrong feature width, mismatched rows. Skipped
        chunks are marked processed so one bad file can't wedge the
        stream; the skip is loud (counter + event) either way. The
        state assignment is last and atomic, so a failed fold leaves
        the accumulated statistics untouched."""
        reg = _metrics.get_registry()
        path = os.path.join(self.watch_dir, fname)
        try:
            if _faults.fire("refit.corrupt_chunk", fname):
                raise OSError(
                    f"injected corrupt chunk (refit.corrupt_chunk, "
                    f"{fname})"
                )
            with np.load(path) as z:
                data = np.asarray(z["data"])
                labels = np.asarray(z["labels"])
        except Exception as e:  # noqa: BLE001 — skip loudly, continue
            return self._skip(fname, e, reg)
        # plan construction is OUTSIDE the skip bracket: a plan that
        # won't build is a config-level fault (FitStateError) that must
        # HALT the daemon with every chunk still pending, not quietly
        # consume the stream one durable skip at a time
        plan = self._ensure_plan(first_chunk=data)
        try:
            from keystone_tpu.plan import executor as _executor

            state = _executor.fit_stream(
                plan, data, labels, init_state=self.state
            )
        except Exception as e:  # noqa: BLE001 — malformed chunk: skip
            return self._skip(fname, e, reg)
        self.state = state
        self.processed.add(fname)
        rows = int(data.shape[0])
        self.rows_total += rows
        reg.counter("refit_chunks_folded").inc()
        reg.counter("refit_rows_folded").inc(rows)
        return rows

    def _skip(self, fname: str, err: Exception, reg) -> int:
        """Durably skip one bad chunk, loudly (counter + event). Only
        chunk-specific failures land here — producers must publish
        atomically (write a temp name, then rename to ``*.npz``) or a
        file caught mid-write is skipped as torn."""
        reg.counter("refit_chunks_skipped").inc()
        self._emit(
            "chunk_skipped",
            chunk=fname,
            error=f"{type(err).__name__}: {str(err)[:200]}",
        )
        logger.warning("refit: skipping bad chunk %s (%r)", fname, err)
        self.processed.add(fname)
        return 0

    # ---------------------------------------------------------- publish

    def _widths(self):
        return self.fs.widths or (
            self._plan.fit.widths if self._plan else None
        )

    def _save_state(self) -> None:
        """Persist the state + offsets durably (the at-least-once ack —
        also called alone when a cycle only SKIPPED chunks: the skip
        must stick without minting a pointless new model version)."""
        meta = dict(self.fs.meta)
        meta.update(
            processed=sorted(self.processed),
            version=self.version,
            rows=self.rows_total,
        )
        save_fit_state(
            self.state,
            self.state_path,
            est=self.fs.est,
            prefix=self.fs.prefix,
            widths=self._widths(),
            **meta,
        )
        self.fs.meta = meta

    def publish(self) -> str:
        """Re-finalize off the accumulated state (O(D³), N-independent)
        and publish: versioned model file, atomically-replaced
        ``current.kst`` pointer, durable state save carrying the new
        offsets — in that order, so a crash between steps re-publishes
        rather than losing data."""
        from keystone_tpu.core.pipeline import Pipeline
        from keystone_tpu.core.serialization import (
            atomic_write,
            save_fitted,
        )

        t0 = time.perf_counter()
        model = self.fs.est.fit_stats_finalize(
            self.state, widths=self._widths()
        )
        pipe = Pipeline.of(*self.fs.prefix, model)
        self.version += 1
        vname = f"model_v{self.version:06d}.kst"
        vpath = os.path.join(self.out_dir, vname)
        save_fitted(
            pipe,
            vpath,
            version=self.version,
            rows=self.rows_total,
            refit=True,
            sample=self.fs.meta.get("sample"),
        )
        # the pointer: byte-copy then os.replace, so a reader holding
        # current.kst open mid-swap still reads one complete artifact
        current = os.path.join(self.out_dir, CURRENT_MODEL)
        with open(vpath, "rb") as src, atomic_write(current) as dst:
            shutil.copyfileobj(src, dst)
        self._save_state()
        wall = time.perf_counter() - t0
        _metrics.get_registry().counter("refit_publishes").inc()
        self._emit(
            "publish",
            version=self.version,
            model=vname,
            rows_total=self.rows_total,
            wall_s=round(wall, 3),
        )
        logger.info(
            "refit: published %s (v%d, %d rows total) in %.2fs",
            vpath, self.version, self.rows_total, wall,
        )
        self._notify(current)
        return vpath

    def _notify(self, model_path: str) -> None:
        """Best-effort POST /admin/reload at the configured server —
        the push half of the loop; a server that is down simply picks
        the new ``current.kst`` up on its next reload."""
        if not self.notify_url:
            return
        import urllib.request

        url = self.notify_url.rstrip("/") + "/admin/reload"
        body = json.dumps({"path": os.path.abspath(model_path)}).encode()
        try:
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30.0) as r:
                payload = json.loads(r.read())
            logger.info("refit: server reloaded: %s", payload)
        except Exception as e:  # noqa: BLE001 — push is best-effort
            _metrics.get_registry().counter("refit_notify_failed").inc()
            self._emit(
                "notify_failed",
                url=url,
                error=f"{type(e).__name__}: {str(e)[:200]}",
            )
            logger.warning("refit: reload notify failed: %r", e)

    def _emit(self, action: str, **fields: Any) -> None:
        log = _events.active()
        if log is not None:
            log.emit("refit", action=action, **fields)

    # -------------------------------------------------------------- run

    def run_once(self) -> dict:
        """One scan-fold-publish cycle; returns a summary (no publish
        when nothing new arrived)."""
        folded = skipped = rows = 0
        for fname in self.pending():
            if self._stop.is_set():
                break
            n = self.fold(fname)
            if n:
                folded += 1
                rows += n
            else:
                skipped += 1
        out = {
            "chunks_folded": folded,
            "chunks_skipped": skipped,
            "rows": rows,
            "version": self.version,
        }
        if folded:
            out["model"] = self.publish()
            out["version"] = self.version
        elif skipped:
            # nothing new folded: persist the skip offsets only — no
            # new model version, no pointless reload of the server
            self._save_state()
        return out

    def run(self, interval_s: float = 2.0) -> None:
        """Poll until stopped (SIGTERM/SIGINT set the stop event; the
        in-flight cycle completes — the last durable state always
        covers every acked chunk)."""
        while not self._stop.is_set():
            summary = self.run_once()
            if summary.get("model"):
                print(
                    f"refit: v{summary['version']} "
                    f"({summary['chunks_folded']} chunk(s), "
                    f"{summary['rows']} row(s)) -> {summary['model']}",
                    flush=True,
                )
            self._stop.wait(interval_s)

    def stop(self) -> None:
        self._stop.set()


# --------------------------------------------------------------------- CLI


USAGE = """usage: python -m keystone_tpu refit <state.ksts> --watch DIR [options]
<state.ksts>: a save_fit_state artifact (digest-checked on load)
options:
  --watch DIR     labeled-chunk directory to tail (*.npz with data/labels)
  --out DIR       published-model directory (default: the state file's dir)
  --once          one scan-fold-publish cycle, then exit
  --interval S    poll interval in seconds (default 2)
  --notify URL    POST /admin/reload at this server after each publish
"""


def _parse(argv: list[str]) -> tuple[str, dict]:
    if not argv or argv[0] in ("-h", "--help"):
        raise SystemExit(USAGE)
    state, args, i = argv[0], {}, 1
    valued = {
        "--watch": "watch", "--out": "out",
        "--interval": "interval", "--notify": "notify",
    }
    while i < len(argv):
        a = argv[i]
        if a == "--once":
            args["once"] = True
            i += 1
        elif a in valued:
            if i + 1 >= len(argv):
                raise SystemExit(f"{a} needs a value")
            args[valued[a]] = argv[i + 1]
            i += 2
        else:
            raise SystemExit(f"unknown option {a!r}\n{USAGE}")
    if "watch" not in args:
        raise SystemExit(f"--watch DIR is required\n{USAGE}")
    return state, args


def main(argv: list[str] | None = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    state_path, args = _parse(argv)
    from keystone_tpu.core.runtime import init_backend

    init_backend()
    try:
        daemon = RefitDaemon(
            state_path,
            args["watch"],
            out_dir=args.get("out"),
            notify_url=args.get("notify"),
        )
    except FitStateError as e:
        raise SystemExit(f"refit: {e}")
    if args.get("once"):
        summary = daemon.run_once()
        print(json.dumps(summary), flush=True)
        return
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: daemon.stop())
    daemon.run(interval_s=float(args.get("interval", 2.0)))


if __name__ == "__main__":
    main()
