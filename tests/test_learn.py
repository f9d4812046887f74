"""Online learning (keystone_tpu/learn/): merge, refit, swap, shadow.

Contracts under test:

- ``fit_stats_merge`` is commutative/associative: a corpus split k ways
  folds to the same finalized mapper (within 1e-6 relative) in any
  merge order, for both state types.
- Fit-state persistence is atomic and digest-checked: a corrupted file
  (or the ``refit.state_digest`` drill) refuses loudly.
- Incremental refit — fold new chunks into saved state, re-finalize —
  matches a from-scratch fit on the union corpus within 1e-6 for all
  three estimator types, WITHOUT revisiting old data (the
  ``plan_fused_fit_rows`` counter pins that only new rows pass through
  the fused featurize+accumulate step).
- A live server survives hot swaps under continuous threaded traffic
  with zero dropped/5xx requests, each swap visible as a ``model_swap``
  event with old/new version ids; an injected ``serve.swap_fail``
  rolls back to the prior version loudly.
- Shadow scoring records per-request divergence spans, and the
  promotion gate blocks on divergence and on feature-drift alerts.
- The refit CLI folds a watch directory once and publishes a
  versioned model (smoke, real subprocess).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.core.pipeline import ChainedLabelEstimator, Identity, Pipeline
from keystone_tpu.core.serialization import load_fitted, save_fitted
from keystone_tpu.learn import refit as refit_mod
from keystone_tpu.learn.merge import (
    FitStateError,
    fit_stats_merge,
    load_fit_state,
    save_fit_state,
)
from keystone_tpu.learn.shadow import ShadowRunner, divergence, input_feature_stats
from keystone_tpu.learn.swap import ModelSwapper, SwapError
from keystone_tpu.observe import events as observe_events
from keystone_tpu.observe import health as observe_health
from keystone_tpu.observe import metrics as observe_metrics
from keystone_tpu.ops.linear import (
    BlockLeastSquaresEstimator,
    LinearMapEstimator,
)
from keystone_tpu.ops.weighted_linear import BlockWeightedLeastSquaresEstimator
from keystone_tpu.resilience import faults
from keystone_tpu.serve.export import ExportedApply
from keystone_tpu.serve.server import ServeApp


def _counter(name: str) -> float:
    return observe_metrics.get_registry().snapshot().get(name, 0)


def _regression(rng, n, d=10, k=3, scale=1.5, offset=0.5):
    a = (rng.normal(size=(n, d)) * scale + offset).astype(np.float32)
    x_true = rng.normal(size=(d, k)).astype(np.float32)
    b = (a @ x_true + 0.25).astype(np.float32)
    return a, b


def _classification(rng, n, d=10, k=4):
    a = (rng.normal(size=(n, d)) * 1.5 + 0.5).astype(np.float32)
    cls = rng.integers(0, k, size=n)
    y = -np.ones((n, k), np.float32)
    y[np.arange(n), cls] = 1.0
    return a, y


def _accumulate(est, a, b):
    state = est.fit_stats_init(a.shape[-1], b.shape[-1])
    return est.fit_stats_update(state, jnp.asarray(a), jnp.asarray(b))


# ---------------------------------------------------------------------------
# merge: the third verb's algebra


def test_merge_commutative_and_associative_normal_eq(rng):
    """Split the corpus 4 ways; every fold order — left fold, right
    fold, balanced tree, reversed — finalizes to the same mapper
    within 1e-6."""
    a, b = _regression(rng, 400)
    est = LinearMapEstimator(lam=0.7)
    parts = [
        _accumulate(est, a[i : i + 100], b[i : i + 100])
        for i in range(0, 400, 100)
    ]
    orders = [
        fit_stats_merge(
            fit_stats_merge(fit_stats_merge(parts[0], parts[1]), parts[2]),
            parts[3],
        ),
        fit_stats_merge(
            parts[3],
            fit_stats_merge(parts[2], fit_stats_merge(parts[1], parts[0])),
        ),
        fit_stats_merge(
            fit_stats_merge(parts[0], parts[2]),
            fit_stats_merge(parts[1], parts[3]),
        ),
    ]
    one_shot = _accumulate(est, a, b)
    x_ref = np.asarray(est.fit_stats_finalize(one_shot).x)
    scale = max(1.0, float(np.max(np.abs(x_ref))))
    for merged in orders:
        x = np.asarray(est.fit_stats_finalize(merged).x)
        assert float(np.max(np.abs(x - x_ref))) / scale < 1e-6
    # commutativity exactly: merge(a, b) vs merge(b, a) on raw state
    m_ab = fit_stats_merge(parts[0], parts[1])
    m_ba = fit_stats_merge(parts[1], parts[0])
    np.testing.assert_allclose(
        np.asarray(m_ab.ata), np.asarray(m_ba.ata), rtol=1e-6, atol=1e-4
    )


def test_merge_weighted_state_any_order(rng):
    a, y = _classification(rng, 300, d=12, k=4)
    est = BlockWeightedLeastSquaresEstimator(
        block_size=6, num_iter=2, lam=0.5, mixture_weight=0.4
    )
    parts = [
        _accumulate(est, a[i : i + 100], y[i : i + 100])
        for i in range(0, 300, 100)
    ]
    m1 = fit_stats_merge(fit_stats_merge(parts[0], parts[1]), parts[2])
    m2 = fit_stats_merge(parts[2], fit_stats_merge(parts[1], parts[0]))
    one = _accumulate(est, a, y)
    p_ref = np.asarray(est.fit_stats_finalize(one)(jnp.asarray(a[:32])))
    scale = max(1.0, float(np.max(np.abs(p_ref))))
    for m in (m1, m2):
        p = np.asarray(est.fit_stats_finalize(m)(jnp.asarray(a[:32])))
        assert float(np.max(np.abs(p - p_ref))) / scale < 1e-6


def test_merge_rejects_mismatched_states(rng):
    a, b = _regression(rng, 60, d=8)
    a2, b2 = _regression(rng, 60, d=6)
    lin = LinearMapEstimator()
    s8 = _accumulate(lin, a, b)
    s6 = _accumulate(lin, a2, b2)
    with pytest.raises(FitStateError, match="different shapes"):
        fit_stats_merge(s8, s6)
    w = BlockWeightedLeastSquaresEstimator()
    sw = _accumulate(w, *_classification(rng, 60, d=8, k=3))
    with pytest.raises(FitStateError, match="different types"):
        fit_stats_merge(s8, sw)


def test_merge_empty_state_is_identity(rng):
    a, b = _regression(rng, 120)
    est = LinearMapEstimator(lam=0.3)
    s = _accumulate(est, a, b)
    zero = est.fit_stats_init(a.shape[-1], b.shape[-1])
    merged = fit_stats_merge(zero, s)
    np.testing.assert_allclose(
        np.asarray(merged.ata), np.asarray(s.ata), rtol=1e-6, atol=1e-5
    )
    assert float(np.asarray(merged.n)) == 120.0


def test_allmerge_single_process_returns_local(rng):
    from keystone_tpu.learn.merge import allmerge_fit_state

    a, b = _regression(rng, 50)
    s = _accumulate(LinearMapEstimator(), a, b)
    assert allmerge_fit_state(s) is s


# ---------------------------------------------------------------------------
# state persistence: atomic, digest-checked, loud on corruption


def test_fit_state_round_trip_and_no_temp_litter(tmp_path, rng):
    a, b = _regression(rng, 100)
    est = LinearMapEstimator(lam=0.4)
    s = _accumulate(est, a, b)
    path = str(tmp_path / "s.ksts")
    save_fit_state(s, path, est=est, widths=(4, 6), rows=100, version=3)
    fs = load_fit_state(path)
    np.testing.assert_allclose(
        np.asarray(fs.state.ata), np.asarray(s.ata), rtol=0, atol=0
    )
    assert type(fs.est) is LinearMapEstimator and fs.est.lam == 0.4
    assert fs.widths == (4, 6)
    assert fs.meta == {"rows": 100, "version": 3}
    # atomic_write cleaned its temp file
    assert [p.name for p in tmp_path.iterdir()] == ["s.ksts"]


def test_fit_state_corruption_is_loud(tmp_path, rng):
    a, b = _regression(rng, 80)
    est = LinearMapEstimator()
    path = str(tmp_path / "s.ksts")
    save_fit_state(_accumulate(est, a, b), path, est=est)
    blob = bytearray(open(path, "rb").read())
    blob[-3] ^= 0xFF  # flip one payload byte
    open(path, "wb").write(bytes(blob))
    with pytest.raises(FitStateError, match="digest mismatch"):
        load_fit_state(path)
    with pytest.raises(FitStateError, match="not a keystone_tpu"):
        load_fit_state(__file__)


def test_fit_state_digest_drill(tmp_path, rng):
    """refit.state_digest: the deterministic CI drill — a healthy file
    refuses exactly as a torn one would."""
    a, b = _regression(rng, 80)
    est = LinearMapEstimator()
    path = str(tmp_path / "s.ksts")
    save_fit_state(_accumulate(est, a, b), path, est=est)
    faults.configure("refit.state_digest:1:0")
    try:
        with pytest.raises(FitStateError, match="digest mismatch"):
            load_fit_state(path)
    finally:
        faults.reset()
    assert load_fit_state(path).est is not None  # clean again


def test_atomic_write_failure_keeps_old_artifact(tmp_path):
    from keystone_tpu.core.serialization import atomic_write

    path = str(tmp_path / "f.bin")
    with atomic_write(path) as f:
        f.write(b"good")
    with pytest.raises(RuntimeError):
        with atomic_write(path) as f:
            f.write(b"torn")
            raise RuntimeError("writer died mid-artifact")
    assert open(path, "rb").read() == b"good"
    assert [p.name for p in tmp_path.iterdir()] == ["f.bin"]


# ---------------------------------------------------------------------------
# incremental refit == from-scratch fit on the union, old rows untouched


@pytest.mark.parametrize(
    "make_est,make_data",
    [
        (lambda: LinearMapEstimator(lam=0.5), _regression),
        (
            lambda: BlockLeastSquaresEstimator(
                block_size=4, num_iter=3, lam=0.5
            ),
            _regression,
        ),
        (
            lambda: BlockWeightedLeastSquaresEstimator(
                block_size=4, num_iter=3, lam=0.5, mixture_weight=0.4
            ),
            _classification,
        ),
    ],
    ids=["linear_map", "block", "weighted"],
)
def test_incremental_refit_matches_full_fit(
    tmp_path, rng, make_est, make_data
):
    est = make_est()
    a0, b0 = make_data(rng, 400)
    a1, b1 = make_data(rng, 130)
    a2, b2 = make_data(rng, 70)
    watch = tmp_path / "chunks"
    watch.mkdir()
    state_path = str(tmp_path / "state.ksts")
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    refit_mod.bootstrap_state(chain, a0, b0, state_path)
    np.savez(watch / "chunk_000.npz", data=a1, labels=b1)
    np.savez(watch / "chunk_001.npz", data=a2, labels=b2)

    daemon = refit_mod.RefitDaemon(
        state_path, str(watch), out_dir=str(tmp_path)
    )
    rows_before = _counter("plan_fused_fit_rows")
    summary = daemon.run_once()
    assert summary["chunks_folded"] == 2 and summary["version"] == 1
    # THE pin: only the new 200 rows passed through the fused
    # featurize+accumulate step — the base 400 were never revisited
    assert _counter("plan_fused_fit_rows") - rows_before == 200

    inc, meta = load_fitted(summary["model"], with_meta=True)
    assert meta["version"] == 1 and meta["rows"] == 600
    ua = np.concatenate([a0, a1, a2])
    ub = np.concatenate([b0, b1, b2])
    full = est.fit(jnp.asarray(ua), jnp.asarray(ub))
    probe = jnp.asarray(ua[:64])
    p_inc = np.asarray(inc(probe))
    p_full = np.asarray(full(probe))
    scale = max(1.0, float(np.max(np.abs(p_full))))
    assert float(np.max(np.abs(p_inc - p_full))) / scale < 1e-6

    # idempotent: nothing new → no new version, offsets persisted
    assert daemon.run_once()["chunks_folded"] == 0
    resumed = refit_mod.RefitDaemon(
        state_path, str(watch), out_dir=str(tmp_path)
    )
    assert resumed.pending() == []
    assert resumed.version == 1


def test_refit_current_pointer_tracks_latest(tmp_path, rng):
    est = LinearMapEstimator(lam=0.2)
    a0, b0 = _regression(rng, 200)
    watch = tmp_path / "chunks"
    watch.mkdir()
    state_path = str(tmp_path / "state.ksts")
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    refit_mod.bootstrap_state(chain, a0, b0, state_path)
    daemon = refit_mod.RefitDaemon(
        state_path, str(watch), out_dir=str(tmp_path)
    )
    for i in range(2):
        a, b = _regression(rng, 50)
        np.savez(watch / f"c{i}.npz", data=a, labels=b)
        daemon.run_once()
    cur, meta = load_fitted(
        str(tmp_path / refit_mod.CURRENT_MODEL), with_meta=True
    )
    assert meta["version"] == 2
    v2, _ = load_fitted(str(tmp_path / "model_v000002.kst"), with_meta=True)
    probe = jnp.asarray(a0[:8])
    np.testing.assert_array_equal(np.asarray(cur(probe)), np.asarray(v2(probe)))


def test_refit_corrupt_chunk_skipped_loudly(tmp_path, rng):
    est = LinearMapEstimator(lam=0.2)
    a0, b0 = _regression(rng, 200)
    a1, b1 = _regression(rng, 60)
    watch = tmp_path / "chunks"
    watch.mkdir()
    state_path = str(tmp_path / "state.ksts")
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    refit_mod.bootstrap_state(chain, a0, b0, state_path)
    np.savez(watch / "good.npz", data=a1, labels=b1)
    (watch / "torn.npz").write_bytes(b"not an npz at all")
    daemon = refit_mod.RefitDaemon(
        state_path, str(watch), out_dir=str(tmp_path)
    )
    skipped_before = _counter("refit_chunks_skipped")
    summary = daemon.run_once()
    assert summary["chunks_folded"] == 1
    assert summary["chunks_skipped"] == 1
    assert _counter("refit_chunks_skipped") - skipped_before == 1
    # the skip is durable: a fresh daemon does not retry the bad file
    resumed = refit_mod.RefitDaemon(
        state_path, str(watch), out_dir=str(tmp_path)
    )
    assert resumed.pending() == []


def test_refit_corrupt_chunk_drill(tmp_path, rng):
    """refit.corrupt_chunk: a HEALTHY chunk is skipped deterministically
    — the drill proves the skip path without needing a real torn file."""
    est = LinearMapEstimator(lam=0.2)
    a0, b0 = _regression(rng, 150)
    a1, b1 = _regression(rng, 60)
    watch = tmp_path / "chunks"
    watch.mkdir()
    state_path = str(tmp_path / "state.ksts")
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    refit_mod.bootstrap_state(chain, a0, b0, state_path)
    np.savez(watch / "c0.npz", data=a1, labels=b1)
    faults.configure("refit.corrupt_chunk:1:0")
    try:
        daemon = refit_mod.RefitDaemon(
            state_path, str(watch), out_dir=str(tmp_path)
        )
        summary = daemon.run_once()
    finally:
        faults.reset()
    assert summary["chunks_folded"] == 0 and summary["chunks_skipped"] == 1
    # a skip-only cycle publishes NO new model version (no pointless
    # server reload) but the skip offset IS durable
    assert "model" not in summary and summary["version"] == 0
    resumed = refit_mod.RefitDaemon(
        state_path, str(watch), out_dir=str(tmp_path)
    )
    assert resumed.pending() == []


def test_refit_malformed_chunk_skipped_not_crash_loop(tmp_path, rng):
    """A READABLE chunk with the wrong feature width must skip loudly
    like a torn one — not crash the daemon and wedge every later good
    chunk behind it."""
    est = LinearMapEstimator(lam=0.2)
    a0, b0 = _regression(rng, 150)
    watch = tmp_path / "chunks"
    watch.mkdir()
    state_path = str(tmp_path / "state.ksts")
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    refit_mod.bootstrap_state(chain, a0, b0, state_path)
    wrong_a, wrong_b = _regression(rng, 40, d=17)  # wrong width
    np.savez(watch / "a_wrong.npz", data=wrong_a, labels=wrong_b)
    good_a, good_b = _regression(rng, 60)
    np.savez(watch / "b_good.npz", data=good_a, labels=good_b)
    daemon = refit_mod.RefitDaemon(
        state_path, str(watch), out_dir=str(tmp_path)
    )
    summary = daemon.run_once()
    assert summary["chunks_skipped"] == 1
    assert summary["chunks_folded"] == 1  # the good chunk still folded
    inc, meta = load_fitted(summary["model"], with_meta=True)
    assert meta["rows"] == 210
    full = est.fit(
        jnp.asarray(np.concatenate([a0, good_a])),
        jnp.asarray(np.concatenate([b0, good_b])),
    )
    probe = jnp.asarray(a0[:16])
    np.testing.assert_allclose(
        np.asarray(inc(probe)), np.asarray(full(probe)),
        rtol=1e-4, atol=1e-5,
    )


def test_refit_config_fault_halts_with_chunks_pending(tmp_path, rng):
    """A daemon/config-level failure (the state's own sample no longer
    plans to the state's width) HALTS loudly — it must not consume the
    stream as one durable skip per chunk."""
    est = LinearMapEstimator(lam=0.2)
    a0, b0 = _regression(rng, 150)
    watch = tmp_path / "chunks"
    watch.mkdir()
    state_path = str(tmp_path / "state.ksts")
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    refit_mod.bootstrap_state(chain, a0, b0, state_path)
    # tamper the saved sample to a different width — the stale-state
    # class of fault (code/config drifted under the state file)
    fs = load_fit_state(state_path)
    fs.meta["sample"] = np.zeros((1, 17), np.float32)
    save_fit_state(
        fs.state, state_path, est=fs.est, prefix=fs.prefix,
        widths=fs.widths, **fs.meta,
    )
    a1, b1 = _regression(rng, 60)
    np.savez(watch / "c0.npz", data=a1, labels=b1)
    daemon = refit_mod.RefitDaemon(
        state_path, str(watch), out_dir=str(tmp_path)
    )
    with pytest.raises(FitStateError, match="stale or mismatched"):
        daemon.run_once()
    # the chunk is STILL pending: nothing was durably skipped
    fresh = refit_mod.RefitDaemon(
        state_path, str(watch), out_dir=str(tmp_path)
    )
    assert fresh.pending() == ["c0.npz"]


def test_learn_fault_sites_registered():
    for site in ("refit.corrupt_chunk", "refit.state_digest",
                 "serve.swap_fail"):
        assert site in faults.SITES
    from keystone_tpu.observe import schema

    assert {"model_swap", "refit"} <= schema.declared()


# ---------------------------------------------------------------------------
# hot swap: a live app survives swaps under threaded traffic, zero 5xx


def _fitted_checkpoint(tmp_path, rng, name, version, scale=1.0, d=8, k=3):
    a = rng.normal(size=(120, d)).astype(np.float32) * scale
    b = (a @ rng.normal(size=(d, k)).astype(np.float32)).astype(np.float32)
    pipe = Pipeline.of(LinearMapEstimator(lam=0.1).fit(
        jnp.asarray(a), jnp.asarray(b)
    ))
    path = str(tmp_path / name)
    save_fitted(pipe, path, version=version, sample=a[:1])
    return path, a


def test_hot_swap_under_threaded_burst_zero_errors(tmp_path, rng):
    """≥ 2 swaps under continuous threaded traffic: no request fails,
    every swap emits a model_swap event with old/new version ids, and
    an injected serve.swap_fail rolls back loudly."""
    p1, a = _fitted_checkpoint(tmp_path, rng, "v1.kst", "v1")
    p2, _ = _fitted_checkpoint(tmp_path, rng, "v2.kst", "v2")
    p3, _ = _fitted_checkpoint(tmp_path, rng, "v3.kst", "v3")
    pipe1, meta1 = load_fitted(p1, with_meta=True)
    exported = ExportedApply(pipe1, a[:1], buckets=(4,), optimize=False)
    with observe_events.run(base_dir=str(tmp_path / "obs"),
                            workload="swap_burst") as log:
        app = ServeApp(exported=exported, deadline_ms=2.0,
                       model_version="v1")
        app.swapper = ModelSwapper(app, source_path=p1)
        errors: list[str] = []
        done = 0
        done_lock = threading.Lock()
        stop = threading.Event()

        def hammer():
            nonlocal done
            while not stop.is_set():
                try:
                    out = app.predict(a[:2])
                    assert out.shape[0] == 2
                    with done_lock:
                        done += 1
                except Exception as e:  # noqa: BLE001 — the assertion
                    errors.append(repr(e))

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            time.sleep(0.2)
            r1 = app.swapper.swap_to_path(p2)
            time.sleep(0.2)
            r2 = app.swapper.swap_to_path(p3)
            time.sleep(0.2)
            # the rollback drill, still under traffic
            faults.configure("serve.swap_fail:1:0")
            try:
                failed_before = _counter("serve_model_swap_failed")
                with pytest.raises(SwapError):
                    app.swapper.swap_to_path(p2)
                assert (
                    _counter("serve_model_swap_failed")
                    - failed_before == 1
                )
            finally:
                faults.reset()
            time.sleep(0.1)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
            app.shutdown()
        assert errors == []  # zero dropped / failed requests
        assert done > 0
        assert r1 == {**r1, "old_version": "v1", "new_version": "v2"}
        assert r2 == {**r2, "old_version": "v2", "new_version": "v3"}
        assert app.model_version == "v3" and app.swap_count == 2
        health = app.health()
        assert health["model_version"] == "v3"
        assert health["model_swaps"] == 2
        run_dir = log.run_dir
    events = [
        json.loads(line)
        for line in open(os.path.join(run_dir, "events.jsonl"))
    ]
    swaps = [e for e in events if e.get("event") == "model_swap"]
    committed = [e for e in swaps if e.get("action") == "swap"]
    assert [(e["old_version"], e["new_version"]) for e in committed] == [
        ("v1", "v2"),
        ("v2", "v3"),
    ]
    rollbacks = [e for e in swaps if e.get("action") == "rollback"]
    assert len(rollbacks) == 1
    assert rollbacks[0]["old_version"] == "v3"  # kept serving v3


def test_swap_spec_contract_wrong_row_shape(tmp_path, rng):
    p1, a = _fitted_checkpoint(tmp_path, rng, "v1.kst", "v1", d=8)
    p_wide, _ = _fitted_checkpoint(
        tmp_path, rng, "wide.kst", "wide", d=12
    )
    pipe1, _ = load_fitted(p1, with_meta=True)
    app = ServeApp(
        exported=ExportedApply(pipe1, a[:1], buckets=(4,), optimize=False),
        deadline_ms=2.0,
        model_version="v1",
    )
    app.swapper = ModelSwapper(app, source_path=p1)
    try:
        with pytest.raises(SwapError, match="row shape"):
            app.swapper.swap_to_path(p_wide)
        assert app.model_version == "v1"  # incumbent untouched
        out = app.predict(a[:2])
        assert out.shape[0] == 2
    finally:
        app.shutdown()


# ---------------------------------------------------------------------------
# shadow A/B: divergence spans, drift gate, promotion


def test_shadow_divergence_spans_and_gate(tmp_path, rng):
    """A deliberately different candidate scores high divergence: the
    verdict refuses promotion, shadow.compare spans carry per-request
    divergence, and the rejected candidate is discarded (the last-good
    primary keeps serving)."""
    p1, a = _fitted_checkpoint(tmp_path, rng, "v1.kst", "v1")
    p_bad, _ = _fitted_checkpoint(
        tmp_path, rng, "bad.kst", "bad", scale=50.0
    )
    pipe1, _ = load_fitted(p1, with_meta=True)
    with observe_events.run(base_dir=str(tmp_path / "obs"),
                            workload="shadow") as log:
        app = ServeApp(
            exported=ExportedApply(
                pipe1, a[:1], buckets=(4,), optimize=False
            ),
            deadline_ms=2.0,
            model_version="v1",
        )
        app.swapper = ModelSwapper(app, source_path=p1)
        try:
            app.start_shadow(
                p_bad, sample_every=1, min_samples=4,
                divergence_threshold=0.01,
            )
            for i in range(6):
                app.predict(a[i : i + 2])
            app.shadow.drain()
            verdict = app.shadow.verdict()
            assert verdict["samples"] >= 4
            assert verdict["mean_divergence"] > 0.01
            assert verdict["promote"] is False
            res = app.promote_shadow()
            assert res["promoted"] is False
            assert app.shadow is None  # discarded
            assert app.model_version == "v1"  # last good kept
        finally:
            app.shutdown()
        run_dir = log.run_dir
    spans = [
        json.loads(line)
        for line in open(os.path.join(run_dir, "spans.jsonl"))
    ]
    compares = [s for s in spans if s.get("name") == "shadow.compare"]
    assert len(compares) >= 4
    assert all("divergence" in s for s in compares)
    assert all(s.get("candidate_version") == "bad" for s in compares)
    events = [
        json.loads(line)
        for line in open(os.path.join(run_dir, "events.jsonl"))
    ]
    rollbacks = [
        e
        for e in events
        if e.get("event") == "model_swap" and e.get("action") == "rollback"
    ]
    assert rollbacks and rollbacks[0]["reason"] == "shadow_gate"


def test_shadow_identical_candidate_promotes(tmp_path, rng):
    p1, a = _fitted_checkpoint(tmp_path, rng, "v1.kst", "v1")
    pipe1, _ = load_fitted(p1, with_meta=True)
    # identical weights: re-save v1's pipeline under a new version id
    p_same = str(tmp_path / "same.kst")
    save_fitted(pipe1, p_same, version="v2-same", sample=a[:1])
    observe_health.reset_monitor()
    app = ServeApp(
        exported=ExportedApply(pipe1, a[:1], buckets=(4,), optimize=False),
        deadline_ms=2.0,
        model_version="v1",
    )
    app.swapper = ModelSwapper(app, source_path=p1)
    try:
        app.start_shadow(p_same, sample_every=1, min_samples=4)
        for i in range(6):
            app.predict(a[i : i + 2])
        app.shadow.drain()
        res = app.promote_shadow()
        assert res["promoted"] is True
        assert app.model_version == "v2-same"
        assert app.swap_count == 1
        out = app.predict(a[:2])
        assert out.shape[0] == 2
    finally:
        app.shutdown()


def test_shadow_feature_drift_blocks_promotion(rng):
    """Requests drawn far from the state's accumulated means fire
    serve.feature_drift, and the gate refuses even a zero-divergence
    candidate."""
    observe_health.reset_monitor()
    d, k = 6, 2
    a = rng.normal(size=(100, d)).astype(np.float32)
    b = (a @ rng.normal(size=(d, k)).astype(np.float32)).astype(np.float32)
    est = LinearMapEstimator(lam=0.1)
    state = _accumulate(est, a, b)
    pipe = Pipeline.of(est.fit_stats_finalize(state))
    exported = ExportedApply(pipe, a[:1], buckets=(4,), optimize=False)
    mean = np.asarray(state.mean_a)
    var = np.diag(np.asarray(state.ata)) / float(np.asarray(state.n))
    runner = ShadowRunner(
        exported, "cand", sample_every=1, min_samples=2,
        feature_stats=(mean, var),
    )
    try:
        shifted = a[:4] + 100.0  # nowhere near the accumulated means
        primary = np.asarray(exported(shifted))
        runner.observe(shifted, primary, rid=0)
        runner.drain()
        verdict = runner.verdict()
        assert verdict["drift_alerts"] >= 1
        assert verdict["promote"] is False
        mon = observe_health.get_monitor()
        assert any(
            al.get("kind") == "serve.feature_drift" for al in mon.alerts
        )
    finally:
        runner.close()
        observe_health.reset_monitor()


def test_divergence_metric_shapes():
    assert divergence(np.array([1, 2, 3]), np.array([1, 2, 3])) == 0.0
    assert divergence(np.array([1, 2]), np.array([1, 3])) == 0.5
    scores = np.array([[0.9, 0.1], [0.2, 0.8]], np.float32)
    flipped = scores[:, ::-1]
    assert divergence(scores, scores) == 0.0
    assert divergence(scores, flipped) == 1.0
    assert divergence(np.zeros((2, 2)), np.zeros((3, 2))) == 1.0


def test_input_feature_stats_identity_prefix_only(tmp_path, rng):
    a, b = _regression(rng, 100, d=5)
    est = LinearMapEstimator()
    path = str(tmp_path / "s.ksts")
    save_fit_state(
        _accumulate(est, a, b), path, est=est, prefix=(Identity(),)
    )
    fs = load_fit_state(path)
    stats = input_feature_stats(fs)
    assert stats is not None
    mean, var = stats
    np.testing.assert_allclose(mean, a.mean(axis=0), rtol=1e-4, atol=1e-4)
    assert var.shape == (5,)

    from keystone_tpu.ops.stats import CosineRandomFeatures
    import jax

    feat = CosineRandomFeatures.create(5, 8, jax.random.key(0))
    save_fit_state(
        _accumulate(est, np.asarray(feat(jnp.asarray(a))), b),
        path, est=est, prefix=(feat,),
    )
    assert input_feature_stats(load_fit_state(path)) is None


# ---------------------------------------------------------------------------
# observe surfaces: serving panel version/swaps, report lifecycle section


def test_top_and_report_render_model_swaps(tmp_path):
    from keystone_tpu.observe import report, top

    events = [
        {"ts": 0.5, "event": "serve", "action": "start", "model": "m",
         "port": 8123},
        {"ts": 1.0, "event": "model_swap", "action": "swap",
         "old_version": "v1", "new_version": "v2", "swaps": 1},
        {"ts": 2.0, "event": "model_swap", "action": "rollback",
         "old_version": "v2", "new_version": "v3",
         "error": "SwapError: injected"},
        {"ts": 3.0, "event": "refit", "action": "publish", "version": 2,
         "model": "model_v000002.kst", "rows_total": 600},
    ]
    state = top.summarize([], events)
    sv = state["serve"]
    assert sv["version"] == "v2" and sv["swaps"] == 1
    assert sv["rollbacks"] == 1
    screen = top.render(state, str(tmp_path))
    assert "model=v2" in screen
    assert "swaps=1" in screen and "rollbacks=1" in screen

    summary = report.summarize(events)
    assert len(summary["model_swaps"]) == 2
    assert len(summary["refits"]) == 1
    run = tmp_path / "run"
    run.mkdir()
    (run / "events.jsonl").write_text(
        "".join(json.dumps(e) + "\n" for e in events)
    )
    text = report.render(str(run))
    assert "model swaps (online-learning lifecycle):" in text
    assert "swap: old_version=v1, new_version=v2" in text
    assert "refit daemon (online-learning folds):" in text
    assert "publish: version=2" in text


# ---------------------------------------------------------------------------
# CLI smokes: refit --once over a real watch dir; HTTP /admin/reload


def test_refit_cli_smoke(tmp_path, rng):
    est = LinearMapEstimator(lam=0.3)
    a0, b0 = _regression(rng, 200)
    a1, b1 = _regression(rng, 80)
    watch = tmp_path / "chunks"
    watch.mkdir()
    state_path = str(tmp_path / "state.ksts")
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    refit_mod.bootstrap_state(chain, a0, b0, state_path)
    np.savez(watch / "c0.npz", data=a1, labels=b1)
    out = subprocess.run(
        [
            sys.executable, "-m", "keystone_tpu", "refit", state_path,
            "--watch", str(watch), "--out", str(tmp_path), "--once",
        ],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["chunks_folded"] == 1 and summary["version"] == 1
    model, meta = load_fitted(summary["model"], with_meta=True)
    assert meta["version"] == 1 and meta["rows"] == 280
    # and the state advanced durably: this process can keep folding
    fs = load_fit_state(state_path)
    assert fs.meta["version"] == 1
    assert fs.meta["processed"] == ["c0.npz"]


def test_refit_cli_rejects_corrupt_state(tmp_path):
    bad = tmp_path / "bad.ksts"
    bad.write_bytes(b"KSTS1\n" + b"0" * 64 + b"\nnot the payload")
    out = subprocess.run(
        [
            sys.executable, "-m", "keystone_tpu", "refit", str(bad),
            "--watch", str(tmp_path), "--once",
        ],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode != 0
    assert "digest mismatch" in (out.stderr + out.stdout)


def test_serve_admin_reload_http_smoke(tmp_path, rng, free_tcp_port):
    """Real server on a checkpoint, real /admin/reload hot-swap over
    HTTP: healthz shows the new version + swap count; a reload of a
    missing path answers 500 rolled_back and the version is unchanged."""
    p1, _ = _fitted_checkpoint(tmp_path, rng, "v1.kst", "v1")
    p2, _ = _fitted_checkpoint(tmp_path, rng, "v2.kst", "v2")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "KEYSTONE_SERVE_DEADLINE_MS": "5",
    }
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "keystone_tpu", "serve", p1,
            "--port", str(free_tcp_port), "--buckets", "1,4",
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    base = f"http://127.0.0.1:{free_tcp_port}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=10) as r:
            return json.loads(r.read())

    def post(path, body):
        req = urllib.request.Request(
            base + path,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    try:
        deadline = time.time() + 180
        health = None
        while time.time() < deadline:
            if proc.poll() is not None:
                pytest.fail("server died: " + proc.stderr.read()[-2000:])
            try:
                health = get("/healthz")
                break
            except OSError:
                time.sleep(0.25)
        assert health is not None, "server never came up"
        assert health["model_version"] == "v1"
        assert health["model_swaps"] == 0
        out = post("/admin/reload", {"path": p2})
        assert out["old_version"] == "v1" and out["new_version"] == "v2"
        health = get("/healthz")
        assert health["model_version"] == "v2"
        assert health["model_swaps"] == 1
        # requests keep answering on the new model
        rows = np.zeros((2, 8), np.float32).tolist()
        assert len(post("/predict", {"rows": rows})["predictions"]) == 2
        # a bad reload answers 500 rolled_back and changes nothing
        try:
            post("/admin/reload", {"path": str(tmp_path / "missing.kst")})
            pytest.fail("reload of a missing checkpoint must fail")
        except urllib.error.HTTPError as e:
            payload = json.loads(e.read())
            assert e.code == 500
            assert payload["rolled_back"] is True
            assert payload["version"] == "v2"
        assert get("/healthz")["model_version"] == "v2"
    finally:
        if proc.poll() is None:
            proc.terminate()
            proc.wait(timeout=60)
