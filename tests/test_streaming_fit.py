"""Fused streaming normal-equations fit (plan/fused_fit.py + the
fit_stats protocol in ops/linear.py / ops/weighted_linear.py).

Contract under test: a fit accumulated over staged chunks — pad rows
masked, featurize prefix fused into the update step, Gram operator
planner-chosen — equals the one-shot materialized fit, and the fused
path never materializes features (the counter stays 0)."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.core.batching import pad_to_chunk
from keystone_tpu.core.pipeline import ChainedLabelEstimator, Identity, Pipeline
from keystone_tpu.ops.linear import (
    BlockLeastSquaresEstimator,
    LinearMapEstimator,
    block_widths,
    split_by_widths,
)
from keystone_tpu.ops.util import ClassLabelIndicators


def _planted(rng, n=220, d=12, k=3, mean=4.0, scale=2.0):
    a = (rng.normal(size=(n, d)) * scale + mean).astype(np.float32)
    x_true = rng.normal(size=(d, k)).astype(np.float32)
    b = (a @ x_true + 1.5).astype(np.float32)
    return jnp.asarray(a), jnp.asarray(b)


def _accumulate(est, a, b, chunk, n_valid=None, gram_fn=None):
    """Drive the protocol by hand: padded chunks, per-chunk valid."""
    n = a.shape[0]
    n_ok = n if n_valid is None else n_valid
    state = est.fit_stats_init(a.shape[-1], b.shape[-1])
    for s in range(0, n, chunk):
        ca, va = pad_to_chunk(a[s : s + chunk], chunk)
        cb, _ = pad_to_chunk(b[s : s + chunk], chunk)
        valid = max(0, min(n_ok - s, va))
        state = est.fit_stats_update(
            state, ca, cb, n_valid=jnp.int32(valid), gram_fn=gram_fn
        )
    return state


# ---------------------------------------------------------------------------
# protocol units: streaming == one-shot for every estimator


def test_linear_map_streaming_matches_oneshot(rng):
    a, b = _planted(rng)
    est = LinearMapEstimator(lam=0.7)
    one = est.fit(a, b)
    m = est.fit_stats_finalize(_accumulate(est, a, b, chunk=64))
    np.testing.assert_allclose(
        np.asarray(m.x), np.asarray(one.x), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(m(a)), np.asarray(one(a)), rtol=1e-4, atol=1e-4
    )


def test_linear_map_streaming_masks_pad_rows(rng):
    """n_valid masking: trailing pad rows (ragged tail AND an explicit
    global n_valid) must not touch the statistics."""
    a, b = _planted(rng, n=150)
    est = LinearMapEstimator(lam=0.5)
    one = est.fit(a[:130], b[:130])
    # stream the PADDED batch with n_valid=130, uneven 64-row chunks
    m = est.fit_stats_finalize(_accumulate(est, a, b, 64, n_valid=130))
    np.testing.assert_allclose(
        np.asarray(m.x), np.asarray(one.x), rtol=1e-4, atol=1e-5
    )


def test_linear_map_sweep_streaming_matches(rng):
    a, b = _planted(rng)
    est = LinearMapEstimator()
    lams = [0.01, 1.0, 10.0]
    sweep = est.fit_sweep(a, b, lams)
    streamed = est.fit_sweep_finalize(_accumulate(est, a, b, 64), lams)
    for m1, m2 in zip(sweep, streamed):
        np.testing.assert_allclose(
            np.asarray(m2.x), np.asarray(m1.x), rtol=1e-4, atol=1e-5
        )


@pytest.mark.parametrize("num_iter", [1, 3])
def test_bcd_streaming_matches_oneshot(rng, num_iter):
    """Gram-form BCD (full accumulated Gram, block slices) equals the
    data-form block fit — including multi-pass and block means."""
    a, b = _planted(rng, n=240, d=17)
    est = BlockLeastSquaresEstimator(block_size=5, num_iter=num_iter, lam=0.4)
    one = est.fit(a, b)
    m = est.fit_stats_finalize(_accumulate(est, a, b, 80))
    for x1, x2 in zip(one.xs, m.xs):
        np.testing.assert_allclose(
            np.asarray(x2), np.asarray(x1), rtol=2e-4, atol=1e-5
        )
    for mu1, mu2 in zip(one.means, m.means):
        np.testing.assert_allclose(
            np.asarray(mu2), np.asarray(mu1), rtol=1e-4, atol=1e-5
        )
    np.testing.assert_allclose(
        np.asarray(m(a)), np.asarray(one(a)), rtol=1e-3, atol=1e-3
    )


def test_bcd_streaming_block_list_widths(rng):
    """A block-LIST input (bank output, last block narrower) streams
    with the caller's widths and matches the list fit exactly."""
    a, b = _planted(rng, n=200, d=11)
    widths = (4, 4, 3)
    blocks = split_by_widths(a, widths)
    est = BlockLeastSquaresEstimator(block_size=4, num_iter=2, lam=0.3)
    one = est.fit(blocks, b)
    state = est.fit_stats_init(11, b.shape[-1])
    for s in range(0, 200, 64):
        ca, va = pad_to_chunk(a[s : s + 64], 64)
        cb, _ = pad_to_chunk(b[s : s + 64], 64)
        state = est.fit_stats_update(
            state,
            split_by_widths(ca, widths),
            cb,
            n_valid=jnp.int32(va),
        )
    m = est.fit_stats_finalize(state, widths=widths)
    for x1, x2 in zip(one.xs, m.xs):
        np.testing.assert_allclose(
            np.asarray(x2), np.asarray(x1), rtol=2e-4, atol=1e-5
        )


def test_bcd_sweep_streaming_matches(rng):
    a, b = _planted(rng, n=160, d=10)
    est = BlockLeastSquaresEstimator(block_size=4, num_iter=2)
    lams = [0.05, 2.0]
    sweep = est.fit_sweep(a, b, lams)
    streamed = est.fit_sweep_finalize(_accumulate(est, a, b, 64), lams)
    for m1, m2 in zip(sweep, streamed):
        for x1, x2 in zip(m1.xs, m2.xs):
            np.testing.assert_allclose(
                np.asarray(x2), np.asarray(x1), rtol=2e-4, atol=1e-5
            )


@pytest.mark.parametrize("block_size,num_iter", [(14, 1), (6, 2)])
def test_weighted_streaming_matches_oneshot(rng, block_size, num_iter):
    from keystone_tpu.ops.weighted_linear import (
        BlockWeightedLeastSquaresEstimator,
    )

    n, d, c = 380, 14, 5
    cls = rng.integers(0, c, size=n)
    centers = rng.normal(size=(c, d)).astype(np.float32)
    a = jnp.asarray(
        (centers[cls] + rng.normal(size=(n, d))).astype(np.float32)
    )
    y = ClassLabelIndicators(num_classes=c)(cls.astype(np.int32))
    est = BlockWeightedLeastSquaresEstimator(
        block_size=block_size, num_iter=num_iter, lam=0.5, mixture_weight=0.3
    )
    one = est.fit(a, y)
    m = est.fit_stats_finalize(_accumulate(est, a, y, 128))
    x1 = np.concatenate([np.asarray(x) for x in one.xs])
    x2 = np.concatenate([np.asarray(x) for x in m.xs])
    scale = max(np.abs(x1).max(), 1e-6)
    assert np.abs(x1 - x2).max() / scale < 2e-3
    np.testing.assert_allclose(
        np.asarray(m.b), np.asarray(one.b), rtol=2e-3, atol=2e-4
    )


def test_weighted_streaming_masks_pad_rows(rng):
    from keystone_tpu.ops.weighted_linear import (
        BlockWeightedLeastSquaresEstimator,
    )

    n, d, c = 200, 8, 4
    cls = rng.integers(0, c, size=n)
    a = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = ClassLabelIndicators(num_classes=c)(cls.astype(np.int32))
    est = BlockWeightedLeastSquaresEstimator(
        block_size=d, num_iter=1, lam=0.2
    )
    one = est.fit(a[:170], y[:170])
    m = est.fit_stats_finalize(_accumulate(est, a, y, 64, n_valid=170))
    x1 = np.concatenate([np.asarray(x) for x in one.xs])
    x2 = np.concatenate([np.asarray(x) for x in m.xs])
    assert np.abs(x1 - x2).max() / max(np.abs(x1).max(), 1e-6) < 2e-3


# ---------------------------------------------------------------------------
# shared block-boundary helper (satellite)


def test_block_widths_is_the_one_boundary_rule(rng):
    from keystone_tpu.ops.linear import BlockLinearMapper, _split_blocks

    for d, bs in [(16, 5), (12, 12), (7, 3), (1, 4)]:
        widths = block_widths(d, bs)
        assert sum(widths) == d
        assert all(w <= bs for w in widths)
        a = jnp.asarray(rng.normal(size=(6, d)).astype(np.float32))
        blocks = _split_blocks(a, bs)
        assert [b.shape[-1] for b in blocks] == list(widths)
        # a mapper built from those blocks re-splits at the same edges
        mapper = BlockLinearMapper(
            xs=tuple(
                jnp.zeros((w, 2), jnp.float32) for w in widths
            ),
            block_size=bs,
        )
        assert [
            blk.shape[-1] for blk in mapper._blocks_of(a)
        ] == list(widths)


# ---------------------------------------------------------------------------
# KEYSTONE_MATMUL_PRECISION env knob (satellite)


def test_matmul_precision_env_knob(rng, monkeypatch):
    from keystone_tpu.ops.linear import _matmul_precision

    monkeypatch.delenv("KEYSTONE_MATMUL_PRECISION", raising=False)
    with _matmul_precision(None):
        assert jax.config.jax_default_matmul_precision is None
    monkeypatch.setenv("KEYSTONE_MATMUL_PRECISION", "highest")
    with _matmul_precision(None):
        assert jax.config.jax_default_matmul_precision == "highest"
    # an explicit estimator precision wins over the env
    monkeypatch.setenv("KEYSTONE_MATMUL_PRECISION", "default")
    with _matmul_precision("highest"):
        assert jax.config.jax_default_matmul_precision == "highest"
    # and the knob reaches a real fit without changing its result class
    a, b = _planted(rng, n=60, d=6)
    monkeypatch.setenv("KEYSTONE_MATMUL_PRECISION", "highest")
    m = LinearMapEstimator(lam=0.1).fit(a, b)
    assert np.isfinite(np.asarray(m.x)).all()


# ---------------------------------------------------------------------------
# quantized Gram operator


def test_int8_gram_pallas_matches_xla(rng):
    from keystone_tpu.ops.gram import ata_int8_pallas, ata_int8_xla

    a = jnp.asarray(rng.normal(size=(300, 24)).astype(np.float32))
    gq = np.asarray(ata_int8_xla(a))
    gp = np.asarray(ata_int8_pallas(a, interpret=True))
    np.testing.assert_allclose(gp, gq, rtol=1e-5, atol=1e-4)


def test_int8_gram_close_to_fp32_on_wellscaled(rng):
    from keystone_tpu.ops.gram import ata_fp32, ata_int8

    a = jnp.asarray(rng.normal(size=(400, 16)).astype(np.float32))
    g = np.asarray(ata_fp32(a))
    gq = np.asarray(ata_int8(a))
    assert np.linalg.norm(gq - g) / np.linalg.norm(g) < 0.02


def test_quantization_error_gate_separates(rng):
    from keystone_tpu.ops.gram import gram_quantization_error

    a = rng.normal(size=(300, 24)).astype(np.float32)
    assert gram_quantization_error(a) < 0.03
    assert gram_quantization_error(np.maximum(a, 0)) < 0.03
    bad = a.copy()
    bad[0] *= 1e4  # one heavy-tailed row blows every column's scale
    assert gram_quantization_error(bad) > 1.0


def test_int8_gram_fit_within_tolerance(rng):
    """A streamed fit on the int8 Gram operator stays close to the
    exact fit on well-scaled features (the regime the planner's error
    gate admits)."""
    from keystone_tpu.ops.gram import ata_int8

    a, b = _planted(rng, n=300, d=16, mean=0.0, scale=1.0)
    est = LinearMapEstimator(lam=1.0)
    exact = est.fit(a, b)
    m = est.fit_stats_finalize(
        _accumulate(est, a, b, 128, gram_fn=ata_int8)
    )
    rel = np.abs(np.asarray(m.x) - np.asarray(exact.x)).max() / np.abs(
        np.asarray(exact.x)
    ).max()
    assert rel < 0.05


# ---------------------------------------------------------------------------
# the planned fused fit


def _mnist_chain(rng, num_ffts=2, block_size=1024, lam=5.0):
    from keystone_tpu.models.mnist_random_fft import FeaturizerBank
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator

    bank = FeaturizerBank.create(
        num_ffts=num_ffts, block_size=block_size, seed=0
    )
    est = BlockLeastSquaresEstimator(
        block_size=block_size, num_iter=1, lam=lam
    )
    return ChainedLabelEstimator(prefix=bank, est=est)


def _counters(*names):
    from keystone_tpu.observe import metrics as om

    snap = om.get_registry().snapshot()
    return {n: snap.get(n, 0) for n in names}


def test_fused_fit_matches_naive_mnist(rng):
    """Acceptance: planned fused fit == naive materialized fit within
    1e-4 relative on the params, featurize outputs never materialized
    (the counter stays 0 for the fused path)."""
    from keystone_tpu import plan as plan_mod

    n = 2600  # > d = 1024: the well-conditioned regime the models run
    x = jnp.asarray(rng.normal(size=(n, 784)).astype(np.float32))
    y = ClassLabelIndicators(num_classes=10)(
        rng.integers(0, 10, size=n).astype(np.int32)
    )
    chain = _mnist_chain(rng)
    naive = chain.fit(x, y, n_valid=n - 100)
    before = _counters("plan_fused_fits", "plan_fit_materialized")
    fitted, plan = plan_mod.fit_streaming(
        chain, x, y, n_valid=n - 100, chunk_size=512, return_plan=True
    )
    after = _counters("plan_fused_fits", "plan_fit_materialized")
    assert after["plan_fused_fits"] - before["plan_fused_fits"] == 1
    assert after["plan_fit_materialized"] == before["plan_fit_materialized"]
    assert plan.fit.fused and plan.fit.gram in ("fp32", "int8")
    fuse = [d for d in plan.decisions if d["action"] == "fuse_fit"]
    assert fuse and fuse[0]["materialize_features"] is False
    x1 = np.concatenate([np.asarray(a) for a in naive[-1].xs])
    x2 = np.concatenate([np.asarray(a) for a in fitted[-1].xs])
    assert np.abs(x1 - x2).max() / np.abs(x1).max() < 1e-4
    np.testing.assert_allclose(
        np.asarray(fitted(x)), np.asarray(naive(x)), rtol=1e-3, atol=1e-3
    )


def test_fused_fit_cifar_shaped_scaler_prefix(rng):
    """LinearMapEstimator behind a fitted StandardScaler prefix (the
    CIFAR wiring): fused == classic."""
    from keystone_tpu import plan as plan_mod
    from keystone_tpu.ops.stats import StandardScaler

    n, d, k = 900, 40, 10
    raw = jnp.asarray(
        (rng.normal(size=(n, d)) * 3 + 7).astype(np.float32)
    )
    y = ClassLabelIndicators(num_classes=k)(
        rng.integers(0, k, size=n).astype(np.int32)
    )
    scaler = StandardScaler(normalize_std_dev=True).fit(raw, n_valid=800)
    est = LinearMapEstimator(lam=0.5)
    classic = est.fit(scaler(raw), y, n_valid=800)
    fitted = plan_mod.fit_streaming(
        ChainedLabelEstimator(prefix=scaler, est=est),
        raw,
        y,
        n_valid=800,
        chunk_size=256,
    )
    np.testing.assert_allclose(
        np.asarray(fitted[-1].x), np.asarray(classic.x), rtol=2e-4, atol=1e-5
    )


def test_fused_fit_timit_shaped_bank(rng):
    """Multi-block cosine bank (the TIMIT wiring) with multi-pass BCD:
    fused == classic at the bank's block boundaries."""
    from keystone_tpu import plan as plan_mod
    from keystone_tpu.models.timit_pipeline import ScaledCosineBank
    from keystone_tpu.ops.stats import CosineRandomFeatures, StandardScaler

    n, d_in, feat_d, k = 700, 30, 24, 6
    x = jnp.asarray(rng.normal(size=(n, d_in)).astype(np.float32))
    y = ClassLabelIndicators(num_classes=k)(
        rng.integers(0, k, size=n).astype(np.int32)
    )
    keys = jax.random.split(jax.random.key(0), 2)
    chains = []
    for i in range(2):
        f = CosineRandomFeatures.create(d_in, feat_d, keys[i], gamma=0.1)
        s = StandardScaler().fit(f(x), n_valid=n)
        chains.append(Pipeline.of(f, s))
    bank = ScaledCosineBank(chains=tuple(chains))
    est = BlockLeastSquaresEstimator(block_size=feat_d, num_iter=3, lam=0.5)
    classic = est.fit(bank(x), y, n_valid=n)
    fitted = plan_mod.fit_streaming(
        ChainedLabelEstimator(prefix=bank, est=est),
        x,
        y,
        n_valid=n,
        chunk_size=256,
    )
    for x1, x2 in zip(classic.xs, fitted[-1].xs):
        np.testing.assert_allclose(
            np.asarray(x2), np.asarray(x1), rtol=5e-4, atol=1e-5
        )


def test_fused_fit_weighted_identity_prefix(rng):
    """The weighted solver behind an Identity prefix (the ImageNet
    wiring): fused == classic."""
    from keystone_tpu import plan as plan_mod
    from keystone_tpu.ops.weighted_linear import (
        BlockWeightedLeastSquaresEstimator,
    )

    n, d, c = 500, 12, 4
    cls = rng.integers(0, c, size=n)
    centers = rng.normal(size=(c, d)).astype(np.float32)
    a = jnp.asarray(
        (centers[cls] + rng.normal(size=(n, d))).astype(np.float32)
    )
    y = ClassLabelIndicators(num_classes=c)(cls.astype(np.int32))
    est = BlockWeightedLeastSquaresEstimator(
        block_size=d, num_iter=2, lam=0.3, mixture_weight=0.4
    )
    classic = est.fit(a, y, n_valid=n)
    fitted = plan_mod.fit_streaming(
        ChainedLabelEstimator(prefix=Identity(), est=est),
        a,
        y,
        n_valid=n,
        chunk_size=128,
    )
    x1 = np.concatenate([np.asarray(v) for v in classic.xs])
    x2 = np.concatenate([np.asarray(v) for v in fitted[-1].xs])
    assert np.abs(x1 - x2).max() / max(np.abs(x1).max(), 1e-6) < 2e-3


def test_fused_fit_sharded_matches_local(rng, mesh8):
    """Sharded staged chunks (mesh8, shard-divisible chunk) == local."""
    from keystone_tpu import plan as plan_mod

    n = 640
    a, b = _planted(rng, n=n, d=16)
    est = LinearMapEstimator(lam=0.4)
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    local = plan_mod.fit_streaming(chain, a, b, chunk_size=128)
    sharded = plan_mod.fit_streaming(
        chain, a, b, chunk_size=128, mesh=mesh8
    )
    np.testing.assert_allclose(
        np.asarray(sharded[-1].x),
        np.asarray(local[-1].x),
        rtol=1e-4,
        atol=1e-5,
    )


# ---------------------------------------------------------------------------
# operator selection + fallbacks


def test_gram_operator_fallback_on_bad_features(rng, tmp_path):
    """Heavy-tailed features → planner takes fp32 despite int8 being
    requested as 'auto', records the decision, and emits the optimize
    event."""
    from keystone_tpu import plan as plan_mod
    from keystone_tpu.observe import events

    n, d = 400, 16
    raw = rng.normal(size=(n, d)).astype(np.float32)
    raw[0] *= 1e4
    a = jnp.asarray(raw)
    b = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    est = LinearMapEstimator(lam=1.0)
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    with events.run(str(tmp_path)) as log:
        plan = plan_mod.plan_fit(chain, a, b, chunk_size=128)
        run_dir = log.run_dir
    ops = [d_ for d_ in plan.decisions if d_["action"] == "fit_operator"]
    assert ops and ops[0]["op"] == "fp32"
    assert ops[0]["reason"] == "quantization_error"
    assert ops[0]["quantization_error"] > ops[0]["threshold"]
    evs = [
        e
        for e in events.read_events(run_dir)
        if e["event"] == "optimize" and e.get("source") == "planner"
    ]
    assert any(
        d_["action"] == "fit_operator" and d_["op"] == "fp32"
        for e in evs
        for d_ in e.get("decisions", [])
    )


def test_gram_operator_forced_int8(rng):
    """gram='int8' overrides the cost model (CPU has no advantage) and
    the streamed fit still lands within int8 tolerance."""
    from keystone_tpu import plan as plan_mod

    a, b = _planted(rng, n=600, d=16, mean=0.0, scale=1.0)
    est = LinearMapEstimator(lam=1.0)
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    exact = est.fit(a, b)
    fitted, plan = plan_mod.fit_streaming(
        chain, a, b, chunk_size=128, gram="int8", return_plan=True
    )
    assert plan.fit.gram == "int8"
    rel = np.abs(
        np.asarray(fitted[-1].x) - np.asarray(exact.x)
    ).max() / np.abs(np.asarray(exact.x)).max()
    assert rel < 0.05


def test_fallback_state_over_budget(rng):
    """A state bigger than the budget → materialized fit + counter +
    decision (the weighted solver's real-ImageNet regime)."""
    from keystone_tpu import plan as plan_mod

    a, b = _planted(rng, n=100, d=10)
    est = LinearMapEstimator(lam=0.1)
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    before = _counters("plan_fit_materialized")
    fitted, plan = plan_mod.fit_streaming(
        chain, a, b, budget_bytes=64, return_plan=True
    )
    after = _counters("plan_fit_materialized")
    assert not plan.fit.fused
    assert (
        after["plan_fit_materialized"] - before["plan_fit_materialized"] == 1
    )
    assert any(
        d_["action"] == "fit_fallback"
        and d_["reason"] == "state_over_budget"
        for d_ in plan.decisions
    )
    # the fallback still fits correctly
    np.testing.assert_allclose(
        np.asarray(fitted[-1].x),
        np.asarray(est.fit(a, b).x),
        rtol=1e-5,
        atol=1e-6,
    )


def test_fallback_no_protocol_estimator(rng):
    """An estimator without fit_stats_* falls back with its own
    decision."""
    from keystone_tpu import plan as plan_mod
    from keystone_tpu.core.pipeline import LabelEstimator
    from keystone_tpu.core.treenode import treenode

    @treenode
    class Plain(LabelEstimator):
        def fit(self, data, labels, n_valid=None):
            return Identity()

    a, b = _planted(rng, n=50, d=4)
    fitted, plan = plan_mod.fit_streaming(
        ChainedLabelEstimator(prefix=Identity(), est=Plain()),
        a,
        b,
        return_plan=True,
    )
    assert not plan.fit.fused
    assert any(
        d_["action"] == "fit_fallback"
        and d_["reason"] == "no_fit_stats_protocol"
        for d_ in plan.decisions
    )


# ---------------------------------------------------------------------------
# observability: solver telemetry rows + report heading


def test_solver_stream_rows_and_report_heading(rng, tmp_path):
    from keystone_tpu import plan as plan_mod
    from keystone_tpu.observe import events, report
    from keystone_tpu.observe import telemetry as otel

    a, b = _planted(rng, n=400, d=12)
    est = LinearMapEstimator(lam=0.5)
    chain = ChainedLabelEstimator(prefix=Identity(), est=est)
    with events.run(str(tmp_path)) as log:
        plan_mod.fit_streaming(chain, a, b, chunk_size=128)
        run_dir = log.run_dir
        steplog = otel.active_step_log()
        rows = [
            r for r in steplog.records if r.get("source") == "solver"
        ]
    assert len(rows) == 1
    r = rows[0]
    assert r["rows"] == 400
    assert r["chunks"] == 4  # 400 rows / 128-row chunks, tail padded
    assert r["rows_per_s"] > 0
    assert r["gram"] == "fp32"
    # cost-priced off the fused node's flops; a CPU run gets the
    # achieved rate but no utilization against a device peak
    assert "tflops_per_s" in r and "mfu" not in r
    text = report.render(run_dir)
    assert "solver streams (fused streaming fits): 1 fit(s)" in text
    assert "LinearMapEstimator" in text
    # solver rows must NOT leak into the generic plan chunk-stream line
    assert "plan chunk streams" not in text


# ---------------------------------------------------------------------------
# models under KEYSTONE_PLAN=1


def test_mnist_model_planned_fit_matches(monkeypatch):
    from keystone_tpu.models import mnist_random_fft as m

    conf = m.MnistRandomFFTConfig(
        synthetic=500, num_ffts=2, block_size=1024, lam=10.0
    )
    monkeypatch.delenv("KEYSTONE_PLAN", raising=False)
    classic = m.run(conf)
    monkeypatch.setenv("KEYSTONE_PLAN", "1")
    planned = m.run(conf)
    assert planned["test_error"] == pytest.approx(
        classic["test_error"], abs=0.02
    )
    assert planned["train_error"] == pytest.approx(
        classic["train_error"], abs=0.02
    )


def test_timit_model_planned_fit_matches(monkeypatch):
    from keystone_tpu.models import timit_pipeline as m

    conf = m.TimitConfig(
        synthetic=400, num_cosines=2, cosine_features=128, num_epochs=2
    )
    monkeypatch.delenv("KEYSTONE_PLAN", raising=False)
    classic = m.run(conf)
    monkeypatch.setenv("KEYSTONE_PLAN", "1")
    planned = m.run(conf)
    assert planned["test_error"] == pytest.approx(
        classic["test_error"], abs=0.02
    )


def test_cifar_model_planned_fit_matches(monkeypatch):
    from keystone_tpu.models import cifar_random as m

    conf = m.RandomCifarFilterConfig(
        synthetic=200, num_filters=8, chunk_size=64
    )
    monkeypatch.delenv("KEYSTONE_PLAN", raising=False)
    classic = m.run(conf)
    monkeypatch.setenv("KEYSTONE_PLAN", "1")
    planned = m.run(conf)
    assert planned["test_error"] == pytest.approx(
        classic["test_error"], abs=0.05
    )


# ---------------------------------------------------------------------------
# CLI


def test_plan_cli_fit_smoke():
    out = subprocess.run(
        [sys.executable, "-m", "keystone_tpu", "plan",
         "mnist-random-fft", "--fit"],
        capture_output=True,
        text=True,
        timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-800:]
    assert "fit: fused streaming" in out.stdout
    assert "fuse_streaming_fit" in out.stdout
    assert "fit_operator" in out.stdout
