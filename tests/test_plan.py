"""Cost-based pipeline planner tests: plan IR + passes, plan-equivalence
(planned execution bit-exact vs naive), shared-prefix fits, the chunked
executor's backpressure, and the ``plan`` CLI."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import plan as plan_mod
from keystone_tpu.core.pipeline import (
    ChainedEstimator,
    ChainedLabelEstimator,
    Estimator,
    Pipeline,
    Transformer,
    is_tracing,
    jit_apply,
    transformer,
)
from keystone_tpu.core.treenode import treenode
from keystone_tpu.observe import metrics as observe_metrics
from keystone_tpu.plan.ir import NodeCost, Plan, PlanNode
from keystone_tpu.plan import passes as plan_passes


@treenode
class Scale(Transformer):
    factor: jnp.ndarray

    def __call__(self, batch):
        return batch * self.factor


@treenode
class MeanCenterEstimator(Estimator):
    def fit(self, data):
        mu = jnp.mean(data, axis=0)
        return transformer(lambda b, mu=mu: b - mu, name="center")


@treenode
class MaxScaleEstimator(Estimator):
    def fit(self, data):
        mx = jnp.max(jnp.abs(data), axis=0)
        return transformer(lambda b, mx=mx: b / mx, name="maxscale")


def _counter(name: str) -> float:
    return observe_metrics.get_registry().snapshot().get(name, 0)


# ---------------------------------------------------------------------------
# plan IR + passes


def test_plan_pipeline_builds_costed_ir(rng):
    pipe = Scale(factor=jnp.asarray(2.0)) >> transformer(lambda b: b + 1.0)
    x = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    plan = plan_mod.plan_pipeline(pipe, sample=x)
    assert [pn.label for pn in plan.prefix] == ["00:Scale", "01:<lambda>"]
    assert all(pn.cost.source == "sampled" for pn in plan.prefix)
    assert all(pn.cost.wall_s is not None for pn in plan.prefix)
    assert plan.prefix[0].cost.output_bytes > 0
    assert "node" in plan.explain() and "decisions" in plan.explain()


def test_materialization_rule_benefit_vs_budget():
    """The paper's caching rule: cache iff (reuse-1) x recompute beats the
    residency penalty within the budget; over-budget candidates refused."""

    def plan_with(output_bytes, budget):
        node = PlanNode(
            label="feat",
            op=transformer(lambda b: b),
            cost=NodeCost(
                output_bytes=output_bytes, wall_s=1e-3, source="sampled"
            ),
            reuse=3,
        )
        p = Plan(
            prefix=[node],
            branches=[[], []],
            budget_bytes=budget,
            rows=100,
        )
        return plan_passes.choose_materialization(p), node

    p, node = plan_with(output_bytes=10.0, budget=10_000)
    assert node.materialize and p.share_prefix
    assert any(d["action"] == "cache" for d in p.decisions)

    p, node = plan_with(output_bytes=1000.0, budget=10_000)  # 100k > budget
    assert not node.materialize and not p.share_prefix
    assert any(
        d["action"] == "no_cache" and d["reason"] == "over_budget"
        for d in p.decisions
    )


def test_materialization_priced_at_execution_rows():
    """Residency scales with the REAL execution size: a cache that fits
    at the profiling-sample size must still be refused when the actual
    fit is orders of magnitude larger (code-review regression)."""
    node = PlanNode(
        label="feat",
        op=transformer(lambda b: b),
        cost=NodeCost(output_bytes=10.0, wall_s=1e-3, source="sampled"),
        reuse=2,
    )
    p = Plan(prefix=[node], branches=[[]], budget_bytes=10_000, rows=100)
    plan_passes.choose_materialization(p, rows=100_000)  # 1 MB > 10 kB
    assert not node.materialize
    assert any(d.get("reason") == "over_budget" for d in p.decisions)


def test_materialization_unknown_costs_default_to_sharing():
    node = PlanNode(label="feat", op=transformer(lambda b: b), reuse=2)
    p = Plan(prefix=[node], branches=[[]], budget_bytes=1 << 20)
    plan_passes.choose_materialization(p)
    assert node.materialize and p.share_prefix


def test_operator_selection_applies_registered_conv_rewrite(rng):
    from keystone_tpu.ops.images import (
        Convolver,
        FusedConvRectifyPool,
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )

    f, k = 8, 3
    filters = jnp.asarray(rng.normal(size=(f, k * k * 3)).astype(np.float32))
    pipe = (
        Convolver(filters=filters, patch_size=k, normalize_patches=True)
        >> SymmetricRectifier(alpha=0.1)
        >> Pooler(stride=3, pool_size=4)
        >> ImageVectorizer()
    )
    plan = plan_mod.plan_pipeline(pipe)
    assert [type(pn.op).__name__ for pn in plan.prefix] == [
        "FusedConvRectifyPool",
        "ImageVectorizer",
    ]
    assert isinstance(plan.prefix[0].op, FusedConvRectifyPool)
    assert plan.prefix[0].rewritten_from == (
        "00:Convolver",
        "01:SymmetricRectifier",
        "02:Pooler",
    )
    assert any(
        d["action"] == "rewrite" and d["rule"] == "conv_rectify_pool"
        for d in plan.decisions
    )
    # the CLASSIC fusion pass reports only under fusion_rewrites — it
    # must not claim planner activity (plan_rewrites) it didn't do
    from keystone_tpu.core.fusion import optimize

    plan_before = _counter("plan_rewrites{rule=conv_rectify_pool}")
    fusion_before = _counter("fusion_rewrites{rule=conv_rectify_pool}")
    optimize(pipe)
    assert _counter("plan_rewrites{rule=conv_rectify_pool}") == plan_before
    assert (
        _counter("fusion_rewrites{rule=conv_rectify_pool}")
        == fusion_before + 1
    )


def test_chunk_size_choice_bounds_working_set():
    node = PlanNode(
        label="n",
        op=transformer(lambda b: b),
        cost=NodeCost(peak_bytes=1024.0, source="sampled"),
    )
    p = Plan(prefix=[node], budget_bytes=1 << 20, rows=64)
    plan_passes.choose_chunk_size(p, n_rows=1 << 20)
    # 0.25 * 1 MiB / 1 KiB per row = 256 rows
    assert p.chunk_size == 256
    p2 = Plan(prefix=[node], budget_bytes=1 << 20, rows=64)
    plan_passes.choose_chunk_size(p2, n_rows=100)  # fits whole batch
    assert p2.chunk_size is None


# ---------------------------------------------------------------------------
# plan equivalence: planned execution is bit-exact vs naive


def test_planned_execution_bit_exact_simple_chain(rng):
    pipe = (
        Scale(factor=jnp.asarray(2.0))
        >> transformer(lambda b: jnp.maximum(b, 0.0))
        >> Scale(factor=jnp.asarray(0.5))
    )
    x = jnp.asarray(rng.normal(size=(100, 7)).astype(np.float32))
    naive = np.asarray(pipe(x))
    np.testing.assert_array_equal(np.asarray(plan_mod.execute(pipe, x)), naive)
    # chunked executor, including the zero-pad tail (100 % 16 != 0)
    np.testing.assert_array_equal(
        np.asarray(plan_mod.execute(pipe, x, chunk_size=16)), naive
    )


def test_planned_execution_bit_exact_mnist_pipeline(rng):
    """Planned execution (jitted segments + chunked executor) of the
    fitted MNIST random-FFT apply pipeline is bit-exact vs the naive
    ``pipe(batch)`` apply."""
    from keystone_tpu.models.mnist_random_fft import FeaturizerBank
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicators, MaxClassifier

    x = jnp.asarray(rng.normal(size=(256, 784)).astype(np.float32))
    y = ClassLabelIndicators(num_classes=10)(
        rng.integers(0, 10, size=256).astype(np.int32)
    )
    bank = FeaturizerBank.create(2, 1024, seed=0)
    model = BlockLeastSquaresEstimator(block_size=1024, num_iter=1, lam=1.0).fit(
        bank(x), y
    )
    pipe = Pipeline.of(bank, model, MaxClassifier())
    naive = np.asarray(pipe(x))
    np.testing.assert_array_equal(np.asarray(plan_mod.execute(pipe, x)), naive)
    np.testing.assert_array_equal(
        np.asarray(plan_mod.execute(pipe, x, chunk_size=64)), naive
    )


def test_planned_execution_bit_exact_cifar_conv_pipeline(rng):
    """Planned execution of the CIFAR conv chain is bit-exact vs the
    production path for the same physical operators — the fusion rewrite
    applied and the pipeline run under the shared jit wrapper (the jit
    boundary itself moves floats at the documented ~1e-4; that tolerance
    is owned by test_conv_fusion, not the executor)."""
    from keystone_tpu.core.fusion import optimize
    from keystone_tpu.ops.images import (
        Convolver,
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )

    k, f = 6, 16
    d = k * k * 3
    pipe = (
        Convolver(
            filters=jnp.asarray(rng.normal(size=(f, d)).astype(np.float32)),
            whitener_means=jnp.asarray(rng.normal(size=(d,)).astype(np.float32)),
            patch_size=k,
            normalize_patches=True,
        )
        >> SymmetricRectifier(alpha=0.25)
        >> Pooler(stride=13, pool_size=14)
        >> ImageVectorizer()
    )
    x = jnp.asarray(rng.normal(size=(18, 32, 32, 3)).astype(np.float32))
    naive = np.asarray(jit_apply(optimize(pipe), x))
    np.testing.assert_array_equal(np.asarray(plan_mod.execute(pipe, x)), naive)
    np.testing.assert_array_equal(
        np.asarray(plan_mod.execute(pipe, x, chunk_size=8)), naive
    )
    # and the rewrite stayed within the fused node's documented tolerance
    np.testing.assert_allclose(naive, np.asarray(pipe(x)), atol=1e-3)


def test_chunked_segment_with_pytree_output_falls_back(rng):
    """A chunked plan whose segment ends in a pytree output (the
    featurizer bank's block list at an explicit Cacher boundary) must
    run that segment unchunked instead of list-slicing it (code-review
    regression) — results stay bit-exact."""
    from keystone_tpu.core.pipeline import Cacher
    from keystone_tpu.models.mnist_random_fft import FeaturizerBank
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicators, MaxClassifier

    x = jnp.asarray(rng.normal(size=(96, 784)).astype(np.float32))
    y = ClassLabelIndicators(num_classes=10)(
        rng.integers(0, 10, size=96).astype(np.int32)
    )
    bank = FeaturizerBank.create(1, 512, seed=0)
    model = BlockLeastSquaresEstimator(block_size=512, num_iter=1, lam=1.0).fit(
        bank(x), y
    )
    pipe = Pipeline.of(bank, Cacher(name="blocks"), model, MaxClassifier())
    naive = np.asarray(pipe(x))
    np.testing.assert_array_equal(
        np.asarray(plan_mod.execute(pipe, x, chunk_size=32)), naive
    )


def test_planned_execution_respects_explicit_cacher(rng):
    from keystone_tpu.core.pipeline import Cacher

    pipe = (
        Scale(factor=jnp.asarray(3.0))
        >> Cacher(name="mid")
        >> transformer(lambda b: b - 1.0)
    )
    x = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
    plan = plan_mod.plan_pipeline(pipe, sample=x)
    np.testing.assert_array_equal(
        np.asarray(plan.execute(x)), np.asarray(pipe(x))
    )


# ---------------------------------------------------------------------------
# shared-prefix fit: the prefix runs exactly once


def test_fit_shared_runs_prefix_once_and_matches_naive(rng):
    eager_calls = {"n": 0}

    def feat(b):
        if not is_tracing(b):
            eager_calls["n"] += 1
        return b * 2.0 + 1.0

    prefix = transformer(feat, name="feat")
    chains = [
        ChainedEstimator(prefix=prefix, est=MeanCenterEstimator()),
        ChainedEstimator(prefix=prefix, est=MaxScaleEstimator()),
    ]
    x = jnp.asarray(rng.normal(size=(64, 5)).astype(np.float32) + 3.0)

    naive = [c.fit(x) for c in chains]
    eager_calls["n"] = 0
    saved_before = _counter("plan_featurize_passes_saved")
    fitted = plan_mod.fit_shared(chains, x)
    # the shared prefix executed as ONE jitted program: zero eager calls,
    # and the metrics counter records the eliminated featurization pass
    assert eager_calls["n"] == 0
    assert _counter("plan_featurize_passes_saved") - saved_before == 1
    for got, want in zip(fitted, naive):
        np.testing.assert_allclose(
            np.asarray(got(x)), np.asarray(want(x)), rtol=1e-6, atol=1e-6
        )


def test_fit_shared_label_estimator_and_distinct_prefixes(rng):
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator

    x = jnp.asarray(rng.normal(size=(64, 12)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(64, 3)).astype(np.float32))
    shared = Scale(factor=jnp.asarray(1.5))
    chains = [
        ChainedLabelEstimator(
            prefix=shared,
            est=BlockLeastSquaresEstimator(block_size=12, num_iter=1, lam=lam),
        )
        for lam in (1e-2, 1.0)
    ]
    fitted = plan_mod.fit_shared(chains, x, y, n_valid=60)
    for chain, got in zip(chains, fitted):
        want = chain.fit(x, y, n_valid=60)
        np.testing.assert_allclose(
            np.asarray(got(x)), np.asarray(want(x)), rtol=2e-5, atol=2e-5
        )
    # chains with NO common prefix fall back to per-chain naive fits
    other = ChainedEstimator(
        prefix=Scale(factor=jnp.asarray(2.0)), est=MeanCenterEstimator()
    )
    third = ChainedEstimator(
        prefix=Scale(factor=jnp.asarray(3.0)), est=MeanCenterEstimator()
    )
    saved_before = _counter("plan_featurize_passes_saved")
    out = plan_mod.fit_shared([other, third], x)
    assert len(out) == 2
    assert _counter("plan_featurize_passes_saved") == saved_before


def test_fit_shared_over_budget_recomputes(rng):
    """When the shared intermediate doesn't fit the budget, the planner
    refuses the cache and every chain fits the naive way — same results,
    no saved-pass counter."""
    prefix = transformer(lambda b: b * 2.0, name="feat")
    chains = [
        ChainedEstimator(prefix=prefix, est=MeanCenterEstimator()),
        ChainedEstimator(prefix=prefix, est=MaxScaleEstimator()),
    ]
    x = jnp.asarray(rng.normal(size=(64, 5)).astype(np.float32) + 3.0)
    saved_before = _counter("plan_featurize_passes_saved")
    fitted = plan_mod.fit_shared(chains, x, sample=x, budget_bytes=1)
    assert _counter("plan_featurize_passes_saved") == saved_before
    for chain, got in zip(chains, fitted):
        np.testing.assert_allclose(
            np.asarray(got(x)), np.asarray(chain.fit(x)(x)), rtol=1e-6
        )


def test_apply_shared_chunks_prefix_once_per_chunk(rng):
    """The streaming form: prefix computed once per chunk, branches fed
    from it, outputs identical to independent full passes."""
    prefix_calls = {"n": 0}

    def scale(b):
        if not is_tracing(b):
            prefix_calls["n"] += 1
        return b / 255.0

    prefix_fn = transformer(scale)
    a_fn = jax.jit(lambda s: s * 2.0)
    b_fn = jax.jit(lambda s: s + 1.0)
    x = np.asarray(
        rng.integers(0, 255, size=(20, 4, 4)).astype(np.float32)
    )
    out_a, out_b = plan_mod.apply_shared(
        prefix_fn, (a_fn, b_fn), x, chunk_size=8
    )
    np.testing.assert_allclose(np.asarray(out_a), x / 255.0 * 2.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out_b), x / 255.0 + 1.0, rtol=1e-6)
    assert prefix_calls["n"] == 3  # ceil(20/8) chunks, once each


def test_plan_pipeline_form_inserts_cacher_at_cache_points(rng):
    """Plan.pipeline(): the optimized chain as a plain Pipeline, with
    planner cache points rendered as explicit Cacher nodes — same
    outputs as the source pipeline; multi-branch plans have no single
    pipeline form."""
    from keystone_tpu.core.pipeline import Cacher

    pipe = Scale(factor=jnp.asarray(2.0)) >> transformer(lambda b: b + 1.0)
    x = jnp.asarray(rng.normal(size=(16, 3)).astype(np.float32))
    plan = plan_mod.plan_pipeline(pipe, sample=x)
    plan.prefix[0].materialize = True
    rendered = plan.pipeline()
    assert [type(n).__name__ for n in rendered.nodes] == [
        "Scale",
        "Cacher",
        "FnTransformer",
    ]
    np.testing.assert_array_equal(np.asarray(rendered(x)), np.asarray(pipe(x)))
    with pytest.raises(ValueError):
        Plan(prefix=[], branches=[[]], budget_bytes=0).pipeline()


def test_run_plan_multibranch_shares_and_recomputes(rng):
    """run_plan on a hand-built multi-branch plan: shared prefix runs
    once into every branch; with share_prefix refused, each branch
    recomputes from the source — same outputs either way."""
    from keystone_tpu.plan.executor import run_plan

    x = jnp.asarray(rng.normal(size=(40, 6)).astype(np.float32))
    prefix = PlanNode(
        label="feat", op=Scale(factor=jnp.asarray(2.0)), reuse=2
    )
    branches = [
        [PlanNode(label="a", op=transformer(lambda b: b + 1.0))],
        [PlanNode(label="b", op=transformer(lambda b: b - 1.0))],
    ]
    want = [np.asarray(x * 2.0 + 1.0), np.asarray(x * 2.0 - 1.0)]
    for share in (True, False):
        p = Plan(
            prefix=[prefix],
            branches=branches,
            share_prefix=share,
            budget_bytes=1 << 20,
        )
        out = run_plan(p, x)
        for got, ref in zip(out, want):
            np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-6)


# ---------------------------------------------------------------------------
# satellites: jitted() memoization, apply_in_chunks backpressure


def test_jitted_is_memoized_per_class():
    s1 = Scale(factor=jnp.asarray(2.0))
    s2 = Scale(factor=jnp.asarray(5.0))
    x = jnp.ones((4, 3), jnp.float32)
    np.testing.assert_allclose(np.asarray(s1.jitted()(x)), 2.0)
    misses = jit_apply._cache_size()
    # second jitted() wrapper on the same class + new weights: NO retrace
    np.testing.assert_allclose(np.asarray(s2.jitted()(x)), 5.0)
    assert jit_apply._cache_size() == misses


def test_apply_in_chunks_bounded_inflight_matches_legacy(rng):
    from keystone_tpu.core.batching import apply_in_chunks

    fn = jax.jit(lambda b: b * 2.0 + 1.0)
    data = jnp.asarray(rng.normal(size=(70, 6)).astype(np.float32))
    want = np.asarray(fn(data))
    for inflight in (0, 2, 100):
        got = apply_in_chunks(fn, data, 16, inflight=inflight)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    host = apply_in_chunks(fn, np.asarray(data), 16, to_host=True)
    assert isinstance(host, np.ndarray)
    np.testing.assert_allclose(host, want, rtol=1e-6)


def test_pad_to_chunk_shared_helper():
    from keystone_tpu.core.batching import pad_to_chunk

    full, valid = pad_to_chunk(np.ones((8, 3), np.float32), 8)
    assert valid == 8 and full.shape == (8, 3)
    padded, valid = pad_to_chunk(np.ones((5, 3), np.float32), 8)
    assert valid == 5 and padded.shape == (8, 3)
    np.testing.assert_array_equal(padded[5:], 0.0)


# ---------------------------------------------------------------------------
# staging engine: bounded depth, error propagation, clean shutdown


def test_staging_engine_bounded_stage_depth():
    """The staging thread runs AHEAD of the consumer but never further
    than its bound: consumed results + inflight dispatches + staged
    queue + one chunk in the producer's hand."""
    import time

    from keystone_tpu.core.staging import run_staged

    produced = []

    def chunks():
        for i in range(50):
            produced.append(i)
            yield np.full((4, 2), float(i), np.float32), 4

    fn = jax.jit(lambda b: b + 1.0)
    it = run_staged(chunks(), fn, stage_depth=2, inflight=1)
    try:
        first = next(it)
        np.testing.assert_array_equal(np.asarray(first), 1.0)
        deadline = time.monotonic() + 2.0
        stable = len(produced)
        while time.monotonic() < deadline:
            time.sleep(0.05)
            if len(produced) == stable:
                break
            stable = len(produced)
        # 2 consumed by the drain + 1 yielded-pending + depth 2 staged
        # + 1 in the producer's hand (+1 slack for the put/pull race)
        assert len(produced) <= 7, produced
    finally:
        it.close()


def test_staging_engine_producer_error_propagates():
    from keystone_tpu.core.staging import run_staged

    def chunks():
        yield np.ones((4, 2), np.float32), 4
        raise RuntimeError("stage source exploded")

    it = run_staged(chunks(), jax.jit(lambda b: b * 2.0), stage_depth=2)
    with pytest.raises(RuntimeError, match="stage source exploded"):
        list(it)


def test_staging_engine_clean_shutdown_on_close():
    """Closing the consumer mid-stream retires the staging thread and
    stops the chunk source instead of draining it."""
    import threading
    import time

    from keystone_tpu.core.staging import run_staged

    produced = []

    def chunks():
        for i in range(200):
            produced.append(i)
            yield np.zeros((4, 2), np.float32), 4

    before = threading.active_count()
    it = run_staged(chunks(), jax.jit(lambda b: b + 1.0), stage_depth=1)
    next(it)
    it.close()
    deadline = time.monotonic() + 5.0
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before, "staging thread leaked"
    assert len(produced) < 200, "source should stop early, not drain"


def test_staging_engine_passthrough_alias_safe():
    """A passthrough fn can alias its staged input into the output; the
    eager input-free must detect the shared buffer and keep it."""
    from keystone_tpu.core.staging import run_staged

    fn = jax.jit(lambda b: b)
    chunks = [(np.full((4, 2), float(i), np.float32), 4) for i in range(5)]
    outs = list(run_staged(iter(chunks), fn, stage_depth=0, inflight=0))
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(out), float(i))


def test_all_drain_loops_route_through_staging_engine(rng):
    """apply_in_chunks, featurize_stream, and apply_shared all stage
    through the ONE engine — every chunk shows up in the shared
    plan_transfer_chunks counter."""
    from keystone_tpu.core.batching import apply_in_chunks
    from keystone_tpu.loaders.streaming import featurize_stream

    fn = jax.jit(lambda b: b * 2.0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    before = _counter("plan_transfer_chunks")
    apply_in_chunks(fn, x, 16)  # 4 chunks
    featurize_stream(iter([x]), fn, chunk_size=16)  # 4 chunks
    plan_mod.apply_shared(
        jax.jit(lambda b: b + 1.0), (fn,), x, chunk_size=16
    )  # 4 chunks
    assert _counter("plan_transfer_chunks") - before == 12


# ---------------------------------------------------------------------------
# sharded planned execution: bit-exact vs single-device naive


def test_sharded_planned_execution_bit_exact_mnist(rng, mesh8):
    """Planned execution dispatched data-sharded over the 8-way mesh —
    whole-batch SPMD and chunked (each staged chunk sharded) — is
    bit-exact vs the naive single-device apply, and the staging engine's
    transfer/shard metrics record the dispatch."""
    from keystone_tpu.models.mnist_random_fft import FeaturizerBank
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicators, MaxClassifier

    x = jnp.asarray(rng.normal(size=(256, 784)).astype(np.float32))
    y = ClassLabelIndicators(num_classes=10)(
        rng.integers(0, 10, size=256).astype(np.int32)
    )
    bank = FeaturizerBank.create(2, 1024, seed=0)
    model = BlockLeastSquaresEstimator(block_size=1024, num_iter=1, lam=1.0).fit(
        bank(x), y
    )
    pipe = Pipeline.of(bank, model, MaxClassifier())
    naive = np.asarray(pipe(x))

    dispatches_before = _counter("plan_shard_dispatches")
    got = plan_mod.execute(pipe, x, mesh=mesh8)
    np.testing.assert_array_equal(np.asarray(got), naive)
    assert _counter("plan_shard_dispatches") > dispatches_before

    chunks_before = _counter("plan_shard_chunks")
    transfer_before = _counter("plan_transfer_chunks")
    got_chunked = plan_mod.execute(pipe, x, chunk_size=64, mesh=mesh8)
    np.testing.assert_array_equal(np.asarray(got_chunked), naive)
    assert _counter("plan_shard_chunks") - chunks_before >= 4
    assert _counter("plan_transfer_chunks") - transfer_before >= 4


def test_sharded_planned_execution_bit_exact_cifar(rng, mesh8):
    """The CIFAR conv chain sharded over the mesh (18 images do NOT
    divide over 8 slots — the executor pads, runs SPMD, trims) matches
    the production fused path bit for bit."""
    from keystone_tpu.core.fusion import optimize
    from keystone_tpu.ops.images import (
        Convolver,
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )

    k, f = 6, 16
    d = k * k * 3
    pipe = (
        Convolver(
            filters=jnp.asarray(rng.normal(size=(f, d)).astype(np.float32)),
            whitener_means=jnp.asarray(rng.normal(size=(d,)).astype(np.float32)),
            patch_size=k,
            normalize_patches=True,
        )
        >> SymmetricRectifier(alpha=0.25)
        >> Pooler(stride=13, pool_size=14)
        >> ImageVectorizer()
    )
    x = jnp.asarray(rng.normal(size=(18, 32, 32, 3)).astype(np.float32))
    naive = np.asarray(jit_apply(optimize(pipe), x))
    pad_before = _counter("plan_shard_pad_rows")
    got = plan_mod.execute(pipe, x, mesh=mesh8)
    assert np.asarray(got).shape == naive.shape  # pad rows trimmed
    np.testing.assert_array_equal(np.asarray(got), naive)
    assert _counter("plan_shard_pad_rows") - pad_before == 6  # 18 → 24


def test_apply_in_chunks_sharded_matches(rng, mesh8):
    from keystone_tpu.core.batching import apply_in_chunks
    from keystone_tpu.parallel.mesh import data_sharding

    fn = jax.jit(lambda b: b * 2.0 + 1.0)
    data = jnp.asarray(rng.normal(size=(70, 6)).astype(np.float32))
    want = np.asarray(fn(data))
    got = apply_in_chunks(
        fn, data, 16, sharding=lambda c: data_sharding(mesh8, c.ndim)
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_mnist_run_planned_sharded_matches_naive(rng, monkeypatch, mesh8):
    """End to end: KEYSTONE_PLAN + an 8-way mesh routes the MNIST test
    pass through sharded planned execution; measured errors match the
    naive mesh run exactly."""
    from keystone_tpu.models import mnist_random_fft as m

    conf = m.MnistRandomFFTConfig(
        synthetic=128, num_ffts=1, block_size=512, lam=10.0
    )
    monkeypatch.delenv(plan_mod.ENV_ENABLE, raising=False)
    naive = m.run(conf, mesh=mesh8)
    monkeypatch.setenv(plan_mod.ENV_ENABLE, "1")
    planned = m.run(conf, mesh=mesh8)
    assert planned["test_error"] == naive["test_error"]
    assert planned["train_error"] == naive["train_error"]


# ---------------------------------------------------------------------------
# comms-aware staging/sharding pass


def test_choose_staging_depth_from_cost_model(monkeypatch):
    monkeypatch.delenv("KEYSTONE_STAGE_DEPTH", raising=False)

    def plan_with(input_bytes, wall_s):
        node = PlanNode(
            label="n",
            op=transformer(lambda b: b),
            cost=NodeCost(
                input_bytes=input_bytes, wall_s=wall_s, source="sampled"
            ),
        )
        return Plan(prefix=[node], budget_bytes=1 << 20, chunk_size=100)

    # transfer-bound (1000 B/row over ~2e10 B/s vs 0.1 ns/row compute):
    # staging goes deeper than double-buffering, capped at 4
    p = plan_passes.choose_staging(plan_with(1000.0, 1e-10), n_rows=1000)
    assert p.stage_depth == 4
    stage = next(d for d in p.decisions if d["action"] == "stage")
    assert stage["source"] == "cost_model" and not stage["hidden"]

    # compute-bound: double buffering hides the transfer entirely
    p = plan_passes.choose_staging(plan_with(1.0, 1e-3), n_rows=1000)
    assert p.stage_depth == 2
    stage = next(d for d in p.decisions if d["action"] == "stage")
    assert stage["hidden"]

    # env override wins over the cost model
    monkeypatch.setenv("KEYSTONE_STAGE_DEPTH", "3")
    p = plan_passes.choose_staging(plan_with(1000.0, 1e-10), n_rows=1000)
    assert p.stage_depth == 3
    assert any(
        d["action"] == "stage" and d["source"] == "env" for d in p.decisions
    )


def test_choose_staging_shard_decision_rounds_chunk(mesh8):
    node = PlanNode(
        label="n",
        op=transformer(lambda b: b),
        cost=NodeCost(wall_s=1e-6, source="sampled"),
    )
    p = Plan(prefix=[node], budget_bytes=1 << 20, chunk_size=100, mesh=mesh8)
    plan_passes.choose_staging(p, n_rows=1000)
    assert p.shard and p.chunk_size == 104  # rounded UP to a multiple of 8
    shard = next(d for d in p.decisions if d["action"] == "shard")
    assert shard["shards"] == 8 and shard["axis"] == "data"
    # no mesh → no shard decision
    p2 = Plan(prefix=[node], budget_bytes=1 << 20, chunk_size=100)
    plan_passes.choose_staging(p2, n_rows=1000)
    assert not p2.shard


def test_chunk_size_choice_scales_with_shards():
    """A sharded chunk splits its working set over the mesh: the same
    budget admits shards x more rows per dispatch, kept divisible."""
    node = PlanNode(
        label="n",
        op=transformer(lambda b: b),
        cost=NodeCost(peak_bytes=1024.0, source="sampled"),
    )
    p = Plan(prefix=[node], budget_bytes=1 << 20, rows=64)
    plan_passes.choose_chunk_size(p, n_rows=1 << 20, shards=8)
    assert p.chunk_size == 2048  # 8 x the single-device 256
    assert p.chunk_size % 8 == 0


def test_node_cost_comms_terms():
    cost = NodeCost(input_bytes=100.0, collective_bytes=10.0)
    # cpu peaks: h2d 2e10 B/s, ici 2e10 B/s
    assert cost.h2d_s(1000) == pytest.approx(100.0 * 1000 / 2e10)
    assert cost.collective_s(1000) == pytest.approx(10.0 * 1000 / 2e10)
    from keystone_tpu.plan.ir import device_peaks

    # no kind given means the device this process runs on (the CPU here)
    assert device_peaks(None) == device_peaks("cpu")
    assert device_peaks("TPU v5 lite").int8_ops == pytest.approx(3.93e14)
    with pytest.raises(ValueError, match="DEVICE_PEAKS"):
        device_peaks("TPU v9")


# ---------------------------------------------------------------------------
# env gate + CLI


def test_plan_env_gate(monkeypatch):
    monkeypatch.delenv(plan_mod.ENV_ENABLE, raising=False)
    assert not plan_mod.enabled()
    for off in ("0", "false", "off", "no", ""):
        monkeypatch.setenv(plan_mod.ENV_ENABLE, off)
        assert not plan_mod.enabled()
    monkeypatch.setenv(plan_mod.ENV_ENABLE, "1")
    assert plan_mod.enabled()
    monkeypatch.setenv(plan_mod.ENV_BUDGET_MB, "2")
    assert plan_mod.default_budget_bytes() == 2 * 2**20


def test_mnist_run_planned_matches_naive(rng, monkeypatch):
    """KEYSTONE_PLAN routes the MNIST test pass through the planner's
    executor; the measured error must match the naive run exactly."""
    from keystone_tpu.models import mnist_random_fft as m

    conf = m.MnistRandomFFTConfig(
        synthetic=128, num_ffts=1, block_size=512, lam=10.0
    )
    monkeypatch.delenv(plan_mod.ENV_ENABLE, raising=False)
    naive = m.run(conf, mesh=None)
    monkeypatch.setenv(plan_mod.ENV_ENABLE, "1")
    planned = m.run(conf, mesh=None)
    assert planned["test_error"] == naive["test_error"]
    assert planned["train_error"] == naive["train_error"]


def test_plan_cli_smoke(capsys):
    from keystone_tpu.__main__ import main as cli_main

    cli_main(["plan", "cifar-random-patch", "--rows", "4096"])
    out = capsys.readouterr().out
    assert "plan:" in out and "FusedConvRectifyPool" in out
    assert "rewrite" in out and "conv_rectify_pool" in out
    assert "chunk" in out


def test_plan_cli_usage():
    from keystone_tpu.__main__ import main as cli_main

    with pytest.raises(SystemExit):
        cli_main(["plan"])
    with pytest.raises(SystemExit):
        cli_main(["plan", "no-such-model"])
