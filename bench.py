"""Benchmark harness — prints ONE JSON line for the driver.

Workloads (reference shapes, BASELINE.md):

1. MnistRandomFFT featurize+fit (60k x 784 synthetic MNIST, numFFTs=4,
   blockSize=2048 — the reference README example): end-to-end samples/s,
   plus solver-phase GFLOPs/chip and MFU.
2. CIFAR random-patch convolution (BASELINE.md row "CIFAR random-patch":
   6x6 patches, patch-normalized whitened filter bank): featurize
   samples/s through the conv-algebra Convolver + rectifier + pooler.

Baseline: the same computation in numpy/BLAS on this host's CPU (the
moral stand-in for the reference's single-node Spark local mode — the
reference repo publishes no numbers, see BASELINE.md). O(N) phases are
measured on a subset and scaled; the fixed O(d^3) solve is timed once at
full width and added unscaled.

Device rule (keystone_tpu/core/runtime.py): with ``JAX_PLATFORMS`` unset
the bench runs on the TPU, and a machine with no TPU is a non-zero exit
with the backend's own error — never a quiet CPU run. An explicit
``JAX_PLATFORMS=cpu`` run is allowed for debugging the harness: it uses
reduced sizes, skips the chip-sized LM workloads, says ``platform: cpu``
in its line and prints no MFU or per-chip TFLOP/s key. Every line names
platform, device_kind and device count. A section that raises is
recorded in ``errors`` and makes the exit code 1 after the line is
printed.

Measurement notes: steps are timed by dispatching several iterations
asynchronously and syncing ONCE via an on-device scalar index + host
transfer.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

def _git_sha() -> str:
    import subprocess

    try:
        return (
            subprocess.run(
                ["git", "rev-parse", "HEAD"],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True,
                text=True,
                timeout=10,
            ).stdout.strip()
            or "unknown"
        )
    except Exception:  # noqa: BLE001
        return "unknown"


# ---------------------------------------------------------------------------
# perf-regression gate: `bench.py --check BASELINE.json --tolerance PCT`
# compares two recorded bench artifacts and exits nonzero on regression,
# so a CI step can gate on the bench trajectory instead of eyeballing
# JSON. No jax import — this path must run anywhere, instantly.

_SKIP_METRIC_KEYS = frozenset(
    {"ts", "timestamp", "saved_ts", "git_sha", "num_devices"}
)


def _metric_leaves(record: dict, prefix: str = "") -> dict[str, float]:
    """Flatten a bench record to dotted-path → numeric leaves."""
    out: dict[str, float] = {}
    for key, val in record.items():
        if key in _SKIP_METRIC_KEYS:
            continue
        path = f"{prefix}.{key}" if prefix else str(key)
        if isinstance(val, dict):
            out.update(_metric_leaves(val, path))
        elif isinstance(val, (int, float)) and not isinstance(val, bool):
            out[path] = float(val)
    return out


def _metric_direction(path: str) -> str | None:
    """``higher`` / ``lower`` / None (not comparable) for one metric
    path — rates and MFUs must not drop, latencies must not grow;
    anything ambiguous is skipped rather than guessed."""
    last = path.split(".")[-1]
    if (
        last.endswith(("per_s", "per_sec", "per_chip", "_gflops"))
        or last.startswith(("mfu", "vs_", "speedup", "aggregate_over"))
        or "tokens_per_s" in last
        or "samples_per_s" in last
        or "rows_per_s" in last
        or last == "value"
    ):
        return "higher"
    if (
        last.endswith(("_ms", "_s"))
        or "p50" in last
        or "p95" in last
        or "p99" in last
    ):
        return "lower"
    return None


def compare_records(
    baseline: dict, current: dict, tolerance_pct: float
) -> tuple[list[str], int]:
    """Regression lines + count of metrics actually compared. A metric
    present in only one record is skipped (workloads come and go); only
    a shared metric moving the WRONG way past tolerance regresses."""
    base = _metric_leaves(baseline)
    cur = _metric_leaves(current)
    tol = max(float(tolerance_pct), 0.0) / 100.0
    regressions: list[str] = []
    checked = 0
    for path in sorted(set(base) & set(cur)):
        direction = _metric_direction(path)
        if direction is None:
            continue
        b, c = base[path], cur[path]
        if b <= 0:
            continue
        checked += 1
        delta = (c - b) / b
        if direction == "higher" and c < b * (1.0 - tol):
            regressions.append(
                f"REGRESSION {path}: {b:g} -> {c:g} "
                f"({delta * 100:+.1f}% < -{tolerance_pct:g}%)"
            )
        elif direction == "lower" and c > b * (1.0 + tol):
            regressions.append(
                f"REGRESSION {path}: {b:g} -> {c:g} "
                f"({delta * 100:+.1f}% > +{tolerance_pct:g}%)"
            )
    return regressions, checked


def _load_record_file(path: str) -> dict:
    with open(path) as f:
        record = json.load(f)
    # accept both the raw result dict and a {"result": {...}} wrapper
    if isinstance(record.get("result"), dict):
        record = record["result"]
    return record


def check_main(argv: list[str]) -> int:
    """``bench.py --check BASELINE.json --against CURRENT.json
    [--tolerance PCT]`` — exit 1 when any shared metric regressed past
    tolerance (default 5%)."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="bench.py --check", add_help=True
    )
    parser.add_argument("--check", required=True, metavar="BASELINE.json")
    parser.add_argument(
        "--against",
        required=True,
        metavar="CURRENT.json",
        help="record to judge",
    )
    parser.add_argument("--tolerance", type=float, default=5.0)
    args = parser.parse_args(argv)
    try:
        baseline = _load_record_file(args.check)
        current = _load_record_file(args.against)
    except (OSError, ValueError) as e:
        print(f"bench --check: {e}", file=sys.stderr)
        return 2
    regressions, checked = compare_records(
        baseline, current, args.tolerance
    )
    for line in regressions:
        print(line)
    print(
        f"bench --check: {checked} metric(s) compared, "
        f"{len(regressions)} regression(s) past {args.tolerance:g}% "
        f"({args.check} vs {args.against})"
    )
    return 1 if regressions else 0

N_TRAIN = 60_000
IMAGE_SIZE = 784
NUM_FFTS = 4
BLOCK_SIZE = 2048
LAM = 1e-2
CPU_SUBSET = 6_000

CIFAR_N = 4096
CIFAR_FILTERS = 256
CIFAR_PATCH = 6
CIFAR_CPU_SUBSET = 256

# TIMIT-shaped weighted solver (BASELINE.md "TIMIT": C=147 phone classes;
# width cut to one 1024 block so the bench step stays seconds, not
# minutes — rates are per-sample and the class economics are what's
# under test). Class sizes keep the Woodbury path active.
TIMIT_N = 32_768
TIMIT_D = 1024
TIMIT_C = 147

# ImageNet-shaped weighted solver (BASELINE.md "ImageNet": 4096-col
# solver blocks, 1000 classes — ImageNetSiftLcsFV.scala:186-218). The
# shape the round-2 Woodbury redesign was built for: ~16 rows/class, so
# the per-class low-rank correction is tiny against d=4096 and the
# dominant work is the batched B⁻¹V triangular solves + class gemms —
# MXU-bound, unlike TIMIT's thin HBM-bound d=440 (VERDICT r3 weak #5).
IMNET_W_N = 16_384
IMNET_W_D = 4_096
IMNET_W_C = 1_000

# dense-SIFT featurize (VOC shapes: step 3, bin 4, 5 scales)
SIFT_N = 16
SIFT_HW = 256
SIFT_NATIVE_SUBSET = 2

# bf16 peaks live in ONE place now: keystone_tpu.observe.report
# (PEAK_FLOPS / peak_flops_for) — see _device_peak below


def _synthetic(n: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    centers = np.random.default_rng(42).normal(size=(10, IMAGE_SIZE)).astype(
        np.float32
    )
    data = centers[labels] + rng.normal(size=(n, IMAGE_SIZE)).astype(np.float32)
    return labels, data


def _sync(tree) -> float:
    """Force completion: on-device scalar index, then host transfer of
    that one scalar (np.asarray of a full array would time the copy)."""
    import jax

    leaf = jax.tree_util.tree_leaves(tree)[0]
    return float(np.asarray(leaf.ravel()[0]))


def _timed(step, iters: int = 4) -> float:
    """Seconds per call: `iters` async dispatches, one sync."""
    _sync(step())  # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = step()
    _sync(out)
    return (time.perf_counter() - t0) / iters


def dispatch_floor_ms() -> float:
    """Per-dispatch launch latency: time a trivial jitted op with the
    same discipline as every workload. Workload numbers embed it —
    record it so the artifact states how much of each step is launch
    latency, not chip time."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda v: v + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    return _timed(lambda: f(x), iters=8) * 1e3


def _mnist_per_node_breakdown(fitted, x) -> dict:
    """Per-node wall time + compiler cost profile of the fitted MNIST
    apply pipeline, via the observe subsystem: one instrumented eager
    apply on a bounded probe batch, events collected in-memory (or into
    the ambient KEYSTONE_OBSERVE_DIR run when one is active) — the
    KeystoneML-style operator breakdown the flat samples/s number can't
    show. ``fitted`` is the pipeline the timed fit loop already built —
    no re-fit here."""
    from keystone_tpu.core.pipeline import Pipeline
    from keystone_tpu.observe import events
    from keystone_tpu.observe.cost import record_pipeline_profile
    from keystone_tpu.observe.report import per_node_breakdown
    from keystone_tpu.ops.util import MaxClassifier

    pipe = Pipeline.of(*fitted.nodes, MaxClassifier())
    probe = x[:2048]

    def collect(log):
        # only the records this probe appends: the ambient log already
        # holds the timed fit-loop's events, which are not apply rows
        start = len(log.records)
        profiles = record_pipeline_profile(pipe, probe, save_dir=log.run_dir)
        return per_node_breakdown(log, profiles, since=start)

    ambient = events.active()
    if ambient is not None:
        # an env-activated run is in flight: keep everything (node
        # events, cost profiles, the final bench record) in ONE run dir
        return collect(ambient)
    with events.run(workload="mnist_random_fft") as log:  # memory-only
        return collect(log)


def _mnist_planner_record(fitted, x, y, n, mesh=None) -> dict:
    """Planned-vs-naive record for the fitted MNIST pipeline: the
    cost-based planner's executor against the plain eager apply on the
    same probe, plus — on a multi-device host — the same plan dispatched
    data-sharded over the mesh (the sharded-planned vs single-device-
    planned delta, with the staging engine's transfer counters), plus a
    shared-prefix fit (two solvers riding ONE featurizer bank) whose
    metrics-counter delta shows the planner eliminating a redundant
    featurization pass. Decisions ride along so the perf trajectory
    records WHAT the planner chose, not just the delta."""
    import jax

    from keystone_tpu import plan as plan_mod
    from keystone_tpu.core.pipeline import ChainedLabelEstimator, Pipeline
    from keystone_tpu.observe import metrics as observe_metrics
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import MaxClassifier

    pipe = Pipeline.of(*fitted.nodes, MaxClassifier())
    probe = x[:2048]
    naive_s = _timed(lambda: pipe(probe), iters=4)
    plan = plan_mod.plan_pipeline(
        pipe, sample=probe[:256], n_rows=probe.shape[0]
    )
    planned_s = _timed(lambda: plan.execute(probe), iters=4)

    sharded = None
    if mesh is not None and len(jax.devices()) > 1:
        plan_sharded = plan_mod.plan_pipeline(
            pipe, sample=probe[:256], n_rows=probe.shape[0], mesh=mesh
        )
        plan_sharded.execute(probe)  # warm the executables
        # counter deltas bracket ONE execution, so transfer_bytes is
        # comparable to the probe's nbytes (timed reps would inflate 5x)
        reg0 = observe_metrics.get_registry().snapshot()
        plan_sharded.execute(probe)
        snap = observe_metrics.get_registry().snapshot()
        sharded_s = _timed(lambda: plan_sharded.execute(probe), iters=4)
        from keystone_tpu.parallel.mesh import data_axis_size

        sharded = {
            "sharded_planned_ms": round(sharded_s * 1e3, 2),
            "sharded_vs_single_planned": round(planned_s / sharded_s, 3),
            "shards": data_axis_size(mesh),
            "stage_depth": plan_sharded.stage_depth,
            "transfer_metrics": {
                k: snap.get(k, 0) - reg0.get(k, 0)
                for k in (
                    "plan_transfer_chunks",
                    "plan_transfer_bytes",
                    "plan_shard_chunks",
                    "plan_shard_dispatches",
                )
            },
            "decisions": plan_sharded.decisions,
        }

    bank = fitted.nodes[0]
    chains = [
        ChainedLabelEstimator(
            prefix=bank,
            est=BlockLeastSquaresEstimator(
                block_size=BLOCK_SIZE, num_iter=1, lam=lam
            ),
        )
        for lam in (LAM, 10 * LAM)
    ]
    reg = observe_metrics.get_registry()
    saved_before = reg.snapshot().get("plan_featurize_passes_saved", 0)
    t0 = time.perf_counter()
    jax.block_until_ready(
        [f[-1] for f in plan_mod.fit_shared(chains, x, y, n_valid=n)]
    )
    shared_fit_s = time.perf_counter() - t0
    saved = reg.snapshot().get("plan_featurize_passes_saved", 0) - saved_before
    rec = {
        "naive_apply_ms": round(naive_s * 1e3, 2),
        "planned_apply_ms": round(planned_s * 1e3, 2),
        "planned_vs_naive": round(naive_s / planned_s, 3),
        "decisions": plan.decisions,
        "chunk_size": plan.chunk_size,
        "shared_prefix_fit": {
            "branches": len(chains),
            "featurize_passes_saved": saved,
            "fit_s": round(shared_fit_s, 3),
        },
    }
    if sharded is not None:
        rec["sharded"] = sharded
    return rec


def bench_mnist(labels: np.ndarray, data: np.ndarray) -> dict:
    import jax

    from keystone_tpu.models import mnist_random_fft as m
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicators
    from keystone_tpu.parallel.mesh import create_mesh, shard_batch

    mesh = create_mesh() if len(jax.devices()) > 1 else None
    n = len(labels)
    x = shard_batch(data, mesh)
    y = ClassLabelIndicators(num_classes=10)(
        np.pad(labels, (0, x.shape[0] - n))
    )
    from keystone_tpu.core.pipeline import ChainedLabelEstimator

    bank = m.FeaturizerBank.create(NUM_FFTS, BLOCK_SIZE, seed=0)
    est = BlockLeastSquaresEstimator(block_size=BLOCK_SIZE, num_iter=1, lam=LAM)
    chained = ChainedLabelEstimator(prefix=bank, est=est)

    # featurize + fit as ONE traced program (fit_fused): a fit step pays a
    # single device launch instead of one per stage. Return the fitted
    # MODEL node ([-1]) — the pipeline's first leaves are the prefix
    # bank's constants, and _sync on one of those would return before the
    # fit program has executed. The box keeps the last fitted pipeline so
    # the per-node breakdown below doesn't pay a sixth fit.
    fitted_box = {}

    def step():
        fitted_box["pipe"] = chained.fit_fused(x, y, n_valid=n)
        return fitted_box["pipe"][-1]

    sec = _timed(step)
    try:
        per_node = _mnist_per_node_breakdown(fitted_box["pipe"], x)
    except Exception as e:  # noqa: BLE001 — observability must not cost
        # the bench its headline number
        per_node = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
    try:
        planner = _mnist_planner_record(fitted_box["pipe"], x, y, n, mesh=mesh)
    except Exception as e:  # noqa: BLE001 — same rule for the planner
        planner = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
    d = NUM_FFTS * 512  # total feature width
    # solver-phase FLOPs: Gram N*d^2 + AtB N*d*10, Cholesky d^3/3 + refine
    flops = 2 * n * d * d + 2 * n * d * 10 + d**3 / 3
    # featurize-phase FLOPs: per FFT chain a sign multiply + the
    # DFT-as-matmul cosine gemm (N x 784) @ (784 x 512) + rectifier
    feat_flops = NUM_FFTS * 2 * n * IMAGE_SIZE * 512
    return {
        "samples_per_s": n / sec,
        "step_ms": sec * 1e3,
        "solver_gflops": flops / 1e9,
        # the batch is sharded over every device: divide by the device
        # count so the per-chip label is honest on multi-chip hosts
        "solver_tflops_per_s": flops / sec / 1e12 / len(jax.devices()),
        # whole-step rate (featurize + solver FLOPs over the same step
        # time) — the number the solver-only rate under-reports
        "e2e_tflops_per_s": (flops + feat_flops)
        / sec
        / 1e12
        / len(jax.devices()),
        "per_node": per_node,
        "planner": planner,
    }


def bench_cifar_conv() -> dict:
    """CIFAR random-patch featurization: conv-algebra Convolver +
    SymmetricRectifier + Pooler (BASELINE.md "CIFAR random-patch")."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.ops.images import (
        Convolver,
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )

    from keystone_tpu.core.fusion import optimize

    rng = np.random.default_rng(1)
    batch = jnp.asarray(
        rng.normal(size=(CIFAR_N, 32, 32, 3)).astype(np.float32)
    )
    d = CIFAR_PATCH * CIFAR_PATCH * 3
    filters = jnp.asarray(
        rng.normal(size=(CIFAR_FILTERS, d)).astype(np.float32)
    )
    means = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    pipe = optimize(
        Convolver(
            filters=filters,
            whitener_means=means,
            patch_size=CIFAR_PATCH,
            normalize_patches=True,
        )
        >> SymmetricRectifier(alpha=0.25)
        >> Pooler(stride=13, pool_size=14)
        >> ImageVectorizer()
    )
    fn = jax.jit(lambda b: pipe(b))
    sec = _timed(lambda: fn(batch))
    oh = 32 - CIFAR_PATCH + 1
    conv_flops = 2 * CIFAR_N * oh * oh * d * CIFAR_FILTERS
    return {
        "samples_per_s": CIFAR_N / sec,
        # single unsharded batch, but keep the same per-chip convention
        "conv_tflops_per_s": conv_flops / sec / 1e12 / len(jax.devices()),
    }


def bench_weighted() -> dict:
    """Class-weighted BCD fit at TIMIT class count (VERDICT r2 #8: the
    bench must track the solver the round-2/3 engineering went into)."""
    import jax

    from keystone_tpu.ops.weighted_linear import (
        BlockWeightedLeastSquaresEstimator,
    )

    rng = np.random.default_rng(3)
    n, d, c = TIMIT_N, TIMIT_D, TIMIT_C
    cls = rng.integers(0, c, size=n)
    centers = rng.normal(size=(c, d)).astype(np.float32)
    data = (centers[cls] + rng.normal(size=(n, d))).astype(np.float32)
    labels = -np.ones((n, c), np.float32)
    labels[np.arange(n), cls] = 1.0
    import jax.numpy as jnp

    x, y = jnp.asarray(data), jnp.asarray(labels)
    est = BlockWeightedLeastSquaresEstimator(
        block_size=d,
        num_iter=2,
        lam=1e-3,
        mixture_weight=0.5,
        class_chunk=16,
    )
    sec = _timed(lambda: est.fit(x, y), iters=2)
    # dominant FLOPs (see weighted_linear.py): pass-invariant pop Gram +
    # grid class Grams (2·N·d² each) + Woodbury prep y=B⁻¹V
    # (2·C·d²·(L+1)) and G (2·C·d·(L+1)²); per pass pop_xtr (2·N·d·C)
    # + per-class solves (~8·C·d² incl. 3 refine matvecs)
    l_pad = max(-(-int(np.bincount(cls).max()) // 64) * 64, 64)
    lp1 = l_pad + 1
    setup = 2 * n * d * d * 2 + 2 * c * d * d * lp1 + 2 * c * d * lp1**2
    per_pass = 2 * n * d * c + 8 * c * d * d
    flops = setup + est.num_iter * per_pass
    return {
        "samples_per_s": n / sec,
        "tflops_per_s": flops / sec / 1e12 / len(jax.devices()),
    }


def weighted_imagenet_problem():
    """(x, y, estimator, analytic FLOPs) for the ImageNet-shaped weighted
    solve — the single home of this workload's data generation and cost
    model, shared with tools/mfu_sweep.py. The FLOPs follow the same
    structure as bench_weighted (see weighted_linear.py); here the
    2·C·d²·(L+1) Woodbury prep dominates (~2.2 of the ~3.6 TFLOPs at
    L_pad=64)."""
    import jax.numpy as jnp

    from keystone_tpu.ops.weighted_linear import (
        BlockWeightedLeastSquaresEstimator,
    )

    rng = np.random.default_rng(5)
    n, d, c = IMNET_W_N, IMNET_W_D, IMNET_W_C
    cls = rng.integers(0, c, size=n)
    centers = rng.normal(size=(c, d)).astype(np.float32)
    data = (centers[cls] + rng.normal(size=(n, d))).astype(np.float32)
    labels = -np.ones((n, c), np.float32)
    labels[np.arange(n), cls] = 1.0
    est = BlockWeightedLeastSquaresEstimator(
        block_size=d,
        num_iter=1,
        lam=1e-3,
        mixture_weight=0.5,
        class_chunk=64,
    )
    l_pad = max(-(-int(np.bincount(cls).max()) // 64) * 64, 64)
    lp1 = l_pad + 1
    setup = 2 * n * d * d * 2 + 2 * c * d * d * lp1 + 2 * c * d * lp1**2
    per_pass = 2 * n * d * c + 8 * c * d * d
    flops = setup + est.num_iter * per_pass
    return jnp.asarray(data), jnp.asarray(labels), est, flops


def bench_weighted_imagenet() -> dict:
    """Class-weighted BCD fit at the ImageNet solver shape (d=4096,
    C=1000): records the Woodbury path's FLOP rate at the shape it was
    designed for. TPU-only (the ~3.6 TFLOP fit is a couple of minutes
    of host BLAS on the CPU fallback — too slow for the fallback's
    prompt-finish goal; the TIMIT workload covers the weighted solver
    there)."""
    import jax

    x, y, est, flops = weighted_imagenet_problem()
    sec = _timed(lambda: est.fit(x, y), iters=1)
    return {
        "samples_per_s": x.shape[0] / sec,
        "fit_s": sec,
        "tflops_per_s": flops / sec / 1e12 / len(jax.devices()),
    }


def bench_cpu_weighted() -> float:
    """Reference-economics CPU baseline: per-class Grams over sorted
    segments + C dense Cholesky solves (the reference's per-executor
    dense path, BlockWeightedLeastSquares.scala) in numpy/BLAS. O(N)
    phases timed on a row subset and scaled; the C·d³ solve phase timed
    on a class subset and scaled."""
    rng = np.random.default_rng(3)
    n, d, c = TIMIT_N, TIMIT_D, TIMIT_C
    n_sub, c_sub = max(n // 8, 1024), 8
    cls = rng.integers(0, c, size=n_sub)
    data = rng.normal(size=(n_sub, d)).astype(np.float32)
    t0 = time.perf_counter()
    data.T @ data  # pop Gram
    order = np.argsort(cls, kind="stable")
    srt = data[order]
    for k in range(c_sub):  # per-class Grams, subset scaled below
        seg = srt[k * (n_sub // c_sub) : (k + 1) * (n_sub // c_sub)]
        seg.T @ seg
    t_gram = time.perf_counter() - t0
    # scale: pop gram O(n), class grams O(n) total (c_sub covers
    # n_sub//c_sub rows each -> already n_sub rows total)
    t_gram *= n / n_sub
    m = data.T @ data / n_sub + 1e-3 * np.eye(d, dtype=np.float32)
    rhs = rng.normal(size=(d, 1)).astype(np.float32)
    t0 = time.perf_counter()
    for _ in range(c_sub):
        np.linalg.solve(m, rhs)
    t_solve = (time.perf_counter() - t0) * (c / c_sub)
    # two BCD passes of solves (Grams are cached pass-invariant)
    return n / (t_gram + 2 * t_solve)


LM_DIM, LM_DEPTH, LM_HEADS = 1024, 8, 16
LM_SEQ, LM_BATCH, LM_VOCAB = 2048, 8, 32_768


def _lm_train_step_rate(
    *, seq, dim, depth, heads, batch, pos_encoding="learned",
    use_mesh=True, iters=3, remat=False, logit_chunk=0,
) -> dict:
    """Shared scaffold for the LM train-step benches: build a bf16-policy
    model, one donated train step, dp-shard the batch when a mesh helps,
    and time steady-state steps. ``remat=False`` is the honest default at
    these shapes: activations + logits fit HBM with room to spare, and
    full remat would silently add ~1/3 recompute FLOPs the analytic
    6·P·tokens model doesn't count (ROOFLINE.md §6). Pass remat="dots"
    or "full" for memory-bound shapes."""
    import jax
    import jax.numpy as jnp
    import optax

    from keystone_tpu.models import lm_transformer as lm
    from keystone_tpu.parallel.mesh import create_mesh

    mesh = (
        create_mesh() if use_mesh and len(jax.devices()) > 1 else None
    )
    model = lm.TransformerLM.create(
        jax.random.key(0),
        vocab=LM_VOCAB,
        max_seq=seq,
        dim=dim,
        depth=depth,
        num_heads=heads,
        compute_dtype="bfloat16",
        pos_encoding=pos_encoding,
        # the local flash kernel is shard_mapped over the mesh the
        # batch is split on (GSPMD cannot partition a Mosaic kernel)
        mesh=mesh,
    )
    if remat:
        # accept legacy remat=True as full remat, not a policy name
        policy = "full" if remat is True else remat
        model = dataclasses.replace(
            model, remat=True, remat_policy=policy
        )
    model = lm.shard_params(model, mesh)
    optimizer = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = optimizer.init(model)
    step = lm.make_train_step(optimizer, logit_chunk=logit_chunk)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(
            0, LM_VOCAB, size=(batch, seq + 1), dtype=np.int32
        )
    )
    n_chips = 1
    if mesh is not None and batch % mesh.shape.get("data", 1) == 0:
        from keystone_tpu.parallel.mesh import data_sharding

        # dp-shard the batch; only then is a per-chip divide honest
        # (unsharded, every chip would replicate the full step)
        toks = jax.device_put(toks, data_sharding(mesh, ndim=2))
        n_chips = len(jax.devices())
    flops = lm.train_step_flops(model, batch, seq)
    state = [model, opt_state]

    def stepper():
        m2, o2, loss = step(state[0], state[1], toks)
        state[0], state[1] = m2, o2
        return loss

    sec = _timed(stepper, iters=iters)
    return {
        "tokens_per_s": batch * seq / sec,
        "tflops_per_s": flops / sec / 1e12 / n_chips,
        "params": model.num_params(),
    }


@contextlib.contextmanager
def _env_override(updates: dict):
    """Apply env-var ``updates`` for the duration of the block and
    restore the prior state on exit (value ``None`` means unset the
    var). Shared by the tuned-config benches — the None-means-pop
    restore pattern is subtle enough to keep in ONE place."""
    saved = {k: os.environ.get(k) for k in updates}
    try:
        for k, v in updates.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _lm_tuned_config() -> dict | None:
    """Winning knob set from tools/lm_mfu_push.py, if one was captured
    on chip for the current bench shape (LM_BENCH_TUNED.json). The push
    sweep writes it only when a config beats the default by >3%, so
    honoring it here means the closing bench of a chip session records
    the tuned number automatically."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "LM_BENCH_TUNED.json")
    try:
        with open(path) as f:
            t = json.load(f)
    except (OSError, ValueError):
        return None
    if t.get("shape") != f"dim{LM_DIM}_depth{LM_DEPTH}_s{LM_SEQ}":
        return None  # stale: bench shape moved since the capture
    return t


def bench_solver_mfu(n: int | None = None, d_feats: int | None = None) -> dict:
    """Streamed-vs-materialized fused fit: the solver-MFU trajectory
    record (plan/fused_fit.py). One featurize→fit workload (cosine
    random features → exact normal-equations ridge) run both ways on
    the same data: the classic path materializes the (N, D) feature
    matrix then fits; the planned path streams staged chunks through
    ONE fused featurize+accumulate jit. Records the throughput delta,
    the planner's chosen Gram operator + decisions, and the
    cost-priced solver TFLOP/s — runs on the CPU fallback too (the
    delta there sanity-checks the shape of the win; the MFU number is
    the on-chip target)."""
    import jax

    from keystone_tpu import plan as plan_mod
    from keystone_tpu.core.pipeline import ChainedLabelEstimator
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.plan import executor as _plan_exec
    from keystone_tpu.ops.util import ClassLabelIndicators

    on_cpu = jax.devices()[0].platform == "cpu"
    n = n or (65_536 if on_cpu else 524_288)
    d_in, k, passes = 256, 10, 5
    d = d_feats or (512 if on_cpu else 4096)
    chunk = 4096
    rng = np.random.default_rng(7)
    # HOST corpus: the fit's real starting point — the classic path
    # places it whole, the streamed path overlaps h2d with accumulate
    x = rng.normal(size=(n, d_in)).astype(np.float32)
    labels = rng.integers(0, k, size=n).astype(np.int32)
    y = ClassLabelIndicators(num_classes=k)(labels)
    feat = CosineRandomFeatures.create(d_in, d, jax.random.key(0))
    # the TIMIT epoch regime (one 4096-wide solver block, multi-pass
    # BCD): Gram work is identical both ways, but the classic data-form
    # passes re-touch all N rows per epoch while the streamed Gram-form
    # passes are N-independent — the single-block slice keeps the
    # comparison FLOP-honest (a B-block full Gram costs B× the
    # per-block Grams; the planner's budget guard owns that trade)
    est = BlockLeastSquaresEstimator(block_size=d, num_iter=passes, lam=1.0)
    chain = ChainedLabelEstimator(prefix=feat, est=est)

    featurize = jax.jit(lambda b: feat(b))

    def materialized():
        # the unplanned model codepath: featurize the whole corpus to a
        # resident feature matrix, then fit from it
        feats = jax.block_until_ready(featurize(jax.device_put(x)))
        return est.fit(feats, y).xs[0]

    # plan ONCE (a real corpus fit plans once; the probe/profiling cost
    # is not the steady state), then time the planned execution
    plan = plan_mod.plan_fit(chain, x, y, chunk_size=chunk, prefetch=4)

    def streamed():
        state = _plan_exec.fit_stream(plan, x, y)
        return est.fit_stats_finalize(state, widths=plan.fit.widths).xs[0]

    mat_s = _timed(materialized, iters=3)
    stream_s = _timed(streamed, iters=3)
    # modeled fit FLOPs: featurize gemm + Gram/AᵀB accumulation
    flops = 2.0 * n * d_in * d + 2.0 * n * d * (d + k)
    rec = {
        "n_rows": n,
        "d_features": d,
        "bcd_passes": passes,
        "chunk_size": plan.chunk_size,
        "materialized_fit_s": round(mat_s, 4),
        "streamed_fit_s": round(stream_s, 4),
        "streamed_vs_materialized": round(mat_s / stream_s, 3),
        "rows_per_s": round(n / stream_s, 1),
        "chosen_operator": plan.fit.gram if plan.fit else "?",
        "decisions": plan.decisions,
    }
    peak = _device_peak()
    if not on_cpu:
        # per-chip rates are device metrics: a CPU run prints none
        rec["solver_tflops_per_chip"] = round(
            flops / stream_s / 1e12 / len(jax.devices()), 3
        )
    if peak is not None:
        rec["mfu_streamed_vs_bf16_peak"] = round(
            flops / stream_s / len(jax.devices()) / peak, 4
        )
    return rec


def bench_lm_train() -> dict:
    """One sharded LM train step (models/lm_transformer.py): the
    training-side MFU workload — forward+backward+AdamW as a single
    buffer-donated program. TPU-only (skipped on the CPU fallback: a
    ~17 TFLOP step is minutes of host time). Applies the on-chip tuned
    config (LM_BENCH_TUNED.json) when one exists; MFU stays honest
    because tflops_per_s divides ANALYTIC step FLOPs by measured time
    at whatever batch runs."""
    tuned = _lm_tuned_config()
    default_kwargs = dict(
        seq=LM_SEQ, dim=LM_DIM, depth=LM_DEPTH, heads=LM_HEADS,
        batch=LM_BATCH,
    )
    if not tuned:
        return _lm_train_step_rate(**default_kwargs)
    kwargs = dict(default_kwargs)
    kwargs["batch"] = int(tuned.get("batch", LM_BATCH))
    kwargs["logit_chunk"] = int(tuned.get("logit_chunk", 0))
    if tuned.get("remat"):
        kwargs["remat"] = tuned["remat"]
    # knob set for the tuned run: dense_bwd EXPLICITLY both ways (so a
    # pre-existing export can't silently mislabel the artifact) plus any
    # per-call KST_* knobs the stage-2 push recorded (attention impl,
    # flash block sizes — tools/lm_mfu_push2.py writes tuned["env"])
    env_updates: dict = {
        "KST_FLASH_DENSE_BWD_MAX": (
            None if tuned.get("dense_bwd", True) else "0"
        )
    }
    env_updates.update(tuned.get("env") or {})
    try:
        with _env_override(env_updates):
            res = _lm_train_step_rate(**kwargs)
        res["tuned_config"] = {
            k: tuned[k]
            for k in ("batch", "logit_chunk", "dense_bwd", "remat", "env")
            if k in tuned
        }
        return res
    except Exception as e:  # noqa: BLE001 — stale tuned config (e.g. OOM)
        print(
            f"# tuned LM config failed ({type(e).__name__}: {e}); "
            "falling back to the default config",
            file=sys.stderr,
        )
        # the context manager already restored on unwind: the default
        # rerun sees a clean env
        return _lm_train_step_rate(**default_kwargs)


LM_LONG_SEQ, LM_LONG_DIM, LM_LONG_DEPTH = 16_384, 512, 4


def _flash_tuned_env(path: str | None = None) -> dict:
    """Winning block sizes from the on-chip flash sweep
    (FLASH_SWEEP.json, tools/flash_sweep.py), as KST_FLASH_* env knobs
    for the long-context bench. The sweep tags configs ``q{bq}_k{bk}``
    (an artifact from before the backward became a kernel carries two
    more parts, which nothing reads); a malformed or missing artifact
    means no override (kernel defaults)."""
    if path is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "FLASH_SWEEP.json")
    try:
        with open(path) as f:
            best = json.load(f)["best"]["config"]
        bq, bk = (part.lstrip("qk") for part in best.split("_")[:2])
        return {
            "KST_FLASH_BLOCK_Q": str(int(bq)),
            "KST_FLASH_BLOCK_K": str(int(bk)),
        }
    except (OSError, ValueError, KeyError, TypeError):
        return {}


def bench_lm_longctx() -> dict:
    """One long-context causal train step (S=16k, rope positions): the
    attention S² term dominates and the FlashAttention-style blockwise
    backward carries the step — the dense-recompute backward's transient
    (S, S) tensors would not fit. TPU-only like bench_lm_train. Applies
    the on-chip flash-sweep winner's block sizes (FLASH_SWEEP.json) when
    one exists, recorded in the result."""
    tuned = _flash_tuned_env()
    with _env_override(tuned):
        res = _lm_train_step_rate(
            seq=LM_LONG_SEQ, dim=LM_LONG_DIM, depth=LM_LONG_DEPTH,
            heads=8, batch=1, pos_encoding="rope", use_mesh=False,
            iters=2,
            # never materialize the (S, 32k-vocab) f32 logits (2.1 GB +
            # its grad at S=16k): the CE runs in 4k-position chunks
            logit_chunk=4096,
        )
    if tuned:
        res["flash_tuned_env"] = tuned
    res.pop("params", None)
    return res


def bench_lm_decode() -> dict:
    """Autoregressive generation throughput: prefill + lax.scan KV-cache
    decode as ONE jitted program (models/lm_transformer.py generate).
    Decode is the HBM-bound regime — every step re-reads all params — so
    tokens/s, not MFU, is the honest metric. TPU-only like bench_lm_train."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.models import lm_transformer as lm

    model = lm.TransformerLM.create(
        jax.random.key(0),
        vocab=LM_VOCAB,
        max_seq=LM_SEQ,
        dim=LM_DIM,
        depth=LM_DEPTH,
        num_heads=LM_HEADS,
        compute_dtype="bfloat16",
    )
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(
            0, LM_VOCAB, size=(LM_BATCH, 128), dtype=np.int32
        )
    )
    max_new = 256
    # max_new=1 is prefill + one pick (zero decode steps); the delta to
    # max_new=256 is 255 pure decode steps — keeps prefill time out of
    # the decode rate
    def decode_rate(m):
        sec_prefill = _timed(
            lambda: lm.generate(m, prompt, max_new=1), iters=3
        )
        sec_full = _timed(
            lambda: lm.generate(m, prompt, max_new=max_new), iters=3
        )
        step_s = max(sec_full - sec_prefill, 1e-9) / (max_new - 1)
        return step_s, sec_prefill

    step_s, sec_prefill = decode_rate(model)
    # weight-only int8: decode re-reads all params every step (HBM-bound);
    # the measured side-by-side rate is the honest claim (whether the
    # weight stream halves rests on XLA fusing the convert into the dot).
    # The pallas variant streams the BLOCK weights as int8 by
    # construction (ops/int8_matmul); the tied-embedding logits matmul
    # (~1/4 of the per-step weight bytes, (V,1) row scales) takes the
    # XLA path in both legs — the e2e leg of mfu_sweep's decode_mm_* A/B
    qmodel = lm.quantize_for_decode(model)
    step_q, _ = decode_rate(qmodel)
    step_qp, _ = decode_rate(
        dataclasses.replace(qmodel, int8_kernel="pallas")
    )
    return {
        "decode_tokens_per_s": LM_BATCH / step_s,
        "ms_per_step": step_s * 1e3,
        "prefill_ms": sec_prefill * 1e3,
        "decode_int8_tokens_per_s": LM_BATCH / step_q,
        "decode_int8_pallas_tokens_per_s": LM_BATCH / step_qp,
    }


def bench_lm_step_telemetry() -> dict:
    """Tiny LM train loop driven through the live telemetry stream
    (observe/telemetry.py): steps/s p50/p95 from the per-step records
    plus the HBM peak watermark, so BENCH_*.json carries a perf
    trajectory for the TRAIN LOOP itself (per-step host overhead, step
    cadence), not just the single-step rates above. Deliberately small —
    it runs on the CPU fallback too."""
    import jax

    from keystone_tpu.models import lm_transformer as lm
    from keystone_tpu.observe import events as observe_events
    from keystone_tpu.observe import telemetry

    steps = 24

    def run_loop() -> list[dict]:
        corpus = lm.synthetic_corpus(4096, 256, seed=0)
        model = lm.TransformerLM.create(
            jax.random.key(0), vocab=256, max_seq=64, dim=64, depth=2,
            num_heads=4,
        )
        lm.train(model, corpus, steps=steps, batch=8, seq=64, lr=1e-3)
        sl = telemetry.active_step_log()
        recs = list(sl.records) if sl is not None else []
        return [r for r in recs if r.get("source") == "train"][-steps:]

    if observe_events.active() is not None:
        recs = run_loop()  # ambient run dir: records land there too
    else:
        with observe_events.run(workload="lm_step_telemetry"):
            recs = run_loop()
    # drop the first record (jit compile dominates it) from the cadence
    walls = [
        r["wall_s"] for r in recs if isinstance(r.get("wall_s"), (int, float))
    ]
    walls = walls[1:] or walls
    rates = [1.0 / w for w in walls if w > 0]
    p_rate = telemetry.percentiles(rates, (5, 50, 95))
    p_wall = telemetry.percentiles(walls, (50, 95))
    out: dict = {"steps": len(recs)}
    if p_rate:
        # p95 steps/s is the FAST tail; p5 is the stall tail
        out.update(
            steps_per_s_p50=round(p_rate[50], 3),
            steps_per_s_p95=round(p_rate[95], 3),
            steps_per_s_p5=round(p_rate[5], 3),
            step_ms_p50=round(p_wall[50] * 1e3, 2),
            step_ms_p95=round(p_wall[95] * 1e3, 2),
        )
    mfus = [r["mfu"] for r in recs if isinstance(r.get("mfu"), (int, float))]
    if mfus:
        out["mfu_p50"] = round(
            telemetry.percentiles(mfus, (50,))[50], 6
        )
    hbm = [
        r["hbm_peak_bytes"]
        for r in recs
        if isinstance(r.get("hbm_peak_bytes"), (int, float))
    ]
    if hbm:
        out["peak_hbm_bytes"] = int(max(hbm))
    return out


def bench_goodput() -> dict:
    """Where-the-time-went record from the span stream (observe/spans.py):
    goodput bucket shares + critical-path length for (a) the planned
    mnist demo apply streaming chunks through the staging engine and
    (b) a tiny LM train loop — so BENCH_*.json carries the stall/compute
    split the self-tuning planner will consume, not just headline rates.
    Deliberately small — runs on the CPU fallback too."""
    import jax

    from keystone_tpu import plan as plan_mod
    from keystone_tpu.models import lm_transformer as lm
    from keystone_tpu.observe import events as observe_events
    from keystone_tpu.observe import spans as observe_spans
    from keystone_tpu.serve.server import _fit_mnist_demo

    def summarize() -> dict:
        sl = observe_spans.active_span_log()
        recs = list(sl.records) if sl is not None else []
        g = observe_spans.goodput_summary(recs)
        return {
            "buckets": {
                b: row["share"] for b, row in g["buckets"].items()
            },
            "classified_s": g["total_s"],
            "critical_path_s": g["critical_path_s"],
            "spans": g["spans"],
        }

    out: dict = {}
    rng = np.random.default_rng(0)
    pipe, sample = _fit_mnist_demo(512, num_ffts=4)
    rows = rng.normal(size=(2048, sample.shape[1])).astype(np.float32)
    plan = plan_mod.plan_pipeline(
        pipe, sample=rows[:256], n_rows=rows.shape[0]
    )
    if not plan.chunk_size:
        # the probe workload is small enough that the planner may choose
        # an unchunked pass — force a chunked stream so the record shows
        # the staging engine's h2d/wait split, which is its point
        plan.chunk_size = 512
    jax.block_until_ready(plan_mod.run_plan(plan, rows))  # warm executables
    with observe_events.run(workload="goodput_mnist_planned"):
        jax.block_until_ready(plan_mod.run_plan(plan, rows))
        out["mnist_planned"] = summarize()

    corpus = lm.synthetic_corpus(4096, 256, seed=0)
    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=256, max_seq=64, dim=64, depth=2,
        num_heads=4,
    )
    with observe_events.run(workload="goodput_lm_train"):
        lm.train(model, corpus, steps=8, batch=8, seq=64, lr=1e-3)
        out["lm_train"] = summarize()
    return out


def bench_autotune(
    n_items: int = 48, decode_s: float = 0.004, compute_s: float = 0.001
) -> dict:
    """Self-tuning-runtime record (plan/tune.py + the ingest frontier):
    a synthetic HOST-BOUND stream — each item costs ``decode_s`` of
    host-side decode against ``compute_s`` of consumer work — run once
    static (one ingest worker, no controller) and once under the
    autotuner. The tuned run must attribute the dominant wait_host
    stall, raise the ingest-worker knob, and end with tuned throughput
    ≥ static and a lower wait_host share — the acceptance numbers this
    record carries. Pure host work: runs identically on the CPU
    fallback."""
    import time as _t

    from keystone_tpu.loaders.streaming import ingest_frontier
    from keystone_tpu.plan import tune as tune_mod

    def decode(i):
        _t.sleep(decode_s)
        return i

    def drive(workers) -> float:
        t0 = _t.perf_counter()
        for _ in ingest_frontier(
            range(n_items), decode, workers=workers, span_name=None
        ):
            _t.sleep(compute_s)
        return _t.perf_counter() - t0

    prev_enabled = tune_mod.active()
    try:
        tune_mod.configure(None)  # static: no controller, serial decode
        static_wall = drive(workers=1)

        tuner = tune_mod.Autotuner(
            tune_mod.TuneConfig(
                window_s=0.03, cooldown_s=0.03, min_share=0.2
            )
        )
        tuner.register(
            tune_mod.value_knob("ingest_workers", 1, lo=1, hi=8, scale=2)
        )
        tune_mod.configure(tuner)
        tuned_wall = drive(workers=None)  # None → the live knob
        tuner.tick(force=True)  # close out the final partial window
    finally:
        tune_mod.configure(prev_enabled)

    hist = list(tuner.history)
    waits = [
        h["shares"].get("wait_host", 0.0) for h in hist if h.get("shares")
    ]
    actions: dict[str, int] = {}
    for h in hist:
        a = h.get("action")
        if a:
            actions[a] = actions.get(a, 0) + 1
    return {
        "items": n_items,
        "decode_ms": decode_s * 1e3,
        "static_items_per_s": round(n_items / static_wall, 1),
        "tuned_items_per_s": round(n_items / tuned_wall, 1),
        "tuned_over_static": round(static_wall / tuned_wall, 2),
        "wait_host_share_first": round(waits[0], 4) if waits else None,
        "wait_host_share_last": round(waits[-1], 4) if waits else None,
        "final_ingest_workers": tuner.value("ingest_workers"),
        "windows": len(hist),
        "decisions": actions,
    }


def bench_obs_overhead(steps: int = 30, matmuls: int = 4) -> dict:
    """Fleet-observability overhead record (observe/collector.py): the
    SAME jitted step loop run bare, then fully instrumented — event
    sink + per-step telemetry writing a run dir that a LIVE collector
    tails (and whose /metrics it scrapes) every 100 ms from a
    background thread. The number this pins: whole-system observability
    — per-step records, file tailing, scraping, SLO evaluation — costs
    < 5% of throughput on the CPU fallback. Pure host+jit work, runs
    everywhere."""
    import tempfile
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    import jax
    import jax.numpy as jnp

    from keystone_tpu.observe import events as obs_events
    from keystone_tpu.observe import telemetry as obs_telemetry
    from keystone_tpu.observe.collector import Collector
    from keystone_tpu.serve.server import write_metrics_response

    rng = np.random.default_rng(0)
    # a chunky step (tens of ms on the CPU fallback): the question is
    # the collector's cost against a REAL training step, not against a
    # microbenchmark whose wall is all fixed per-step overhead
    w = rng.normal(size=(2048, 2048)).astype(np.float32) * 0.02
    x0 = rng.normal(size=(512, 2048)).astype(np.float32)

    @jax.jit
    def step_fn(x):
        for _ in range(matmuls):
            x = jnp.tanh(x @ w)
        return x

    x = jax.device_put(x0)
    jax.block_until_ready(step_fn(x))  # compile outside both timings
    flops = 2.0 * 512 * 2048 * 2048 * matmuls

    def run_loop(sl=None) -> float:
        t0 = time.perf_counter()
        for i in range(steps):
            t1 = time.perf_counter()
            jax.block_until_ready(step_fn(x))
            if sl is not None:
                sl.step(
                    step=i + 1,
                    loss=1.0,
                    tokens=256,
                    wall_s=time.perf_counter() - t1,
                    flops=flops,
                )
        return steps / (time.perf_counter() - t0)

    # bare best-of-2: the shared host's load varies; MAX is the honest
    # denominator (same rule as the CPU baselines)
    bare = max(run_loop() for _ in range(2))

    import shutil

    base = tempfile.mkdtemp(prefix="kst-obs-bench-")
    out_dir = tempfile.mkdtemp(prefix="kst-obs-collector-")

    class MetricsHandler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # noqa: D102 — quiet
            pass

        def do_GET(self):  # noqa: N802 — stdlib API
            write_metrics_response(self)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), MetricsHandler)
    mport = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    stop = threading.Event()
    # 0.5 s cadence: 10x the production default, slow enough that the
    # fsync'd federation publish isn't the workload (at 0.1 s it is)
    collector = Collector(
        out_dir,
        targets=[f"http://127.0.0.1:{mport}/metrics"],
        watch=[base],
        interval_s=0.5,
    )
    thread = threading.Thread(
        target=collector.run, args=(stop,), daemon=True
    )
    thread.start()
    try:
        with obs_events.run(base, pipeline="obs_overhead_bench"):
            sl = obs_telemetry.active_step_log()
            # warm the one-time telemetry imports (roofline pricing,
            # health monitor) outside the timing, then best-of-2 — the
            # same MAX rule the bare side and the CPU baselines use
            sl.step(step=0, loss=1.0, tokens=256, wall_s=1e-3, flops=flops)
            collected = max(run_loop(sl) for _ in range(2))
        stop.set()
        thread.join(timeout=10)
        final = collector.cycle()  # drain what the loop wrote last,
        # while the scrape endpoint is still up
    finally:
        stop.set()
        httpd.shutdown()
        httpd.server_close()
    store_points = len(collector.store.query())
    collector.close()
    for path in (base, out_dir):
        shutil.rmtree(path, ignore_errors=True)
    return {
        "steps": steps,
        "bare_steps_per_s": round(bare, 2),
        "collected_steps_per_s": round(collected, 2),
        "overhead_pct": round((bare - collected) / bare * 100.0, 2),
        "collector_cycles": collector.cycles,
        "store_points": store_points,
        "last_cycle": {
            k: final.get(k)
            for k in ("targets_ok", "targets_failed", "tailed_points")
        },
    }


def bench_refit_latency(
    n_base: int | None = None,
    chunk_rows: int | None = None,
    d_feats: int | None = None,
) -> dict:
    """Online-learning economics record (learn/ subsystem): wall for
    fold+finalize+swap of ONE new labeled chunk into accumulated
    streaming-fit state vs a full from-scratch retrain on the union
    corpus. The incremental path touches only the new rows (O(chunk·D²)
    fold + O(D³) finalize); the full path re-featurizes everything —
    the ratio is the whole point of the refit daemon. Runs on the CPU
    fallback too."""
    import tempfile

    import jax

    from keystone_tpu.core.pipeline import ChainedLabelEstimator, Pipeline
    from keystone_tpu.core.serialization import save_fitted
    from keystone_tpu.learn.swap import ModelSwapper
    from keystone_tpu.ops.linear import LinearMapEstimator
    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.ops.util import ClassLabelIndicators
    from keystone_tpu.plan import executor as _plan_exec
    from keystone_tpu.plan.fused_fit import plan_fit
    from keystone_tpu.serve.export import ExportedApply
    from keystone_tpu.serve.server import ServeApp

    on_cpu = jax.devices()[0].platform == "cpu"
    n0 = n_base or (32_768 if on_cpu else 262_144)
    m = chunk_rows or 4096
    d_in, k = 128, 10
    d = d_feats or (256 if on_cpu else 2048)
    rng = np.random.default_rng(11)
    x = rng.normal(size=(n0 + m, d_in)).astype(np.float32)
    y = ClassLabelIndicators(num_classes=k)(
        rng.integers(0, k, size=n0 + m).astype(np.int32)
    )
    y = np.asarray(y)
    feat = CosineRandomFeatures.create(d_in, d, jax.random.key(3))
    est = LinearMapEstimator(lam=1.0)
    chain = ChainedLabelEstimator(prefix=feat, est=est)
    plan = plan_fit(chain, x[:n0], y[:n0], chunk_size=4096)
    base_state = _plan_exec.fit_stream(plan, x[:n0], y[:n0])
    jax.block_until_ready(base_state.ata)

    def incremental():
        st = _plan_exec.fit_stream(
            plan, x[n0:], y[n0:], init_state=base_state
        )
        return est.fit_stats_finalize(st, widths=plan.fit.widths)

    def full_retrain():
        st = _plan_exec.fit_stream(plan, x, y)
        return est.fit_stats_finalize(st, widths=plan.fit.widths)

    inc_s = _timed(lambda: incremental().x, iters=3)
    full_s = _timed(lambda: full_retrain().x, iters=3)

    # the swap leg: publish the refreshed model and hot-swap it into a
    # live ServeApp (AOT re-export off the warm compile cache included
    # — that IS the swap cost a server pays)
    model = incremental()
    pipe = Pipeline.of(feat, model)
    app = ServeApp(
        exported=ExportedApply(
            pipe, x[:1], buckets=(8,), optimize=False
        ),
        deadline_ms=5.0,
        model_version="base",
    )
    swapper = ModelSwapper(app)
    app.swapper = swapper
    try:
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "refreshed.kst")
            save_fitted(pipe, path, version="refreshed")
            t0 = time.perf_counter()
            swapper.swap_to_path(path)
            swap_s = time.perf_counter() - t0
    finally:
        app.shutdown()
    return {
        "n_base_rows": n0,
        "chunk_rows": m,
        "d_features": d,
        "fold_finalize_s": round(inc_s, 4),
        "full_retrain_s": round(full_s, 4),
        "incremental_vs_full": round(full_s / inc_s, 2),
        "swap_s": round(swap_s, 4),
        "e2e_refresh_s": round(inc_s + swap_s, 4),
    }


def bench_serve_latency(
    n_requests: int = 48,
    fit_n: int = 512,
    max_new: int = 48,
    streams: int = 8,
) -> dict:
    """Online-serving record (serve/ subsystem): micro-batched request
    latency percentiles + batch-fill through the AOT-exported mnist demo
    pipeline, and continuous-batching decode aggregate-vs-single-stream
    tokens/s over the SAME workload (N prompts through a 1-slot pool vs
    an N-slot pool — the serialized and continuous schedules of the same
    token budget). Deliberately small — runs on the CPU fallback too."""
    import concurrent.futures
    import time as _time

    import jax

    from keystone_tpu.models.lm.model import TransformerLM
    from keystone_tpu.observe import metrics as observe_metrics
    from keystone_tpu.observe.telemetry import percentiles
    from keystone_tpu.serve.decode_loop import DecodeLoop
    from keystone_tpu.serve.export import ExportedApply
    from keystone_tpu.serve.queue import MicroBatcher
    from keystone_tpu.serve.server import _fit_mnist_demo

    out: dict = {}
    reg = observe_metrics.get_registry()
    rng = np.random.default_rng(0)

    # ---- request path: burst of concurrent /predict-shaped requests
    pipe, sample = _fit_mnist_demo(fit_n)
    exported = ExportedApply(pipe, sample, buckets=(1, 8, 32))
    out["cold_start_s"] = round(exported.cold_start_s, 3)
    snap0 = reg.snapshot()
    batcher = MicroBatcher(
        exported, buckets=exported.buckets, deadline_ms=10.0
    )
    row_shape = sample.shape[1:]
    reqs = [
        rng.normal(size=(int(rng.integers(1, 5)), *row_shape)).astype(
            np.float32
        )
        for _ in range(n_requests)
    ]
    lat: list[float] = []

    def one(rows):
        t0 = _time.perf_counter()
        batcher.submit(rows).result(timeout=120.0)
        return _time.perf_counter() - t0

    with concurrent.futures.ThreadPoolExecutor(max_workers=16) as pool:
        lat = list(pool.map(one, reqs))
    batcher.close(drain=True)
    snap1 = reg.snapshot()

    def delta(name):
        return (snap1.get(name) or 0) - (snap0.get(name) or 0)

    p = percentiles(lat, (50, 95))
    n_rows = delta("serve_rows")
    pad_rows = delta("serve_pad_rows")
    out.update(
        requests=n_requests,
        request_p50_ms=round(p[50] * 1e3, 2),
        request_p95_ms=round(p[95] * 1e3, 2),
        batches=int(delta("serve_batches")),
        batch_fill=round(n_rows / max(n_rows + pad_rows, 1), 4),
    )

    # ---- decode path: the same token budget, serialized vs continuous
    model = TransformerLM.create(
        jax.random.key(0), vocab=256, max_seq=160, dim=64, depth=2,
        num_heads=4,
    )
    prompts = [
        rng.integers(1, 256, size=int(rng.integers(4, 12)), dtype=np.int32)
        for _ in range(streams)
    ]

    def agg_rate(slots: int) -> float:
        loop = DecodeLoop(
            model, slots=slots, s_max=160, max_new=max_new,
            prefill_buckets=(16,),
        )
        loop.warm()
        t0 = _time.perf_counter()
        loop.run(prompts)
        wall = _time.perf_counter() - t0
        return loop.tokens_out / wall

    single = agg_rate(1)
    multi = agg_rate(streams)
    out.update(
        decode_single_stream_tokens_per_s=round(single, 1),
        decode_concurrent_tokens_per_s=round(multi, 1),
        decode_streams=streams,
        aggregate_vs_single=round(multi / single, 2),
    )
    return out


def bench_fleet_latency(
    n_requests: int = 48,
    replicas: int = 3,
    fit_n: int = 96,
    num_ffts: int = 2,
    compare_single: bool = True,
) -> dict:
    """Serving-fleet record (serve/fleet.py): aggregate throughput +
    request p50/p95 through the health-aware router over N real mnist
    replica processes vs a single replica, and the same burst with one
    replica SIGKILLed mid-run (`fleet.replica_kill` drill — the record
    pins zero client errors and the failover count). Replicas run on
    the CPU backend regardless of the bench host: N processes cannot
    share one chip, and the fleet's routing/failover economics are
    host-side anyway."""
    import concurrent.futures
    import time as _time

    from keystone_tpu.observe import metrics as observe_metrics
    from keystone_tpu.observe.telemetry import percentiles
    from keystone_tpu.resilience import faults as _flt
    from keystone_tpu.serve.fleet import Fleet

    reg = observe_metrics.get_registry()
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "KEYSTONE_SERVE_DEADLINE_MS": "5",
    }
    cmd = [
        sys.executable, "-m", "keystone_tpu", "serve", "mnist",
        "--port", "{port}", "--synthetic", str(fit_n),
        "--num-ffts", str(num_ffts), "--buckets", "1,4,8",
    ]
    rng = np.random.default_rng(0)
    reqs = [
        rng.normal(size=(int(rng.integers(1, 4)), 784))
        .astype(np.float32)
        .tolist()
        for _ in range(n_requests)
    ]

    def burst(fleet, kill_at=None):
        if kill_at is not None:
            _flt.configure(f"fleet.replica_kill:@{kill_at}:0")
        lat: list[float] = []
        errors = 0

        def one(rows):
            t0 = _time.perf_counter()
            fleet.forward("/predict", {"rows": rows})
            return _time.perf_counter() - t0

        t0 = _time.perf_counter()
        try:
            with concurrent.futures.ThreadPoolExecutor(
                max_workers=8
            ) as pool:
                for fut in [pool.submit(one, r) for r in reqs]:
                    try:
                        lat.append(fut.result(timeout=180.0))
                    except Exception:  # noqa: BLE001 — tallied
                        errors += 1
        finally:
            _flt.reset()
        return lat, errors, _time.perf_counter() - t0

    def run_tier(n, kill_drill=False):
        fleet = Fleet(
            cmd=cmd, n=n, env=env, poll_s=0.2, grace_s=15.0,
            boot_timeout_s=300.0, deadline_ms=20000.0, max_inflight=64,
        )
        t_boot = _time.perf_counter()
        try:
            fleet.start(wait_up=n, timeout=300.0)
            boot_s = _time.perf_counter() - t_boot
            lat, errors, wall = burst(fleet)
            p = percentiles(lat, (50, 95)) if lat else {50: 0.0, 95: 0.0}
            rec = {
                "boot_s": round(boot_s, 2),
                "request_p50_ms": round(p[50] * 1e3, 2),
                "request_p95_ms": round(p[95] * 1e3, 2),
                "requests_per_s": round(len(lat) / wall, 1) if wall else 0.0,
                "errors": errors,
            }
            if kill_drill:
                # the same burst again, killing a replica mid-run: the
                # router's rid counter has advanced, so key the drill
                # relative to what it will hand out next
                failover0 = reg.snapshot().get("fleet_failover", 0)
                # key the drill a third of the way into the burst,
                # relative to the next id the router will hand out
                kill_at = fleet.next_rid + max(len(reqs) // 3, 1)
                lat_k, errors_k, wall_k = burst(fleet, kill_at=kill_at)
                pk = (
                    percentiles(lat_k, (50, 95))
                    if lat_k
                    else {50: 0.0, 95: 0.0}
                )
                rec["kill_drill"] = {
                    "errors": errors_k,
                    "failover": int(
                        reg.snapshot().get("fleet_failover", 0) - failover0
                    ),
                    "request_p50_ms": round(pk[50] * 1e3, 2),
                    "request_p95_ms": round(pk[95] * 1e3, 2),
                    "requests_per_s": (
                        round(len(lat_k) / wall_k, 1) if wall_k else 0.0
                    ),
                }
            return rec
        finally:
            fleet.shutdown(grace_s=10.0)

    out: dict = {
        "replicas": replicas,
        "requests": n_requests,
        # said plainly in the record: these are host-side numbers
        "replica_platform": "cpu",
    }
    tier = run_tier(replicas, kill_drill=True)
    out.update(tier)
    if compare_single:
        single = run_tier(1)
        out["single_replica"] = {
            k: single[k]
            for k in (
                "request_p50_ms", "request_p95_ms", "requests_per_s",
            )
        }
        if single["requests_per_s"]:
            out["aggregate_vs_single"] = round(
                out["requests_per_s"] / single["requests_per_s"], 2
            )
    return out


def bench_chaos_drill() -> dict:
    """Composed-fault recovery record (resilience/chaos.py): the canned
    fleet game-day campaign — replica SIGKILL + conn reset + slow
    replica injected mid-burst against 3 CPU-pinned stub replicas —
    run end to end through `chaos run`'s engine. The record pins the
    client-visible outcome (zero failures), the failover count, and
    the campaign wall, so a regression in composed-fault recovery
    fails the bench gate exactly like a perf number."""
    import tempfile as _tempfile
    import time as _time

    from keystone_tpu.resilience.chaos import run_campaign

    report = _tempfile.mkdtemp(prefix="keystone-bench-chaos-")
    t0 = _time.perf_counter()
    try:
        result = run_campaign("fleet_game_day", report_dir=report)
    except Exception as e:
        # a crashed campaign (boot failure, OSError) must still point
        # the operator at whatever evidence landed on disk
        raise RuntimeError(
            f"chaos_drill: campaign crashed ({e!r}); partial evidence "
            f"under {report}"
        ) from e
    wall = _time.perf_counter() - t0
    w = result.get("workload") or {}
    out = {
        "campaign": result["campaign"],
        "passed": bool(result["passed"]),
        "invariants_ok": sum(
            1 for v in result["invariants"] if v["ok"]
        ),
        "invariants_total": len(result["invariants"]),
        "client_ok": int(w.get("client_ok", 0)),
        "client_failures": int(w.get("client_failures", 0)),
        "failover": next(
            (
                float(v.get("evidence", {}).get("failover") or 0.0)
                for v in result["invariants"]
                if v["name"].startswith("failover_fired")
            ),
            0.0,
        ),
        "request_p95_ms": w.get("request_p95_ms", 0.0),
        "requests_per_s": (
            round(w.get("client_ok", 0) / w["wall_s"], 1)
            if w.get("wall_s")
            else 0.0
        ),
        "campaign_wall_s": round(result.get("wall_s", wall), 2),
    }
    if not result["passed"]:
        out["failed_invariants"] = [
            v["name"] for v in result["invariants"] if not v["ok"]
        ]
        raise RuntimeError(
            f"chaos_drill: fleet game day FAILED "
            f"({out['failed_invariants']}); evidence preserved under "
            f"{report}"
        )
    import shutil as _shutil

    _shutil.rmtree(report, ignore_errors=True)
    return out


def bench_sift() -> dict:
    """Dense-SIFT featurize, device (XLA) path, with the C++ host kernel
    (native/dsift.cpp, the VLFeat-shim parity fallback) as baseline."""
    import jax

    from keystone_tpu.ops.sift import SIFTExtractor

    rng = np.random.default_rng(4)
    imgs = rng.random((SIFT_N, SIFT_HW, SIFT_HW)).astype(np.float32)
    import jax.numpy as jnp

    batch = jnp.asarray(imgs)
    dev = SIFTExtractor()
    fn = jax.jit(lambda b: dev(b))
    sec = _timed(lambda: fn(batch), iters=2)
    out = {"images_per_s": SIFT_N / sec}
    try:
        # call the native kernel DIRECTLY: SIFTExtractor(backend="native")
        # silently falls back to the device path when the library is
        # unavailable, which would make this a device-vs-device ratio
        from keystone_tpu.native import native_dsift

        sub = imgs[:SIFT_NATIVE_SUBSET]
        if native_dsift(sub) is not None:  # bind/warm; None = no library
            t0 = time.perf_counter()
            native_dsift(sub)
            host_sec = (time.perf_counter() - t0) / SIFT_NATIVE_SUBSET
            out["vs_native_host"] = (SIFT_N / sec) * host_sec
    except Exception:  # noqa: BLE001 — no native toolchain: device only
        pass
    return out


def bench_cpu_numpy(
    labels: np.ndarray, data: np.ndarray, full_n: int
) -> float:
    """Same MNIST math in numpy/BLAS (single host CPU baseline). O(N)
    phases are timed on the given subset and scaled to ``full_n``; the
    O(d^3) solve is timed once and added unscaled."""
    n = len(labels)
    rng = np.random.default_rng(7)
    signs = rng.choice([-1.0, 1.0], size=(NUM_FFTS, IMAGE_SIZE)).astype(
        np.float32
    )
    onehot = -np.ones((n, 10), np.float32)
    onehot[np.arange(n), labels] = 1.0

    t0 = time.perf_counter()
    blocks = []
    for f in range(NUM_FFTS):
        padded = np.zeros((n, 1024), np.float32)
        padded[:, :IMAGE_SIZE] = data * signs[f]
        feat = np.maximum(np.real(np.fft.rfft(padded, axis=1))[:, :512], 0.0)
        blocks.append(feat)
    a = np.concatenate(blocks, axis=1)
    a -= a.mean(axis=0)
    b = onehot - onehot.mean(axis=0)
    ata = a.T @ a + LAM * np.eye(a.shape[1], dtype=np.float32)
    atb = a.T @ b
    t_linear = time.perf_counter() - t0
    np.linalg.solve(ata, atb)
    t_solve = time.perf_counter() - t0 - t_linear
    return full_n / (t_linear * (full_n / n) + t_solve)


def bench_cpu_cifar_conv() -> float:
    """CIFAR conv featurize in numpy im2col/BLAS, scaled to CIFAR_N."""
    rng = np.random.default_rng(2)
    n = CIFAR_CPU_SUBSET
    k, f = CIFAR_PATCH, CIFAR_FILTERS
    d = k * k * 3
    batch = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
    filters = rng.normal(size=(f, d)).astype(np.float32)
    means = rng.normal(size=(d,)).astype(np.float32)
    oh = 32 - k + 1
    t0 = time.perf_counter()
    pat = np.empty((n, oh, oh, d), np.float32)
    for dy in range(k):
        for dx in range(k):
            pat[..., (dy * k + dx) * 3 : (dy * k + dx + 1) * 3] = batch[
                :, dy : dy + oh, dx : dx + oh, :
            ]
    mat = pat.reshape(-1, d)
    mu = mat.mean(1, keepdims=True)
    cent = mat - mu
    var = (cent * cent).sum(1, keepdims=True) / (d - 1)
    mat = cent / np.sqrt(var + 10.0) - means
    out = (mat @ filters.T).reshape(n, oh, oh, f)
    # rectify + 14/13 pool (cheap; include for parity of work)
    np.maximum(out - 0.25, 0.0) + np.maximum(-out - 0.25, 0.0)
    sec = time.perf_counter() - t0
    return n / sec


def _device_peak() -> float | None:
    import jax

    from keystone_tpu.observe.report import peak_flops_for

    return peak_flops_for(jax.devices()[0].device_kind)


def main(argv: list[str] | None = None) -> int:
    global N_TRAIN, CIFAR_N, TIMIT_N, TIMIT_D, SIFT_N

    argv = list(sys.argv[1:] if argv is None else argv)
    if "--check" in argv:
        # the perf-regression gate: pure JSON compare, no jax, no bench
        return check_main(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from keystone_tpu.core.runtime import init_backend

    # the platform rule: unset JAX_PLATFORMS means TPU, and no TPU
    # raises here with the backend's own error (non-zero exit, no line)
    device = init_backend()
    on_cpu = device["platform"] == "cpu"
    if on_cpu:
        # an asked-for CPU run debugs the harness: reduced sizes (rates
        # stay per-sample) and no chip-sized LM workloads
        N_TRAIN = 12_000
        CIFAR_N = 512
        TIMIT_N = 8_192
        TIMIT_D = 512
        SIFT_N = 4
    errors: dict[str, str] = {}

    def section(name, fn):
        """Run one workload; a raise is recorded, reported on stderr and
        turns the exit code non-zero once the line is printed — the
        other sections still run, so one OOM does not cost the rest."""
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — recorded, exit code 1
            errors[name] = f"{type(e).__name__}: {str(e)[:200]}"
            print(f"# section {name} failed: {errors[name]}", file=sys.stderr)
            return None

    def chip_section(name, fn):
        return None if on_cpu else section(name, fn)

    labels, data = _synthetic(N_TRAIN)
    mnist = section("mnist", lambda: bench_mnist(labels, data))
    cifar = section("cifar_conv", bench_cifar_conv)
    weighted = section("weighted_timit", bench_weighted)
    sift = section("sift", bench_sift)
    w_im = chip_section("weighted_imagenet", bench_weighted_imagenet)
    lm = chip_section("lm_train", bench_lm_train)
    lm_dec = chip_section("lm_decode", bench_lm_decode)
    lm_long = chip_section("lm_longctx", bench_lm_longctx)
    floor_ms = section("dispatch_floor", dispatch_floor_ms)
    # best-of-3 CPU baselines: the shared host's load varies between
    # sessions (~3x observed across rounds); the MAX rate is the honest
    # comparison point and the stable one
    cpu_rate = max(
        bench_cpu_numpy(labels[:CPU_SUBSET], data[:CPU_SUBSET], N_TRAIN)
        for _ in range(3)
    )
    cpu_cifar = max(bench_cpu_cifar_conv() for _ in range(3))
    cpu_weighted = max(bench_cpu_weighted() for _ in range(3))
    peak = _device_peak()
    result: dict = {
        "metric": "mnist_random_fft featurize+fit samples/sec",
        "unit": "samples/s",
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "num_devices": device["count"],
        "git_sha": _git_sha(),
        "baseline_samples_per_s": round(cpu_rate, 1),
        "baseline": "numpy/BLAS single-host CPU, same workloads "
        "(reference publishes no numbers; see BASELINE.md)",
    }

    def per_chip(key: str, tflops: float) -> None:
        # per-chip TFLOP/s is a device metric: a CPU run prints none
        if not on_cpu:
            result[key] = round(tflops, 2)

    if mnist is not None:
        result["value"] = round(mnist["samples_per_s"], 1)
        result["vs_baseline"] = round(mnist["samples_per_s"] / cpu_rate, 2)
        result["solver_gflops"] = round(mnist["solver_gflops"], 1)
        per_chip("solver_tflops_per_chip", mnist["solver_tflops_per_s"])
        per_chip("e2e_tflops_per_chip", mnist["e2e_tflops_per_s"])
        # per-node operator breakdown (observe subsystem): wall time per
        # pipeline node plus compiler-modeled FLOPs/bytes when available
        result["mnist_per_node"] = mnist.get("per_node", {})
        # planned-vs-naive execution of the same pipeline (plan
        # subsystem): the planner's decisions + measured delta + the
        # shared-prefix fit's eliminated featurization pass
        result["mnist_planner"] = mnist.get("planner", {})
    if cifar is not None:
        result["cifar_conv_samples_per_s"] = round(cifar["samples_per_s"], 1)
        per_chip("cifar_conv_tflops_per_chip", cifar["conv_tflops_per_s"])
        result["cifar_conv_vs_baseline"] = round(
            cifar["samples_per_s"] / cpu_cifar, 2
        )
    if weighted is not None:
        result["weighted_timit_samples_per_s"] = round(
            weighted["samples_per_s"], 1
        )
        per_chip("weighted_timit_tflops_per_chip", weighted["tflops_per_s"])
        result["weighted_timit_vs_baseline"] = round(
            weighted["samples_per_s"] / cpu_weighted, 2
        )
    if sift is not None:
        result["sift_images_per_s"] = round(sift["images_per_s"], 2)
        if "vs_native_host" in sift:
            result["sift_vs_native_host"] = round(sift["vs_native_host"], 2)
    if floor_ms is not None:
        # launch latency embedded in every per-step time above — see
        # ROOFLINE.md "dispatch floor"
        result["dispatch_floor_ms"] = round(floor_ms, 2)
    # the subsystem records, each a section of its own:
    for key, fn in (
        # train-loop telemetry trajectory (observe/telemetry.py):
        # per-step cadence percentiles + HBM watermark
        ("lm_step_telemetry", bench_lm_step_telemetry),
        # online-serving record (serve/): micro-batched request latency
        # + batch fill, continuous-batching decode aggregate vs
        # single-stream tokens/s
        ("serve_latency", bench_serve_latency),
        # serving-fleet record (serve/fleet.py): N replicas vs 1 through
        # the router + the replica-kill drill; replicas are CPU-pinned
        # on purpose (the record says so)
        ("fleet_latency", bench_fleet_latency),
        # goodput breakdown (observe/spans.py): bucket shares + critical
        # path for the planned mnist run and the LM loop
        ("goodput", bench_goodput),
        # self-tuning record (plan/tune.py + ingest frontier): a
        # synthetic host-bound stream static vs autotuned; host work
        ("autotune", bench_autotune),
        # composed-fault recovery gate (resilience/chaos.py): the canned
        # fleet game day on stub replicas; host work
        ("chaos_drill", bench_chaos_drill),
        # fleet-observability overhead (observe/collector.py): the same
        # jitted loop bare vs instrumented with a live collector
        ("obs_overhead", bench_obs_overhead),
        # fused streaming-fit record (plan/fused_fit.py): streamed-vs-
        # materialized fit delta + chosen Gram operator + rows/s
        ("solver_mfu", bench_solver_mfu),
        # online-learning record (learn/): fold+finalize+swap of one new
        # chunk vs full retrain from scratch
        ("refit_latency", bench_refit_latency),
    ):
        rec = section(key, fn)
        if rec is not None:
            result[key] = rec
    if w_im is not None:
        result["weighted_imagenet_samples_per_s"] = round(
            w_im["samples_per_s"], 1
        )
        result["weighted_imagenet_fit_s"] = round(w_im["fit_s"], 2)
        per_chip("weighted_imagenet_tflops_per_chip", w_im["tflops_per_s"])
    if lm is not None:
        result["lm_train_tokens_per_s"] = round(lm["tokens_per_s"], 1)
        per_chip("lm_train_tflops_per_chip", lm["tflops_per_s"])
        if "tuned_config" in lm:
            result["lm_train_tuned_config"] = lm["tuned_config"]
        if peak is not None:
            result["lm_train_mfu_vs_bf16_peak"] = round(
                lm["tflops_per_s"] * 1e12 / peak, 4
            )
    if lm_dec is not None:
        result["lm_decode_tokens_per_s"] = round(
            lm_dec["decode_tokens_per_s"], 1
        )
        result["lm_decode_int8_tokens_per_s"] = round(
            lm_dec["decode_int8_tokens_per_s"], 1
        )
        result["lm_decode_int8_pallas_tokens_per_s"] = round(
            lm_dec["decode_int8_pallas_tokens_per_s"], 1
        )
    if lm_long is not None:
        result["lm_longctx16k_tokens_per_s"] = round(
            lm_long["tokens_per_s"], 1
        )
        per_chip("lm_longctx16k_tflops_per_chip", lm_long["tflops_per_s"])
    if peak is not None and mnist is not None and cifar is not None:
        # "est": featurize FLOPs are an analytic estimate (cosine gemm
        # term only) — measured time, modeled FLOPs. The solver-phase
        # MFU is fully measured-FLOPs and kept separately.
        result["mfu_est_vs_bf16_peak"] = round(
            max(
                mnist["e2e_tflops_per_s"], cifar["conv_tflops_per_s"]
            )
            * 1e12
            / peak,
            4,
        )
        result["mfu_solver_vs_bf16_peak"] = round(
            mnist["solver_tflops_per_s"] * 1e12 / peak, 4
        )
    if errors:
        result["errors"] = errors
    try:
        # route the bench record through the structured event log too,
        # so a KEYSTONE_OBSERVE_DIR run dir carries the full artifact —
        # but never let observability discard a completed bench run
        from keystone_tpu.observe import events as observe_events

        log = observe_events.active()
        if log is not None:
            log.emit("bench", result=result)
    except Exception as e:  # noqa: BLE001
        print(f"# bench event-log emit failed: {e!r}", file=sys.stderr)
    print(json.dumps(result))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
