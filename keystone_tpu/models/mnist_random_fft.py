"""MNIST random-FFT pipeline — the framework's minimum end-to-end slice.

Rebuild of the reference's ``pipelines/images/mnist/MnistRandomFFT.scala``:
random-sign flip → padded FFT → rectify, ``num_ffts`` independent draws
grouped into feature batches of ``block_size`` columns (512 FFT features per
draw on 28×28 inputs), solved with block least squares, argmax classified,
multiclass-evaluated.

TPU shape of the same computation: each feature batch is one jitted
chain over the sharded (N, 784) batch; the solver contracts Grams over the
mesh "data" axis. The whole pipeline is pure jnp — no native kernels.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.core.config import arg, parse_config
from keystone_tpu.core.logging import get_logger
from keystone_tpu.core.pipeline import Pipeline, Transformer
from keystone_tpu.core.treenode import treenode
from keystone_tpu.loaders.csv_loader import load_labeled_csv
from keystone_tpu.loaders.labeled import LabeledData
from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
from keystone_tpu.ops.stats import LinearRectifier, PaddedFFT, RandomSignNode
from keystone_tpu.ops.util import ClassLabelIndicators, MaxClassifier, ZipVectors
from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.observe import events as observe_events
from keystone_tpu.parallel.mesh import create_mesh, shard_batch

logger = get_logger("keystone_tpu.models.mnist_random_fft")

NUM_CLASSES = 10
IMAGE_SIZE = 784  # 28 x 28
FFT_FEATURES = 512  # PaddedFFT output dim for 784 → next pow2 1024 → half


def fft_features(image_size: int) -> int:
    """PaddedFFT output width for a given input dim: next_pow2 // 2."""
    n = 1 << max(int(np.ceil(np.log2(image_size))), 0) if image_size > 1 else 1
    return n // 2


@dataclasses.dataclass
class MnistRandomFFTConfig:
    """MNIST random-FFT workload (reference MnistRandomFFTConfig)."""

    train_location: str = arg(default="", help="train csv (label first, 1-indexed)")
    test_location: str = arg(default="", help="test csv")
    num_ffts: int = arg(default=200, help="number of random FFT draws")
    block_size: int = arg(default=2048, help="solver block size (multiple of 512)")
    lam: float = arg(default=0.0, help="L2 regularization")
    lam_sweep: str = arg(
        default="",
        help="comma-separated λ list: fit the whole ridge path at shared-"
        "Gram cost, pick the best on a held-out 10%% of train, refit on "
        "all of train at that λ (overrides --lam)",
    )
    seed: int = arg(default=0)
    synthetic: int = arg(
        default=0, help="if > 0, run on N synthetic samples instead of csvs"
    )


def build_batch_featurizers(
    num_ffts: int, block_size: int, seed: int, image_size: int = IMAGE_SIZE
) -> list[list[Pipeline]]:
    """Group ``num_ffts`` (sign → fft → relu) chains into batches whose
    concatenated width is ``block_size`` (last batch may be smaller)."""
    ffts_per_batch = max(block_size // fft_features(image_size), 1)
    keys = jax.random.split(jax.random.key(seed), num_ffts)
    chains = [
        RandomSignNode.create(image_size, keys[i]) >> PaddedFFT() >> LinearRectifier()
        for i in range(num_ffts)
    ]
    return [
        chains[i : i + ffts_per_batch]
        for i in range(0, num_ffts, ffts_per_batch)
    ]


@jax.jit
def _featurize_batch(chains: tuple, data):
    return ZipVectors()([chain(data) for chain in chains])


@treenode
class FeaturizerBank(Transformer):
    """The full random-FFT featurizer as one Transformer: applies every
    feature batch and returns the list of (N, ≤block_size) blocks.

    Being a treenode Transformer lets the whole featurize+fit run as a
    single traced program via ``ChainedLabelEstimator.fit_fused`` — the
    block solver consumes the block list directly, so featurize output
    never round-trips through a host dispatch boundary.
    """

    batches: tuple  # tuple of tuples of (sign → fft → relu) Pipelines

    @staticmethod
    def create(
        num_ffts: int, block_size: int, seed: int, image_size: int = IMAGE_SIZE
    ) -> "FeaturizerBank":
        groups = build_batch_featurizers(num_ffts, block_size, seed, image_size)
        return FeaturizerBank(batches=tuple(tuple(g) for g in groups))

    def __call__(self, data):
        return featurize(self.batches, data)


def _sign_fft_relu_parts(chain):
    """Match the ``RandomSignNode >> PaddedFFT >> LinearRectifier`` shape;
    returns (signs, fft_impl, alpha, max_val) or None."""
    nodes = getattr(chain, "nodes", ())
    if len(nodes) != 3:
        return None
    s, f, r = nodes
    if not (
        isinstance(s, RandomSignNode)
        and isinstance(f, PaddedFFT)
        and isinstance(r, LinearRectifier)
    ):
        return None
    return s.signs, f.impl, r.alpha, r.max_val


@functools.partial(jax.jit, static_argnames=("n", "alpha", "max_val"))
def _featurize_fused(signs_mat, data, n: int, alpha: float, max_val: float):
    """All chains of one feature batch as ONE gemm: the sign flip is a
    diagonal on the gemm's contraction side, so k chains fold into
    ``relu(X @ [diag(s_1)C | … | diag(s_k)C])`` — one MXU pass over the
    batch instead of k (reads X once; wider output tile)."""
    from keystone_tpu.ops.stats import _cos_matrix

    d = data.shape[-1]
    cos = _cos_matrix(d, n, str(data.dtype))  # (d, n//2)
    # build w directly in (d, k·n/2) chain-major layout (no transpose:
    # a transposed operand can drag a copy or refuse a clean gemm tiling)
    w = (signs_mat.T[:, :, None] * cos[:, None, :]).reshape(d, -1)
    # materialize w BEFORE the gemm: without the barrier XLA may fuse the
    # signs x cos construction into the dot's RHS loads, recomputing it
    # per k-tile, at equal nominal FLOPs
    w = jax.lax.optimization_barrier(w)
    return jnp.maximum(max_val, data @ w - alpha)


def featurize(batch_featurizers: list[list[Pipeline]], data) -> list:
    """Apply each batch of chains → list of (N, ≤block_size) feature blocks.

    When a batch is all (sign → fft → relu) chains and the FFT resolves
    to the matmul backend (TPU), the whole batch runs as one fused gemm;
    identical values either way (the matmul backend IS the fft values).
    """
    from keystone_tpu.ops.flash_attention import on_tpu

    out = []
    for chains in batch_featurizers:
        parts = [_sign_fft_relu_parts(c) for c in chains]
        fusable = all(p is not None for p in parts) and len(parts) > 0
        if fusable:
            signs, impls, alphas, maxvals = zip(*parts)
            fusable = (
                len(set(alphas)) == 1
                and len(set(maxvals)) == 1
                and all(i in ("auto", "matmul") for i in impls)
                and (on_tpu() or all(i == "matmul" for i in impls))
            )
        if fusable:
            d = signs[0].shape[-1]
            n = 2 * fft_features(d)
            out.append(
                _featurize_fused(
                    jnp.stack(signs), data, n, alphas[0], maxvals[0]
                )
            )
        else:
            out.append(_featurize_batch(tuple(chains), data))
    return out


def _load(conf: MnistRandomFFTConfig, which: str) -> LabeledData:
    if conf.synthetic:
        n = conf.synthetic if which == "train" else max(conf.synthetic // 6, 1)
        rng = np.random.default_rng(0 if which == "train" else 1)
        labels = rng.integers(0, NUM_CLASSES, size=n).astype(np.int32)
        # class-dependent means (shared across splits) so the linear model
        # has signal to find
        centers = (
            np.random.default_rng(42)
            .normal(size=(NUM_CLASSES, IMAGE_SIZE))
            .astype(np.float32)
        )
        data = centers[labels] + rng.normal(size=(n, IMAGE_SIZE)).astype(np.float32)
        return LabeledData(labels=labels, data=data)
    path = conf.train_location if which == "train" else conf.test_location
    return _load_mnist_csv(path)


def _load_mnist_csv(path: str) -> LabeledData:
    from keystone_tpu.loaders.idx import (
        guess_labels_path,
        is_idx_path,
        load_labeled_idx,
    )

    if is_idx_path(path):
        # upstream MNIST ubyte distribution (0-indexed labels); labels
        # file located by the conventional sibling name
        labels = guess_labels_path(path)
        if labels is None:
            raise FileNotFoundError(
                f"{path} looks like an IDX images file but no labels "
                "sibling (…labels-idx1…) was found next to it"
            )
        return load_labeled_idx(path, labels)
    # the reference's MNIST csvs carry 1-indexed labels (MnistRandomFFT.scala)
    return load_labeled_csv(path, label_offset=1)


def run(conf: MnistRandomFFTConfig, mesh=None) -> dict:
    if mesh is None and len(jax.devices()) > 1:
        mesh = create_mesh()
    t0 = time.perf_counter()

    train = _load(conf, "train")
    test = _load(conf, "test")
    n_train, n_test = len(train), len(test)

    train_x = shard_batch(train.data, mesh)
    test_x = shard_batch(test.data, mesh)
    train_y = np.zeros(train_x.shape[0], np.int32)
    train_y[:n_train] = train.labels
    label_indicators = ClassLabelIndicators(num_classes=NUM_CLASSES)(train_y)

    batch_featurizers = build_batch_featurizers(
        conf.num_ffts,
        conf.block_size,
        conf.seed,
        # width from the data, not the MNIST constant — the reference's
        # CsvDataLoader accepts any row width (CsvDataLoader.scala:69-82)
        image_size=train.data.shape[-1],
    )
    t_load = time.perf_counter()

    from keystone_tpu import plan as plan_mod

    # KEYSTONE_PLAN: the TRAIN fit streams — featurize + normal-equation
    # accumulation fused into one jitted chunk step by the planner
    # (plan/fused_fit.py), so the feature blocks are never materialized
    # for the fit; the λ-sweep and eval paths still need them resident.
    streamed_fit = plan_mod.enabled() and not conf.lam_sweep
    # ONE bank object for the fit, the train eval, and the test pass —
    # planner prefix sharing keys on node identity
    bank = (
        FeaturizerBank(batches=tuple(tuple(g) for g in batch_featurizers))
        if plan_mod.enabled()
        else None
    )
    train_blocks = None
    if not streamed_fit:
        train_blocks = jax.block_until_ready(
            featurize(batch_featurizers, train_x)
        )
    t_feat = time.perf_counter()

    lam = conf.lam
    if conf.lam_sweep:
        from keystone_tpu.evaluation.model_selection import (
            holdout_lambda_sweep,
        )

        report = holdout_lambda_sweep(
            BlockLeastSquaresEstimator(
                block_size=conf.block_size, num_iter=1
            ),
            train_blocks,
            label_indicators,
            train_y,
            conf.lam_sweep,
            n_train=n_train,
            num_classes=NUM_CLASSES,
        )
        lam = report["best_lam"]
        logger.info(
            "lambda sweep %s -> val errors %s; refitting at best lam=%g",
            report["lams"],
            [round(e, 4) for e in report["val_errors"]],
            lam,
        )
    est = BlockLeastSquaresEstimator(
        block_size=conf.block_size, num_iter=1, lam=lam
    )
    if streamed_fit:
        from keystone_tpu.core.pipeline import ChainedLabelEstimator

        fitted_fit = plan_mod.fit_streaming(
            ChainedLabelEstimator(prefix=bank, est=est),
            train_x,
            label_indicators,
            n_valid=n_train,
            mesh=mesh,
        )
        model = jax.block_until_ready(fitted_fit[-1])
    else:
        model = jax.block_until_ready(
            est.fit(train_blocks, label_indicators, n_valid=n_train)
        )
    t_fit = time.perf_counter()

    evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)
    classify = MaxClassifier()

    errors: dict[str, float] = {}

    def streaming_eval(name: str, labels: np.ndarray, n_valid: int):
        def cb(partial_pred):
            metrics = evaluator(classify(partial_pred), labels, n_valid=n_valid)
            errors[name] = metrics.error
            logger.info("%s error so far: %.2f%%", name, 100 * metrics.error)

        return cb

    if streamed_fit:
        # blocks were never materialized: the train error comes from the
        # same planned apply pass the test pass uses
        pred = plan_mod.execute(
            Pipeline.of(bank, model, MaxClassifier()), train_x, mesh=mesh
        )
        errors["train"] = evaluator(pred, train_y, n_valid=n_train).error
        logger.info(
            "train error (planned): %.2f%%", 100 * errors["train"]
        )
    else:
        model.apply_and_evaluate(
            train_blocks, streaming_eval("train", train_y, n_train)
        )
    test_y = np.zeros(test_x.shape[0], np.int32)
    test_y[:n_test] = test.labels

    if plan_mod.enabled():
        # KEYSTONE_PLAN: the test pass runs through the cost-based
        # planner's executor — one planned apply pipeline (featurizer
        # bank → block model → argmax), jitted segments, chunked with
        # bounded in-flight dispatch when the plan says so, and — with a
        # mesh — dispatched data-sharded so the pass runs as one SPMD
        # program per segment. Predictions are identical to the block
        # path; only the execution differs.
        pred = plan_mod.execute(
            Pipeline.of(bank, model, MaxClassifier()), test_x, mesh=mesh
        )
        errors["test"] = evaluator(pred, test_y, n_valid=n_test).error
        logger.info("test error (planned): %.2f%%", 100 * errors["test"])
    else:
        test_blocks = featurize(batch_featurizers, test_x)
        model.apply_and_evaluate(
            test_blocks, streaming_eval("test", test_y, n_test)
        )
    t_end = time.perf_counter()

    ev = observe_events.active()
    if ev is not None:
        for phase, wall in (
            ("load", t_load - t0),
            ("featurize", t_feat - t_load),
            ("fit", t_fit - t_feat),
            ("eval", t_end - t_fit),
        ):
            ev.emit("phase", phase=phase, wall_s=wall)
        try:
            _record_observability(ev, batch_featurizers, model, test_x)
        except Exception as e:  # noqa: BLE001 — observability must not
            # fail a pipeline run that already trained and evaluated
            logger.warning("observability recording failed: %r", e)

    result = {
        "train_error": errors["train"],
        "test_error": errors["test"],
        "n_train": n_train,
        "n_test": n_test,
        "load_s": t_load - t0,
        "featurize_s": t_feat - t_load,
        "fit_s": t_fit - t_feat,
        "total_s": t_end - t0,
        "train_samples_per_s": n_train / (t_fit - t_load),
    }
    logger.info(
        "MnistRandomFFT: train err %.2f%%, test err %.2f%%, "
        "featurize+fit %.1f samples/s",
        100 * result["train_error"],
        100 * result["test_error"],
        result["train_samples_per_s"],
    )
    return result


def _record_observability(ev, batch_featurizers, model, test_x) -> None:
    """Per-node wall-time events + compiler cost profiles for the fitted
    apply pipeline (featurizer bank → block model → argmax), recorded on
    a bounded probe batch so observability cost stays a small constant.
    This is the KeystoneML operator-profile sample for this pipeline."""
    from keystone_tpu.observe.cost import record_pipeline_profile

    bank = FeaturizerBank(batches=tuple(tuple(g) for g in batch_featurizers))
    pipe = Pipeline.of(bank, model, MaxClassifier())
    probe = test_x[: min(2048, test_x.shape[0])]
    record_pipeline_profile(pipe, probe, save_dir=ev.run_dir)


def main(argv=None) -> dict:
    conf = parse_config(MnistRandomFFTConfig, argv)
    if not conf.synthetic and not (conf.train_location and conf.test_location):
        raise SystemExit("need --train-location AND --test-location, or --synthetic N")
    return run(conf)


if __name__ == "__main__":
    main()
