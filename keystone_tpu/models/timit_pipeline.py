"""TIMIT phoneme classification
(reference ``pipelines/speech/TimitPipeline.scala``):
440-dim pre-featurized frames → ``num_cosines`` batches of 4096 cosine
random features (gaussian or cauchy W), each standard-scaled → block least
squares over the feature batches with ``num_epochs`` BCD passes → argmax →
multiclass eval (147 classes)."""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import numpy as np

from keystone_tpu.core.config import arg, parse_config
from keystone_tpu.core.logging import get_logger
from keystone_tpu.core.pipeline import Pipeline, Transformer
from keystone_tpu.core.treenode import treenode
from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.loaders.labeled import LabeledData
from keystone_tpu.loaders.timit import NUM_CLASSES, TIMIT_DIMENSION, load_timit_split
from keystone_tpu.observe.spans import force, span
from keystone_tpu.ops.linear import BlockLeastSquaresEstimator
from keystone_tpu.ops.stats import CosineRandomFeatures, StandardScaler
from keystone_tpu.ops.util import ClassLabelIndicators, MaxClassifier
from keystone_tpu.parallel.mesh import create_mesh, shard_batch

logger = get_logger("keystone_tpu.models.timit")


@dataclasses.dataclass
class TimitConfig:
    """TIMIT workload (reference TimitConfig: 50 batches x 4096 cosine
    features, gamma 0.0555, 5 epochs)."""

    train_data_location: str = arg(default="")
    train_labels_location: str = arg(default="")
    test_data_location: str = arg(default="")
    test_labels_location: str = arg(default="")
    num_cosines: int = arg(default=50, help="number of 4096-wide batches")
    cosine_features: int = arg(default=4096)
    gamma: float = arg(default=0.05555)
    rf_type: str = arg(default="gaussian", choices=("gaussian", "cauchy"))
    lam: float = arg(default=0.0)
    lam_sweep: str = arg(
        default="",
        help="comma-separated λ list: ridge path at shared-Gram cost, "
        "selected on a held-out 10%% of train, refit at the winner "
        "(overrides --lam)",
    )
    num_epochs: int = arg(default=5)
    checkpoint_dir: str = arg(
        default="",
        help="if set, checkpoint the solver between BCD epochs and "
        "resume from this directory (reference setCheckpointDir, "
        "TimitPipeline.scala:34,38)",
    )
    checkpoint_every: int = arg(
        default=1,
        help="BCD epochs per checkpoint chunk (higher amortizes the "
        "per-chunk Gram recomputation)",
    )
    seed: int = arg(default=123)
    synthetic: int = arg(default=0, help="if > 0, N synthetic frames")


@treenode
class ScaledCosineBank(Transformer):
    """The full TIMIT featurizer as one row-wise Transformer: every
    (cosine features → standard scaler) chain applied to the batch,
    returning the list of (N, cosine_features) blocks — the shape the
    block solver consumes. Being a treenode lets the planner's
    fused-fit rule absorb the whole bank into the streaming
    normal-equations sink (one jitted chunk step, blocks never
    corpus-resident)."""

    chains: tuple  # of Pipeline(featurizer >> fitted scaler)

    def __call__(self, batch):
        return [chain(batch) for chain in self.chains]


_SYNTHETIC_CLASSES = min(NUM_CLASSES, 12)


@functools.cache
def _synthetic_centres() -> np.ndarray:
    """The class centres, which the train and the test corpus share."""
    return np.random.default_rng(42).normal(
        size=(_SYNTHETIC_CLASSES, TIMIT_DIMENSION)
    )


# A train and a test corpus of one size, and room for one more pair: a
# corpus of another size evicts the oldest.
@functools.lru_cache(maxsize=4)
def _synthetic(which: str, n: int) -> tuple[np.ndarray, np.ndarray]:
    """``n`` synthetic frames as (labels, data). The seeds are fixed
    (train 0, test 1, centres 42) and ``conf.seed`` draws the features,
    not the rows: the corpus is a function of these two arguments, made
    by the first fit of a process that needs it and handed to every later
    one as the same arrays, which nobody may write."""
    rng = np.random.default_rng(0 if which == "train" else 1)
    labels = rng.integers(0, _SYNTHETIC_CLASSES, size=n).astype(np.int32)
    data = (
        _synthetic_centres()[labels] * 2 + rng.normal(size=(n, TIMIT_DIMENSION))
    ).astype(np.float32)
    labels.flags.writeable = data.flags.writeable = False
    return labels, data


def _load(conf: TimitConfig, which: str) -> LabeledData:
    if conf.synthetic:
        n = conf.synthetic if which == "train" else max(conf.synthetic // 5, 1)
        labels, data = _synthetic(which, n)
        return LabeledData(labels=labels, data=data)
    if which == "train":
        return load_timit_split(
            conf.train_data_location, conf.train_labels_location
        )
    return load_timit_split(conf.test_data_location, conf.test_labels_location)


# The fit's three device programs, made once per process: every fitted
# node is a pytree argument, so jax's own cache answers each later fit
# (new weights, a new seed) with the executable of the first. The names
# are what the device programs are called in a profile.
@jax.jit
def cosine_features(node, b):
    return node(b)


@jax.jit
def standard_scale(node, b):
    return node(b)


@jax.jit
def score(model, bank, b):
    return model(bank(b))


def run(conf: TimitConfig, mesh=None) -> dict:
    """One fit. While spans are on (``--observe`` or ``--profile``; see
    ``observe/spans.py``) the call is one ``fit`` root span with a child
    per layer, and its three phase boundaries (``featurize_s``,
    ``fit_s``, the end) wait for their device work, so those keys are
    sound then; with spans off nothing is forced that was not before and
    ``featurize_s`` is the time to enqueue."""
    if mesh is None and len(jax.devices()) > 1:
        mesh = create_mesh()
    with span(
        "fit",
        parent=None,
        blocks=conf.num_cosines,
        epochs=conf.num_epochs,
        chips=mesh.size if mesh is not None else 1,
    ):
        return _fit(conf, mesh)


def _fit(conf: TimitConfig, mesh) -> dict:
    t0 = time.perf_counter()
    # how many of this fit's two corpora the memo answered: known when the
    # span closes, so the attribute is a callable (observe/spans.py::span)
    hits = _synthetic.cache_info().hits
    with span(
        "fit.load",
        bucket="wait_host",
        cached=lambda: _synthetic.cache_info().hits - hits,
    ):
        train, test = _load(conf, "train"), _load(conf, "test")
    n_train, n_test = len(train), len(test)

    with span("fit.featurize_init"):
        keys = jax.random.split(jax.random.key(conf.seed), conf.num_cosines)
        featurizers = [
            CosineRandomFeatures.create(
                TIMIT_DIMENSION,
                conf.cosine_features,
                keys[i],
                gamma=conf.gamma,
                distribution=conf.rf_type,
            )
            for i in range(conf.num_cosines)
        ]

    with span(
        "fit.h2d",
        bucket="wait_host",
        rows=n_train,
        bytes=train.data.nbytes + test.data.nbytes,
    ):
        x_train = shard_batch(train.data, mesh)
        x_test = shard_batch(test.data, mesh)
        force((x_train, x_test))

    from keystone_tpu import plan as plan_mod

    # KEYSTONE_PLAN: the fit streams chunks through featurize+scale+
    # accumulate fused (plan/fused_fit.py) — the corpus-wide block list
    # (num_cosines × 4096 × N, the big resident object of the classic
    # path) is never materialized. Scalers still need their one pass
    # over each block's raw features, but each block is dropped as soon
    # as its scaler is fitted. The λ-sweep and the between-epoch
    # checkpoint protocol both consume resident blocks — those runs
    # keep the classic path.
    streamed_fit = plan_mod.enabled() and not (
        conf.lam_sweep or conf.checkpoint_dir
    )

    # per-batch cosine features, standard-scaled (fit on train)
    train_blocks, scalers = [], []
    for i, f in enumerate(featurizers):
        with span("fit.featurize", bank=i):
            with span("featurize.cosine"):
                raw = cosine_features(f, x_train)
            with span("featurize.scale_fit"):
                scaler = StandardScaler().fit(raw, n_valid=n_train)
            scalers.append(scaler)
            if not streamed_fit:
                with span("featurize.scale_apply"):
                    train_blocks.append(standard_scale(scaler, raw))
            del raw

    with span("fit.labels"):
        y = np.zeros(x_train.shape[0], np.int32)
        y[:n_train] = train.labels
        indicators = ClassLabelIndicators(num_classes=NUM_CLASSES)(y)
    # the featurize phase ends when its device work has: only a recorded
    # fit waits here (the bank spans above time the enqueue alone)
    with span("fit.featurize_wait", bucket="wait_device"):
        force((train_blocks, scalers, indicators))
    t_feat = time.perf_counter()

    with span("fit.solve", bucket="compute"):
        lam = conf.lam
        if conf.lam_sweep:
            from keystone_tpu.evaluation.model_selection import (
                holdout_lambda_sweep,
            )

            # selection at one BCD pass (like MNIST): cheap relative to
            # the final multi-epoch fit, and the final fit stays under
            # the --checkpoint-dir preemption protection
            report = holdout_lambda_sweep(
                BlockLeastSquaresEstimator(
                    block_size=conf.cosine_features, num_iter=1
                ),
                train_blocks,
                indicators,
                y,
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
            block_size=conf.cosine_features, num_iter=conf.num_epochs, lam=lam
        )
        bank = ScaledCosineBank(
            chains=tuple(
                Pipeline.of(f, s) for f, s in zip(featurizers, scalers)
            )
        )
        if streamed_fit:
            from keystone_tpu.core.pipeline import ChainedLabelEstimator

            fitted = plan_mod.fit_streaming(
                ChainedLabelEstimator(prefix=bank, est=est),
                x_train,
                indicators,
                n_valid=n_train,
                mesh=mesh,
            )
            model = jax.block_until_ready(fitted[-1])
        else:
            from keystone_tpu.core.checkpoint import checkpointed_fit

            model = jax.block_until_ready(
                checkpointed_fit(
                    est,
                    train_blocks,
                    indicators,
                    checkpoint_dir=conf.checkpoint_dir,
                    every=conf.checkpoint_every,
                    n_valid=n_train,
                )
            )
    t_fit = time.perf_counter()

    with span("fit.score"):
        classify = MaxClassifier()
        evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)

        with span("score.train"):
            # classic path: the blocks are already resident — don't
            # re-featurize
            train_scores = (
                score(model, bank, x_train)
                if streamed_fit
                else model(train_blocks)
            )
            train_eval = evaluator(classify(train_scores), y, n_valid=n_train)
        with span("score.test"):
            y_test = np.zeros(x_test.shape[0], np.int32)
            y_test[:n_test] = test.labels
            # the evaluator reads the confusion matrix back: forced
            test_eval = evaluator(
                classify(score(model, bank, x_test)), y_test, n_valid=n_test
            )

    result = {
        "train_error": train_eval.error,
        "test_error": test_eval.error,
        "n_train": n_train,
        "n_test": n_test,
        "featurize_s": t_feat - t0,
        "fit_s": t_fit - t_feat,
        "total_s": time.perf_counter() - t0,
    }
    logger.info(
        "Timit: train err %.4f, test err %.4f", train_eval.error, test_eval.error
    )
    return result


def main(argv=None) -> dict:
    conf = parse_config(TimitConfig, argv)
    if not conf.synthetic and not conf.train_data_location:
        raise SystemExit("need the four TIMIT locations, or --synthetic N")
    return run(conf)


if __name__ == "__main__":
    main()
