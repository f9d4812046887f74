"""Plain reference of the TIMIT random-feature pipeline: float32
``jax.numpy`` at full matmul precision, no ``keystone_tpu``.

Follows KeystoneML's TimitPipeline: cosine random features
``cos(x W^T + b)`` with W = gamma x normal, b uniform on [0, 2 pi), a
standard scaler per bank (unbiased std, floored), block coordinate
descent least squares on +-1 indicators (labels and each block centred,
``num_epochs`` passes, ridge ``lam``), argmax. Departure: the reference
solves each block's normal equations by a Cholesky factor of the Gram
plus 1e-6 of its mean diagonal, where the program equilibrates, jitters
and refines; on the well-conditioned Grams here both reach the same
least-squares solution.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

OCCUPIED_CLASSES = 12
EPS = 2.2e-16


def corpus(which: str, n: int, dim: int):
    """The corpus ``models/timit_pipeline.py::_load`` makes, re-derived:
    its seeds are fixed (train 0, test 1, centres 42)."""
    rng = np.random.default_rng(0 if which == "train" else 1)
    labels = rng.integers(0, OCCUPIED_CLASSES, size=n).astype(np.int32)
    centres = np.random.default_rng(42).normal(size=(OCCUPIED_CLASSES, dim))
    data = (centres[labels] * 2 + rng.normal(size=(n, dim))).astype(np.float32)
    return data, labels


def draw_bank(key, dim: int, width: int, gamma: float):
    kw, kb = jax.random.split(key)
    w = gamma * jax.random.normal(kw, (width, dim), dtype=jnp.float32)
    b = jax.random.uniform(
        kb, (width,), minval=0.0, maxval=2 * np.pi, dtype=jnp.float32
    )
    return w, b


def scaler(feats):
    n = feats.shape[0]
    mean = jnp.mean(feats, axis=0)
    std = jnp.sqrt(jnp.var(feats, axis=0) * (n / max(n - 1, 1)))
    return mean, jnp.where(std < EPS, 1.0, std)


def rows_over_devices(x: np.ndarray):
    """Place rows over every device jax sees (zero rows pad the last
    shard), so that the reference of a four-chip fit has the room the
    program has; the jitted steps below then partition themselves."""
    devices = np.array(jax.devices())
    mesh = jax.sharding.Mesh(devices, ("rows",))
    pad = -len(x) % len(devices)
    x = np.concatenate([x, np.zeros((pad, *x.shape[1:]), x.dtype)])
    spec = jax.sharding.PartitionSpec("rows")
    return jax.device_put(x, jax.sharding.NamedSharding(mesh, spec))


def fit(seed: int, sizes: dict) -> dict:
    """The reference fit on the program's synthetic corpus: the error
    rates ``run()`` returns, and what ``scores`` needs to put any
    weights, its own or the program's, on the test rows: the scaled test
    blocks (rows over every device, zero rows after ``n_test``) and the
    fitted ``xs``, ``means`` and ``intercept``."""
    dim, width, k = (
        sizes["input_dim"], sizes["cosine_features"], sizes["num_classes"]
    )
    n_train = sizes["train_rows"]
    n_test = max(n_train // 5, 1)
    x_train, y_train = corpus("train", n_train, dim)
    x_test, y_test = corpus("test", n_test, dim)
    keys = jax.random.split(jax.random.key(seed), sizes["num_cosines"])

    @jax.jit
    def featurize(key, x, x_t):
        w, b = draw_bank(key, dim, width, sizes["gamma"])
        raw = jnp.cos(x @ w.T + b)
        mean, std = scaler(raw)
        a = (raw - mean) / std
        a_t = (jnp.cos(x_t @ w.T + b) - mean) / std
        centre = jnp.mean(a, axis=0)
        a_c = a - centre
        return a_c, a_t, centre, a_c.T @ a_c

    @jax.jit
    def factor(gram):
        d = gram.shape[0]
        ridge = sizes["lam"] + 1e-6 * jnp.trace(gram) / d
        return jax.scipy.linalg.cho_factor(gram + ridge * jnp.eye(d))[0]

    @jax.jit
    def update(a_c, gram, chol, x, resid):
        rhs = a_c.T @ resid + gram @ x
        x_new = jax.scipy.linalg.cho_solve((chol, False), rhs)
        return x_new, resid - a_c @ (x_new - x)

    with jax.default_matmul_precision("highest"):
        # train rows divide by the chips (rows are given per chip), so
        # only the test rows can be padded, and their pad is cut below
        xt, xe = rows_over_devices(x_train), rows_over_devices(x_test)
        blocks = [featurize(key, xt, xe) for key in keys]
        chols = [factor(g) for *_rest, g in blocks]
        y = -np.ones((n_train, k), np.float32)
        y[np.arange(n_train), y_train] = 1.0
        y = rows_over_devices(y)
        y_mean = jnp.mean(y, axis=0)
        resid = y - y_mean
        xs = [jnp.zeros((width, k), jnp.float32) for _ in blocks]
        for _ in range(sizes["num_epochs"]):
            for i, (a_c, _a_t, _centre, gram) in enumerate(blocks):
                xs[i], resid = update(a_c, gram, chols[i], xs[i], resid)
        train_scores = sum(b[0] @ x for b, x in zip(blocks, xs)) + y_mean
        train_pred = np.asarray(jnp.argmax(train_scores, axis=-1))
    out = {
        "n_train": n_train,
        "n_test": n_test,
        "test_blocks": [b[1] for b in blocks],
        "xs": xs,
        "means": [b[2] for b in blocks],
        "intercept": y_mean,
    }
    test_pred = np.argmax(scores(out, out)[:n_test], axis=-1)
    out["train_error"] = float(np.mean(train_pred != y_train))
    out["test_error"] = float(np.mean(test_pred != y_test))
    return out


def scores(fitted: dict, weights: dict) -> np.ndarray:
    """Scores of ``weights`` (``xs``, ``means``, ``intercept``: the parts
    of a fitted block linear model) on the reference's test blocks."""
    with jax.default_matmul_precision("highest"):
        out = weights["intercept"] + sum(
            (a - jnp.asarray(m)) @ jnp.asarray(x)
            for a, m, x in zip(
                fitted["test_blocks"], weights["means"], weights["xs"]
            )
        )
    return np.asarray(out)


def distance(got: np.ndarray, want: np.ndarray, origin=0.0) -> float:
    """||got - want|| over ||want - origin|| (Frobenius)."""
    return float(np.linalg.norm(got - want) / np.linalg.norm(want - origin))
