"""Statistical feature nodes.

TPU-native rebuild of the reference's ``nodes/stats/`` (SURVEY.md §2.4).
All nodes operate on ``(N, d)`` float batches with the leading axis sharded
over the mesh "data" axis; XLA turns the axis-0 reductions in the estimators
into ICI all-reduces (the successor of Spark ``treeAggregate``).
"""

from __future__ import annotations

from typing import Callable

import functools

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.core.pipeline import Estimator, Transformer
from keystone_tpu.core.treenode import static_field, treenode

# Matlab eps — the reference's variance/norm floor (utils/Stats.scala).
EPS = 2.2e-16


@treenode
class StandardScalerModel(Transformer):
    """Subtract mean, optionally divide by std (nodes/stats/StandardScaler.scala).

    ``std`` is None when fitted with ``normalize_std_dev=False`` (the solver
    layer fits label/feature centering this way, e.g. the reference's
    ``BlockLeastSquaresEstimator`` per-block centering).
    """

    mean: jnp.ndarray
    std: jnp.ndarray | None = None

    def __call__(self, batch):
        out = batch - self.mean
        if self.std is not None:
            out = out / self.std
        return out


@treenode
class StandardScaler(Estimator):
    """Fit per-feature mean/std with a single sharded pass.

    The reference computes these with ``treeAggregate`` of a
    ``MultivariateOnlineSummarizer``; here ``jnp.mean``/``jnp.var`` over the
    sharded batch compile to per-shard partial sums + ICI ``psum``.

    ``n_valid``: number of real rows if the batch was zero-padded for
    sharding (see ``parallel.mesh.pad_batch``) — padding rows are masked out
    of the moments.
    """

    normalize_std_dev: bool = static_field(default=True)
    eps: float = static_field(default=EPS)

    def fit(self, data, n_valid: int | None = None) -> StandardScalerModel:
        mean, var = _masked_moments(data, n_valid)
        if not self.normalize_std_dev:
            return StandardScalerModel(mean=mean, std=None)
        n = data.shape[0] if n_valid is None else n_valid
        # unbiased (sample) std, matching the summarizer's variance
        var = var * (n / max(n - 1, 1))
        std = jnp.sqrt(var)
        std = jnp.where(std < self.eps, jnp.ones_like(std), std)
        return StandardScalerModel(mean=mean, std=std)


def _masked_moments(data, n_valid: int | None):
    """Population mean/var over valid rows of a possibly padded batch."""
    if n_valid is None or n_valid == data.shape[0]:
        return jnp.mean(data, axis=0), jnp.var(data, axis=0)
    mask = (jnp.arange(data.shape[0]) < n_valid)[:, None].astype(data.dtype)
    denom = jnp.asarray(n_valid, data.dtype)
    mean = jnp.sum(data * mask, axis=0) / denom
    var = jnp.sum(mask * (data - mean) ** 2, axis=0) / denom
    return mean, var


@treenode
class RandomSignNode(Transformer):
    """Elementwise multiply by a fixed ±1 mask (nodes/stats/RandomSignNode.scala)."""

    signs: jnp.ndarray

    def __call__(self, batch):
        return batch * self.signs

    @staticmethod
    def create(num_features: int, key: jax.Array) -> "RandomSignNode":
        signs = jax.random.rademacher(key, (num_features,), dtype=jnp.float32)
        return RandomSignNode(signs=signs)


@functools.lru_cache(maxsize=32)
def _cos_matrix_host(d: int, n: int):
    """Cached HOST (d, n/2) half-spectrum cosine matrix for PaddedFFT's
    matmul backend: real part of rfft of the zero-padded row — pad columns
    drop out of the sum, so only the d live rows exist. Cached as numpy so
    repeat eager calls skip the trig, without pinning device buffers."""
    k = np.arange(n // 2)[None, :]
    nn = np.arange(d)[:, None]
    return np.cos(2.0 * np.pi * k * nn / n)


def _cos_matrix(d: int, n: int, dtype: str):
    return jnp.asarray(_cos_matrix_host(d, n), dtype)


@treenode
class PaddedFFT(Transformer):
    """Zero-pad each row to the next power of two, FFT, return the real part
    of the first half (nodes/stats/PaddedFFT.scala).

    Output dim for input dim d: ``next_pow2(d) // 2``. Two backends:

    - ``fft``: ``Re(rfft)[:n/2]`` — best on CPU (O(n log n) butterflies).
    - ``matmul``: the same values as one cosine-matrix gemm,
      ``x @ cos(2π k n / N)`` — only the needed half-spectrum's real part
      is ever computed, the zero padding never materializes, and the work
      lands on the MXU where it fuses with neighboring ops.
    - ``auto`` (default): matmul on TPU, fft elsewhere.
    """

    impl: str = static_field(default="auto")

    def __call__(self, batch):
        if self.impl not in ("auto", "fft", "matmul"):
            raise ValueError(
                f"PaddedFFT impl={self.impl!r}; expected auto|fft|matmul"
            )
        d = batch.shape[-1]
        n = 1 << max(int(np.ceil(np.log2(d))), 0) if d > 1 else 1
        impl = self.impl
        if impl == "auto":
            from keystone_tpu.ops.flash_attention import on_tpu

            impl = "matmul" if on_tpu() else "fft"
        if impl == "matmul":
            return batch @ _cos_matrix(d, n, str(batch.dtype))
        padded = jnp.pad(batch, [(0, 0)] * (batch.ndim - 1) + [(0, n - d)])
        return jnp.real(jnp.fft.rfft(padded, axis=-1))[..., : n // 2]


@treenode
class LinearRectifier(Transformer):
    """``max(max_val, x - alpha)`` (nodes/stats/LinearRectifier.scala)."""

    max_val: float = static_field(default=0.0)
    alpha: float = static_field(default=0.0)

    def __call__(self, batch):
        return jnp.maximum(self.max_val, batch - self.alpha)


@treenode
class CosineRandomFeatures(Transformer):
    """Random Fourier features ``cos(x W^T + b)``
    (nodes/stats/CosineRandomFeatures.scala).

    The reference batches each partition into one gemm; here the whole
    sharded batch is one MXU gemm. W: (num_features, input_dim), b:
    (num_features,). Gaussian W approximates an RBF kernel, Cauchy W a
    Laplacian kernel.
    """

    w: jnp.ndarray
    b: jnp.ndarray

    def __call__(self, batch):
        return jnp.cos(batch @ self.w.T + self.b)

    @staticmethod
    def create(
        input_dim: int,
        num_features: int,
        key: jax.Array,
        gamma: float = 1.0,
        distribution: str = "gaussian",
    ) -> "CosineRandomFeatures":
        kw, kb = jax.random.split(key)
        shape = (num_features, input_dim)
        if distribution == "gaussian":
            w = gamma * jax.random.normal(kw, shape, dtype=jnp.float32)
        elif distribution == "cauchy":
            w = gamma * jax.random.cauchy(kw, shape, dtype=jnp.float32)
        else:
            raise ValueError(f"unknown distribution {distribution!r}")
        b = jax.random.uniform(
            kb, (num_features,), minval=0.0, maxval=2 * np.pi, dtype=jnp.float32
        )
        return CosineRandomFeatures(w=w, b=b)


@treenode
class NormalizeRows(Transformer):
    """Row L2 normalization with eps floor (nodes/stats/NormalizeRows.scala)."""

    eps: float = static_field(default=EPS)

    def __call__(self, batch):
        norms = jnp.linalg.norm(batch, axis=-1, keepdims=True)
        return batch / jnp.maximum(norms, self.eps)


@treenode
class SignedHellingerMapper(Transformer):
    """``sign(x) * sqrt(|x|)`` (nodes/stats/SignedHellingerMapper.scala)."""

    def __call__(self, batch):
        return jnp.sign(batch) * jnp.sqrt(jnp.abs(batch))


@treenode
class Sampler:
    """Sample up to ``size`` rows from a batch (nodes/stats/Sampling.scala).

    The reference's ``takeSample``-backed FunctionNode; here a host-level
    helper used to feed driver-style fits (PCA/GMM/ZCA).
    """

    size: int = static_field(default=1000)
    seed: int = static_field(default=42)

    def __call__(self, batch):
        n = batch.shape[0]
        if n <= self.size:
            return batch
        idx = np.random.default_rng(self.seed).choice(n, self.size, replace=False)
        return jnp.take(batch, jnp.asarray(np.sort(idx)), axis=0)


def sample_columns(desc, num: int, seed: int) -> jnp.ndarray:
    """Sample up to ``num`` descriptor columns as (num, d) rows.

    ``desc``: an (N, d, m) batch of feature-major descriptor matrices, or a
    list of (d, n_i) matrices (ragged). The single implementation behind
    :class:`ColumnSampler` and the Fisher pipelines' PCA/GMM sampling.
    """
    if isinstance(desc, (list, tuple)):
        flat = jnp.concatenate(
            [jnp.asarray(m).T for m in desc], axis=0
        )  # (Σn_i, d)
    else:
        n, d, m = desc.shape
        flat = jnp.transpose(desc, (0, 2, 1)).reshape(n * m, d)
    total = flat.shape[0]
    if total > num:
        idx = np.sort(
            np.random.default_rng(seed).choice(total, num, replace=False)
        )
        if jax.default_backend() == "cpu" and getattr(
            flat, "is_fully_addressable", True
        ):
            # host-side gather: the index draw already lives on the host,
            # and jax 0.9's CPU gather flakily aborts when dispatched after
            # a multi-device shard_map run in the same process
            flat = jnp.asarray(np.asarray(flat)[idx])
        else:
            flat = jnp.take(flat, jnp.asarray(idx), axis=0)
    return flat


@treenode
class ColumnSampler:
    """Sample ``num_cols`` columns across descriptor matrices
    (nodes/stats/Sampling.scala ColumnSampler).

    Input: (N, d, m) array or list of per-item (d, n_i) feature-major
    matrices. Output: (num_cols, d) row batch suitable for PCA/GMM fits.
    """

    num_cols: int = static_field(default=100000)
    seed: int = static_field(default=42)

    def __call__(self, mats):
        return sample_columns(mats, self.num_cols, self.seed)


@treenode
class TermFrequency:
    """Per-item term counts re-weighted by ``fn`` (nodes/stats/TermFrequency.scala).

    Host-side: batch of token sequences → batch of {token: weight} dicts.
    """

    fn: Callable[[float], float] = static_field(default=lambda x: x)

    def __call__(self, batch):
        out = []
        for doc in batch:
            counts: dict = {}
            for tok in doc:
                counts[tok] = counts.get(tok, 0) + 1
            out.append({t: self.fn(c) for t, c in counts.items()})
        return out
