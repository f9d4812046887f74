"""Image nodes (reference ``nodes/images/``, SURVEY.md §2.3).

All nodes operate on (N, H, W, C) float batches. Patch/feature layouts
flatten as (dy, dx, c) with channel fastest — the reference's patch index
``c + x·C + y·C·k`` (Convolver.makePatches), so fitted filters/whiteners are
layout-compatible across the whole stack.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.core.pipeline import FunctionNode, Transformer
from keystone_tpu.core.treenode import static_field, treenode
from keystone_tpu.utils.images import rgb_to_gray


@treenode
class GrayScaler(Transformer):
    """MATLAB rgb2gray weights (reference ImageUtils.toGrayScale)."""

    def __call__(self, batch):
        return rgb_to_gray(batch)


@treenode
class PixelScaler(Transformer):
    """Scale byte pixels to [0,1] (reference nodes/images/PixelScaler.scala)."""

    def __call__(self, batch):
        return batch / 255.0


@treenode
class ImageVectorizer(Transformer):
    """(N, H, W, C) → (N, H·W·C), channel fastest
    (reference nodes/images/ImageVectorizer.scala)."""

    def __call__(self, batch):
        return batch.reshape(batch.shape[0], -1)


def extract_patches(batch, patch_size: int, stride: int = 1):
    """All patch_size×patch_size windows at the given stride.

    Returns (N, oh, ow, patch_size·patch_size·C) with (dy, dx, c) flattening,
    channel fastest — matching the reference patch layout.

    Pure strided slicing — exact data movement, no arithmetic. (The
    previous ``conv_general_dilated_patches`` formulation lowers to a
    real convolution, which at XLA's default precision rounds the patch
    VALUES through bf16 passes — ~0.2% error on pixels, measured on both
    CPU and TPU backends.)
    """
    n, h, w, c = batch.shape
    k = patch_size
    oh = (h - k) // stride + 1
    ow = (w - k) // stride + 1
    slabs = [
        batch[
            :,
            dy : dy + (oh - 1) * stride + 1 : stride,
            dx : dx + (ow - 1) * stride + 1 : stride,
            :,
        ]
        for dy in range(k)
        for dx in range(k)
    ]  # k² slabs of (N, oh, ow, C), ordered (dy, dx) — channel fastest
    return jnp.concatenate(slabs, axis=-1).reshape(n, oh, ow, k * k * c)


@treenode
class Windower(FunctionNode):
    """FlatMap each image into all stride-spaced square windows
    (reference nodes/images/Windower.scala).

    (N, H, W, C) → (N·n_windows, w, w, C).
    """

    stride: int = static_field(default=1)
    window_size: int = static_field(default=6)

    def __call__(self, batch):
        n, _, _, c = batch.shape
        w = self.window_size
        p = extract_patches(batch, w, self.stride)
        return p.reshape(n * p.shape[1] * p.shape[2], w, w, c)


def normalize_patch_rows(mat, var_constant: float = 10.0):
    """Per-row mean-center and divide by sqrt(var + alpha)
    (reference utils/Stats.scala normalizeRows; var uses d-1 denominator)."""
    d = mat.shape[-1]
    mean = jnp.mean(mat, axis=-1, keepdims=True)
    var = jnp.sum((mat - mean) ** 2, axis=-1, keepdims=True) / max(d - 1, 1)
    return (mat - mean) / jnp.sqrt(var + var_constant)


def conv_convolver(
    batch,
    filters,
    *,
    patch_size: int,
    normalize_patches: bool,
    var_constant: float,
    whitener_means=None,
    precision=None,
):
    """Convolver forward as ONE dense convolution plus box-filter algebra.

    The reference's per-patch normalization (``Stats.normalizeRows``) is
    affine in the patch: with per-patch mean mu and sigma = sqrt(var+vc),

        ((p - mu)/sigma - m) . F_f = (p.F_f - mu * sum(F_f)) / sigma - m.F_f

    so the whole im2col pipeline factors into a plain MXU convolution
    (``p.F_f``) plus per-patch scalars from two box-filter reductions —
    no (N, oh, ow, k^2 C) patch tensor ever exists. HBM traffic drops from
    ~k^2 x image bytes to image-in/featuremap-out; this is the TPU-first
    design the retired fused Pallas kernel approximated.

    The box sums run through ``lax.reduce_window`` (exact f32 VPU adds),
    not the MXU, so mu/sigma carry no bf16-pass rounding.
    """
    n, h, w, c = batch.shape
    k = patch_size
    f = filters.shape[0]
    d = k * k * c
    batch = batch.astype(jnp.float32)
    filters = filters.astype(jnp.float32)
    # (F, d) rows are (dy, dx, c) flattened, channel fastest -> HWIO
    wts = jnp.transpose(filters.reshape(f, k, k, c), (1, 2, 3, 0))
    dn = jax.lax.conv_dimension_numbers(
        batch.shape, wts.shape, ("NHWC", "HWIO", "NHWC")
    )
    out = jax.lax.conv_general_dilated(
        batch, wts, (1, 1), "VALID", dimension_numbers=dn,
        precision=precision,
    )  # (N, oh, ow, F)
    if normalize_patches:
        csum = jnp.sum(batch, axis=-1)  # (N, H, W)
        csq = jnp.sum(batch * batch, axis=-1)
        box = lambda x: jax.lax.reduce_window(  # noqa: E731
            x, 0.0, jax.lax.add, (1, k, k), (1, 1, 1), "VALID"
        )
        s1 = box(csum)  # (N, oh, ow) patch sums
        s2 = box(csq)
        mu = s1 / d
        # clamp: one-pass variance can round slightly negative for flat
        # patches, which would NaN the sqrt at var_constant=0
        var = jnp.maximum(s2 - s1 * mu, 0.0) / max(d - 1, 1)
        sigma = jnp.sqrt(var + var_constant)
        colsum = jnp.sum(filters, axis=1)  # (F,)
        out = (out - mu[..., None] * colsum) / sigma[..., None]
    if whitener_means is not None:
        out = out - jnp.einsum(
            "fd,d->f",
            filters,
            jnp.asarray(whitener_means, jnp.float32),
            precision=precision,
        )
    return out


@treenode
class Convolver(Transformer):
    """Filter-bank convolution (reference nodes/images/Convolver.scala).

    The reference packs every patch into a row, optionally normalizes each
    patch (``Stats.normalizeRows`` with ``varConstant``), optionally
    subtracts the whitener means, then does one gemm with the filter bank.
    Implementations:

    - ``conv`` (default via auto): :func:`conv_convolver` — the
      normalization algebra folded around one dense MXU convolution.
    - ``xla``: im2col — materialize patches, normalize, gemm (the
      reference's schedule; the parity baseline the others are tested
      against).

    A Pallas im2col kernel (``impl="fused"``) existed through round 2 and
    was retired: per-image im2col with C=3 writes 3-of-128 lanes per
    store, structurally lane-hostile. Folding the normalization
    *algebraically* around XLA's native conv lowering is the TPU-first
    answer here, not a hand-written kernel.

    ``filters``: (num_filters, patch_size²·C), rows in (dy, dx, c) layout —
    exactly what :class:`Windower`+:class:`ImageVectorizer` sampling or
    ``RandomPatchCifar``-style whitened filter construction produces.
    """

    filters: jnp.ndarray
    whitener_means: jnp.ndarray | None = None
    patch_size: int = static_field(default=6)
    normalize_patches: bool = static_field(default=True)
    var_constant: float = static_field(default=10.0)
    impl: str = static_field(default="auto")
    # gemm/conv precision: None = backend default (bf16 MXU passes on
    # TPU, ~0.2% relative); "highest" = full f32 (reference-BLAS class)
    precision: str | None = static_field(default=None)

    def __call__(self, batch):
        if self.impl not in ("auto", "conv", "xla"):
            raise ValueError(
                f"Convolver impl={self.impl!r}; expected auto|conv|xla"
            )
        # every impl computes and emits float32; keeps auto-path output
        # independent of which impl runs
        batch = batch.astype(jnp.float32)
        if self.impl in ("auto", "conv"):
            return conv_convolver(
                batch,
                self.filters,
                patch_size=self.patch_size,
                normalize_patches=self.normalize_patches,
                var_constant=self.var_constant,
                whitener_means=self.whitener_means,
                precision=self.precision,
            )
        p = extract_patches(batch, self.patch_size)  # (N, oh, ow, k²C)
        if self.normalize_patches:
            p = normalize_patch_rows(p, self.var_constant)
        if self.whitener_means is not None:
            p = p - self.whitener_means
        return jnp.einsum(
            "nhwp,fp->nhwf",
            p,
            self.filters.astype(p.dtype),
            precision=self.precision,
        )


@treenode
class SymmetricRectifier(Transformer):
    """x → [max(maxVal, x−α), max(maxVal, −x−α)] stacked on the channel axis
    (reference nodes/images/SymmetricRectifier.scala): C → 2C channels."""

    max_val: float = static_field(default=0.0)
    alpha: float = static_field(default=0.0)

    def __call__(self, batch):
        pos = jnp.maximum(self.max_val, batch - self.alpha)
        neg = jnp.maximum(self.max_val, -batch - self.alpha)
        return jnp.concatenate([pos, neg], axis=-1)


@treenode
class Pooler(Transformer):
    """Strided pooling with the reference's exact window geometry
    (reference nodes/images/Pooler.scala):

    - pool centers start at ``strideStart = pool_size // 2``,
    - each window spans ``[x − pool_size/2, min(x + pool_size/2, dim))`` —
      i.e. windows start at 0, stride apart, edge windows truncated,
    - ``num_pools = ceil((dim − strideStart) / stride)``.

    Implemented as pixel_fn → zero-pad right → ``lax.reduce_window``.
    Zero padding reproduces the truncated edge windows for sum/max pooling
    (the reference's pool buffer is likewise zero-filled). NOTE (reference
    quirk, SURVEY.md §7): a mean pool would divide by the wrong count at
    edges — replicated faithfully by dividing by pool_size².
    """

    stride: int = static_field(default=13)
    pool_size: int = static_field(default=14)
    pixel_fn: Callable | None = static_field(default=None)
    pool_fn: str = static_field(default="sum")  # sum | max | mean

    def __call__(self, batch):
        if self.pixel_fn is not None:
            batch = self.pixel_fn(batch)
        n, h, w, c = batch.shape
        ph = self._num_pools(h)
        pw = self._num_pools(w)
        pad_h = (ph - 1) * self.stride + self.pool_size - h
        pad_w = (pw - 1) * self.stride + self.pool_size - w
        if self.pool_fn == "max":
            init, op = -jnp.inf, jax.lax.max
            pad_val = -jnp.inf
        else:
            init, op = 0.0, jax.lax.add
            pad_val = 0.0
        if pad_h > 0 or pad_w > 0:
            batch = jnp.pad(
                batch,
                ((0, 0), (0, max(pad_h, 0)), (0, max(pad_w, 0)), (0, 0)),
                constant_values=pad_val,
            )
        out = jax.lax.reduce_window(
            batch,
            jnp.asarray(init, batch.dtype),
            op,
            window_dimensions=(1, self.pool_size, self.pool_size, 1),
            window_strides=(1, self.stride, self.stride, 1),
            padding="VALID",
        )
        if self.pool_fn == "mean":
            out = out / float(self.pool_size * self.pool_size)
        return out

    def _num_pools(self, dim: int) -> int:
        stride_start = self.pool_size // 2
        return -(-(dim - stride_start) // self.stride)


@treenode
class FusedConvRectifyPool(Transformer):
    """``Convolver >> SymmetricRectifier >> Pooler`` as one node.

    Produced by :func:`keystone_tpu.core.fusion.optimize`; carries the
    union of the three nodes' parameters. Implementations:

    - ``auto`` (default): conv-algebra convolution, then each rectifier
      half is pooled *before* the channel concat. The unfused chain's
      ``concatenate`` forces XLA to materialize the (N, oh, ow, 2F) map
      in HBM between the rectifier and the pooler; pooling each half
      first keeps the rectifier fused into ``reduce_window``'s operand
      and the concat runs on the tiny pooled map (the 2F map never
      exists).
    - ``unfused``: the literal three-node chain (parity baseline).

    A single fused VMEM Pallas kernel (``impl="pallas"``) existed through
    round 2 and was retired with the Convolver's kernel, for the same
    per-image im2col.

    Output is identical in shape/layout to the chain: (N, ph, pw, 2F),
    channels ``[pos | neg]``.
    """

    filters: jnp.ndarray
    whitener_means: jnp.ndarray | None = None
    patch_size: int = static_field(default=6)
    normalize_patches: bool = static_field(default=True)
    var_constant: float = static_field(default=10.0)
    alpha: float = static_field(default=0.0)
    max_val: float = static_field(default=0.0)
    pool_stride: int = static_field(default=13)
    pool_size: int = static_field(default=14)
    pool_fn: str = static_field(default="sum")
    impl: str = static_field(default="auto")  # auto | unfused

    def _unfused(self) -> Transformer:
        from keystone_tpu.core.pipeline import Pipeline

        return Pipeline.of(
            Convolver(
                filters=self.filters,
                whitener_means=self.whitener_means,
                patch_size=self.patch_size,
                normalize_patches=self.normalize_patches,
                var_constant=self.var_constant,
            ),
            SymmetricRectifier(max_val=self.max_val, alpha=self.alpha),
            Pooler(
                stride=self.pool_stride,
                pool_size=self.pool_size,
                pool_fn=self.pool_fn,
            ),
        )

    def __call__(self, batch):
        if self.impl not in ("auto", "unfused"):
            raise ValueError(
                f"FusedConvRectifyPool impl={self.impl!r}; "
                "expected auto|unfused"
            )
        if self.impl == "unfused":
            return self._unfused()(batch)
        conv = conv_convolver(
            batch,
            self.filters,
            patch_size=self.patch_size,
            normalize_patches=self.normalize_patches,
            var_constant=self.var_constant,
            whitener_means=self.whitener_means,
        )
        pool = Pooler(
            stride=self.pool_stride,
            pool_size=self.pool_size,
            pool_fn=self.pool_fn,
        )
        pos = pool(jnp.maximum(self.max_val, conv - self.alpha))
        neg = pool(jnp.maximum(self.max_val, -conv - self.alpha))
        return jnp.concatenate([pos, neg], axis=-1)


@treenode
class LabelExtractor(Transformer):
    """Project labels out of a LabeledImages batch
    (reference nodes/images/LabeledImageExtractors.scala)."""

    def __call__(self, batch):
        return batch.labels


@treenode
class ImageExtractor(Transformer):
    """Project images out of a LabeledImages batch."""

    def __call__(self, batch):
        return batch.images


# Multi-label variants are the same projections; provided for parity.
MultiLabelExtractor = LabelExtractor
MultiLabeledImageExtractor = ImageExtractor
