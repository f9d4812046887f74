"""Weight-only int8 quantization for inference.

Decode is the HBM-bound regime (every step re-reads all params), so the
serving lever on TPU is weight bytes, not FLOPs: int8 weights halve the
bf16 stream. Symmetric per-output-channel
scales keep the matmul exact up to rounding, applied to the
activation-sized result (``(y @ q) * scale``).

The int8→compute-dtype convert is written as ``q.astype`` feeding the
dot; whether the weight stream actually halves rests on XLA fusing that
convert into the dot's operand load (the usual TPU lowering). That is a
compiler property, not a code guarantee, so nothing here asserts the
ratio: :mod:`keystone_tpu.ops.int8_matmul` is the path whose stream is
int8 by construction.

The reference has no quantization (it serves f64 BLAS models); this is a
beyond-reference serving capability in the spirit of the KV-cache
decode path.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from keystone_tpu.core.treenode import treenode


@treenode
class QTensor:
    """Symmetric int8 tensor: ``q * scale`` reconstructs the original.
    ``scale`` is broadcast-shaped against the reconstruction — (1, out)
    for (in, out) matmul weights, (V, 1) for row-quantized embeddings."""

    q: jnp.ndarray  # int8, original shape
    scale: jnp.ndarray  # f32, broadcastable to q's shape

    @property
    def shape(self):
        return self.q.shape

    def dequantize(self):
        return self.q.astype(jnp.float32) * self.scale


def symmetric_int8(w, reduce_axes):
    """The one symmetric-int8 recipe (amax/127 scales, round, clip ±127)
    shared by weight and KV-cache quantization — ``reduce_axes`` are the
    axes the scale pools over (keepdims). Returns (int8 codes, f32
    scale)."""
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_int8(w, *, channel_axis: int = -1) -> QTensor:
    """Per-channel symmetric quantization: scales are max|w|/127 along
    every axis EXCEPT ``channel_axis`` (the one that stays per-channel).
    channel_axis=-1 suits (in, out) weights; 0 suits (V, d) embeddings
    (per-row, so both the gather and the tied-logit transpose see a
    per-output scale)."""
    w = jnp.asarray(w)
    channel_axis = channel_axis % w.ndim
    reduce_axes = tuple(a for a in range(w.ndim) if a != channel_axis)
    q, scale = symmetric_int8(w, reduce_axes)
    return QTensor(q=q, scale=scale)


def mm(y, w, dt):
    """``y @ w`` where ``w`` is a plain array or a :class:`QTensor` with
    per-output-channel (1, out) scales. The int8 path scales the
    activation-sized result; the convert-into-dot is left to XLA fusion
    (see module docstring)."""
    if isinstance(w, QTensor):
        # scale stays f32: rounding it to bf16 first would add ~0.4%
        # relative error to every element of a channel on top of the int8
        # rounding; the single cast of the product is the cost of the
        # output dtype, not an avoidable one
        return ((y @ w.q.astype(dt)) * w.scale).astype(dt)
    return y @ w.astype(dt)


def quantization_error(w) -> float:
    """Max abs reconstruction error of quantizing ``w`` (diagnostics)."""
    qt = quantize_int8(np.asarray(w))
    return float(np.max(np.abs(np.asarray(qt.dequantize()) - w)))
