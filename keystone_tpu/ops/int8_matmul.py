"""Fused int8-dequant matmul Pallas kernel for the decode path.

Weight-only int8 serving (``ops/quantization.py``) leans on XLA fusing
the ``q.astype(bf16)`` convert into the dot's operand load — a compiler
property, not a guarantee. This kernel
removes the bet: the int8 codes stream from HBM *as int8* (half the
bytes of bf16 — decode's entire economics) and are widened in VMEM right
before the MXU pass, with the per-output-channel f32 scale applied to
the accumulator.

Decode shapes are tall-K, tiny-M (B·1 activations against (K, N)
weights), so the kernel grids over N with K streamed sequentially per
tile and the f32 accumulator carried in VMEM scratch. Runs compiled on
TPU and in Pallas interpret mode on the CPU (tests).

The serving entry point stays :func:`keystone_tpu.ops.quantization.mm`;
``mm_fused`` here is the alternative (the LM's ``int8_kernel="pallas"``).
Which of the two streams fewer bytes a token on the chip is not
measured: no benchmark cell decodes yet.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from keystone_tpu.ops.quantization import QTensor, mm as _xla_mm


def _kernel(y_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    """One (M, N_blk) output tile; grid = (N tiles, K tiles) with K the
    minor (sequential) dimension. y (M, K_blk) in the caller's compute
    dtype; q (K_blk, N_blk) int8; s (1, N_blk) f32 scale applied once at
    the last K step."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the widening happens HERE, after the int8 bytes landed in VMEM —
    # the HBM stream stays 1 byte/weight. Widen to y's dtype so the
    # kernel matches quantization.mm's compute semantics (bf16 policy →
    # bf16 MXU passes; f32 → f32 emulation), f32 accumulate either way
    acc_ref[...] += jnp.dot(
        y_ref[...],
        q_ref[...].astype(y_ref.dtype),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _finalize():
        o_ref[...] = acc_ref[...] * s_ref[...]


# Largest M the single-tile layout may carry: (M, block_n) f32 scratch +
# (M, block_k) activation tile stay well under ~1 MB of VMEM at the
# default 512 blocks. Decode uses M = batch ≤ 64; 256 leaves headroom.
_MAX_M = 256


def _pad_dim(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def mm_fused(
    y,
    w: QTensor,
    *,
    block_n: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """``y @ w.q * w.scale`` with the dequant fused into the kernel.

    y: (..., K) float; w.q: (K, N) int8 with (1, N) f32 scales. Returns
    (..., N) in y's dtype (f32 accumulation, like ``mm``)."""
    if interpret is None:
        from keystone_tpu.ops.flash_attention import interpret_default

        interpret = interpret_default()
    if w.scale.shape != (1, w.q.shape[1]):
        raise ValueError(
            f"mm_fused needs (1, N) per-output-channel scales; got "
            f"{w.scale.shape} for q {w.q.shape}"
        )
    lead = y.shape[:-1]
    k_dim = y.shape[-1]
    if k_dim != w.q.shape[0]:
        raise ValueError(f"contraction mismatch: {y.shape} @ {w.q.shape}")
    ym = y.reshape(-1, k_dim)
    m = ym.shape[0]
    # MXU-friendly tiles: M to the 16-sublane tile, K/N to blocks. The
    # whole M extent rides in one tile (plus an (M, block_n) scratch) —
    # this kernel is for decode's tiny-M regime, so the decode-only
    # contract is enforced here: past _MAX_M the full-M activation tile
    # + f32 scratch would blow VMEM, so fall back to the XLA path
    # rather than leave the guard to callers (models/lm/model.model_mm)
    if m > _MAX_M:
        return _xla_mm(y, w, y.dtype)
    ym = _pad_dim(_pad_dim(ym, 0, 16), 1, block_k)
    q = _pad_dim(_pad_dim(w.q, 0, block_k), 1, block_n)
    s = _pad_dim(w.scale.astype(jnp.float32), 1, block_n)
    m_pad, k_pad = ym.shape
    n_pad = q.shape[1]
    n_k = k_pad // block_k

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(n_pad // block_n, n_k),
        in_specs=[
            pl.BlockSpec((m_pad, block_k), lambda n, k: (0, k)),
            pl.BlockSpec((block_k, block_n), lambda n, k: (k, n)),
            pl.BlockSpec((1, block_n), lambda n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((m_pad, block_n), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m_pad, block_n), jnp.float32)],
        # N tiles are independent; K is the sequential accumulator dim —
        # telling Mosaic lets it pipeline the int8 HBM loads across steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(ym, q, s)
    out = out[:m, : w.q.shape[1]]
    return out.reshape(*lead, w.q.shape[1]).astype(y.dtype)
