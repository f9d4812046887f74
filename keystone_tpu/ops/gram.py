"""Quantized Gram operators for the streaming normal-equations fit.

The solver path's hottest contraction is ``AᵀA`` over streamed feature
chunks (:func:`keystone_tpu.ops.linear.normal_eq_update`). On TPU the
int8 MXU runs ~2× the bf16 rate, and the decode path already owns the
machinery (``quantization.py`` symmetric scales, the
``int8_matmul.mm_fused`` Pallas idiom) — this module generalizes it to
the Gram shape: per-column symmetric int8 codes, ``qᵀq`` accumulated in
f32 (int32 per k-tile — exact), the per-column scales applied as a
rank-1 outer product on the (D, D) result.

Selection is the PLANNER's call, not the caller's: the fused-fit pass
(:mod:`keystone_tpu.plan.fused_fit`) measures the quantization error on
its probe features (:func:`gram_quantization_error`, relative Frobenius
error of the probe Gram) and only picks int8 when the error is under
``KEYSTONE_GRAM_INT8_MAX_ERR`` AND the device's int8 rate beats fp32
(:func:`keystone_tpu.plan.costs.int8_gram_speedup`) — otherwise it
falls back to the exact fp32 Gram and records the decision. The
``KEYSTONE_GRAM_OP`` env knob (``auto`` | ``fp32`` | ``int8``)
overrides.

Like ``mm_fused``, the Pallas kernel runs compiled on TPU; on the CPU
:func:`ata_int8` takes the XLA int8→int32 dot instead (interpret mode
is opt-in for tests) — same numerics either way.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from keystone_tpu.ops.quantization import symmetric_int8

ENV_GRAM_OP = "KEYSTONE_GRAM_OP"
ENV_INT8_MAX_ERR = "KEYSTONE_GRAM_INT8_MAX_ERR"
_DEFAULT_INT8_MAX_ERR = 0.03


def gram_op_request() -> str:
    """The requested Gram operator: ``KEYSTONE_GRAM_OP`` env knob,
    normalized to ``auto`` | ``fp32`` | ``int8`` (unknown → auto)."""
    raw = os.environ.get(ENV_GRAM_OP, "").strip().lower()
    return raw if raw in ("fp32", "int8") else "auto"


def int8_error_threshold() -> float:
    """Max relative Gram quantization error the planner accepts before
    falling back to fp32 (``KEYSTONE_GRAM_INT8_MAX_ERR``)."""
    raw = os.environ.get(ENV_INT8_MAX_ERR, "").strip()
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    return _DEFAULT_INT8_MAX_ERR


def ata_fp32(a) -> jnp.ndarray:
    """The exact default Gram operator: ``aᵀa`` in f32."""
    a = a.astype(jnp.float32)
    return a.T @ a


def _quantize_cols(a):
    """Per-COLUMN symmetric int8 (scales pool over rows): the Gram's
    (i, j) entry then reconstructs as ``s_i s_j · (qᵀq)_{ij}``. Masked
    (zero) pad rows quantize to zero codes and contribute nothing."""
    q, scale = symmetric_int8(a, reduce_axes=(0,))  # scale (1, D)
    return q, scale


def ata_int8_xla(a) -> jnp.ndarray:
    """XLA form of the quantized Gram: int8 codes contracted with an
    int32 accumulator (exact — |q| ≤ 127), scaled back to f32. The
    non-TPU half of :func:`ata_int8`; also the reference the kernel is
    tested against."""
    q, scale = _quantize_cols(a)
    qtq = jax.lax.dot_general(
        q,
        q,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return qtq.astype(jnp.float32) * (scale[0][:, None] * scale[0][None, :])


def _ata_kernel(x1_ref, x2_ref, o_ref, acc_ref, *, n_k: int):
    """One (bm, bn) output tile of qᵀq; grid = (D/bm, D/bn, N/bk) with
    the row (contraction) dimension k sequential. The int8 codes stream
    from HBM as int8 (the economics — ¼ the f32 bytes) and contract on
    the row axis via ``dot_general``; each k-step's partial product is
    exact in int32 (≤ bk·127² < 2²⁴) and folds into the f32 VMEM
    accumulator."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    prod = jax.lax.dot_general(
        x1_ref[...],
        x2_ref[...],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    acc_ref[...] += prod.astype(jnp.float32)

    @pl.when(k == n_k - 1)
    def _finalize():
        o_ref[...] = acc_ref[...]


def _pad_dim(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit, static_argnames=("block_d", "block_k", "interpret")
)
def ata_int8_pallas(
    a,
    *,
    block_d: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``AᵀA`` with per-column int8 codes streamed through a Pallas
    kernel (f32 accumulation) — the Gram-shaped generalization of
    ``int8_matmul.mm_fused``. ``a``: (N, D) float; returns (D, D) f32.
    """
    if interpret is None:
        from keystone_tpu.ops.flash_attention import interpret_default

        interpret = interpret_default()
    n, d = a.shape
    q, scale = _quantize_cols(a)
    # int8 tiles are (32, 128)-granular; rows pad to the k block (zero
    # codes contribute nothing), columns to the d block and trimmed back
    q = _pad_dim(_pad_dim(q, 0, block_k), 1, block_d)
    n_pad, d_pad = q.shape
    n_k = n_pad // block_k

    qtq = pl.pallas_call(
        functools.partial(_ata_kernel, n_k=n_k),
        grid=(d_pad // block_d, d_pad // block_d, n_k),
        in_specs=[
            pl.BlockSpec((block_k, block_d), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_k, block_d), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d_pad, d_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, block_d), jnp.float32)],
        # the two D-tile axes are independent; k is the sequential
        # accumulator dim — declaring it lets Mosaic pipeline the int8
        # HBM loads across steps (same contract as mm_fused)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, q)
    qtq = qtq[:d, :d]
    return qtq * (scale[0][:, None] * scale[0][None, :])


def ata_int8(a) -> jnp.ndarray:
    """The planner-selectable int8 Gram operator: the Pallas kernel on a
    one-chip TPU, the XLA int32 dot on the CPU and wherever there is
    more than one device — identical numerics, chosen at trace time
    (``gram_fn`` is jit-static, so each backend compiles its own form).
    Under a mesh the chunk arrives row-sharded, and GSPMD can partition
    the XLA dot but not a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"); until the kernel is shard_mapped over
    the data axis (per-shard qᵀq, then psum) many devices take the XLA
    form."""
    from keystone_tpu.ops.flash_attention import on_tpu

    if on_tpu() and jax.device_count() == 1:
        return ata_int8_pallas(a)
    return ata_int8_xla(a)


def gram_quantization_error(a) -> float:
    """Worst per-column quantization error of int8 codes on a probe
    slice, relative to the column's TYPICAL magnitude:
    ``max_col (amax_col/127) / (√12 · median|col|_nonzero)`` — the RMS
    rounding noise of a column's codes over the scale of the mass that
    actually carries the normal equations' signal.

    Norm-relative metrics (Gram Frobenius ratio, whole-matrix RMS) are
    blind to exactly the failure int8 Grams have: one heavy-tailed row
    blows a column's scale so every other entry quantizes to zero, yet
    the outlier dominates the norms too, so the ratio stays tiny. The
    median-of-nonzeros denominator is what the outlier can't move, and
    the max over columns is deliberate — a single destroyed column
    poisons every weight the solve produces through it. ~0.01 on
    well-scaled gaussian or relu features; orders of magnitude past any
    threshold once a column's amax dwarfs its typical value. Host-side
    eager; probe-sized inputs only.
    """
    a = np.abs(np.asarray(a, np.float32))
    if a.size == 0:
        return 0.0
    amax = a.max(axis=0)
    step_rms = amax / 127.0 / np.sqrt(12.0)
    worst = 0.0
    for j in range(a.shape[1]):
        col = a[:, j]
        nz = col[col > 0]
        if nz.size == 0:
            continue
        worst = max(worst, float(step_rms[j] / np.median(nz)))
    return worst
