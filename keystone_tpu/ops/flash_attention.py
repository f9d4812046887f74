"""Pallas TPU flash attention — fused blockwise attention kernels.

The jnp attention in :mod:`keystone_tpu.ops.attention` materializes the
(S_q, S_k) score matrix in HBM; on TPU the arithmetic intensity of
attention is set by how much of that traffic can stay in VMEM. These
kernels fuse the score gemm, online softmax, and value gemm into one
VMEM-resident pass (flash-attention schedule):

- :func:`flash_attention` — full attention, grid over (batch*heads,
  query blocks), K/V streamed through VMEM block by block with a running
  (max, sum, accumulator) online softmax.
- :func:`flash_attention_step` — one K/V block's contribution with the
  online-softmax state (m, l, acc) carried in and out. This is the fused
  inner step of ring attention: the ring loop keeps K/V rotating via
  ``ppermute`` (XLA collectives over ICI) and calls this kernel per hop.
- :func:`flash_attention_bwd` — the training backward of long sequences:
  one kernel that holds a K/V head's keys in VMEM and recomputes each
  live score block there, for dq, dk and dv at once.

All run compiled on TPU and in Pallas interpret mode on the CPU (the
8-device test mesh), selected automatically. Numerics: scores and the
online-softmax state are always float32; masked positions use a large
negative finite constant so no ±inf arithmetic appears in the kernel.

Reference: the reference framework has no attention (SURVEY.md §5 — out of
scope for parity); this is part of the beyond-parity long-context stack.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # masked-score value: exp(_NEG - m) underflows to exactly 0
_LANE = 128
# queries by keys of one score block of the forward, where the caller
# passes none (read at call time)
_BLOCK_Q = _BLOCK_K = 512


def on_tpu() -> bool:
    """True on TPU hardware — the flash-by-default policy in
    :mod:`keystone_tpu.ops.attention` and the other operator choices
    that follow the MXU."""
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """The one place that decides compiled vs interpret for every Pallas
    kernel in the repo: interpret mode on the CPU only. On any other
    backend the kernel is lowered for real, and one that fails to lower
    raises — it never quietly runs interpreted on a chip."""
    return jax.default_backend() == "cpu"


@functools.cache
def _vmem_limit_bytes() -> int | None:
    """Mosaic scoped-VMEM limit to request for this process's device,
    from the device table (None keeps the compiler's 16 MiB default).
    Raising it lets the K/V-resident flash variant keep whole heads in
    VMEM at long context. A device the table does not hold raises."""
    from keystone_tpu.plan.costs import device_peaks

    return device_peaks().vmem_limit


def _kv_vmem_budget() -> int:
    """K+V bytes above which K/V is streamed instead of held resident.

    Mosaic double-buffers every windowed input, so residency costs
    2x(K+V) + q/out double-buffers + softmax temporaries against the
    scoped limit (measured on v5e: K+V of 8MB OOMs a 16MB limit at
    16.25MB — exactly the 2x plus overhead)."""
    limit = _vmem_limit_bytes()
    if limit is None:
        return 6 * 1024 * 1024  # 2x6 + overhead < 16MB default
    return limit // 3  # 2x budget + overhead comfortably under limit


def _pad_to(x, axis: int, mult: int, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _flash_kernel_fori(
    scalars_ref,  # (3,) int32: [s_k_valid, q_offset, k_offset]
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, s_k_pad, d) — K/V resident in VMEM for this head
    v_ref,
    o_ref,  # (1, block_q, d)
    *maybe_lse,  # (1, block_q, LANE) lse output when with_lse
    scale: float,
    block_k: int,
    causal: bool,
    with_lse: bool = False,
    window: int = 0,
):
    """K/V-resident variant: one program per q block, fori over K blocks.

    Faster than grid-streaming K when K/V fit VMEM (no per-step grid
    overhead, no scratch churn); selected automatically by size. With a
    causal ``window`` the loop starts at the first K block that holds a
    key some query of this block may see: blocks outside the window are
    skipped, not masked.
    """
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    num_k = k_ref.shape[1] // block_k

    s_k_valid = scalars_ref[0]
    q_start = scalars_ref[1] + pl.program_id(1) * block_q
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    if causal:
        # skip K blocks entirely above the diagonal (dense attention pays
        # compute for the full rectangle)
        num_k_live = jnp.clip(
            (q_start + block_q - scalars_ref[2] + block_k - 1) // block_k,
            0,
            num_k,
        )
    else:
        num_k_live = num_k
    first_k_live = 0
    if window:
        first_k_live = jnp.clip(
            (q_start - (window - 1) - scalars_ref[2]) // block_k, 0, num_k
        )

    q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        k_pos = (
            scalars_ref[2]
            + j * block_k
            + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        )
        valid = k_pos < s_k_valid
        if causal:
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        if window:
            valid = jnp.logical_and(valid, q_pos - k_pos < window)
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # explicit zero on masked lanes: when a row is fully masked m_new
        # stays at the _NEG init and exp(s - m_new) alone would be 1
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l, acc

    m0 = jnp.full((block_q, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = lax.fori_loop(
        first_k_live, num_k_live, body, (m0, l0, acc0)
    )
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if with_lse:
        # row logsumexp of the masked scaled scores — the O(S) residual
        # the backward kernel needs (fully masked rows stay at _NEG)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        maybe_lse[0][0] = jnp.broadcast_to(lse, maybe_lse[0].shape[1:])


def _flash_kernel_stream(
    scalars_ref,  # (3,) int32: [s_k_valid, q_offset, k_offset]
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, block_k, d) — streamed via the sequential grid dim
    v_ref,
    o_ref,  # (1, block_q, d)
    *rest,  # [(1, block_q, LANE) lse out when with_lse], then the three
    # scratch refs: m (block_q, LANE), l (block_q, LANE), acc (block_q, d)
    scale: float,
    causal: bool,
    with_lse: bool = False,
    window: int = 0,
):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    kk = pl.program_id(2)
    num_k = pl.num_programs(2)

    s_k_valid = scalars_ref[0]
    q_start = scalars_ref[1] + pl.program_id(1) * block_q
    k_start = scalars_ref[2] + kk * block_k

    @pl.when(kk == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # K blocks entirely above the causal diagonal contribute nothing; the
    # pipeline still streams them but the MXU work is skipped (dense
    # attention pays compute for the full rectangle)
    live = k_start < q_start + block_q if causal else True
    if window:
        # nor does a block that ends before the first query's window
        live = jnp.logical_and(
            live, k_start + block_k > q_start - (window - 1)
        )

    @pl.when(live)
    def _step():
        q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)
        k_blk, v_blk = k_ref[0], v_ref[0]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        q_pos = q_start + lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0
        )
        k_pos = k_start + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        valid = k_pos < s_k_valid
        if causal:
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        if window:
            valid = jnp.logical_and(valid, q_pos - k_pos < window)
        s = jnp.where(valid, s, _NEG)
        m = m_scr[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l, l_scr.shape)

    @pl.when(kk == num_k - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if with_lse:
            lse = m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-30))
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    window: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    q_offset=0,
    k_offset=0,
    mxu_dtype=None,
    kv_resident: bool | None = None,
    interpret: bool | None = None,
    return_lse: bool = False,
):
    """Fused attention. q: (B, H, S_q, D); k, v: (B, KV, S_k, D) with
    ``H % KV == 0``: query head ``h`` reads K/V head ``h // (H / KV)``
    through the kernels' index maps, so grouped-query K and V are never
    repeated up to H heads (consecutive programs of one group find their
    K/V block already in VMEM).

    ``window > 0`` (with ``causal``) lets query ``i`` see keys
    ``i - window + 1 .. i`` only; K blocks outside are skipped.

    ``return_lse=True`` additionally returns the per-row logsumexp of the
    masked scaled scores, (B, H, S_q) float32 — the O(S) residual the
    kernel training backward consumes (computed in-kernel from the
    online-softmax state; costs one extra lane-tile write, not a sweep).

    ``kv_resident`` forces the K/V-in-VMEM variant (True) or the
    streamed long-context variant (False); default None picks by the
    scoped-VMEM budget.

    ``mxu_dtype=jnp.bfloat16`` feeds the two gemms bf16 inputs (float32
    accumulation and softmax state) for ~2x MXU rate at ~1e-3 output
    error; default None keeps the gemms in the input precision.

    ``q_offset``/``k_offset`` give the global positions of the local q/k
    windows for causal masking (used when sequence shards carry different
    ranges, e.g. under Ulysses head-sharding the offsets stay 0 because
    each chip sees full sequences). Exact (== dense softmax attention).
    """
    if interpret is None:
        interpret = interpret_default()
    if block_q is None:
        block_q = _BLOCK_Q
    if block_k is None:
        block_k = _BLOCK_K
    b, h, s_q, d = q.shape
    kvh, s_k = k.shape[1], k.shape[2]
    if h % kvh or v.shape[1] != kvh:
        raise ValueError(
            f"{h} query heads over {kvh} / {v.shape[1]} K/V heads"
        )
    if window and not causal:
        raise ValueError("a window is causal: pass causal=True")
    g = h // kvh  # query heads per K/V head
    scale = 1.0 / math.sqrt(d)
    out_dtype = q.dtype

    # clamp to the sequence, rounded UP to a multiple of 8: Mosaic needs
    # 8-aligned f32 sublane tiles, and a short unaligned sequence (e.g.
    # ViT's 196 patches) would otherwise become the block shape itself
    block_q = -(-min(block_q, max(s_q, 8)) // 8) * 8
    block_k = -(-min(block_k, max(s_k, 8)) // 8) * 8

    if mxu_dtype is not None:
        # cast on the XLA side: halves the K/V HBM→VMEM stream for bf16
        q, k, v = (x.astype(mxu_dtype) for x in (q, k, v))
    qf = _pad_to(q.reshape(b * h, s_q, d), 1, block_q)
    kf = _pad_to(k.reshape(b * kvh, s_k, d), 1, block_k)
    vf = _pad_to(v.reshape(b * kvh, s_k, d), 1, block_k)
    # zero-padding D is free: extra K columns don't change scores, extra V
    # columns produce zero output columns that are sliced away
    qf = _pad_to(qf, 2, _LANE)
    kf = _pad_to(kf, 2, _LANE)
    vf = _pad_to(vf, 2, _LANE)
    s_q_pad, d_pad = qf.shape[1], qf.shape[2]
    s_k_pad = kf.shape[1]

    scalars = jnp.array([s_k + k_offset, q_offset, k_offset], jnp.int32)
    vmem_limit = None if interpret else _vmem_limit_bytes()
    kv_bytes = 2 * s_k_pad * d_pad * kf.dtype.itemsize
    if kv_resident is None:
        budget = 6 * 1024 * 1024 if interpret else _kv_vmem_budget()
        kv_resident = kv_bytes <= budget
    out_shape = jax.ShapeDtypeStruct((b * h, s_q_pad, d_pad), out_dtype)
    lse_shape = jax.ShapeDtypeStruct((b * h, s_q_pad, _LANE), jnp.float32)
    if kv_resident:
        # K/V resident in VMEM per program — lowest overhead
        out_spec = pl.BlockSpec(
            (1, block_q, d_pad), lambda i, j, *_: (i, j, 0)
        )
        lse_spec = pl.BlockSpec(
            (1, block_q, _LANE), lambda i, j, *_: (i, j, 0)
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, s_q_pad // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d_pad), lambda i, j, *_: (i, j, 0)),
                pl.BlockSpec(
                    (1, s_k_pad, d_pad), lambda i, j, *_: (i // g, 0, 0)
                ),
                pl.BlockSpec(
                    (1, s_k_pad, d_pad), lambda i, j, *_: (i // g, 0, 0)
                ),
            ],
            out_specs=(out_spec, lse_spec) if return_lse else out_spec,
        )
        kernel = functools.partial(
            _flash_kernel_fori, scale=scale, block_k=block_k, causal=causal,
            with_lse=return_lse, window=window,
        )
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit,
        )
    else:
        # long-context: stream K/V block-by-block through the pipelined
        # sequential grid dimension, state in VMEM scratch
        out_spec = pl.BlockSpec(
            (1, block_q, d_pad), lambda i, j, kk, *_: (i, j, 0)
        )
        lse_spec = pl.BlockSpec(
            (1, block_q, _LANE), lambda i, j, kk, *_: (i, j, 0)
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, s_q_pad // block_q, s_k_pad // block_k),
            in_specs=[
                pl.BlockSpec(
                    (1, block_q, d_pad), lambda i, j, kk, *_: (i, j, 0)
                ),
                pl.BlockSpec(
                    (1, block_k, d_pad), lambda i, j, kk, *_: (i // g, kk, 0)
                ),
                pl.BlockSpec(
                    (1, block_k, d_pad), lambda i, j, kk, *_: (i // g, kk, 0)
                ),
            ],
            out_specs=(out_spec, lse_spec) if return_lse else out_spec,
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANE), jnp.float32),
                pltpu.VMEM((block_q, _LANE), jnp.float32),
                pltpu.VMEM((block_q, d_pad), jnp.float32),
            ],
        )
        kernel = functools.partial(
            _flash_kernel_stream, scale=scale, causal=causal,
            with_lse=return_lse, window=window,
        )
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit,
        )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(out_shape, lse_shape) if return_lse else out_shape,
        compiler_params=compiler_params,
        interpret=interpret,
    )(scalars, qf, kf, vf)
    if return_lse:
        out, lse = res
        return (
            out[:, :s_q, :d].reshape(b, h, s_q, d),
            lse[:, :s_q, 0].reshape(b, h, s_q),
        )
    return res[:, :s_q, :d].reshape(b, h, s_q, d)


def _flash_step_kernel(
    scalars_ref,  # (3,) int32: [q_offset, k_offset, valid-K end]
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, s_k, d)
    v_ref,  # (1, s_k, d)
    m_ref,  # (1, block_q, LANE) broadcast state
    l_ref,
    acc_ref,  # (1, block_q, d)
    m_out,
    l_out,
    acc_out,
    *,
    scale: float,
    block_k: int,
    causal: bool,
):
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    num_k = k_ref.shape[1] // block_k

    q = q_ref[0].astype(jnp.float32) * scale
    q_start = scalars_ref[0] + pl.program_id(1) * block_q
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    if causal:
        num_k_live = jnp.clip(
            (q_start + block_q - scalars_ref[1] + block_k - 1) // block_k,
            0,
            num_k,
        )
    else:
        num_k_live = num_k

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        k_pos = (
            scalars_ref[1]
            + j * block_k
            + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        )
        valid = k_pos < scalars_ref[2]  # mask zero-padded K positions
        if causal:
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # explicit zero on masked lanes: when a row is fully masked m_new
        # stays at the _NEG init and exp(s - m_new) alone would be 1
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l, acc

    m0 = m_ref[0, :, :1]
    l0 = l_ref[0, :, :1]
    m, l, acc = lax.fori_loop(0, num_k_live, body, (m0, l0, acc_ref[0]))
    m_out[0] = jnp.broadcast_to(m, (block_q, m_out.shape[2]))
    l_out[0] = jnp.broadcast_to(l, (block_q, l_out.shape[2]))
    acc_out[0] = acc


def flash_attention_step(
    q,
    k_blk,
    v_blk,
    m,
    l,
    acc,
    *,
    q_offset,
    k_offset,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 128,
    padded_state: bool = False,
    interpret: bool | None = None,
):
    """One fused online-softmax update: attend q over a single K/V block.

    State: m, l of shape (B, H, S_q) and acc of shape (B, H, S_q, D),
    always float32 (initialize m to a large negative value, l and acc to
    zeros). Returns updated (m, l, acc); finalize with ``acc / l``. The
    offsets are the *global* sequence positions of the q and k windows —
    traced values are fine (ring attention passes axis_index-derived
    offsets). Shards that don't tile evenly into blocks are zero-padded
    (padded K positions are masked; padded q rows are sliced away).

    With ``padded_state`` the m/l state is carried as (B, H, S_q, LANE)
    float32 — the kernel's native VMEM tile — so a multi-hop caller (ring
    attention) avoids re-broadcasting lane-1 state to 128 lanes and
    re-slicing it on every hop; only column 0 is meaningful.
    """
    if interpret is None:
        interpret = interpret_default()
    b, h, s_q, d = q.shape
    s_k = k_blk.shape[2]
    scale = 1.0 / math.sqrt(d)
    block_q = -(-min(block_q, max(s_q, 8)) // 8) * 8
    block_k = -(-min(block_k, max(s_k, 8)) // 8) * 8

    qf = _pad_to(q.reshape(b * h, s_q, d), 1, block_q)
    kf = _pad_to(k_blk.reshape(b * h, s_k, d), 1, block_k)
    vf = _pad_to(v_blk.reshape(b * h, s_k, d), 1, block_k)
    qf = _pad_to(qf, 2, _LANE)
    kf = _pad_to(kf, 2, _LANE)
    vf = _pad_to(vf, 2, _LANE)
    s_q_pad, d_pad = qf.shape[1], qf.shape[2]
    s_k_pad = kf.shape[1]
    # state rides as (BH, S_q, LANE)/(BH, S_q, d_pad) VMEM-tiled arrays
    if padded_state:
        mf = _pad_to(
            m.reshape(b * h, s_q, _LANE).astype(jnp.float32), 1, block_q
        )
        lf = _pad_to(
            l.reshape(b * h, s_q, _LANE).astype(jnp.float32), 1, block_q
        )
    else:
        mf = _pad_to(
            jnp.broadcast_to(
                m.reshape(b * h, s_q, 1), (b * h, s_q, _LANE)
            ).astype(jnp.float32),
            1,
            block_q,
        )
        lf = _pad_to(
            jnp.broadcast_to(
                l.reshape(b * h, s_q, 1), (b * h, s_q, _LANE)
            ).astype(jnp.float32),
            1,
            block_q,
        )
    accf = _pad_to(
        _pad_to(acc.reshape(b * h, s_q, d), 2, _LANE).astype(jnp.float32),
        1,
        block_q,
    )

    scalars = jnp.stack(
        [
            jnp.asarray(q_offset, jnp.int32),
            jnp.asarray(k_offset, jnp.int32),
            jnp.asarray(k_offset + s_k, jnp.int32),  # valid-K end
        ]
    )
    qspec = pl.BlockSpec((1, block_q, d_pad), lambda i, j, *_: (i, j, 0))
    kspec = pl.BlockSpec((1, s_k_pad, d_pad), lambda i, j, *_: (i, 0, 0))
    sspec = pl.BlockSpec((1, block_q, _LANE), lambda i, j, *_: (i, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, s_q_pad // block_q),
        in_specs=[qspec, kspec, kspec, sspec, sspec, qspec],
        out_specs=(sspec, sspec, qspec),
    )
    m2, l2, acc2 = pl.pallas_call(
        functools.partial(
            _flash_step_kernel, scale=scale, block_k=block_k, causal=causal
        ),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b * h, s_q_pad, _LANE), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s_q_pad, _LANE), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s_q_pad, d_pad), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=None if interpret else _vmem_limit_bytes(),
        ),
        interpret=interpret,
    )(scalars, qf, kf, vf, mf, lf, accf)
    if padded_state:
        return (
            m2[:, :s_q, :].reshape(b, h, s_q, _LANE),
            l2[:, :s_q, :].reshape(b, h, s_q, _LANE),
            acc2[:, :s_q, :d].reshape(b, h, s_q, d),
        )
    return (
        m2[:, :s_q, 0].reshape(b, h, s_q),
        l2[:, :s_q, 0].reshape(b, h, s_q),
        acc2[:, :s_q, :d].reshape(b, h, s_q, d),
    )


# bytes budget for the dense-recompute backward's transient (S_q, S_k)
# tensors (~4 of them, f32, per (b, h)): above this the backward kernel
# takes over
_DENSE_BWD_MAX_BYTES = 4 << 30


def _dense_bwd_bytes(q, k) -> int:
    b, h, s_q, _ = q.shape
    return 4 * 4 * b * h * s_q * k.shape[2]


def _bwd_mask(q_pos, k_pos, s_k_valid, causal: bool):
    """(rows, blk) validity mask for one KV block (padding and
    causality).

    Causal positions are BEGIN-aligned (q_pos = i, k_pos = j), matching
    the flash forward's offset convention at q_offset = k_offset = 0; the
    trainable wrapper rejects causal s_q != s_k, where begin- and
    end-aligned conventions diverge."""
    valid = (k_pos < s_k_valid)[None, :]
    if causal:
        valid = valid & (q_pos[:, None] >= k_pos[None, :])
    return valid


def _grads_rect(qf, kp, vp, gf, delta, lse, q_off, s_k_valid, causal, block,
                k_off=0):
    """Rectangle sweep of the ring backward (``ops/attention.py``, whose
    offsets are traced) over one q range: a ``jnp`` scan
    over the given (padded) K/V blocks, recomputing each score block from
    (q, k, lse). ``qf`` / ``gf`` are (B, KV, G, S_q, D) and ``kp`` /
    ``vp`` (B, KV, S_k, D): the G query heads of a K/V head are swept
    as G·S_q rows against that head's keys, so grouped K and V are never
    repeated and dk / dv sum over the group in the product itself.
    Positions are global begin-aligned (q_off / k_off = the global
    position of the first q / k row — nonzero k_off serves the ring
    backward's rotating K/V shards).
    Returns (dq, dk, dv) for this rectangle, dk/dv over kp's full padded
    length. Peak memory O(S·d) state + O(G·S_q·block) transient."""
    b, kvh, grp, s_q, d = qf.shape
    scale = 1.0 / math.sqrt(d)
    nb = kp.shape[2] // block
    kb = jnp.moveaxis(kp.reshape(b, kvh, nb, block, d), 2, 0)
    vb = jnp.moveaxis(vp.reshape(b, kvh, nb, block, d), 2, 0)
    rows = grp * s_q
    qf = qf.reshape(b, kvh, rows, d)
    gf = gf.reshape(b, kvh, rows, d)
    delta = delta.reshape(b, kvh, rows)
    lse = lse.reshape(b, kvh, rows)
    q_pos = jnp.tile(q_off + jnp.arange(s_q), grp)

    def step(dq, inp):
        kblk, vblk, j = inp
        kf = kblk.astype(jnp.float32)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
        k_pos = k_off + j * block + jnp.arange(block)
        mask = _bwd_mask(q_pos, k_pos, s_k_valid, causal)
        p = jnp.where(mask, jnp.exp(scores - lse[..., None]), 0.0)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vblk.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        dq = dq + scale * jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
        dk_j = scale * jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((b, kvh, rows, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(step, dq0, (kb, vb, jnp.arange(nb)))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, kvh, nb * block, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, kvh, nb * block, d)
    return dq.reshape(b, kvh, grp, s_q, d), dk, dv


_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _flash_bwd_kernel(
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, seg, d): this K/V head's segment, resident in VMEM
    v_ref,
    g_ref,  # (1, block_q, d): the output's cotangent
    lse_ref,  # (1, 1, block_q) float32 rows
    delta_ref,
    dq_ref,  # (1, 1, block_q, d)
    dk_ref,  # (1, seg, d) float32, resident: summed over the group's
    dv_ref,  # heads and the q blocks
    *,
    scale: float,
    block_k: int,
    causal: bool,
    window: int,
    s_k: int,
):
    """One program per (K/V head, K segment, query head of the group, q
    block): sweeps the K blocks of the segment that this q block sees and
    no others. A score block is made, used and dropped in VMEM, keys on
    sublanes and queries on lanes, so that ``lse`` and ``delta`` come in
    as rows and only ``ds`` is transposed (for ``dq``)."""
    block_q = q_ref.shape[1]
    seg = k_ref.shape[1]
    num_k = seg // block_k
    q_start = pl.program_id(3) * block_q
    k0 = pl.program_id(1) * seg

    @pl.when(jnp.logical_and(pl.program_id(2) == 0, pl.program_id(3) == 0))
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    # live blocks [lo, hi) of this segment; among them [full_lo, full_hi)
    # hold no masked pair and skip the mask's arithmetic
    lo, hi = 0, num_k
    full_hi = (s_k - k0) // block_k
    if causal:
        hi = jnp.clip((q_start + block_q - k0 + block_k - 1) // block_k, 0, num_k)
        full_hi = jnp.minimum(full_hi, (q_start - k0 + 1) // block_k)
    full_lo = lo
    if window:
        lo = jnp.clip((q_start - (window - 1) - k0) // block_k, 0, num_k)
        full_lo = (q_start + block_q - 1 - window - k0) // block_k + 1
    full_lo = jnp.clip(full_lo, lo, hi)
    full_hi = jnp.clip(full_hi, full_lo, hi)

    q, g = q_ref[0], g_ref[0]
    # the forward's scores, rounding and all: lse is theirs
    qs = q * jnp.asarray(scale, q.dtype)
    lse, delta = lse_ref[0], delta_ref[0]
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, (1, block_q), 1)

    def step(j, dq, masked: bool):
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k_blk, v_blk = k_ref[0, rows, :], v_ref[0, rows, :]
        x = lax.dot_general(
            k_blk, qs, _NT, preferred_element_type=jnp.float32
        ) - lse  # (block_k, block_q)
        if masked:
            k_pos = (
                k0 + j * block_k
                + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
            )
            valid = k_pos < s_k
            if causal:
                valid = jnp.logical_and(valid, q_pos >= k_pos)
            if window:
                valid = jnp.logical_and(valid, q_pos - k_pos < window)
            x = jnp.where(valid, x, _NEG)
        p = jnp.exp(x)
        dv_ref[0, rows, :] += jnp.dot(
            p.astype(g.dtype), g, preferred_element_type=jnp.float32
        )
        dp = lax.dot_general(
            v_blk, g, _NT, preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_ref[0, rows, :] += jnp.dot(
            ds, qs, preferred_element_type=jnp.float32
        )
        return dq + lax.dot_general(
            ds, k_blk, _TN, preferred_element_type=jnp.float32
        )

    edge = functools.partial(step, masked=True)
    dq = jnp.zeros(q.shape, jnp.float32)
    dq = lax.fori_loop(lo, full_lo, edge, dq)
    dq = lax.fori_loop(full_lo, full_hi, functools.partial(step, masked=False), dq)
    dq = lax.fori_loop(full_hi, hi, edge, dq)
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


# queries by keys of one score block of the backward kernel. On the chip
# (v5e, head 128, bfloat16, S = 8192, PR 29) 512 x 512 ran a causal
# layer's backward in 26.7 ms and a window-512 layer's in 11.0 ms;
# 256 x 256 took 47.0 and 13.8 ms, 1024 x 1024 26.9 ms (causal)
_BWD_BLOCK = 512


def _bwd_blocks(s_q: int, s_k: int, d_pad: int, itemsize: int):
    """(block_q, block_k, K blocks a segment) of the backward kernel,
    from the shape. A segment of K and V stays in VMEM with its float32
    dk and dv, each double-buffered, inside half the scoped limit."""
    block_q = min(_BWD_BLOCK, -(-s_q // 8) * 8)
    block_k = min(_BWD_BLOCK, -(-s_k // 8) * 8)
    limit = None if interpret_default() else _vmem_limit_bytes()
    held = 2 * 2 * d_pad * (itemsize + 4)  # bytes a key of K, V, dk, dv
    fit = max(1, ((limit or 16 << 20) // 2) // (held * block_k))
    num_k = -(-s_k // block_k)
    segments = -(-num_k // fit)  # of equal length, so the last pads least
    return block_q, block_k, -(-num_k // segments)


def flash_attention_bwd(q, k, v, g, out, lse, *, causal: bool, window: int = 0):
    """(dq, dk, dv) of :func:`flash_attention` from its residuals, as one
    Pallas kernel: FlashAttention's backward with a K/V head's keys held
    in VMEM. Score blocks the mask kills are skipped; a K/V head's dk
    and dv sum over its query heads inside the kernel; the products take
    operands in the inputs' dtype and accumulate in float32. Keys past
    what VMEM holds are swept a segment at a time, each segment's dq
    summed outside. Positions are begin-aligned at offset 0."""
    interpret = interpret_default()
    b, h, s_q, d = q.shape
    kvh, s_k = k.shape[1], k.shape[2]
    grp = h // kvh
    scale = 1.0 / math.sqrt(d)
    d_pad = -(-d // _LANE) * _LANE
    block_q, block_k, seg_blocks = _bwd_blocks(
        s_q, s_k, d_pad, q.dtype.itemsize
    )
    seg = seg_blocks * block_k
    n_seg = -(-s_k // seg)

    def rows(x, heads, block):
        x = _pad_to(x.reshape(b * heads, x.shape[2], d), 1, block)
        return _pad_to(x, 2, _LANE)

    qf, gf = rows(q, h, block_q), rows(g, h, block_q)
    kf, vf = rows(k, kvh, seg), rows(v, kvh, seg)
    s_q_pad = qf.shape[1]
    # delta_i = sum_d g * out: the softmax jacobian's diagonal term
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def row_vectors(x):
        return _pad_to(x.reshape(b * h, 1, s_q), 2, block_q)

    q_spec = pl.BlockSpec(
        (1, block_q, d_pad), lambda i, sg, gg, j: (i * grp + gg, j, 0)
    )
    kv_spec = pl.BlockSpec((1, seg, d_pad), lambda i, sg, gg, j: (i, sg, 0))
    row_spec = pl.BlockSpec(
        (1, 1, block_q), lambda i, sg, gg, j: (i * grp + gg, 0, j)
    )
    kv_shape = jax.ShapeDtypeStruct((b * kvh, n_seg * seg, d_pad), jnp.float32)
    with jax.named_scope("attn_bwd"):
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _flash_bwd_kernel, scale=scale, block_k=block_k,
                causal=causal, window=window, s_k=s_k,
            ),
            grid=(b * kvh, n_seg, grp, s_q_pad // block_q),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=(
                pl.BlockSpec(
                    (1, 1, block_q, d_pad),
                    lambda i, sg, gg, j: (sg, i * grp + gg, j, 0),
                ),
                kv_spec,
                kv_spec,
            ),
            out_shape=(
                jax.ShapeDtypeStruct(
                    (n_seg, b * h, s_q_pad, d_pad),
                    q.dtype if n_seg == 1 else jnp.float32,
                ),
                kv_shape,
                kv_shape,
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(
                    "parallel", "arbitrary", "arbitrary", "arbitrary"
                ),
                vmem_limit_bytes=None if interpret else _vmem_limit_bytes(),
            ),
            interpret=interpret,
        )(qf, kf, vf, gf, row_vectors(lse), row_vectors(delta))
    dq = dq[0] if n_seg == 1 else jnp.sum(dq, axis=0)
    return (
        dq[:, :s_q, :d].reshape(q.shape).astype(q.dtype),
        dk[:, :s_k, :d].reshape(k.shape).astype(k.dtype),
        dv[:, :s_k, :d].reshape(v.shape).astype(v.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_trainable(q, k, v, causal: bool = False, window: int = 0):
    """Differentiable fused attention: Pallas flash forward, recompute
    backward. q: (B, H, S, D); k, v: (B, KV, S, D) with H a multiple of
    KV (grouped-query attention without repeating K and V); ``window``
    as in :func:`flash_attention`.

    Nothing S²-sized persists between the forward and the backward (with
    per-layer remat that's what bounds memory ACROSS the step). The
    backward recomputes attention one of two ways, by the shape:

    - short context (transient bytes ≤ ``_DENSE_BWD_MAX_BYTES``, counting
      the B·H multiplier): save only (q, k, v) and differentiate the
      dense formulation — a few transient (S_q, S_k) tensors, fastest at
      sizes where they fit;
    - long context: the forward kernel also emits the row logsumexp
      (O(S), in-kernel, no extra sweep), and :func:`flash_attention_bwd`
      makes dq/dk/dv from (q, k, v, out, lse) in one Pallas kernel whose
      score blocks never leave VMEM. Peak memory O(S·d), which is what
      makes 32k+ causal *training* fit a single chip.
    """
    return flash_attention(q, k, v, causal=causal, window=window)


def _flash_trainable_fwd(q, k, v, causal: bool, window: int = 0):
    if causal and q.shape[2] != k.shape[2]:
        # the flash forward masks begin-aligned (q_pos >= k_pos at offset
        # 0) while dense_attention's tril is end-aligned — the two only
        # agree at s_q == s_k, and the backward kernel assumes the
        # forward's convention. Reject rather than return wrong grads.
        raise ValueError(
            f"flash_attention_trainable: causal cross-attention with "
            f"s_q={q.shape[2]} != s_k={k.shape[2]} is ambiguous"
        )
    if _dense_bwd_bytes(q, k) <= _DENSE_BWD_MAX_BYTES:
        # short context: the dense backward needs only (q, k, v)
        out = flash_attention(q, k, v, causal=causal, window=window)
        return out, (q, k, v, None, None)
    out, lse = flash_attention(
        q, k, v, causal=causal, window=window, return_lse=True
    )
    return out, (q, k, v, out, lse)


def _flash_trainable_bwd(causal: bool, window: int, res, g):
    q, k, v, out, lse = res
    if out is None:
        from keystone_tpu.ops.attention import dense_attention

        _, vjp = jax.vjp(
            lambda q, k, v: dense_attention(
                q, k, v, causal=causal, window=window
            ),
            q, k, v,
        )
        return vjp(g)
    return flash_attention_bwd(
        q, k, v, g, out, lse, causal=causal, window=window
    )


flash_attention_trainable.defvjp(_flash_trainable_fwd, _flash_trainable_bwd)
