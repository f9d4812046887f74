"""Attention with sequence/context parallelism over the mesh.

The reference has no sequence models (SURVEY.md §5), but long-context is
first-class here: two standard distributed-attention strategies scale the
sequence axis across chips, with collectives riding ICI:

- :func:`ring_attention` — blockwise attention with K/V blocks rotating
  around the mesh axis via ``ppermute`` while each chip keeps its query
  shard; a numerically-stable online softmax (flash-style running max/sum)
  accumulates across ring steps. Memory per chip is O(S/n · S/n) per step
  instead of O(S²).
- :func:`ulysses_attention` — all-to-all resharding: swap sequence-sharding
  for head-sharding (``lax.all_to_all``), run dense local attention over
  full sequences on 1/n of the heads, swap back.

Both are exact (== dense attention) and composable under jit; tests verify
equality on an 8-device mesh. ``dense_attention`` is the single-chip
reference implementation.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _check_seq_divisible(q, mesh, seq_axis: str) -> None:
    """Loud precondition shared by ring/Ulysses — shard_map's own error
    for a non-divisible spec is opaque."""
    n = mesh.shape[seq_axis]
    if q.shape[2] % n:
        raise ValueError(
            f"sequence length {q.shape[2]} not divisible by the "
            f"{seq_axis!r} axis ({n} devices)"
        )


def _flash_default() -> bool:
    """Fused Pallas kernels by default on real TPU hardware only."""
    from keystone_tpu.ops.flash_attention import on_tpu

    return on_tpu()


def dense_attention(q, k, v, *, causal: bool = False, window: int = 0):
    """Reference multi-head attention. q: (B, H, S, D); k, v: (B, KV, S,
    D) with H a multiple of KV: query head ``h`` reads K/V head
    ``h // (H / KV)``, grouped in the products and never repeated.
    ``window > 0`` (causal only) lets query ``i`` see keys
    ``i - window + 1 .. i``."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    if window and not causal:
        raise ValueError("a window is causal: pass causal=True")
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, kvh, h // kvh, sq, d)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window:
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqs,bksd->bkgqd", p, v).reshape(b, h, sq, v.shape[-1])


def _ring_fwd_state(q, k, v, *, axis_name: str, causal: bool,
                    use_flash: bool):
    """Ring forward returning (out, lse). lse is the per-row logsumexp of
    the full (all-hops) masked score matrix, (B, H, S_local) f32 — the
    O(S) residual the ring backward consumes; fully masked rows carry
    -1e30."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    scale = 1.0 / math.sqrt(d)

    q_pos = idx * s_local + jnp.arange(s_local)  # global query positions

    if use_flash:
        from keystone_tpu.ops.flash_attention import (
            _LANE,
            flash_attention_step,
        )

        # m/l carried in the kernel's native (…, LANE) tile across hops —
        # only column 0 is meaningful; avoids a 128x broadcast/slice of
        # the softmax state in and out of HBM on every ring step
        m = jnp.full((b, h, s_local, _LANE), -1e30, jnp.float32)
        l = jnp.zeros((b, h, s_local, _LANE), jnp.float32)
        acc = jnp.zeros((b, h, s_local, d), jnp.float32)
        k_blk, v_blk = k, v
        for step in range(n):
            owner = (idx - step) % n
            m, l, acc = flash_attention_step(
                q,
                k_blk,
                v_blk,
                m,
                l,
                acc,
                q_offset=idx * s_local,
                k_offset=owner * s_local,
                causal=causal,
                padded_state=True,
            )
            if step + 1 < n:
                perm = [(j, (j + 1) % n) for j in range(n)]
                k_blk = lax.ppermute(k_blk, axis_name, perm)
                v_blk = lax.ppermute(v_blk, axis_name, perm)
        out = (acc / jnp.maximum(l[..., :1], 1e-30)).astype(q.dtype)
        lse = m[..., 0] + jnp.log(jnp.maximum(l[..., 0], 1e-30))
        return out, lse

    # softmax state in f32 regardless of q.dtype: lse is load-bearing for
    # the trainable backward, and a bf16 lse (abs err ~0.04 at lse≈10)
    # would denormalize every recomputed probability row
    m = jnp.full((b, h, s_local, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, s_local, 1), jnp.float32)
    acc = jnp.zeros((b, h, s_local, d), jnp.float32)

    k_blk, v_blk = k, v
    for step in range(n):
        owner = (idx - step) % n  # which chip's K/V block we hold now
        scores = (
            jnp.einsum(
                "bhqd,bhkd->bhqk", q, k_blk,
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        if causal:
            k_pos = owner * s_local + jnp.arange(s_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask, scores, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) → nan
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - m_safe)
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        alpha = jnp.where(
            jnp.isfinite(m), jnp.exp(m - m_safe), jnp.zeros_like(m)
        )
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        m = m_new
        if step + 1 < n:
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    lse = jnp.where(
        jnp.isfinite(m[..., 0]),
        m[..., 0] + jnp.log(jnp.maximum(l[..., 0], 1e-30)),
        -1e30,
    )
    return out, lse


def _ring_attention_shard(
    q, k, v, *, axis_name: str, causal: bool, use_flash: bool
):
    """Per-shard ring attention body (runs under shard_map).

    q, k, v: (B, H, S_local, D) — this chip's sequence shard. With
    ``use_flash`` the per-hop blockwise update runs as the fused Pallas
    kernel (:func:`keystone_tpu.ops.flash_attention.flash_attention_step`);
    the K/V rotation stays an XLA ``ppermute`` over ICI either way.
    """
    return _ring_fwd_state(
        q, k, v, axis_name=axis_name, causal=causal, use_flash=use_flash
    )[0]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_shard_trainable(q, k, v, axis_name, causal, use_flash):
    """Differentiable per-shard ring attention: flash-rate forward, ring
    backward. The backward circulates each K/V shard around the ring a
    second time together with its grad accumulators — per hop it
    recomputes that rectangle's probabilities from (q, k, lse) with the
    blockwise machinery (never an (S, S) tensor), adds dq locally and
    dk/dv into the traveling accumulators, then one final ppermute brings
    every accumulator home. Exactly n extra ppermutes over ICI; memory
    O(S_local·d)."""
    return _ring_fwd_state(
        q, k, v, axis_name=axis_name, causal=causal, use_flash=use_flash
    )[0]


def _ring_trainable_fwd(q, k, v, axis_name, causal, use_flash):
    out, lse = _ring_fwd_state(
        q, k, v, axis_name=axis_name, causal=causal, use_flash=use_flash
    )
    return out, (q, k, v, out, lse)


# keys a block of the ring backward's sweep over one hop's K/V shard
_RING_BWD_BLOCK = 512


def _ring_trainable_bwd(axis_name, causal, use_flash, res, g):
    from keystone_tpu.ops.flash_attention import _grads_rect

    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    q_off = idx * s_local

    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)

    blk = min(_RING_BWD_BLOCK, -(-s_local // 8) * 8)
    pad = -(-s_local // blk) * blk - s_local

    dq = jnp.zeros((b, h, s_local, d), jnp.float32)
    k_blk, v_blk = k, v
    dk_blk = jnp.zeros((b, h, s_local, d), jnp.float32)
    dv_blk = jnp.zeros_like(dk_blk)
    perm = [(j, (j + 1) % n) for j in range(n)]
    for step in range(n):
        owner = (idx - step) % n
        k_off = owner * s_local

        def hop_grads(k_blk, v_blk, k_off):
            kp = jnp.pad(
                k_blk.astype(jnp.float32),
                ((0, 0), (0, 0), (0, pad), (0, 0)),
            )
            vp = jnp.pad(
                v_blk.astype(jnp.float32),
                ((0, 0), (0, 0), (0, pad), (0, 0)),
            )
            # _grads_rect sweeps (B, KV, G, S, D) query groups: here
            # every head has K and V of its own, a group of one
            dq_h, dk_h, dv_h = _grads_rect(
                qf[:, :, None], kp, vp, gf[:, :, None], delta[:, :, None],
                lse[:, :, None], q_off, k_off + s_local, causal, blk,
                k_off=k_off,
            )
            return dq_h[:, :, 0], dk_h, dv_h

        if causal:
            # hops whose K/V shard is entirely in this chip's future are
            # fully masked — skip their three dead gemm sweeps (the
            # ppermutes below stay unconditional: the ring must rotate)
            dq_c, dk_c, dv_c = lax.cond(
                owner <= idx,
                hop_grads,
                lambda k_, v_, o_: (
                    jnp.zeros_like(dq),
                    jnp.zeros((b, h, pad + s_local, d), jnp.float32),
                    jnp.zeros((b, h, pad + s_local, d), jnp.float32),
                ),
                k_blk, v_blk, k_off,
            )
        else:
            dq_c, dk_c, dv_c = hop_grads(k_blk, v_blk, k_off)
        dq = dq + dq_c
        dk_blk = dk_blk + dk_c[:, :, :s_local]
        dv_blk = dv_blk + dv_c[:, :, :s_local]
        if step + 1 < n:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            dk_blk = lax.ppermute(dk_blk, axis_name, perm)
            dv_blk = lax.ppermute(dv_blk, axis_name, perm)
    # after n-1 rotations shard s (and its accumulated grads) sits on chip
    # s-1; one final hop sends every accumulator home
    dk_blk = lax.ppermute(dk_blk, axis_name, perm)
    dv_blk = lax.ppermute(dv_blk, axis_name, perm)
    return dq.astype(q.dtype), dk_blk.astype(k.dtype), dv_blk.astype(v.dtype)


_ring_shard_trainable.defvjp(_ring_trainable_fwd, _ring_trainable_bwd)


def ring_attention(
    q,
    k,
    v,
    mesh: Mesh,
    *,
    seq_axis: str = "data",
    causal: bool = False,
    use_flash: bool | None = None,
    trainable: bool = False,
):
    """Exact attention with the sequence axis sharded over ``seq_axis``.

    q, k, v: (B, H, S, D) global arrays (S divisible by the axis size).
    ``use_flash`` selects the fused Pallas per-hop kernel (default: on
    when running on TPU). ``trainable`` swaps in the custom-VJP shard
    body (ring backward with traveling dk/dv accumulators) — required to
    differentiate the flash path (its kernels are forward-only), and
    blockwise-memory-bounded for the jnp path too.
    """
    if use_flash is None:
        use_flash = _flash_default()
    _check_seq_divisible(q, mesh, seq_axis)
    spec = P(None, None, seq_axis, None)
    if trainable:
        body = lambda q_, k_, v_: _ring_shard_trainable(  # noqa: E731
            q_, k_, v_, seq_axis, causal, use_flash
        )
    else:
        body = partial(
            _ring_attention_shard,
            axis_name=seq_axis,
            causal=causal,
            use_flash=use_flash,
        )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # pallas_call outputs carry no varying-mesh-axis metadata, and the
        # trainable backward's zero-initialized scan carries start
        # device-invariant before accumulating device-varying grads —
        # both trip the vma consistency check spuriously
        check_vma=not (use_flash or trainable),
    )
    return fn(q, k, v)


def _ulysses_shard(q, k, v, *, axis_name: str, causal: bool,
                   use_flash: bool, trainable: bool = False):
    """All-to-all sequence↔head resharding (DeepSpeed-Ulysses style).

    In: (B, H, S_local, D) sequence-sharded → all_to_all → (B, H/n, S, D)
    head-sharded → local attention over the full sequence (fused Pallas
    flash kernel on TPU, dense jnp otherwise) → all_to_all back.
    ``trainable`` uses the flash trainable wrapper for the local part —
    ``all_to_all`` is linear, so JAX transposes it in the backward on its
    own; only the attention kernel needs the custom VJP.
    """

    def seq_to_heads(x):
        # split heads across the axis, gather sequence
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    def heads_to_seq(x):
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if use_flash and trainable:
        from keystone_tpu.ops.flash_attention import (
            flash_attention_trainable,
        )

        out = flash_attention_trainable(qh, kh, vh, causal)
    elif use_flash:
        from keystone_tpu.ops.flash_attention import flash_attention

        out = flash_attention(qh, kh, vh, causal=causal)
    else:
        out = dense_attention(qh, kh, vh, causal=causal)
    return heads_to_seq(out)


def ulysses_attention(
    q,
    k,
    v,
    mesh: Mesh,
    *,
    seq_axis: str = "data",
    causal: bool = False,
    use_flash: bool | None = None,
    trainable: bool = False,
):
    """Exact attention via all-to-all head/sequence resharding.

    Requires H divisible by the axis size. Prefers ICI bandwidth over ring
    latency — the usual pick when heads are plentiful. ``trainable``
    makes the flash path differentiable (recompute backward).
    """
    if use_flash is None:
        use_flash = _flash_default()
    n = mesh.shape[seq_axis]
    if q.shape[1] % n:
        raise ValueError(f"heads ({q.shape[1]}) not divisible by axis ({n})")
    _check_seq_divisible(q, mesh, seq_axis)
    spec = P(None, None, seq_axis, None)
    fn = jax.shard_map(
        partial(
            _ulysses_shard,
            axis_name=seq_axis,
            causal=causal,
            use_flash=use_flash,
            trainable=trainable,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=not use_flash,
    )
    return fn(q, k, v)
