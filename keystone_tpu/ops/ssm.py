"""State-space mixer (Mamba-2): what a hybrid LM block calls in place of
attention.

The reference has no sequence model at all (SURVEY section 5). A layer
whose state is a recurrence has three parts, each here once:

- :func:`causal_conv`: a depthwise causal convolution over the last few
  positions (zeros before position 0);
- :func:`ssd_scan`: the selective scan ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t B_t^T``, ``y_t = S_t C_t`` in its chunked form: inside a chunk
  of ``chunk`` positions the decay-masked ``chunk x chunk`` block of
  ``C_t . B_s`` times ``dt x``, across chunks the ``P x N`` state a head
  carries. On a TPU the forward is one Pallas kernel, ``ssd_chunk``
  (a sequence's chunks in order on the grid, the state in VMEM scratch,
  the ``chunk x chunk`` blocks made and dropped in VMEM; it reads x, dt,
  B and C positions-major, as the mixer holds them, and makes ``dt x``
  and the running decay itself, so XLA moves nothing around it); off it
  the same sums run as ``jax.numpy``, a chunk at a time. Both return the state
  entering every chunk, and the backward recomputes each chunk from it: a
  short scan carries the state's cotangent backwards over the chunks, then
  every chunk's gradients come from ``jax.vjp`` of the chunk's own sums, a
  group of heads at a time so that the ``chunk x chunk`` blocks alive at
  once stay a few hundred MB;
- :func:`gated_rms_norm`: ``w * rmsnorm(y * silu(z))`` over each of
  the mixer's groups of ``inner / groups`` channels (the gate before the
  norm; one group is the whole inner width).

:class:`Mamba2Mixer` holds the weights and strings the parts together
under ``jax.named_scope``s ``ssm_in_proj``, ``ssm_conv``, ``ssm_scan``
(``ssm_scan_bwd`` in the backward), ``ssm_gate_norm``, ``ssm_out_proj``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from keystone_tpu.core.treenode import static_field, treenode
from keystone_tpu.ops.flash_attention import (
    _vmem_limit_bytes,
    interpret_default,
    on_tpu,
)

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b

# the step program's counters of its state-space layers: positions
# scanned, chunks run, and positions whose scan ran in the kernel on the
# mixer's own layout (0 on the jax.numpy path), summed over layers
COUNTERS = ("ssm_rows", "ssm_chunks", "ssm_kernel_rows")

# heads a program of the forward kernel runs (at 8 the grid's steps are a
# sixth of its time), and heads whose chunk x chunk blocks the backward
# holds at once
_KERNEL_HEADS = 16
_HEAD_BLOCK = 8
_LANES = 128


def causal_conv(x, w, b=None):
    """Depthwise causal convolution. x: (B, S, C); w: (C, K); b: (C,) or
    None. ``out[t] = b + sum_j w[:, j] * x[t - (K - 1) + j]``, positions
    before 0 read as zero. float32 out."""
    k = w.shape[1]
    s = x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    out = sum(xp[:, j : j + s] * wf[:, j] for j in range(k))
    return out if b is None else out + b.astype(jnp.float32)


def step_size(dt, bias):
    """A head's step: ``softplus(dt + bias)`` in float32, never clamped."""
    return jax.nn.softplus(dt.astype(jnp.float32) + bias.astype(jnp.float32))


def gated_rms_norm(y, z, scale, eps: float, groups: int = 1):
    """``scale * rmsnorm(y * silu(z))`` over each of ``groups`` equal
    parts of the last axis on its own (one group: the whole axis),
    statistics in float32, in ``y``'s dtype."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    if groups > 1:
        g = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(y.shape) * scale.astype(jnp.float32)).astype(y.dtype)


# ------------------------------------------------------------ the chunk's sums

def _chunk_sums(x, dt, la, b, c, s_prev):
    """Every chunk given the state entering it. x: (Z, C, L, E, P); dt,
    la: (Z, C, L, E) float32 (``la = dt * A``, the log of a step's
    decay); b, c: (Z, C, L, N); s_prev: (Z, C, E, P, N) float32. Returns
    (y (Z, C, L, E, P) float32, the state leaving each chunk). Products
    take operands in ``x``'s dtype and accumulate in float32; decays are
    float32."""
    cdt, f32 = x.dtype, jnp.float32
    n_l = x.shape[2]
    cum = jnp.cumsum(la, axis=2)  # (Z, C, L, E)
    cum_e = jnp.moveaxis(cum, 3, 2)  # (Z, C, E, L)
    tot = cum_e[..., -1]  # (Z, C, E)
    cb = jnp.einsum("zcln,zcsn->zcls", c, b, preferred_element_type=f32)
    seen = jnp.tril(jnp.ones((n_l, n_l), bool))
    diff = cum_e[..., :, None] - cum_e[..., None, :]  # (Z, C, E, t, s)
    decay = jnp.exp(jnp.where(seen, diff, -jnp.inf))
    m = (cb[:, :, None] * decay).astype(cdt)
    xdt = (x.astype(f32) * dt[..., None]).astype(cdt)
    y = jnp.einsum("zcels,zcsep->zclep", m, xdt, preferred_element_type=f32)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "zcln,zcepn->zclep", c, s_prev.astype(cdt), preferred_element_type=f32
    )
    xw = (xdt.astype(f32) * jnp.exp(tot[:, :, None] - cum)[..., None]).astype(cdt)
    s_next = jnp.exp(tot)[..., None, None] * s_prev + jnp.einsum(
        "zclep,zcln->zcepn", xw, b, preferred_element_type=f32
    )
    return y, s_next


def _in_chunks(a, n_l: int):
    """(Z, S, ...) -> (Z, S / L, L, ...)."""
    return a.reshape(a.shape[0], a.shape[1] // n_l, n_l, *a.shape[2:])


def _forward_jnp(x, dt, la, b, c, n_l: int):
    """The chunked form a chunk at a time: (y (Z, S, E, P) in x's dtype,
    the state entering each chunk (Z, C, E, P, N) float32)."""
    z, _s, e, p = x.shape
    parts = tuple(
        jnp.moveaxis(_in_chunks(a, n_l), 1, 0)[:, :, None]
        for a in (x, dt, la, b, c)
    )  # each (C, Z, 1, L, ...)

    def step(state, part):
        y, nxt = _chunk_sums(*part, state[:, None])
        return nxt[:, 0], (y[:, 0], state)

    start = jnp.zeros((z, e, p, b.shape[-1]), jnp.float32)
    _last, (y, states) = lax.scan(step, start, parts)
    y = jnp.moveaxis(y, 0, 1).reshape(x.shape)
    return y.astype(x.dtype), jnp.moveaxis(states, 0, 1)


# ------------------------------------------------------------ the kernel

def _running_sum(a):
    """Running sum down the rows of a float32 (L, E) block in log2(L)
    shifted adds: every add is a float32 add, on the chip as here (an MXU
    product of float32 operands would round them to bfloat16 pieces)."""
    row = lax.broadcasted_iota(jnp.int32, a.shape, 0)
    k = 1
    while k < a.shape[0]:
        a = a + jnp.where(row >= k, pltpu.roll(a, k, 0), 0.0)
        k *= 2
    return a


def _ssd_chunk_kernel(x_ref, dt_ref, la_ref, b_ref, c_ref, y_ref, st_ref, state,
                      dtc, cumc, cumr, *, heads: int, p: int, tile: int):
    """One program per (sequence, chunk, block of ``heads`` heads); the
    chunks of a sequence run in order and ``state`` carries every head's
    (P, N) state from one to the next, heads down its rows. A chunk's
    first program makes the running sum of the log decays of all its
    heads, and leaves it (and ``dt``) in scratch a head block apart, as
    columns (``cumc``, ``dtc``) and as rows (``cumr``), so that
    ``exp(cum_t - cum_s)`` needs no transpose a head and a program reads
    its own heads by a leading index (Mosaic slices lanes at static
    offsets only). The ``tile`` heads whose ``p`` lanes fill a lane tile
    (two heads of 64) are read, multiplied and written together: each
    has its own ``chunk x chunk`` block, and ``spread`` picks for every
    lane the result of the head it belongs to."""
    f32 = jnp.float32
    n_l, n, w = x_ref.shape[1], b_ref.shape[2], tile * p
    k, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _chunk():
        @pl.when(k == 0)
        def _start():
            state[...] = jnp.zeros_like(state)

        cum, dt = _running_sum(la_ref[0]), dt_ref[0]  # (L, E)
        cumr[...] = cum.T
        for g in range(dt.shape[1] // heads):
            cumc[g] = cum[:, g * heads : (g + 1) * heads]
            dtc[g] = dt[:, g * heads : (g + 1) * heads]

    bm, cm = b_ref[0], c_ref[0]  # (L, N)
    cdt = bm.dtype
    cb = lax.dot_general(cm, bm, _NT, preferred_element_type=f32)  # [t, s]
    seen = lax.broadcasted_iota(jnp.int32, (n_l, n_l), 0) >= lax.broadcasted_iota(
        jnp.int32, (n_l, n_l), 1
    )
    # the first of this program's heads (0 where one block holds them all)
    first = pl.multiple_of(j * heads, heads) if cumc.shape[0] > 1 else 0
    cols, dts = cumc[j], dtc[j]  # (L, heads)
    rows = cumr[pl.ds(first, heads), :]  # (heads, L)
    lane_head = lax.broadcasted_iota(jnp.int32, (1, w), 1) // p
    row_head = lax.broadcasted_iota(jnp.int32, (w, 1), 0) // p

    def spread(parts, head_of):
        out = parts[0]
        for q in range(1, tile):
            out = jnp.where(head_of == q, parts[q], out)
        return out

    for g in range(heads // tile):
        hs = range(g * tile, (g + 1) * tile)
        lanes = slice(g * w, (g + 1) * w)
        cc = [cols[:, h : h + 1] for h in hs]  # (L, 1) each
        # (1, 1): a head's whole log decay over the chunk, its last running sum
        tot = [cols[n_l - 1 : n_l, h : h + 1] for h in hs]
        dt = spread([dts[:, h : h + 1] for h in hs], lane_head)
        xdt = (x_ref[0, :, lanes].astype(f32) * dt).astype(cdt)  # (L, w)
        at = pl.multiple_of(first * p + g * w, w)
        s_prev = state[pl.ds(at, w), :]  # (w, N): these heads' states
        st_ref[0, 0, lanes, :] = s_prev
        ys = []
        for c_t, h in zip(cc, hs):
            decay = jnp.where(seen, jnp.exp(jnp.minimum(c_t - rows[h : h + 1, :], 0.0)), 0.0)
            ys.append(jnp.dot((cb * decay).astype(cdt), xdt, preferred_element_type=f32))
        y = spread(ys, lane_head) + spread([jnp.exp(c_t) for c_t in cc], lane_head) * (
            lax.dot_general(cm, s_prev.astype(cdt), _NT, preferred_element_type=f32)
        )
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        left = spread([jnp.exp(t - c_t) for t, c_t in zip(tot, cc)], lane_head)
        xw = (xdt.astype(f32) * left).astype(cdt)
        # along the state's lanes first: Mosaic has no broadcast of one
        # element along sublanes and lanes at once
        keep = spread([jnp.exp(jnp.broadcast_to(t, (1, n))) for t in tot], row_head)
        state[pl.ds(at, w), :] = keep * s_prev + lax.dot_general(
            xw, bm, _TN, preferred_element_type=f32
        )


def _head_block(e: int, p: int) -> tuple[int, int]:
    """(heads a program of the kernel runs, heads to a lane tile)."""
    hb = _KERNEL_HEADS if e % _KERNEL_HEADS == 0 else e
    tile = min(_LANES // p, hb) if p < _LANES else 1
    return hb, tile if hb % tile == 0 else 1


def ssd_chunk(x, dt, la, b, c, n_l: int):
    """The forward as the Pallas kernel: same arguments and results as
    :func:`_forward_jnp`, read and written as the mixer holds them:
    positions in front of heads, a block of x or y ``n_l`` positions by
    a head block's ``hb * P`` lanes, ``dt`` and ``la`` a chunk of every
    head. XLA prepares nothing for it. ``S`` is a multiple of ``n_l``."""
    z, s, e, p = x.shape
    n = b.shape[-1]
    hb, tile = _head_block(e, p)
    n_c = s // n_l
    f32 = jnp.float32
    interpret = interpret_default()

    def of_chunk(width):
        return pl.BlockSpec((1, n_l, width), lambda i, k, j: (i, k, 0))

    by_heads = pl.BlockSpec((1, n_l, hb * p), lambda i, k, j: (i, k, j))
    with jax.named_scope("ssd_chunk"):
        y, states = pl.pallas_call(
            functools.partial(_ssd_chunk_kernel, heads=hb, p=p, tile=tile),
            grid=(z, n_c, e // hb),
            in_specs=[by_heads, of_chunk(e), of_chunk(e), of_chunk(n), of_chunk(n)],
            out_specs=(
                by_heads,
                pl.BlockSpec((1, 1, hb * p, n), lambda i, k, j: (i, k, j, 0)),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((z, s, e * p), x.dtype),
                jax.ShapeDtypeStruct((z, n_c, e * p, n), f32),
            ),
            scratch_shapes=[
                pltpu.VMEM((e * p, n), f32),
                pltpu.VMEM((e // hb, n_l, hb), f32),
                pltpu.VMEM((e // hb, n_l, hb), f32),
                pltpu.VMEM((e, n_l), f32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=None if interpret else _vmem_limit_bytes(),
            ),
            interpret=interpret,
            name="ssd_chunk",
        )(x.reshape(z, s, e * p), dt, la, b, c)
    return y.reshape(x.shape), states.reshape(z, n_c, e, p, n)


# ------------------------------------------------------------ forward, backward

def _use_kernel(n_l: int, e: int, p: int) -> bool:
    # the kernel's row blocks, and a head block of x, are whole lane tiles
    return on_tpu() and n_l % _LANES == 0 and (_head_block(e, p)[0] * p) % _LANES == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, dt, la, b, c, n_l: int):
    return _ssd_fwd(x, dt, la, b, c, n_l)[0]


def _ssd_fwd(x, dt, la, b, c, n_l: int):
    forward = ssd_chunk if _use_kernel(n_l, *x.shape[2:]) else _forward_jnp
    y, states = forward(x, dt, la, b, c, n_l)
    return y, (x, dt, la, b, c, states)


def _ssd_bwd(n_l: int, saved, dy):
    """Gradients from the states that entered the chunks. The state's
    cotangent obeys ``dS_in[k] = exp(tot_k) dS_in[k + 1]' + G_k`` with
    ``G_k`` what chunk k's own outputs read of the state; after that
    short scan every chunk is on its own."""
    x, dt, la, b, c, states = saved
    f32 = jnp.float32
    z, s, e, p = x.shape
    with jax.named_scope("ssm_scan_bwd"):
        xs, dts, las, bs, cs, dys = (_in_chunks(a, n_l) for a in (x, dt, la, b, c, dy))
        cum = jnp.cumsum(las, axis=2)
        tot = cum[:, :, -1]  # (Z, C, E)
        # what each chunk's y reads of the state entering it
        read = jnp.einsum(
            "zclep,zcln->zcepn",
            (dys.astype(f32) * jnp.exp(cum)[..., None]).astype(x.dtype), cs,
            preferred_element_type=f32,
        )

        def back(d_out, part):
            t, g = part
            return jnp.exp(t)[..., None, None] * d_out + g, d_out

        # d_next[k]: cotangent of the state leaving chunk k (zero after
        # the last: nothing reads the final state)
        _first, d_next = lax.scan(
            back, jnp.zeros_like(read[:, 0]),
            (jnp.moveaxis(tot, 1, 0), jnp.moveaxis(read, 1, 0)), reverse=True,
        )
        d_next = jnp.moveaxis(d_next, 0, 1)  # (Z, C, E, P, N)

        hb = _HEAD_BLOCK if e % _HEAD_BLOCK == 0 else e

        def heads_first(a, axis):
            # (..., E, ...) -> (E / hb, ..., hb, ...)
            a = a.reshape(*a.shape[:axis], e // hb, hb, *a.shape[axis + 1 :])
            return jnp.moveaxis(a, axis, 0)

        def group(part):
            xg, dtg, lag, sg, dyg, dng = part
            _out, vjp = jax.vjp(
                lambda xx, dd, ll, bb, cc: _chunk_sums(xx, dd, ll, bb, cc, sg),
                xg, dtg, lag, bs, cs,
            )
            return vjp((dyg.astype(f32), dng))

        dx, ddt, dla, db, dc = lax.map(
            group,
            (
                heads_first(xs, 3), heads_first(dts, 3), heads_first(las, 3),
                heads_first(states, 2), heads_first(dys, 3), heads_first(d_next, 2),
            ),
        )

        def heads_back(a, axis):
            a = jnp.moveaxis(a, 0, axis)
            return a.reshape(*a.shape[:axis], e, *a.shape[axis + 2 :])

        dx = heads_back(dx, 3).reshape(x.shape).astype(x.dtype)
        ddt = heads_back(ddt, 3).reshape(dt.shape).astype(dt.dtype)
        dla = heads_back(dla, 3).reshape(la.shape).astype(la.dtype)
        db = jnp.sum(db, axis=0).reshape(b.shape).astype(b.dtype)
        dc = jnp.sum(dc, axis=0).reshape(c.shape).astype(c.dtype)
    return dx, ddt, dla, db, dc


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a, b, c, chunk: int = 256):
    """The selective scan from a zero state. x: (B, S, H, P); dt: (B, S,
    H) float32, positive; a: (H,) float32, negative; b, c: (B, S, G, N)
    with H a multiple of G (head ``h`` reads group ``h // (H / G)``).
    Returns y (B, S, H, P) in x's dtype: ``y_t = S_t C_t``, without the
    skip ``D x_t``. ``S`` need not be a multiple of ``chunk``: padded
    positions have ``dt = 0``, which neither decays nor feeds the state."""
    n, s, h, p = x.shape
    g, st = b.shape[2], b.shape[3]
    e = h // g
    n_l = min(chunk, s)
    pad = (-s) % n_l
    la = dt * a  # the log of each step's decay

    def groups_in_front(t, per_head: bool):
        # (B, S, G * E, ...) or (B, S, G, N) -> (B * G, S, ...), padded
        t = t.reshape(n, s, g, e, *t.shape[3:]) if per_head else t
        t = jnp.moveaxis(t, 2, 1).reshape(n * g, s, *t.shape[3:])
        return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))

    y = _ssd(
        groups_in_front(x, True), groups_in_front(dt, True),
        groups_in_front(la, True), groups_in_front(b, False),
        groups_in_front(c, False), n_l,
    )
    y = y[:, :s].reshape(n, g, s, e, p)
    return jnp.moveaxis(y, 1, 2).reshape(n, s, h, p)


# ------------------------------------------------------------ the mixer

@treenode
class Mamba2Mixer:
    """A Mamba-2 mixer's weights (no projection has a bias) and what is
    static of it. ``inner = heads * head_dim``; the convolution runs over
    ``inner + 2 * groups * state`` channels (x, B and C)."""

    w_in: jnp.ndarray  # (d, 2 * inner + 2 * groups * state + heads)
    conv_w: jnp.ndarray  # (inner + 2 * groups * state, K)
    conv_b: jnp.ndarray | None
    dt_bias: jnp.ndarray  # (heads,)
    A_log: jnp.ndarray  # (heads,): A = -exp(A_log)
    D: jnp.ndarray  # (heads,): the skip
    norm: jnp.ndarray  # (inner,): the gated norm's scale
    w_out: jnp.ndarray  # (inner, d)
    heads: int = static_field(default=1)
    head_dim: int = static_field(default=64)
    state: int = static_field(default=128)
    groups: int = static_field(default=1)
    chunk: int = static_field(default=256)
    eps: float = static_field(default=1e-5)

    @staticmethod
    def create(key, d: int, *, heads: int, head_dim: int, state: int,
               groups: int = 1, conv: int = 4, conv_bias: bool = True,
               chunk: int = 256, eps: float = 1e-5) -> "Mamba2Mixer":
        """Seeded weights: matrices normal at 1/sqrt(fan_in); the conv
        uniform in +-1/sqrt(K); ``A_log = log U[1, 16]``; ``dt_bias`` the
        inverse softplus of a log-uniform dt in [1e-3, 1e-1]; ``D`` and
        the norm's scale 1."""
        inner = heads * head_dim
        channels = inner + 2 * groups * state
        ks = jax.random.split(key, 6)
        bound = 1.0 / math.sqrt(conv)
        dt = jnp.exp(
            jax.random.uniform(ks[4], (heads,), jnp.float32)
            * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3)
        )
        return Mamba2Mixer(
            w_in=jax.random.normal(ks[0], (d, 2 * inner + 2 * groups * state + heads))
            / math.sqrt(d),
            conv_w=jax.random.uniform(ks[1], (channels, conv), jnp.float32, -bound, bound),
            conv_b=jax.random.uniform(ks[2], (channels,), jnp.float32, -bound, bound)
            if conv_bias else None,
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            A_log=jnp.log(jax.random.uniform(ks[3], (heads,), jnp.float32, 1.0, 16.0)),
            D=jnp.ones((heads,), jnp.float32),
            norm=jnp.ones((inner,), jnp.float32),
            w_out=jax.random.normal(ks[5], (inner, d)) / math.sqrt(inner),
            heads=heads, head_dim=head_dim, state=state, groups=groups,
            chunk=chunk, eps=eps,
        )

    def __call__(self, y, mesh=None, mm_fn=None):
        """y: (B, S, d) in the compute dtype -> ((B, S, d), the layer's
        counters). Under a ``mesh`` whose ``data`` axis divides the
        batch the scan is shard_mapped over it on a TPU (GSPMD cannot
        partition a Mosaic kernel)."""
        if mm_fn is None:
            from keystone_tpu.ops.quantization import mm as mm_fn
        n, s, _ = y.shape
        cdt, f32 = y.dtype, jnp.float32
        inner, gn = self.heads * self.head_dim, self.groups * self.state
        with jax.named_scope("ssm_in_proj"):
            zxbcdt = mm_fn(y, self.w_in, cdt)
            z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * gn], axis=-1)
        with jax.named_scope("ssm_conv"):
            xbc = jax.nn.silu(causal_conv(xbc, self.conv_w, self.conv_b)).astype(cdt)
            x, b, c = jnp.split(xbc, [inner, inner + gn], axis=-1)
        x = x.reshape(n, s, self.heads, self.head_dim)
        b = b.reshape(n, s, self.groups, self.state)
        c = c.reshape(n, s, self.groups, self.state)
        dt = step_size(dt, self.dt_bias)
        a = -jnp.exp(self.A_log.astype(f32))
        scan = functools.partial(ssd_scan, chunk=self.chunk)
        if mesh is not None and on_tpu():
            from jax.sharding import PartitionSpec as P

            by_row = P("data" if n % dict(mesh.shape).get("data", n + 1) == 0 else None)
            scan = jax.shard_map(
                scan, mesh=mesh, in_specs=(by_row, by_row, P(), by_row, by_row),
                out_specs=by_row, check_vma=False,  # pallas_call outputs carry no vma
            )
        with jax.named_scope("ssm_scan"):
            out = scan(x, dt, a, b, c)
            out = out.astype(f32) + self.D.astype(f32)[:, None] * x.astype(f32)
        with jax.named_scope("ssm_gate_norm"):
            # one group is the whole width: the norm called as it always was
            groups = (self.groups,) if self.groups > 1 else ()
            out = gated_rms_norm(
                out.reshape(n, s, inner).astype(cdt), z, self.norm, self.eps, *groups
            )
        with jax.named_scope("ssm_out_proj"):
            out = mm_fn(out, self.w_out, cdt)
        n_l = min(self.chunk, s)
        in_kernel = _use_kernel(n_l, self.heads // self.groups, self.head_dim)
        return out, {
            "ssm_rows": jnp.int32(n * s),
            "ssm_chunks": jnp.int32(n * -(-s // n_l)),
            "ssm_kernel_rows": jnp.int32(n * s if in_kernel else 0),
        }
