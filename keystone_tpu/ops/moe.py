"""Mixture-of-experts FFN: top-k routing without drops over the experts
held here.

The reference has no MoE (its expert-shaped pattern is the weighted
solver's one-class-per-partition solves,
``BlockWeightedLeastSquares.scala:228-263``, covered by
``ops/weighted_linear.py``). This layer is what a sparse LM block calls
in place of its dense FFN:

- every token is scored against **every** expert of the model and the
  ``top_k`` largest are kept. The scores are the layer's own (one matrix
  ``w_router`` at the published width, then a softmax or a sigmoid) or
  the caller's: ``__call__(x, mesh, scores=(p, select))`` takes the
  probabilities ``p`` and what the choice is made by, ``select`` (``p``
  plus a balancing bias, say), from a router that lives outside the
  layer, such as :class:`CarriedRouter`, an MLP fed by a state carried
  from layer to layer. The kept weights are renormalised to sum to one
  (``renormalize``, the default) or left as the chosen probabilities
  themselves (with one expert a token renormalising would make every
  gate 1 and cut the router off from the loss), then scaled by
  ``routed_scale``;
- the layer holds a contiguous share of the experts,
  ``first_expert .. first_expert + held``: one chip's share of an
  expert-parallel deployment, or all of them. It computes the part of
  the result that its own experts give. What the absent experts would
  have added is left out; on one chip the layer runs without the
  exchange that would bring other chips' tokens here;
- no capacity and no dropped token: the ``tokens x top_k`` assignments
  are sorted by expert, the token rows gathered in that order, and each
  expert's rows multiplied by its own matrices in one grouped matrix
  product (Pallas ``megablox.gmm``: its grid covers the row tiles of
  the held experts alone, so the work follows the rows routed here, not
  the static ``tokens x top_k`` bound); the results are brought back to
  token order and summed with the routing weights;
- where the held experts' share of the ``tokens x top_k`` rows is small
  (:func:`window_rows`), only a window of the sorted order moves: ``W``
  rows from the held experts' first, gathered, multiplied and added back
  into the tokens with their weights (:func:`_expert_windows`, one
  ``custom_vjp`` whose forward and backward each run a ``while_loop``
  over as many windows as the rows routed here need: no row is dropped,
  and no ``tokens x top_k`` array of width ``dim`` is made);
- an optional shared expert runs on every token beside the routed ones;
- the experts may live in a latent (``latent_down`` / ``latent_up``):
  the router reads the full-width row, the routed rows go down to the
  latent width, through the experts and, weighted and summed, back up;
  the shared expert stays at full width;
- an expert is SwiGLU (three matrices), GELU or, with ``activation``
  ``"relu2"``, ``relu(h w1)^2 w2`` (two matrices).

``__call__`` also returns five counters of the step program: the rows
routed to held experts, the largest load of a held expert, the rows the
grouped product ran over (whole row tiles), the rows put through the
grouped products (``dispatch_rows``: ``tokens x top_k``, or the windows
run times ``W``) and the windows run beyond the first
(``extra_windows``); a layer that does not renormalise adds
``gate_sum``, the sum of its gates over the rows routed to held experts.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from keystone_tpu.core.treenode import static_field, treenode

COUNTERS = (
    "routed_rows", "max_expert_rows", "mm_rows", "dispatch_rows", "extra_windows",
)

# The window is WINDOW_C times the held experts' even share of the
# ``tokens x top_k`` rows, in whole row tiles of the grouped product
# (PERF.md section 6, PR 39, says how it was chosen from the chip's
# counters). Where that is over half the rows, every row moves as before.
WINDOW_C = 1.375
_TM = 512  # the grouped product's largest row tile


def ffn(y, w1, w2, w3, cdt, mm_fn=None, activation: str = ""):
    """One feed-forward: SwiGLU ``(silu(y w1) * (y w3)) w2`` when ``w3``
    is there, else ``gelu(y w1) w2``, or ``relu(y w1)^2 w2`` under
    ``activation="relu2"``. Shared by the block's dense FFN and the
    shared expert."""
    if mm_fn is None:
        from keystone_tpu.ops.quantization import mm as mm_fn
    h = mm_fn(y, w1, cdt)
    if w3 is None:
        h = _act(h, None, activation)
    else:
        h = jax.nn.silu(h) * mm_fn(y, w3, cdt)
    return mm_fn(h, w2, cdt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xf, order, inv, k: int):
    """Token rows in sorted-assignment order: row ``r`` is token
    ``order[r] // k``. ``order`` is a permutation of the ``T*k``
    assignments and ``inv`` its inverse, so the backward is a gather
    too (TPU scatters are slow): no scatter-add into the tokens."""
    return xf[order // k]


def _dispatch_fwd(xf, order, inv, k):
    return xf[order // k], (order, inv, xf.shape[0])


def _dispatch_bwd(k, res, g):
    _order, inv, t = res
    return g[inv].reshape(t, k, g.shape[-1]).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect(ys, order, inv):
    """Sorted rows back in assignment order (the inverse permutation)."""
    return ys[inv]


def _collect_fwd(ys, order, inv):
    return ys[inv], (order,)


def _collect_bwd(res, g):
    return g[res[0]], None, None


_collect.defvjp(_collect_fwd, _collect_bwd)


def _row_tile(m: int, most: int = 512) -> int:
    """Largest power-of-two row tile, 8 to ``most``, that divides m (a
    multiple of 8)."""
    t = most
    while m % t:
        t //= 2
    return t


def window_rows(rows: int, held: int, num_experts: int) -> int:
    """Rows of the window the expert layer moves, from its shapes: 0
    where every one of the ``rows`` (``tokens x top_k``) moves, as it
    does where the held share is large or the rows are few (decode)."""
    w = -(-math.ceil(WINDOW_C * rows * held / num_experts) // _TM) * _TM
    return w if w <= rows // 2 else 0


def _tile(n: int, most: int = 1024) -> int:
    """The largest multiple of 128 up to ``most`` that divides ``n`` (896
    for an expert of 2688), else ``min(n, most)``: the kernel masks a
    last partial tile, but a whole one wastes nothing."""
    return next((t for t in range(most, 0, -128) if n % t == 0), min(n, most))


def _tiling(w, tm: int):
    """The grouped product's tiles for a weight stack (held, K, N), the
    same for its transposed product and its weight gradient, as
    ``megablox``'s own backward has them."""
    return (tm, _tile(w.shape[1]), _tile(w.shape[2]))


def grouped_mm(xs, w, group_sizes, first: int, tm: int):
    """``xs[rows of expert e] @ w[e - first]`` for the held experts,
    zeros elsewhere. xs: (M, K) sorted by expert; w: (held, K, N);
    group_sizes: (E,) rows of every expert of the model."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from keystone_tpu.ops.flash_attention import interpret_default

    with jax.named_scope("moe_grouped_mm"):
        return gmm(
            xs,
            w,
            group_sizes,
            xs.dtype,
            _tiling(w, tm),
            jnp.asarray(first, jnp.int32),
            None,
            False,
            interpret_default(),
        )


def _gmm(xs, w, sizes, transpose_rhs: bool = False):
    """One window's grouped product over the held experts (``sizes``:
    their rows in the window, then the rest, which comes out zero): the
    kernel itself, as :func:`_expert_windows` is its own ``custom_vjp``."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    from keystone_tpu.ops.flash_attention import interpret_default

    with jax.named_scope("moe_grouped_mm"):
        return gmm(
            xs, w, sizes, xs.dtype, _tiling(w, _TM), jnp.asarray(0, jnp.int32),
            None, transpose_rhs=transpose_rhs, interpret=interpret_default(),
        )


def _tgmm(xs, g, w, sizes):
    """``xs[rows of e].T @ g[rows of e]`` for each held expert: the
    gradient of ``w`` (held, K, N), in float32 to sum over windows."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    from keystone_tpu.ops.flash_attention import interpret_default

    with jax.named_scope("moe_grouped_mm"):
        return tgmm(
            xs.swapaxes(0, 1), g, sizes, w.dtype, _tiling(w, _TM),
            jnp.asarray(0, jnp.int32), w.shape[0],
            interpret=interpret_default(),
        ).astype(jnp.float32)


def _act(h1, h3, activation: str = ""):
    if activation == "relu2":
        return jnp.square(jax.nn.relu(h1))
    return jax.nn.gelu(h1) if h3 is None else jax.nn.silu(h1) * h3


def _window(i, weights, order, starts, ends, k: int, w: int):
    """Window ``i`` of the held experts' sorted rows: the assignments it
    covers (``order`` is padded by a window, so the slice never shifts),
    their tokens, their weights (0 past the rows routed here) and the
    held experts' rows in it, then the rest."""
    lo = starts[0] + i * w
    rows = jax.lax.dynamic_slice_in_dim(order, lo, w)
    here = jnp.clip(jnp.minimum(ends, lo + w) - jnp.maximum(starts, lo), 0, w)
    sizes = jnp.concatenate([here, (w - jnp.sum(here))[None]])
    valid = jnp.arange(w) < ends[-1] - lo
    wts = jnp.where(valid, weights.reshape(-1)[rows], 0.0)
    return rows, rows // k, wts, sizes, valid


def _num_windows(starts, ends, rows: int):
    return (ends[-1] - starts[0] + rows - 1) // rows


def _forward(xf, weights, w1, w3, w2, order, starts, ends, k: int, w: int,
             activation: str = ""):
    """The held experts' weighted sum (T, d) f32 over every window the
    rows routed here need."""

    def body(i, out):
        _rows, tok, wts, sizes, _valid = _window(i, weights, order, starts, ends, k, w)
        xs = xf[tok]
        h3 = None if w3 is None else _gmm(xs, w3, sizes)
        a = _act(_gmm(xs, w1, sizes), h3, activation)
        y = _gmm(a, w2, sizes).astype(jnp.float32)
        wts = wts.astype(xf.dtype).astype(jnp.float32)
        return out.at[tok].add(wts[:, None] * y)

    return jax.lax.fori_loop(
        0, _num_windows(starts, ends, w), body, jnp.zeros(xf.shape, jnp.float32)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _expert_windows(xf, weights, w1, w3, w2, order, starts, ends, k, w, activation=""):
    """xf (T, d), weights (T, k) f32, the held experts' stacks in the
    compute dtype, ``order`` the sorted assignments padded by a window,
    ``starts`` / ``ends`` the held experts' first and past-last sorted
    rows, ``k`` assignments a token, ``w`` rows a window, the experts'
    ``activation``: (T, d) f32. The backward runs the windows again and
    makes each window's hidden rows anew rather than keeping them."""
    return _forward(xf, weights, w1, w3, w2, order, starts, ends, k, w, activation)


def _expert_windows_fwd(xf, weights, w1, w3, w2, order, starts, ends, k, w,
                        activation=""):
    res = (xf, weights, w1, w3, w2, order, starts, ends)
    return _forward(*res, k, w, activation), res


def _expert_windows_bwd(k, w, activation, res, g):
    xf, weights, w1, w3, w2, order, starts, ends = res
    f32 = jnp.float32

    def body(i, acc):
        dx, dwts, dw1, dw3, dw2 = acc
        rows, tok, wts, sizes, valid = _window(i, weights, order, starts, ends, k, w)
        xs = xf[tok]
        h3 = None if w3 is None else _gmm(xs, w3, sizes)
        a, act_vjp = jax.vjp(
            functools.partial(_act, activation=activation), _gmm(xs, w1, sizes), h3
        )
        y = _gmm(a, w2, sizes).astype(f32)
        gt = g[tok]
        dwts = dwts.at[rows].add(jnp.where(valid, jnp.sum(gt * y, axis=-1), 0.0))
        dy = (wts.astype(xf.dtype).astype(f32)[:, None] * gt).astype(xf.dtype)
        dw2 = dw2 + _tgmm(a, dy, w2, sizes)
        dh1, dh3 = act_vjp(_gmm(dy, w2, sizes, transpose_rhs=True))
        dxs = _gmm(dh1, w1, sizes, transpose_rhs=True).astype(f32)
        dw1 = dw1 + _tgmm(xs, dh1, w1, sizes)
        if w3 is not None:
            dxs = dxs + _gmm(dh3, w3, sizes, transpose_rhs=True).astype(f32)
            dw3 = dw3 + _tgmm(xs, dh3, w3, sizes)
        return dx.at[tok].add(dxs), dwts, dw1, dw3, dw2

    zeros = lambda m: None if m is None else jnp.zeros(m.shape, f32)  # noqa: E731
    dx, dwts, dw1, dw3, dw2 = jax.lax.fori_loop(
        0, _num_windows(starts, ends, w), body,
        (jnp.zeros(xf.shape, f32), jnp.zeros(weights.size, f32),
         zeros(w1), zeros(w3), zeros(w2)),
    )
    cast = lambda d, m: None if m is None else d.astype(m.dtype)  # noqa: E731
    return (
        dx.astype(xf.dtype), dwts.reshape(weights.shape), cast(dw1, w1),
        cast(dw3, w3), cast(dw2, w2), None, None, None,
    )


_expert_windows.defvjp(_expert_windows_fwd, _expert_windows_bwd)


def _window_counters(starts, ends, w: int, total_rows: int):
    """``dispatch_rows``, ``extra_windows`` and the rows of whole tiles
    the windows' products visit (``mm_rows``), from the held experts'
    sorted rows: every window that can run is reckoned, those that do
    not run count nothing."""
    n = _num_windows(starts, ends, w)
    lo = starts[0] + w * jnp.arange(-(-total_rows // w))[:, None]
    s = jnp.clip(starts - lo, 0, w)
    e = jnp.clip(ends - lo, 0, w)
    tiles = jnp.where(e > s, -(-e // _TM) - s // _TM, 0)
    mm_rows = _TM * jnp.sum(jnp.where(jnp.arange(tiles.shape[0]) < n, tiles.sum(1), 0))
    return n * w, jnp.maximum(n - 1, 0), mm_rows


@treenode
class MoELayer:
    """Top-k routed expert FFN over the experts held here:
    (B, S, d) → (out (B, S, d), counters)."""

    w_router: jnp.ndarray  # (d, E): every expert of the model
    w1: jnp.ndarray  # (held, d, ff)
    w2: jnp.ndarray  # (held, ff, d)
    w3: jnp.ndarray | None = None  # (held, d, ff): SwiGLU's second input
    # the shared expert's matrices (every token, no gate), or None
    shared_w1: jnp.ndarray | None = None
    shared_w2: jnp.ndarray | None = None
    shared_w3: jnp.ndarray | None = None
    # the latent the routed rows pass through: (d, L) down, (L, d) up;
    # the expert stacks are then (held, L, ff) and (held, ff, L). None =
    # the experts read and write the full width
    latent_down: jnp.ndarray | None = None
    latent_up: jnp.ndarray | None = None
    top_k: int = static_field(default=2)
    first_expert: int = static_field(default=0)
    # "softmax" over all experts, or "sigmoid" of each score (the
    # layer's own scores; a caller's come as they are)
    scoring: str = static_field(default="softmax")
    routed_scale: float = static_field(default=1.0)
    # the top_k kept are renormalised to sum to one, or stay the chosen
    # probabilities themselves
    renormalize: bool = static_field(default=True)
    # "" = SwiGLU where w3 is held, else GELU; "relu2" = relu(h w1)^2 w2,
    # the routed experts and the shared expert alike
    activation: str = static_field(default="")

    @property
    def num_experts(self) -> int:
        return self.w_router.shape[-1]

    @property
    def held(self) -> int:
        return self.w1.shape[0]

    @staticmethod
    def create(key, dim: int, ff: int, num_experts: int, *,
               held: int | None = None, first_expert: int = 0,
               top_k: int = 2, swiglu: bool = False, shared_ff: int = 0,
               scoring: str = "softmax", routed_scale: float = 1.0,
               router_std: float = 0.02, renormalize: bool = True,
               latent: int = 0, activation: str = "") -> "MoELayer":
        """Seeded weights: matrices normal at 1/sqrt(fan_in), the router
        at ``router_std``. ``latent > 0`` puts the routed experts in a
        latent of that width; ``activation="relu2"`` makes every expert
        two matrices."""
        held = num_experts if held is None else held
        if not 0 <= first_expert <= num_experts - held:
            raise ValueError(
                f"experts {first_expert}..{first_expert + held} of "
                f"{num_experts}"
            )
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring={scoring!r}; expected softmax|sigmoid")
        if activation not in ("", "relu2") or (activation and swiglu):
            raise ValueError(f"activation={activation!r} with swiglu={swiglu}")
        ks = jax.random.split(key, 7)
        width = latent or dim

        def init(k, shape, fan_in):
            return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

        return MoELayer(
            w_router=router_std * jax.random.normal(ks[0], (dim, num_experts)),
            w1=init(ks[1], (held, width, ff), width),
            w2=init(ks[2], (held, ff, width), ff),
            w3=init(ks[3], (held, width, ff), width) if swiglu else None,
            shared_w1=init(ks[4], (dim, shared_ff), dim) if shared_ff else None,
            shared_w2=init(ks[5], (shared_ff, dim), shared_ff)
            if shared_ff
            else None,
            shared_w3=init(ks[6], (dim, shared_ff), dim)
            if shared_ff and swiglu
            else None,
            latent_down=init(jax.random.fold_in(key, 7), (dim, latent), dim)
            if latent
            else None,
            latent_up=init(jax.random.fold_in(key, 8), (latent, dim), latent)
            if latent
            else None,
            top_k=top_k,
            first_expert=first_expert,
            scoring=scoring,
            routed_scale=routed_scale,
            renormalize=renormalize,
            activation=activation,
        )

    def route(self, xf, scores=()):
        """(weights (T, k) f32, expert ids (T, k)) of every token: f32
        throughout, the sums are cheap and the ordering is sensitive.
        ``scores``: the caller's ``(p, select)``, each (T, E) f32, in
        place of the layer's own matrix: the ``top_k`` largest of
        ``select`` are chosen and weighted by their ``p``."""
        if not scores:
            logits = xf.astype(jnp.float32) @ self.w_router.astype(jnp.float32)
            scores = (
                jax.nn.sigmoid(logits)
                if self.scoring == "sigmoid"
                else jax.nn.softmax(logits, axis=-1)
            )
            top, idx = jax.lax.top_k(scores, self.top_k)
        else:
            p, select = scores
            _, idx = jax.lax.top_k(select, self.top_k)
            top = jnp.take_along_axis(p, idx, axis=-1)
        if self.renormalize:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        return top * self.routed_scale, idx

    def __call__(self, x, mesh=None, scores=None):
        """``mesh``: the mesh the caller's arrays are split over, if
        any. GSPMD cannot partition a Mosaic kernel, so under a mesh of
        more than one device the routed part runs in a ``shard_map``:
        the batch split over ``data`` (whole where ``data`` does not
        divide it), every device routing its own tokens through the
        experts, which it holds whole. ``scores``: ``(p, select)``, each
        (B, S, E) f32, from a router outside the layer (see
        :meth:`route`); None = the layer's own ``w_router``."""
        scores = tuple(scores or ())
        if mesh is not None and mesh.size > 1:
            from jax.sharding import PartitionSpec as P

            n_data = mesh.shape.get("data", 1)
            axis = "data" if n_data > 1 and x.shape[0] % n_data == 0 else None
            routed = jax.shard_map(
                lambda m, xs, *sc: m._routed(xs, axis, sc),
                mesh=mesh,
                in_specs=(P(), P(axis)) + (P(axis),) * len(scores),
                out_specs=(P(axis), P()),
                check_vma=False,  # pallas_call outputs carry no vma
            )
            # the shared expert is plain XLA and stays outside
            out, counters = routed(
                dataclasses.replace(
                    self, shared_w1=None, shared_w2=None, shared_w3=None
                ),
                x,
                *scores,
            )
        else:
            out, counters = self._routed(x, scores=scores)
        if self.shared_w1 is not None:
            with jax.named_scope("moe_shared_expert"):
                out = out + ffn(
                    x, self.shared_w1, self.shared_w2, self.shared_w3, x.dtype,
                    activation=self.activation,
                )
        return out, counters

    def _routed(self, x, axis: str | None = None, scores=()):
        """The held experts' part of the routed sum for these tokens,
        and the counters; under a ``shard_map`` that split the tokens
        over ``axis`` the counters are summed over it."""
        b, s, d = x.shape
        t, k = b * s, self.top_k
        xf = x.reshape(t, d)
        cdt = x.dtype
        scores = tuple(sc.reshape(t, sc.shape[-1]) for sc in scores)
        window = window_rows(t * k, self.held, self.num_experts)
        with jax.named_scope("moe_router"):
            weights, idx = self.route(xf, scores)
            flat = idx.reshape(t * k)
            order = jnp.argsort(flat, stable=True).astype(jnp.int32)
            if not window:
                inv = (
                    jnp.zeros(t * k, jnp.int32)
                    .at[order]
                    .set(jnp.arange(t * k, dtype=jnp.int32))
                )
            group_sizes = jnp.zeros(self.num_experts, jnp.int32).at[flat].add(1)
        first = self.first_expert
        if self.latent_down is not None:
            # the router has read the full width; the rows go down to
            # the latent the experts live in
            with jax.named_scope("moe_latent_down"):
                xf = jnp.matmul(xf, self.latent_down.astype(cdt))
        if window:
            held = jax.lax.dynamic_slice_in_dim(group_sizes, first, self.held)
            end = jax.lax.dynamic_slice_in_dim(jnp.cumsum(group_sizes), first, self.held)
            start = end - held
            with jax.named_scope("moe_experts"):
                w3 = None if self.w3 is None else self.w3.astype(cdt)
                out = _expert_windows(
                    xf, weights, self.w1.astype(cdt), w3, self.w2.astype(cdt),
                    jnp.pad(order, (0, window)), start, end, k, window,
                    self.activation,
                ).astype(cdt)
            dispatch_rows, extra_windows, mm_rows = _window_counters(
                start, end, window, t * k
            )
        else:
            out, held, mm_rows = self._every_row(
                xf, weights, order, inv, group_sizes, cdt
            )
            dispatch_rows, extra_windows = jnp.int32(t * k), jnp.int32(0)
        if self.latent_up is not None:
            with jax.named_scope("moe_latent_up"):
                out = jnp.matmul(out, self.latent_up.astype(cdt))
        if axis is not None:
            held, mm_rows, dispatch_rows, extra_windows = jax.lax.psum(
                (held, mm_rows, dispatch_rows, extra_windows), axis
            )
        counters = {
            "routed_rows": jnp.sum(held),
            "max_expert_rows": jnp.max(held),
            "mm_rows": mm_rows,
            "dispatch_rows": dispatch_rows,
            "extra_windows": extra_windows,
        }
        if not self.renormalize:
            # what the router gave the rows routed here (renormalised
            # gates sum to the rows themselves and say nothing)
            here = (idx >= first) & (idx < first + self.held)
            gate_sum = jnp.sum(jnp.where(here, weights, 0.0))
            counters["gate_sum"] = (
                gate_sum if axis is None else jax.lax.psum(gate_sum, axis)
            )
        return out.reshape(b, s, d), counters

    def _every_row(self, xf, weights, order, inv, group_sizes, cdt):
        """Every one of the ``tokens x top_k`` rows through the grouped
        products and back (the rows of experts held elsewhere come out
        zero): (T, d), the held experts' rows and ``mm_rows``."""
        t, d = xf.shape
        k = self.top_k
        # a handful of decode rows is padded up to the kernel's sublane
        # tile; the pad rows are no expert's and are cut off again
        pad = -(t * k) % 8
        tm = _row_tile(t * k + pad)
        first = self.first_expert
        with jax.named_scope("moe_experts"):
            xs = jnp.pad(_dispatch(xf, order, inv, k), ((0, pad), (0, 0)))
            h = grouped_mm(xs, self.w1.astype(cdt), group_sizes, first, tm)
            if self.w3 is None:
                h = _act(h, None, self.activation)
            else:
                h = jax.nn.silu(h) * grouped_mm(
                    xs, self.w3.astype(cdt), group_sizes, first, tm
                )
            ys = grouped_mm(h, self.w2.astype(cdt), group_sizes, first, tm)
            ys = ys[: t * k]
            # rows of experts held elsewhere are zeros: they add nothing
            y = _collect(ys, order, inv).reshape(t, k, d)
            out = jnp.einsum(
                "tk,tkd->td", weights.astype(cdt), y,
                preferred_element_type=jnp.float32,
            ).astype(cdt)
        held = jax.lax.dynamic_slice_in_dim(group_sizes, first, self.held)
        ends = jnp.cumsum(group_sizes)
        end = jax.lax.dynamic_slice_in_dim(ends, first, self.held)
        start = end - held
        # the product visits every row tile a held expert's rows touch
        tiles = jnp.where(held > 0, -(-end // tm) - start // tm, 0)
        return out, held, tm * jnp.sum(tiles)


@treenode
class CarriedRouter:
    """A router outside the expert layer: an MLP fed by a state carried
    from layer to layer. ``r = y w_down + b_down + gamma * r_prev`` is
    this layer's state and goes on to the next layer's router;
    ``p = softmax(w3 gelu(w2 gelu(w1 rms(r))))`` over every expert of
    the model; the choice is made by ``p + beta``, a balancing bias that
    no gradient reaches (an update rule between steps would move it;
    none is applied here). float32 throughout at the highest matmul
    precision: with one expert a token the choice is discrete."""

    w_down: jnp.ndarray  # (d, R)
    b_down: jnp.ndarray  # (R,)
    gamma: jnp.ndarray  # (): the weight of the previous layer's state
    norm: jnp.ndarray  # (R,)
    w1: jnp.ndarray  # (R, R)
    b1: jnp.ndarray
    w2: jnp.ndarray  # (R, R)
    b2: jnp.ndarray
    w3: jnp.ndarray  # (R, E)
    b3: jnp.ndarray
    beta: jnp.ndarray  # (E,)
    eps: float = static_field(default=1e-5)

    @staticmethod
    def create(key, d: int, hidden: int, num_experts: int, *,
               gamma: float = 0.5, eps: float = 1e-5) -> "CarriedRouter":
        """Seeded weights: matrices normal at 1/sqrt(fan_in), biases and
        ``beta`` zero, the norm's scale 1, ``gamma`` as given."""
        ks = jax.random.split(key, 4)

        def init(k, shape):
            return jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[0])

        zeros = functools.partial(jnp.zeros, dtype=jnp.float32)
        return CarriedRouter(
            w_down=init(ks[0], (d, hidden)), b_down=zeros((hidden,)),
            gamma=jnp.asarray(gamma, jnp.float32),
            norm=jnp.ones((hidden,), jnp.float32),
            w1=init(ks[1], (hidden, hidden)), b1=zeros((hidden,)),
            w2=init(ks[2], (hidden, hidden)), b2=zeros((hidden,)),
            w3=init(ks[3], (hidden, num_experts)), b3=zeros((num_experts,)),
            beta=zeros((num_experts,)),
            eps=eps,
        )

    def __call__(self, y, r_prev=None):
        """y: (B, S, d); r_prev: (B, S, R) f32, or None before the first
        such layer (a zero state). Returns ((p, select), r): the scores
        for :meth:`MoELayer.__call__` and the state to carry on."""
        f32 = jnp.float32
        dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
        with jax.named_scope("moe_router_mlp"):
            r = dot(y.astype(f32), self.w_down) + self.b_down
            if r_prev is not None:
                r = r + self.gamma * r_prev
            z = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + self.eps)
            z = z * self.norm
            z = jax.nn.gelu(dot(z, self.w1) + self.b1, approximate=False)
            z = jax.nn.gelu(dot(z, self.w2) + self.b2, approximate=False)
            p = jax.nn.softmax(dot(z, self.w3) + self.b3, axis=-1)
            select = p + jax.lax.stop_gradient(self.beta)
        return (p, select), r
