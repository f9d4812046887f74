"""Compressed convolutional attention (CCA): what a block calls in place
of plain attention when queries, keys and values live in a latent
narrower than the residual stream and q and k are mixed over time before
the kernel sees them.

The reference has no sequence model at all (SURVEY section 5). The layer
is five steps, each under a ``jax.named_scope`` of its own:

- ``cca_proj``: ``q~ = h Wq`` (H heads of hd), ``k~ = h Wk`` and
  ``v = h Wv`` (KV heads), all narrower than ``h``;
- ``cca_value_shift``: the second half of the K/V heads' values come
  from the previous position (zeros at position 0);
- ``cca_qk_mix``: two causal convolutions over ``[q~ | k~]``, the first
  depthwise (:func:`keystone_tpu.ops.ssm.causal_conv`), the second
  mixing the channels of each head (:func:`head_conv`), no activation
  between; to their result is added the mean of each query head with its
  group's key head (for a key head: of its group's mean query with
  itself); then every head is scaled to length ``sqrt(hd)``, a key head
  times its learned temperature, statistics in float32; then rotary;
- the attention itself in the latent, handed in by the caller
  (``attend``), so the flash kernels and their scopes are the model's;
- ``cca_out_proj``: ``o Wo`` back to the stream's width.

Everything between the projections and the kernel is XLA; a fused
kernel of the mix is not written.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from keystone_tpu.core.treenode import static_field, treenode
from keystone_tpu.ops.flash_attention import on_tpu
from keystone_tpu.ops.ssm import causal_conv

# the step program's counter of its CCA layers: positions mixed, summed
# over layers
COUNTERS = ("cca_rows",)


def head_conv(x, w, b):
    """Causal convolution over time that mixes the channels of each head
    and no others. x: (B, S, G * hd); w: (G, K, hd, hd), tap ``K - 1``
    the current position's; b: (G * hd,). ``out[t, g] = b[g] + sum_j
    x[t - (K - 1) + j, g] @ w[g, j]``, positions before 0 read as zero.
    Operands in ``x``'s dtype, as every projection's; on a TPU each
    tap's product leaves the MXU in float32, so the taps' sum and the
    bias see no rounding to ``x``'s dtype. Off it a tap's product is
    rounded to ``x``'s dtype first: the CPU's batched dot refuses
    bfloat16 operands with a float32 result."""
    n, s, c = x.shape
    g, k, hd, _ = w.shape
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).reshape(n, s + k - 1, g, hd)
    wx = w.astype(x.dtype)
    wide = {"preferred_element_type": jnp.float32} if on_tpu() else {}
    out = sum(
        jnp.einsum(
            "bsgi,gio->bsgo", xp[:, j : j + s], wx[:, j], **wide
        ).astype(jnp.float32)
        for j in range(k)
    )
    return out.reshape(n, s, c) + b.astype(jnp.float32)


def shift_values(v, shifted_from: int):
    """Channels ``shifted_from`` onwards of v (B, S, C) read the
    previous position (zeros at position 0); the others stay."""
    late = jnp.pad(v[:, :-1, shifted_from:], ((0, 0), (1, 0), (0, 0)))
    return jnp.concatenate([v[..., :shifted_from], late], axis=-1)


def group_means(q, k):
    """q: (B, S, H, hd); k: (B, S, KV, hd), query head ``i`` of group
    ``i // (H / KV)``. (each query head's mean with its group's key
    head, each key head's mean with its group's mean query head)."""
    n, s, h, hd = q.shape
    kv = k.shape[2]
    mu_q = (q + jnp.repeat(k, h // kv, axis=2)) / 2
    mu_k = (q.reshape(n, s, kv, h // kv, hd).mean(axis=3) + k) / 2
    return mu_q, mu_k


def unit_heads(t, eps: float):
    """Each head of t (..., hd) at length ``sqrt(hd)``: ``t / sqrt(mean(t^2)
    + eps)``, float32."""
    t = t.astype(jnp.float32)
    return t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)


@treenode
class CCAMixer:
    """A CCA layer's weights (no projection has a bias) and what is
    static of it. The latent holds ``heads + kv_heads`` heads of
    ``head_dim`` channels for q and k, and ``kv_heads`` for v."""

    wq: jnp.ndarray  # (d, H * hd)
    wk: jnp.ndarray  # (d, KV * hd)
    wv: jnp.ndarray  # (d, KV * hd): the second half reads the previous position
    wo: jnp.ndarray  # (H * hd, d)
    conv0_w: jnp.ndarray  # ((H + KV) * hd, K0): depthwise
    conv0_b: jnp.ndarray  # ((H + KV) * hd,)
    conv1_w: jnp.ndarray  # (H + KV, K1, hd, hd): within each head
    conv1_b: jnp.ndarray  # ((H + KV) * hd,)
    tau: jnp.ndarray  # (KV,): a key head's temperature
    heads: int = static_field(default=8)
    kv_heads: int = static_field(default=2)
    eps: float = static_field(default=1e-5)

    @property
    def head_dim(self) -> int:
        return self.wq.shape[1] // self.heads

    @staticmethod
    def create(key, d: int, *, heads: int, kv_heads: int, head_dim: int,
               time0: int = 2, time1: int = 2, eps: float = 1e-5) -> "CCAMixer":
        """Seeded weights: matrices normal at 1/sqrt(fan_in) (a head's
        convolution reads ``time1 * head_dim`` inputs); the depthwise
        conv and both biases uniform in +-1/sqrt(taps); ``tau`` 1."""
        if heads % kv_heads or kv_heads % 2:
            raise ValueError(
                f"{heads} heads over {kv_heads} K/V heads: the value shift "
                "wants an even number of K/V heads dividing the query heads"
            )
        ks = jax.random.split(key, 8)
        channels = (heads + kv_heads) * head_dim

        def init(k, shape, fan_in):
            return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

        def uniform(k, shape, taps):
            bound = 1.0 / math.sqrt(taps)
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)

        return CCAMixer(
            wq=init(ks[0], (d, heads * head_dim), d),
            wk=init(ks[1], (d, kv_heads * head_dim), d),
            wv=init(ks[2], (d, kv_heads * head_dim), d),
            wo=init(ks[3], (heads * head_dim, d), heads * head_dim),
            conv0_w=uniform(ks[4], (channels, time0), time0),
            conv0_b=uniform(ks[5], (channels,), time0),
            conv1_w=init(
                ks[6], (heads + kv_heads, time1, head_dim, head_dim),
                time1 * head_dim,
            ),
            conv1_b=uniform(ks[7], (channels,), time1),
            tau=jnp.ones((kv_heads,), jnp.float32),
            heads=heads, kv_heads=kv_heads, eps=eps,
        )

    def __call__(self, y, rotate, attend, mm_fn=None):
        """y: (B, S, d) in the compute dtype -> ((B, S, d), the layer's
        counters). ``rotate(t)`` applies the layer's rotary scheme to
        (B, heads, S, hd); ``attend(q, k, v)`` is the causal attention
        of (B, H, S, hd) over (B, KV, S, hd), grouped."""
        if mm_fn is None:
            from keystone_tpu.ops.quantization import mm as mm_fn
        n, s, _ = y.shape
        cdt = y.dtype
        h, kv, hd = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope("cca_proj"):
            q0 = mm_fn(y, self.wq, cdt)
            k0 = mm_fn(y, self.wk, cdt)
            v = mm_fn(y, self.wv, cdt)
        with jax.named_scope("cca_value_shift"):
            v = shift_values(v, (kv // 2) * hd)
        with jax.named_scope("cca_qk_mix"):
            c = jnp.concatenate([q0, k0], axis=-1)
            c = causal_conv(c, self.conv0_w, self.conv0_b).astype(cdt)
            c = head_conv(c, self.conv1_w, self.conv1_b)
            mu_q, mu_k = group_means(
                q0.astype(jnp.float32).reshape(n, s, h, hd),
                k0.astype(jnp.float32).reshape(n, s, kv, hd),
            )
            q = unit_heads(c[..., : h * hd].reshape(n, s, h, hd) + mu_q, self.eps)
            k = unit_heads(c[..., h * hd :].reshape(n, s, kv, hd) + mu_k, self.eps)
            k = k * self.tau.astype(jnp.float32)[:, None]
            q = rotate(q.transpose(0, 2, 1, 3)).astype(cdt)
            k = rotate(k.transpose(0, 2, 1, 3)).astype(cdt)
        out = attend(q, k, v.reshape(n, s, kv, hd).transpose(0, 2, 1, 3))
        with jax.named_scope("cca_out_proj"):
            out = mm_fn(
                out.transpose(0, 2, 1, 3).reshape(n, s, h * hd).astype(cdt),
                self.wo, cdt,
            )
        return out, {"cca_rows": jnp.int32(n * s)}
