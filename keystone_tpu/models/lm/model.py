"""Decoder-only transformer LM with a fully sharded training step.

The reference has no sequence models at all (SURVEY §5: long-context
"absent"), but long-context + distributed are first-class capabilities of
this framework, not parity afterthoughts. This model is the training-side
consumer of that stack:

- causal attention via :mod:`keystone_tpu.ops.attention` — dense, fused
  Pallas flash, or sequence-parallel ring / Ulysses (`seq_mode`), so one
  flag takes the same model from a single chip to a sequence-sharded mesh
  for contexts that don't fit one device;
- tensor parallelism by sharding each weight over the mesh ``model`` axis
  (head-parallel attention, column/row-parallel MLP, vocab-parallel tied
  embedding) — XLA inserts the psums, the model code stays purely
  functional;
- data parallelism over the ``data`` axis;
- one jitted, buffer-donated train step (AdamW via optax) — the whole
  update is a single XLA program, the idiom the rest of the framework uses
  for its solvers (one launch per step, no host round-trips).

This is a beyond-reference capability in the same spirit as
``models/vit_ridge.py``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.core.treenode import static_field, treenode
from keystone_tpu.ops.attention import (
    dense_attention,
    ring_attention,
    ulysses_attention,
)
from keystone_tpu.ops.cca import COUNTERS as CCA_COUNTERS, CCAMixer
from keystone_tpu.ops.moe import COUNTERS, CarriedRouter, MoELayer, ffn
from keystone_tpu.ops.quantization import QTensor, mm
from keystone_tpu.ops.ssm import COUNTERS as SSM_COUNTERS, Mamba2Mixer
from keystone_tpu.ops.vit import _layer_norm


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One rotary scheme, static: ``theta``; how much of each head is
    rotated; and YaRN's ``(factor, original positions, beta_fast,
    beta_slow, attention_factor)`` or None for plain rotary."""

    theta: float = 10_000.0
    partial: float = 1.0
    yarn: tuple | None = None

    def inv_freq(self, head_dim: int) -> tuple[np.ndarray, float]:
        """(inverse frequencies of the rotated pairs, the factor on cos
        and sin). YaRN blends each pair's ``inv_freq`` with
        ``inv_freq / factor`` by a linear ramp between the pairs that
        turn ``beta_fast`` and ``beta_slow`` times over the original
        positions (the published convention: the ramp's ends are
        floored and ceiled to whole pairs)."""
        dim = int(head_dim * self.partial)
        pos_freqs = self.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        inv = 1.0 / pos_freqs
        if self.yarn is None:
            return inv.astype(np.float32), 1.0
        factor, original, beta_fast, beta_slow, attention_factor = self.yarn

        def pair_that_turns(times):
            return (
                dim * math.log(original / (times * 2 * math.pi))
                / (2 * math.log(self.theta))
            )

        low = max(math.floor(pair_that_turns(beta_fast)), 0)
        high = min(math.ceil(pair_that_turns(beta_slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
        blended = inv / factor * ramp + inv * (1.0 - ramp)
        return blended.astype(np.float32), float(attention_factor)


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What one layer's attention is, static: its head counts, its
    causal window (0 = every earlier key), its rotary scheme (None = no
    rotation: learned positions, or none at all) and its softmax scale
    (None = 1/sqrt(head_dim)). Layers of one model may differ."""

    num_heads: int
    num_kv_heads: int
    window: int = 0
    rope: RopeSpec | None = None
    scale: float | None = None


@treenode
class LMBlock:
    """One decoder block: attention, a state-space mixer or attention in
    a compressed latent, then a dense FFN or routed experts (scored by
    the expert layer's own matrix, or by ``router``, which also reads
    and leaves a state that flows from block to block beside ``x``).
    A block may be one part alone: a mixer with no FFN after it
    (``w1`` / ``w2`` None and no ``moe``), or an FFN or expert layer with
    no mixer before it (``wq`` .. ``wo`` None and no other mixer); the
    absent part runs nothing, not even its norm.
    The one block definition: the toy presets of
    :meth:`TransformerLM.create` and a public ``config.json``
    (:meth:`TransformerLM.from_config`) fill the same fields."""

    # (d, H·hd); zero-width under another mixer, None with no mixer
    wq: jnp.ndarray | None
    wk: jnp.ndarray | None  # (d, KV·hd)
    wv: jnp.ndarray | None
    wo: jnp.ndarray | None  # (H·hd, d)
    # (d, ff); zero-width under routed experts, None with no FFN part
    w1: jnp.ndarray | None
    w2: jnp.ndarray | None  # (ff, d)
    w3: jnp.ndarray | None = None  # (d, ff): SwiGLU's second input
    wg: jnp.ndarray | None = None  # (d, H): one sigmoid gate a head
    norm1: jnp.ndarray | None = None  # learned RMSNorm scales, or None
    norm2: jnp.ndarray | None = None  # for the parameter-free LayerNorm
    moe: object | None = None  # ops.moe.MoELayer in place of the FFN
    ssm: object | None = None  # ops.ssm.Mamba2Mixer in place of attention
    cca: object | None = None  # ops.cca.CCAMixer in place of attention
    router: object | None = None  # ops.moe.CarriedRouter scoring for moe
    # learned scales and biases where a branch joins the stream, rows
    # (s, b, t, u): x = (s * x + b) + (t * branch + u); None = x + branch
    scale1: jnp.ndarray | None = None  # (4, d): after the mixer
    scale2: jnp.ndarray | None = None  # (4, d): after the FFN or experts
    spec: LayerSpec | None = static_field(default=None)

    @property
    def has_mixer(self) -> bool:
        return self.wq is not None or self.ssm is not None or self.cca is not None

    @property
    def has_ffn(self) -> bool:
        return self.w1 is not None or self.moe is not None


@treenode
class MTPModule:
    """Multi-token prediction of depth one: from the main stack's hidden
    state ``x_i`` (before its final norm) and the embedding of the next
    token ``t_(i+1)``, ``h = [rms(E[t_(i+1)]; enorm) | rms(x_i; hnorm)]
    eh_proj``, then the module's own blocks and ``final_norm``, then the
    model's own head, trained on ``t_(i+2)``. Its cross-entropy joins the
    loss times ``weight``."""

    enorm: jnp.ndarray  # (d,)
    hnorm: jnp.ndarray  # (d,)
    eh_proj: jnp.ndarray  # (2d, d)
    blocks: tuple  # of LMBlock
    final_norm: jnp.ndarray  # (d,)
    weight: float = static_field(default=0.3)


def _ln(x, cdt):
    # normalization stats in f32 even under a bf16 policy: the
    # mean/variance cancellation is exactly what bf16 loses
    return _layer_norm(x.astype(jnp.float32)).astype(cdt)


def _norm(x, scale, eps: float, cdt):
    """Learned RMSNorm when the block carries a scale, else the
    parameter-free LayerNorm; statistics in f32 either way."""
    if scale is None:
        return _ln(x, cdt)
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(cdt)


def model_mm(model):
    """The matmul the model's int8 weights go through: plain ``mm`` or,
    under ``int8_kernel="pallas"``, the fused dequant kernel for
    per-output-channel-scaled QTensors (float weights always take
    ``mm``)."""
    if model.int8_kernel == "xla":
        return mm
    if model.int8_kernel != "pallas":
        raise ValueError(
            f"int8_kernel={model.int8_kernel!r}; expected xla|pallas"
        )

    def pallas_mm(y, w, dt):
        # decode-sized M only: mm_fused carries the whole M extent in
        # one VMEM tile, which is the right shape for a handful of
        # decode rows and a VMEM blow-up for prefill/forward (B·S rows)
        m_rows = int(np.prod(y.shape[:-1]))
        if (
            isinstance(w, QTensor)
            and w.scale.shape == (1, w.q.shape[1])
            and m_rows <= 64
        ):
            from keystone_tpu.ops.int8_matmul import mm_fused

            return mm_fused(y.astype(dt), w).astype(dt)
        return mm(y, w, dt)

    return pallas_mm


def _split_heads(y, w, h, mm_fn=mm):
    n, s, _ = y.shape
    out = mm_fn(y, w, y.dtype)  # (n, s, h·hd) — rectangular for GQA K/V
    return out.reshape(n, s, h, out.shape[-1] // h).transpose(0, 2, 1, 3)


def _rope(x, positions, rope: RopeSpec = RopeSpec()):
    """Rotary position embedding. x: (..., S, hd); positions: (S,) int32
    global token positions — or (B, S) when sequences in the batch sit
    at different positions (the serving decode pool: each slot carries
    its own sequence, so each rotates at its own phase). The first
    ``partial`` of each head is rotated in halves, the rest passes
    through. Angles in f32 (bf16 loses phase accuracy fast at long
    context), rotated result back in x.dtype."""
    inv, factor = rope.inv_freq(x.shape[-1])
    half = inv.shape[0]
    freqs = positions.astype(jnp.float32)[..., None] * inv  # (..., S, half)
    cos, sin = jnp.cos(freqs) * factor, jnp.sin(freqs) * factor
    if freqs.ndim == 3:
        # (B, S, half) phases meet (B, H, S, hd/2) halves: insert the
        # head axis so each batch row broadcasts over its own heads
        cos, sin = cos[:, None], sin[:, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half : 2 * half].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., 2 * half :]],
        axis=-1,
    ).astype(x.dtype)


def _block_apply(x, blk: LMBlock, cdt, attn, mm_fn=mm, eps: float = 1e-6,
                 mesh=None, residual: float = 1.0, carried=None):
    """Pre-norm residual block shared by training forward, prefill, and
    decode: ``attn(y, blk) -> (mixer output (N,S,d), aux)``. Routed
    experts (``blk.moe``) take the dense FFN's place, told the ``mesh``
    the activations are split over; each branch joins the stream times
    ``residual``, or under the block's learned scales and biases where
    it has them. ``carried`` is the router state the block before left
    (None where there is none): a block with a ``router`` reads it and
    leaves its own, any other hands it on untouched. Returns (x,
    attn_aux, the expert layer's counters or None, carried)."""

    def join(x, branch, scales):
        if scales is None:
            return x + (branch if residual == 1.0 else branch * residual)
        with jax.named_scope("residual_scale"):
            s, b, t, u = scales.astype(jnp.float32)
            return ((s * x + b) + (t * branch + u)).astype(x.dtype)

    aux = None
    if blk.has_mixer:
        a, aux = attn(_norm(x, blk.norm1, eps, cdt), blk)
        x = join(x, a, blk.scale1)
    if not blk.has_ffn:
        return x, aux, None, carried
    y = _norm(x, blk.norm2, eps, cdt)
    if blk.moe is not None:
        scores = None
        if blk.router is not None:
            scores, carried = blk.router(y, carried)
        f, counters = blk.moe(y, mesh, scores)
        return join(x, f, blk.scale2), aux, counters, carried
    with jax.named_scope("dense_ffn"):
        f = ffn(y, blk.w1, blk.w2, blk.w3, cdt, mm_fn)
        return join(x, f, blk.scale2), aux, None, carried


def _gather_embed(embed, tokens):
    """Embedding-row gather handling the int8 row-quantized table (the
    per-token scales apply to the gathered rows)."""
    if isinstance(embed, QTensor):
        return embed.q[tokens].astype(jnp.float32) * embed.scale[tokens]
    return embed[tokens]


def _embed(model, tokens, cdt):
    """Token embedding + optional learned positions, cast to the compute
    dtype — the one preamble shared by training forward, prefill, and the
    pipeline-parallel forward."""
    x = _gather_embed(model.embed, tokens)
    if model.embed_scale:
        x = x * math.sqrt(model.embed.shape[-1])
    if model.embed_multiplier != 1.0:
        x = x * model.embed_multiplier
    if model.pos_encoding == "learned":
        x = x + model.pos_embed[: tokens.shape[1]]
    return x.astype(cdt)


def final_rows(model, x, cdt):
    """The rows the output head reads: ``x`` through the learned final
    RMSNorm where the model has one, else the parameter-free LayerNorm,
    in the compute dtype."""
    return _norm(x, model.final_norm, model.norm_eps, cdt)


def head_matrix(model):
    """The output head (d, V): the model's own ``head``, else the
    embedding transposed (tied). An int8 embedding stays a QTensor (V, d)."""
    if model.head is None:
        return model.embed if isinstance(model.embed, QTensor) else model.embed.T
    return model.head


def scaled_product(xn, w, scale: float, cdt):
    """``xn @ w`` with operands in ``cdt`` and an f32 result, times
    ``scale``: the head's one product, for a float head (d, V)."""
    logits = jnp.matmul(xn, w.astype(cdt), preferred_element_type=jnp.float32)
    return logits if scale == 1.0 else logits * scale


def head_logits(model, xn, cdt):
    """The output head on the final norm's rows, times ``logits_scale``.
    bf16 operands, f32 accumulate/output: the logits feed a logsumexp —
    bf16 logits would cost real perplexity precision."""
    w = head_matrix(model)
    if not isinstance(w, QTensor):
        return scaled_product(xn, w, model.logits_scale, cdt)
    # (V, 1) row scales become per-output-channel under the transpose
    logits = jnp.matmul(
        xn, w.q.T.astype(cdt), preferred_element_type=jnp.float32
    ) * w.scale[:, 0]
    return logits if model.logits_scale == 1.0 else logits * model.logits_scale


def output_logits(model, x, cdt):
    """Final norm, then the output head: the model's own ``head`` (d, V)
    when it has one, else the embedding transposed (tied: behind the
    learned final norm where the model has one, else behind the
    parameter-free LayerNorm), times ``logits_scale``. f32 out."""
    return head_logits(model, final_rows(model, x, cdt), cdt)


@treenode
class TransformerLM:
    """Pre-norm decoder-only LM. Two ways in, one block definition:
    :meth:`create` (toy presets: parameter-free LayerNorm, GELU, tied
    logits, one head count) and :meth:`from_config` (a public
    ``config.json``: learned RMSNorm, SwiGLU, an untied head, a head
    count and an attention kind per layer, routed experts)."""

    embed: jnp.ndarray  # (V, d)
    pos_embed: jnp.ndarray  # (S_max, d)
    blocks: tuple  # of LMBlock
    # the output head (d, V); None = logits tied to the embedding. The
    # final RMSNorm's scale; None = the parameter-free LayerNorm
    head: jnp.ndarray | None = None
    final_norm: jnp.ndarray | None = None
    # a multi-token prediction module (MTPModule) trained beside the
    # next-token head, or None
    mtp: object | None = None
    num_heads: int = static_field(default=8)
    # attention strategy: "local" (dense or Pallas flash on TPU),
    # "ring" / "ulysses" (sequence-parallel over `seq_axis` of `mesh`).
    # A "local" model whose batch or heads are split over a mesh carries
    # that mesh too: the flash kernel and the experts' grouped product
    # are shard_mapped over it (GSPMD cannot partition a Mosaic kernel)
    seq_mode: str = static_field(default="local")
    mesh: object = static_field(default=None)
    seq_axis: str = static_field(default="data")
    # rematerialize each block in the backward pass: activation memory
    # drops from O(depth · S · d) per-layer intermediates to the block
    # boundaries only — the jax.checkpoint successor of the reference's
    # nothing (it never trained deep models)
    remat: bool = static_field(default=False)
    # "full" recomputes everything inside the block (max memory saving,
    # ~1/3 extra forward FLOPs in the backward); "dots" saves the matmul
    # outputs and recomputes only the cheap elementwise/LN work — the
    # middle ground between memory and speed: the MXU never re-runs, so
    # the step's FLOPs stay at the analytic 6·P·tokens
    remat_policy: str = static_field(default="full")
    # mixed precision: params/optimizer state stay float32; activations
    # and the matmul operands run in this dtype ("bfloat16" halves HBM
    # traffic and feeds the MXU its native input width). Norm statistics
    # and the loss reduction stay float32 regardless.
    compute_dtype: str = static_field(default="float32")
    # "learned" = trained absolute table (pos_embed, capped at max_seq);
    # "rope" = rotary q/k phases — no table, no length cap beyond memory,
    # the right pairing for the long-context kernel backward; "nope" =
    # no positional encoding at all (each layer's spec says: rope=None)
    pos_encoding: str = static_field(default="learned")
    # grouped-query attention: K/V carry this many heads (0 = num_heads,
    # plain MHA; 1 = MQA). The decode cache shrinks by num_heads/kv_heads
    # — composing with kv_dtype="int8" for the full serving story
    num_kv_heads: int = static_field(default=0)
    # how int8 QTensor weights multiply: "xla" trusts the convert-into-
    # dot fusion (ops/quantization.mm); "pallas" streams the codes as
    # int8 via the fused kernel (ops/int8_matmul.mm_fused), which does
    # not depend on XLA keeping that fusion
    int8_kernel: str = static_field(default="xla")
    # the toy presets scale embeddings by sqrt(d); public configs do not
    embed_scale: bool = static_field(default=True)
    norm_eps: float = static_field(default=1e-6)
    # a public config's own multipliers: on the embedding, on each
    # branch as it joins the residual stream, on the logits
    embed_multiplier: float = static_field(default=1.0)
    residual_multiplier: float = static_field(default=1.0)
    logits_scale: float = static_field(default=1.0)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def layer_spec(self, blk: LMBlock) -> LayerSpec:
        """The block's own attention spec; a block built field by field
        (tests, old pickles) follows the model-wide head counts."""
        if blk.spec is not None:
            return blk.spec
        return LayerSpec(
            self.num_heads,
            self.kv_heads,
            rope=RopeSpec() if self.pos_encoding == "rope" else None,
        )

    def uniform_decode_reason(self) -> str | None:
        """None when every layer is the kind the KV-cache path computes
        (attention with one head count, no window, no head gate, plain
        rotary or learned positions, the default softmax scale; tied
        logits behind the parameter-free norm, no multipliers); else what
        it cannot serve, by name: the first layer of each kind it has no
        cache or step for."""
        why: dict[str, str] = {}
        if self.mtp is not None:
            why["mtp"] = (
                "the model predicts a second token ahead (multi-token "
                "prediction): decode has no step that drafts with it"
            )
        for i, blk in enumerate(self.blocks):
            if not (blk.has_mixer and blk.has_ffn):
                why.setdefault(
                    "parts",
                    f"layer {i} is one part alone (a mixer with no FFN after "
                    "it, or an expert layer with no mixer before it)",
                )
            if blk.ssm is not None:
                why.setdefault(
                    "ssm",
                    f"layer {i} is a state-space layer: a slot holds no "
                    "recurrent state",
                )
                continue
            if blk.router is not None:
                why.setdefault(
                    "router",
                    f"layer {i} routes by a state carried from layer to "
                    "layer: a slot holds nothing for it",
                )
            if blk.scale1 is not None or blk.scale2 is not None:
                why.setdefault(
                    "residual",
                    f"layer {i} joins its branches under learned scales "
                    "and biases",
                )
            if not blk.has_mixer:
                continue
            if blk.cca is not None:
                why.setdefault(
                    "cca",
                    f"layer {i} attends in a compressed latent behind "
                    "convolutions (CCA): a slot would have to hold latent K "
                    "and V, the convolutions' tails and the previous "
                    "position's hidden state",
                )
                continue
            spec = self.layer_spec(blk)
            if spec.scale is not None:
                why.setdefault("scale", f"layer {i} scales its scores by {spec.scale}")
            if spec.window:
                why.setdefault(
                    "window", f"layer {i} attends through a window of {spec.window}"
                )
            if (spec.num_heads, spec.num_kv_heads) != (
                self.num_heads, self.kv_heads
            ):
                why.setdefault(
                    "heads",
                    f"layer {i} has {spec.num_heads} heads, the cache is "
                    f"laid out for {self.num_heads}",
                )
            if blk.wg is not None:
                why.setdefault("gate", f"layer {i} gates its heads' outputs")
            if spec.rope is not None and spec.rope != RopeSpec():
                why.setdefault("rope", f"layer {i} rotates by {spec.rope}")
        if self.head is not None:
            why["head"] = "the output head is untied"
        elif self.final_norm is not None:
            why["head"] = "the tied head lies behind a learned final norm"
        if (self.embed_multiplier, self.residual_multiplier, self.logits_scale) != (
            1.0, 1.0, 1.0
        ):
            why["multipliers"] = "the embedding, residual or logits are scaled"
        return "; ".join(why.values()) or None

    def _qkv_heads(self, x, blk: LMBlock, positions=None):
        """(q with H heads, k/v with KV heads, rope applied).
        ``positions`` defaults to 0..S-1 (full-sequence forward); decode
        passes the single global position of its new token."""
        mm_fn = model_mm(self)
        spec = self.layer_spec(blk)
        q = _split_heads(x, blk.wq, spec.num_heads, mm_fn)
        if spec.scale is not None:
            # every attention path scales by 1/sqrt(head_dim): the rest
            # of the layer's own scale goes into q
            q = q * jnp.asarray(spec.scale * math.sqrt(q.shape[-1]), q.dtype)
        k = _split_heads(x, blk.wk, spec.num_kv_heads, mm_fn)
        v = _split_heads(x, blk.wv, spec.num_kv_heads, mm_fn)
        if spec.rope is not None:
            if positions is None:
                positions = jnp.arange(x.shape[1])
            q = _rope(q, positions, spec.rope)
            k = _rope(k, positions, spec.rope)
        return q, k, v

    def _attention(self, x, blk: LMBlock, return_kv: bool = False):
        n, s, _ = x.shape
        spec = self.layer_spec(blk)

        # x is always the full (global) sequence here — the
        # sequence-parallel paths shard inside ring/ulysses_attention
        q, k, v = self._qkv_heads(x, blk)
        kv_raw = (k, v)  # what the decode cache stores
        out = self._attend(q, k, v, spec)
        if blk.wg is not None:
            # one sigmoid gate a head, on that head's output
            gate = jax.nn.sigmoid(model_mm(self)(x, blk.wg, x.dtype))
            out = out * gate.transpose(0, 2, 1)[..., None].astype(out.dtype)
        proj = model_mm(self)(
            out.transpose(0, 2, 1, 3).reshape(n, s, -1).astype(x.dtype),
            blk.wo,
            x.dtype,
        )
        if return_kv:
            return proj, kv_raw
        return proj

    def _attend(self, q, k, v, spec: LayerSpec):
        """Causal attention of q (B, H, S, hd) over grouped k, v
        (B, KV, S, hd) under the layer's window: the sequence-parallel
        bodies, the flash kernel on a TPU, dense off it."""
        n, h, window = q.shape[0], spec.num_heads, spec.window
        # sequence-parallel training runs the custom-VJP bodies: the ring
        # backward circulates dk/dv accumulators around the ring (the
        # per-hop Pallas forward kernels are forward-only), Ulysses
        # differentiates the flash trainable wrapper through all_to_all.
        # use_flash auto-selects: Pallas-rate on TPU, jnp off it.
        if self.seq_mode in ("ring", "ulysses"):
            if window:
                raise ValueError(
                    f"seq_mode={self.seq_mode!r} has no windowed attention"
                )
            if spec.num_kv_heads != h:
                # the sequence-parallel bodies want a K and V per head
                g = h // spec.num_kv_heads
                k = jnp.repeat(k, g, axis=1)
                v = jnp.repeat(v, g, axis=1)
            attend = (
                ring_attention if self.seq_mode == "ring" else ulysses_attention
            )
            out = attend(
                q, k, v, self.mesh, seq_axis=self.seq_axis, causal=True,
                trainable=True,
            )
        else:
            from keystone_tpu.ops.flash_attention import on_tpu

            use_flash = on_tpu()
            # the scope names the layer's kind in the trace's op names
            scope = "attn_window" if window else "attn_full"
            if use_flash:
                # fused Pallas forward with a recompute VJP — training
                # never materializes the (S, S) probabilities; grouped K
                # and V go in as they are, never repeated up to H heads
                from keystone_tpu.ops.flash_attention import (
                    flash_attention_trainable,
                )

                def attend(q, k, v):
                    if window:
                        return flash_attention_trainable(q, k, v, True, window)
                    return flash_attention_trainable(q, k, v, True)

                if self.mesh is not None:
                    # GSPMD cannot partition a Mosaic kernel ("Mosaic
                    # kernels cannot be automatically partitioned" — the
                    # four-chip run, PR 21): under a mesh the batch
                    # (data axis) and the heads (model axis) are split
                    # by hand, each device running the kernel on its
                    # own shard. A dim an axis does not divide stays
                    # whole on every device.
                    from jax.sharding import PartitionSpec as P

                    sizes = dict(self.mesh.shape)
                    n_model = sizes.get("model", h + 1)
                    by_head = (
                        "model"
                        if h % n_model == 0 and spec.num_kv_heads % n_model == 0
                        else None
                    )
                    pspec = P(
                        "data" if n % sizes.get("data", n + 1) == 0 else None,
                        by_head,
                        None,
                        None,
                    )
                    attend = jax.shard_map(
                        attend,
                        mesh=self.mesh,
                        in_specs=(pspec, pspec, pspec),
                        out_specs=pspec,
                        check_vma=False,  # pallas_call outputs carry no vma
                    )
                with jax.named_scope(scope):
                    out = attend(q, k, v)
            else:
                with jax.named_scope(scope):
                    out = dense_attention(
                        q, k, v, causal=True, window=window
                    )
        return out

    def __call__(self, tokens):
        """(B, S) int tokens → (B, S, V) float32 logits."""
        return self.forward_with_aux(tokens)[0]

    def _mixer(self, y, blk: LMBlock):
        """(the block's mixer on ``y``, its counters or None): attention,
        or the state-space or compressed-latent mixer that stands in for
        it."""
        if blk.ssm is not None:
            return blk.ssm(y, self.mesh, model_mm(self))
        if blk.cca is not None:
            spec = self.layer_spec(blk)
            positions = jnp.arange(y.shape[1])
            return blk.cca(
                y,
                rotate=lambda t: _rope(t, positions, spec.rope),
                attend=lambda q, k, v: self._attend(q, k, v, spec),
                mm_fn=model_mm(self),
            )
        return self._attention(y, blk), None

    def backbone(self, tokens):
        """(final hidden states (B, S, d) before the final norm and the
        head, the expert layers' counters summed over layers and, where
        the model has state-space or compressed-latent layers, theirs) —
        the forward minus the logits projection, so losses can choose
        how (or whether) to materialize logits. Beside ``x`` the router
        state flows from block to block (None until a block leaves one)."""
        cdt = jnp.dtype(self.compute_dtype)
        x = _embed(self, tokens, cdt)
        return self._run_blocks(x, self.blocks, {c: jnp.int32(0) for c in COUNTERS})

    def _mtp_blocks(self) -> tuple:
        return () if self.mtp is None else self.mtp.blocks

    def mtp_hidden(self, x, next_tokens, counters):
        """The MTP module's hidden states (B, S, d) before its final
        norm, from the main stack's ``x`` and the ids one position ahead
        (B, S), and ``counters`` with its layers' added."""
        cdt = jnp.dtype(self.compute_dtype)
        m = self.mtp
        with jax.named_scope("mtp_embed_proj"):
            e = _norm(_embed(self, next_tokens, cdt), m.enorm, self.norm_eps, cdt)
            h = _norm(x, m.hnorm, self.norm_eps, cdt)
            h = model_mm(self)(jnp.concatenate([e, h], axis=-1), m.eh_proj, cdt)
        with jax.named_scope("mtp"):
            return self._run_blocks(h, m.blocks, counters)

    def _run_blocks(self, x, blocks, total):
        """``blocks`` in order on ``x``, their counters added to a copy
        of ``total`` (which gains the state-space and compressed-latent
        ones where ``blocks`` has such layers)."""
        cdt = jnp.dtype(self.compute_dtype)

        def block_fn(x, carried, blk):
            out, mixed, counters, carried = _block_apply(
                x, blk, cdt, self._mixer,
                mm_fn=model_mm(self),
                eps=self.norm_eps,
                mesh=self.mesh,
                residual=self.residual_multiplier,
                carried=carried,
            )
            return out, carried, counters, mixed

        if self.remat:
            block_fn = remat_wrap(block_fn, self.remat_policy)
        total = dict(total)
        if any(blk.ssm is not None for blk in blocks):
            for c in SSM_COUNTERS:
                total.setdefault(c, jnp.int32(0))
        if any(blk.cca is not None for blk in blocks):
            for c in CCA_COUNTERS:
                total.setdefault(c, jnp.int32(0))
        carried = None
        for blk in blocks:
            x, carried, counters, mixed = block_fn(x, carried, blk)
            if counters is not None:
                total.update(
                    routed_rows=total["routed_rows"] + counters["routed_rows"],
                    max_expert_rows=jnp.maximum(
                        total["max_expert_rows"], counters["max_expert_rows"]
                    ),
                    mm_rows=total["mm_rows"] + counters["mm_rows"],
                    dispatch_rows=total["dispatch_rows"] + counters["dispatch_rows"],
                    extra_windows=total["extra_windows"] + counters["extra_windows"],
                )
                if "gate_sum" in counters:
                    total["gate_sum"] = (
                        total.get("gate_sum", 0.0) + counters["gate_sum"]
                    )
            if mixed is not None:
                names = SSM_COUNTERS if blk.ssm is not None else CCA_COUNTERS
                total.update({c: total[c] + mixed[c] for c in names})
        return x, total

    def forward_with_aux(self, tokens):
        """(logits (B, S, V) f32, the expert layers' counters)."""
        x, counters = self.backbone(tokens)
        cdt = jnp.dtype(self.compute_dtype)
        return output_logits(self, x, cdt), counters

    @staticmethod
    def create(
        key,
        vocab: int = 256,
        max_seq: int = 512,
        dim: int = 256,
        depth: int = 4,
        num_heads: int = 8,
        ff_mult: int = 4,
        seq_mode: str = "local",
        mesh=None,
        seq_axis: str = "data",
        compute_dtype: str = "float32",
        moe_every: int = 0,
        num_experts: int = 8,
        pos_encoding: str = "learned",
        num_kv_heads: int = 0,
    ) -> "TransformerLM":
        """The toy presets. ``moe_every=k`` replaces the dense FFN of
        every k-th block with top-2 routed experts
        (:class:`~keystone_tpu.ops.moe.MoELayer`, ``num_experts`` of
        them, all held here; 0 = dense everywhere).
        ``pos_encoding="rope"`` drops the learned table (and its max_seq
        cap) for rotary q/k phases."""
        if pos_encoding not in ("learned", "rope"):
            raise ValueError(
                f"pos_encoding={pos_encoding!r}; expected learned|rope"
            )
        if pos_encoding == "rope" and (dim // num_heads) % 2:
            raise ValueError(
                f"rope needs an even head dim; got dim/num_heads = "
                f"{dim}/{num_heads} = {dim // num_heads}"
            )
        kvh = num_kv_heads or num_heads
        if kvh <= 0 or num_heads % kvh:
            raise ValueError(
                f"num_heads={num_heads} not divisible by "
                f"num_kv_heads={kvh}"
            )
        # canonical static field: 0 means MHA, so kvh == num_heads
        # normalizes to 0 (num_kv_heads=H and =0 are the same model)
        num_kv_heads = 0 if kvh == num_heads else kvh
        kv_dim = kvh * (dim // num_heads)
        spec = LayerSpec(
            num_heads, kvh, rope=RopeSpec() if pos_encoding == "rope" else None
        )
        # the split count and per-block stride must not depend on
        # moe_every: dense models seeded before MoE existed must keep
        # bit-identical weights, so MoE keys are folded in separately
        keys = jax.random.split(key, 2 + 6 * depth)

        def init(k, shape, fan_in):
            return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

        blocks = []
        for i in range(depth):
            ks = keys[2 + 6 * i : 8 + 6 * i]
            is_moe = bool(moe_every) and (i + 1) % moe_every == 0
            blocks.append(
                LMBlock(
                    wq=init(ks[0], (dim, dim), dim),
                    wk=init(ks[1], (dim, kv_dim), dim),
                    wv=init(ks[2], (dim, kv_dim), dim),
                    wo=init(ks[3], (dim, dim), dim),
                    # a MoE block's dense FFN is never applied — zero-width
                    # placeholders keep the pytree structure uniform
                    # without dead parameters
                    w1=jnp.zeros((dim, 0), jnp.float32)
                    if is_moe
                    else init(ks[4], (dim, ff_mult * dim), dim),
                    w2=jnp.zeros((0, dim), jnp.float32)
                    if is_moe
                    else init(ks[5], (ff_mult * dim, dim), ff_mult * dim),
                    moe=MoELayer.create(
                        jax.random.fold_in(key, 1_000_003 + i),
                        dim, ff_mult * dim, num_experts,
                    )
                    if is_moe
                    else None,
                    spec=spec,
                )
            )
        return TransformerLM(
            embed=0.02 * jax.random.normal(keys[0], (vocab, dim)),
            # rope keeps a zero-width placeholder: no table params, no cap
            pos_embed=jnp.zeros((0, dim), jnp.float32)
            if pos_encoding == "rope"
            else 0.02 * jax.random.normal(keys[1], (max_seq, dim)),
            blocks=tuple(blocks),
            num_heads=num_heads,
            seq_mode=seq_mode,
            mesh=mesh,
            seq_axis=seq_axis,
            compute_dtype=compute_dtype,
            pos_encoding=pos_encoding,
            num_kv_heads=num_kv_heads,
        )

    @staticmethod
    def from_config(
        key,
        config: dict,
        *,
        mesh=None,
        compute_dtype: str = "float32",
        remat: bool = False,
    ) -> "TransformerLM":
        """A model from a ``config.json``-shaped description (the keys of
        a public decoder: ``hidden_size``, ``head_dim``, ``layer_types``,
        ``num_attention_heads_per_layer``, ``mlp_layer_types``,
        ``rope_parameters`` by layer type, ``num_experts``, or a hybrid's
        ``layer_types`` of "mamba" / "attention" with its ``mamba_*``
        keys, ``position_embedding_type`` "nope" and its four
        multipliers, or ``model_type`` "zaya"'s ``layer_types`` of
        "hybrid": attention in a compressed latent with ``cca_time0`` /
        ``cca_time1`` convolutions, then one routed expert a token chosen
        by an MLP of ``router_hidden_size`` fed by a carried state, each
        branch joining under learned scales ...), at the sizes the
        description gives **as held here**: ``num_hidden_layers`` layers
        from the front of the per-layer lists, ``num_experts`` routed
        experts a layer,
        ``vocab_size`` ids. Where that is one chip's share of a
        deployment, ``published`` gives the model's own counts (the
        router keeps ``published.num_experts`` outputs) and
        ``deployment.expert_shard`` says which share of the experts this
        is. Seeded random weights: no checkpoint is read. A
        ``hybrid_override_pattern`` (one character a layer) builds its
        layers one part alone: see :func:`_from_hybrid_pattern`."""
        c = config
        if "hybrid_override_pattern" in c:
            return _from_hybrid_pattern(
                key, c, mesh=mesh, compute_dtype=compute_dtype, remat=remat
            )
        d = c["hidden_size"]
        hd = c.get("head_dim") or d // c["num_attention_heads"]
        depth, vocab = c["num_hidden_layers"], c["vocab_size"]
        kvh = c["num_key_value_heads"]
        nope = c.get("position_embedding_type") == "nope"
        scale = c.get("attention_multiplier")
        heads = c.get("num_attention_heads_per_layer") or (
            [c["num_attention_heads"]] * depth
        )
        kinds = c.get("layer_types") or ["full_attention"] * depth
        mlps = c.get("mlp_layer_types") or ["dense"] * depth
        held = c.get("num_experts", 0)
        routed = c.get("published", {}).get("num_experts", held)
        shard = c.get("deployment", {}).get("expert_shard", 0)

        def rope_of(kind: str) -> RopeSpec:
            r = c["rope_parameters"][kind]
            yarn = None
            if r.get("rope_type", "default") == "yarn":
                yarn = (
                    r["factor"], r["original_max_position_embeddings"],
                    r["beta_fast"], r["beta_slow"], r["attention_factor"],
                )
            elif r.get("rope_type", "default") != "default":
                raise ValueError(f"rope_type {r['rope_type']!r}")
            return RopeSpec(
                float(r["rope_theta"]), float(r.get("partial_rotary_factor", 1.0)),
                yarn,
            )

        def init(k, shape, fan_in):
            return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

        k_embed, k_head, *k_layers = jax.random.split(key, 2 + depth)
        blocks = []
        for i in range(depth):
            h = heads[i]
            if h % kvh:
                raise ValueError(f"layer {i}: {h} heads over {kvh} K/V heads")
            sliding = kinds[i] == "sliding_attention"
            ks = jax.random.split(k_layers[i], 9)
            hybrid = kinds[i] == "hybrid"
            sparse = mlps[i] == "sparse" or hybrid
            ff = c.get("shared_intermediate_size", c.get("intermediate_size"))
            after_mixer = dict(
                w1=jnp.zeros((d, 0), jnp.float32)
                if sparse
                else init(ks[4], (d, ff), d),
                w2=jnp.zeros((0, d), jnp.float32)
                if sparse
                else init(ks[5], (ff, d), ff),
                w3=None if sparse else init(ks[6], (d, ff), d),
                norm1=jnp.ones((d,), jnp.float32),
                norm2=jnp.ones((d,), jnp.float32),
                moe=MoELayer.create(
                    ks[8], d, c["moe_intermediate_size"], routed,
                    held=held, first_expert=shard * held,
                    top_k=c["num_experts_per_tok"], swiglu=True,
                    shared_ff=c.get("shared_expert_intermediate_size", 0),
                    scoring="sigmoid",
                    routed_scale=c.get("moe_routed_scaling_factor", 1.0),
                    router_std=1.0 / math.sqrt(d),
                    # one gate a token stays the chosen probability
                    renormalize=not hybrid or c["num_experts_per_tok"] > 1,
                )
                if sparse
                else None,
            )
            # a mixer stands in for the attention weights, which stay as
            # zero-width placeholders (as w1 / w2 do under routed experts)
            no_attention = dict(
                wq=jnp.zeros((d, 0), jnp.float32),
                wk=jnp.zeros((d, 0), jnp.float32),
                wv=jnp.zeros((d, 0), jnp.float32),
                wo=jnp.zeros((0, d), jnp.float32),
            )
            if hybrid:
                # the scores come from the block's router: the expert
                # layer's own matrix is a zero-height placeholder
                kc, kr = jax.random.split(ks[0])
                after_mixer["moe"] = dataclasses.replace(
                    after_mixer["moe"], w_router=jnp.zeros((0, routed), jnp.float32)
                )
                blocks.append(
                    LMBlock(
                        cca=CCAMixer.create(
                            kc, d, heads=h, kv_heads=kvh, head_dim=hd,
                            time0=c["cca_time0"], time1=c["cca_time1"],
                            eps=c.get("rms_norm_eps", 1e-6),
                        ),
                        router=CarriedRouter.create(
                            kr, d, c["router_hidden_size"], routed,
                            eps=c.get("rms_norm_eps", 1e-6),
                        ),
                        scale1=jnp.tile(jnp.array([[1.0], [0.0]]), (2, d)),
                        scale2=jnp.tile(jnp.array([[1.0], [0.0]]), (2, d)),
                        spec=LayerSpec(h, kvh, rope=rope_of("hybrid")),
                        **no_attention,
                        **after_mixer,
                    )
                )
                continue
            if kinds[i] == "mamba":
                blocks.append(
                    LMBlock(
                        ssm=Mamba2Mixer.create(
                            ks[0], d,
                            heads=c["mamba_n_heads"], head_dim=c["mamba_d_head"],
                            state=c["mamba_d_state"], groups=c["mamba_n_groups"],
                            conv=c["mamba_d_conv"], conv_bias=c["mamba_conv_bias"],
                            chunk=c["mamba_chunk_size"],
                            eps=c.get("rms_norm_eps", 1e-6),
                        ),
                        spec=LayerSpec(0, 0),
                        **no_attention,
                        **after_mixer,
                    )
                )
                continue
            blocks.append(
                LMBlock(
                    wq=init(ks[0], (d, h * hd), d),
                    wk=init(ks[1], (d, kvh * hd), d),
                    wv=init(ks[2], (d, kvh * hd), d),
                    wo=init(ks[3], (h * hd, d), h * hd),
                    wg=init(ks[7], (d, h), d) if c.get("gating") else None,
                    spec=LayerSpec(
                        h, kvh,
                        window=c["sliding_window"] if sliding else 0,
                        rope=None if nope else rope_of(kinds[i]),
                        scale=scale,
                    ),
                    **after_mixer,
                )
            )
        tied = c.get("tie_word_embeddings", False)
        return TransformerLM(
            embed=0.02 * jax.random.normal(k_embed, (vocab, d)),
            pos_embed=jnp.zeros((0, d), jnp.float32),
            blocks=tuple(blocks),
            head=None if tied else init(k_head, (d, vocab), d),
            final_norm=jnp.ones((d,), jnp.float32),
            num_heads=heads[0],
            mesh=mesh,
            remat=remat,
            compute_dtype=compute_dtype,
            pos_encoding="nope" if nope else "rope",
            num_kv_heads=0 if kvh == heads[0] else kvh,
            embed_scale=False,
            norm_eps=c.get("rms_norm_eps", 1e-6),
            embed_multiplier=float(c.get("embedding_multiplier", 1.0)),
            residual_multiplier=float(c.get("residual_multiplier", 1.0)),
            logits_scale=1.0 / float(c.get("logits_scaling", 1.0)),
        )

    def num_params(self) -> int:
        return sum(
            int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(self)
        )


def _from_hybrid_pattern(key, c: dict, *, mesh, compute_dtype: str,
                         remat: bool) -> TransformerLM:
    """A model whose ``hybrid_override_pattern`` gives each layer as one
    part alone (``model_type`` "nemotron_h"), at the counts held here:
    ``M`` a Mamba-2 mixer of ``mamba_num_heads`` heads in ``n_groups``
    groups; ``*`` attention with no positional encoding; ``E`` an expert
    layer whose ``n_routed_experts`` held experts (of
    ``published.n_routed_experts``, from ``deployment.expert_shard`` on)
    are relu² in a latent of ``moe_latent_size``, beside a relu² shared
    expert of ``moe_shared_expert_intermediate_size`` over
    ``deployment.tensor_parallel`` (the columns one chip of that many
    holds); and ``num_nextn_predict_layers`` = 1 an MTP module whose
    layers follow ``mtp_hybrid_override_pattern``, weighted by
    ``mtp_loss_scaling_factor``. RMSNorm everywhere, an untied head."""
    d, hd = c["hidden_size"], c["head_dim"]
    depth, vocab = c["num_hidden_layers"], c["vocab_size"]
    heads, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    eps = c.get("layer_norm_epsilon", 1e-5)
    deployment = c.get("deployment", {})
    held = c["n_routed_experts"]
    routed = c.get("published", {}).get("n_routed_experts", held)
    pattern = c["hybrid_override_pattern"][:depth]
    if len(pattern) != depth or c.get("mlp_hidden_act") != "relu2":
        raise ValueError(
            f"{depth} layers of pattern {pattern!r}, mlp_hidden_act "
            f"{c.get('mlp_hidden_act')!r}: expected relu2"
        )

    def ones():  # a buffer of its own: the step donates every leaf
        return jnp.ones((d,), jnp.float32)

    absent = dict(wq=None, wk=None, wv=None, wo=None, w1=None, w2=None)

    def init(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    def layer(kind: str, k) -> LMBlock:
        if kind == "M":
            mixer = Mamba2Mixer.create(
                k, d, heads=c["mamba_num_heads"], head_dim=c["mamba_head_dim"],
                state=c["ssm_state_size"], groups=c["n_groups"],
                conv=c["conv_kernel"], conv_bias=c["use_conv_bias"],
                chunk=c["chunk_size"], eps=eps,
            )
            return LMBlock(**absent, ssm=mixer, norm1=ones(), spec=LayerSpec(0, 0))
        if kind == "*":
            ks = jax.random.split(k, 4)
            return LMBlock(
                **{
                    **absent,
                    "wq": init(ks[0], (d, heads * hd), d),
                    "wk": init(ks[1], (d, kvh * hd), d),
                    "wv": init(ks[2], (d, kvh * hd), d),
                    "wo": init(ks[3], (heads * hd, d), heads * hd),
                },
                norm1=ones(),
                spec=LayerSpec(heads, kvh),
            )
        if kind == "E":
            shared = c["moe_shared_expert_intermediate_size"] * c.get("n_shared_experts", 1)
            experts = MoELayer.create(
                k, d, c["moe_intermediate_size"], routed,
                held=held, first_expert=deployment.get("expert_shard", 0) * held,
                top_k=c["num_experts_per_tok"],
                shared_ff=shared // deployment.get("tensor_parallel", 1),
                scoring="sigmoid", routed_scale=c["routed_scaling_factor"],
                router_std=1.0 / math.sqrt(d), renormalize=c["norm_topk_prob"],
                latent=c.get("moe_latent_size") or 0, activation="relu2",
            )
            return LMBlock(**absent, moe=experts, norm2=ones(), spec=LayerSpec(0, 0))
        raise ValueError(f"layer kind {kind!r} of {pattern!r}")

    k_embed, k_head, *k_layers = jax.random.split(key, 2 + depth)
    mtp = None
    if c.get("num_nextn_predict_layers", 0):
        if c["num_nextn_predict_layers"] != 1:
            raise ValueError("multi-token prediction of depth 1 only")
        kinds = c["mtp_hybrid_override_pattern"]
        k_proj, *k_mtp = jax.random.split(jax.random.fold_in(key, 7919), 1 + len(kinds))
        mtp = MTPModule(
            enorm=ones(), hnorm=ones(),
            eh_proj=init(k_proj, (2 * d, d), 2 * d),
            blocks=tuple(layer(kind, k) for kind, k in zip(kinds, k_mtp)),
            final_norm=ones(),
            weight=float(c["mtp_loss_scaling_factor"]),
        )
    return TransformerLM(
        embed=0.02 * jax.random.normal(k_embed, (vocab, d)),
        pos_embed=jnp.zeros((0, d), jnp.float32),
        blocks=tuple(layer(kind, k) for kind, k in zip(pattern, k_layers)),
        head=init(k_head, (d, vocab), d),
        final_norm=ones(),
        mtp=mtp,
        num_heads=heads,
        mesh=mesh,
        remat=remat,
        compute_dtype=compute_dtype,
        pos_encoding="nope",
        num_kv_heads=0 if kvh == heads else kvh,
        embed_scale=False,
        norm_eps=eps,
    )


def mtp_depth(model: TransformerLM) -> int:
    """How many positions beyond the next the model's loss reads: the
    MTP module's depth, 0 without one."""
    return 0 if model.mtp is None else 1


def remat_wrap(fn, policy: str):
    """``jax.checkpoint`` under the model's remat policy (shared by the
    layer loop and the pipeline-parallel stage chain)."""
    if policy == "full":
        return jax.checkpoint(fn)
    if policy == "dots":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    raise ValueError(f"remat_policy={policy!r}; expected full|dots")


def has_quantized_leaves(model) -> bool:
    """True if any leaf is an int8 :class:`QTensor` (a serving model —
    training must reject it: gradients through rounding are silently 0)."""
    return any(
        isinstance(l, QTensor)
        for l in jax.tree_util.tree_leaves(
            model, is_leaf=lambda x: isinstance(x, QTensor)
        )
    )


def train_step_flops(model: TransformerLM, batch: int, seq: int) -> float:
    """Model FLOPs of one train step: six times the parameters a token
    touches (a routed layer's experts at ``top_k`` times the share held
    here, under even routing; the embedding table is a gather unless the
    logits are tied to it; an MTP module's layers and projection, and
    the head a second time), plus the causal score and value products
    (a window layer reckoned at its window, a compressed-latent layer at
    its latent's width; a state-space block, whose ``wq`` is zero-width
    or absent, has none, and its scan's own FLOPs, under 2 % of such a
    step, are left out). Recomputation not counted."""
    tokens = batch * seq
    touched = 0.0
    attn = 0.0
    if model.mtp is not None:
        touched += sum(
            int(np.prod(l.shape))
            for l in (model.mtp.enorm, model.mtp.hnorm, model.mtp.eh_proj)
        )
    for blk in model.blocks + model._mtp_blocks():
        n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(blk))
        m = blk.moe
        if m is not None:
            experts = sum(
                int(np.prod(w.shape)) for w in (m.w1, m.w2, m.w3) if w is not None
            )
            n -= experts * (1.0 - m.top_k / m.num_experts)
        touched += n
        spec = model.layer_spec(blk)
        keys = (seq + 1) / 2  # mean keys a causal query sees
        if spec.window:
            keys = min(keys, spec.window)
        wq = blk.wq if blk.cca is None else blk.cca.wq
        if wq is not None:
            attn += 12 * wq.shape[1] * keys * tokens
    head = model.embed if model.head is None else model.head
    touched += int(np.prod(head.shape)) * (1 + mtp_depth(model))
    return 6.0 * touched * tokens + attn
