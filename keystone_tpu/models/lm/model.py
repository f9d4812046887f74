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
from keystone_tpu.ops.quantization import QTensor, mm
from keystone_tpu.ops.vit import _layer_norm


@treenode
class LMBlock:
    wq: jnp.ndarray  # (d, d)
    wk: jnp.ndarray
    wv: jnp.ndarray
    wo: jnp.ndarray
    w1: jnp.ndarray  # (d, ff)
    w2: jnp.ndarray  # (ff, d)


def _ln(x, cdt):
    # normalization stats in f32 even under a bf16 policy: the
    # mean/variance cancellation is exactly what bf16 loses
    return _layer_norm(x.astype(jnp.float32)).astype(cdt)


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


def _rope(x, positions, base: float = 10_000.0):
    """Rotary position embedding. x: (..., S, hd), hd even; positions:
    (S,) int32 global token positions — or (B, S) when sequences in the
    batch sit at different positions (the serving decode pool: each slot
    carries its own sequence, so each rotates at its own phase). Angles
    in f32 (bf16 loses phase accuracy fast at long context), rotated
    result back in x.dtype."""
    hd = x.shape[-1]
    half = hd // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    freqs = positions.astype(jnp.float32)[..., None] * inv  # (..., S, half)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if freqs.ndim == 3:
        # (B, S, half) phases meet (B, H, S, hd/2) halves: insert the
        # head axis so each batch row broadcasts over its own heads
        cos, sin = cos[:, None], sin[:, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def _block_apply(x, blk: LMBlock, cdt, attn, moe=None, mm_fn=mm):
    """Pre-LN residual block shared by training forward, prefill, and
    decode: ``attn(y, blk) -> (attention output (N,S,d), aux)``. When
    ``moe`` is given it replaces the dense FFN; returns
    (x, attn_aux, moe_aux_loss)."""
    a, aux = attn(_ln(x, cdt), blk)
    x = x + a
    y = _ln(x, cdt)
    if moe is not None:
        f, moe_aux = moe(y)
        return x + f, aux, moe_aux
    hdn = mm_fn(y, blk.w1, cdt)
    return x + mm_fn(jax.nn.gelu(hdn), blk.w2, cdt), aux, jnp.float32(0)


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
    d = model.embed.shape[-1]
    x = _gather_embed(model.embed, tokens) * math.sqrt(d)
    if model.pos_encoding == "learned":
        x = x + model.pos_embed[: tokens.shape[1]]
    return x.astype(cdt)


def _tied_logits(x, embed, cdt):
    # bf16 operands, f32 accumulate/output: the logits feed a logsumexp —
    # bf16 logits would cost real perplexity precision
    if isinstance(embed, QTensor):
        # (V, 1) row scales become per-output-channel under the transpose
        return jnp.matmul(
            _ln(x, cdt), embed.q.T.astype(cdt),
            preferred_element_type=jnp.float32,
        ) * embed.scale[:, 0]
    return jnp.matmul(
        _ln(x, cdt), embed.T.astype(cdt), preferred_element_type=jnp.float32
    )


@treenode
class TransformerLM:
    """Pre-LN decoder-only LM; logits tied to the token embedding."""

    embed: jnp.ndarray  # (V, d)
    pos_embed: jnp.ndarray  # (S_max, d)
    blocks: tuple  # of LMBlock
    num_heads: int = static_field(default=8)
    # attention strategy: "local" (dense or Pallas flash on TPU),
    # "ring" / "ulysses" (sequence-parallel over `seq_axis` of `mesh`).
    # A "local" model whose batch or heads are split over a mesh carries
    # that mesh too: the flash kernel is shard_mapped over it (GSPMD
    # cannot partition a Mosaic kernel)
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
    # memory/MFU middle ground (ROOFLINE.md §6): the MXU never re-runs,
    # so measured step FLOPs stay at the analytic 6·P·tokens
    remat_policy: str = static_field(default="full")
    # mixed precision: params/optimizer state stay float32; activations
    # and the matmul operands run in this dtype ("bfloat16" halves HBM
    # traffic and feeds the MXU its native input width). LayerNorm stats
    # and the loss reduction stay float32 regardless.
    compute_dtype: str = static_field(default="float32")
    # expert parallelism: per-block MoE layers (None entries keep the
    # dense FFN). Tuple parallel to `blocks`; empty = no MoE anywhere.
    moe_layers: tuple = ()
    moe_aux_weight: float = static_field(default=0.01)
    # "learned" = trained absolute table (pos_embed, capped at max_seq);
    # "rope" = rotary q/k phases — no table, no length cap beyond memory,
    # the right pairing for the blockwise long-context backward
    pos_encoding: str = static_field(default="learned")
    # grouped-query attention: K/V carry this many heads (0 = num_heads,
    # plain MHA; 1 = MQA). The decode cache shrinks by num_heads/kv_heads
    # — composing with kv_dtype="int8" for the full serving story
    num_kv_heads: int = static_field(default=0)
    # how int8 QTensor weights multiply: "xla" trusts the convert-into-
    # dot fusion (ops/quantization.mm); "pallas" streams the codes as
    # int8 via the fused kernel (ops/int8_matmul.mm_fused) — the A/B the
    # bench measures e2e (ROOFLINE.md §6 decode note)
    int8_kernel: str = static_field(default="xla")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def _qkv_heads(self, x, blk: LMBlock, positions=None):
        """(q with H heads, k/v with KV heads, rope applied).
        ``positions`` defaults to 0..S-1 (full-sequence forward); decode
        passes the single global position of its new token."""
        mm_fn = model_mm(self)
        q = _split_heads(x, blk.wq, self.num_heads, mm_fn)
        k = _split_heads(x, blk.wk, self.kv_heads, mm_fn)
        v = _split_heads(x, blk.wv, self.kv_heads, mm_fn)
        if self.pos_encoding == "rope":
            if positions is None:
                positions = jnp.arange(x.shape[1])
            q = _rope(q, positions)
            k = _rope(k, positions)
        return q, k, v

    def _attention(self, x, blk: LMBlock, return_kv: bool = False):
        n, s, d = x.shape
        h = self.num_heads

        # x is always the full (global) sequence here — the
        # sequence-parallel paths shard inside ring/ulysses_attention
        q, k, v = self._qkv_heads(x, blk)
        kv_raw = (k, v)  # pre-broadcast: what the decode cache stores
        if self.kv_heads != h:
            # training/prefill compute broadcasts K/V up to H heads
            # (activation-sized, the standard GQA training treatment);
            # the grouped decode path never materializes this
            g = h // self.kv_heads
            k = jnp.repeat(k, g, axis=1)
            v = jnp.repeat(v, g, axis=1)
        # sequence-parallel training runs the custom-VJP bodies: the ring
        # backward circulates dk/dv accumulators around the ring (the
        # per-hop Pallas forward kernels are forward-only), Ulysses
        # differentiates the flash trainable wrapper through all_to_all.
        # use_flash auto-selects: Pallas-rate on TPU, jnp off it.
        if self.seq_mode == "ring":
            out = ring_attention(
                q, k, v, self.mesh, seq_axis=self.seq_axis, causal=True,
                trainable=True,
            )
        elif self.seq_mode == "ulysses":
            out = ulysses_attention(
                q, k, v, self.mesh, seq_axis=self.seq_axis, causal=True,
                trainable=True,
            )
        else:
            from keystone_tpu.ops.flash_attention import on_tpu

            # KST_LOCAL_ATTN overrides the auto-select (read per call,
            # like the KST_FLASH_* knobs): the S=2048 flagship shape sits
            # in the regime where dense XLA attention can rival the
            # Pallas kernel (TPU_VALIDATION 0.98-1.27x at <=8k), so the
            # MFU push sweeps this axis too (tools/lm_mfu_push2.py)
            import os as _os

            mode = _os.environ.get("KST_LOCAL_ATTN", "auto")
            if mode not in ("auto", "flash", "dense"):
                raise ValueError(
                    f"KST_LOCAL_ATTN={mode!r}; expected auto|flash|dense"
                )
            use_flash = on_tpu() if mode == "auto" else mode == "flash"
            if use_flash:
                # fused Pallas forward with a recompute VJP — training
                # never materializes the (S, S) probabilities
                from keystone_tpu.ops.flash_attention import (
                    flash_attention_trainable,
                )

                def attend(q, k, v):
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
                    spec = P(
                        "data" if n % sizes.get("data", n + 1) == 0 else None,
                        "model" if h % sizes.get("model", h + 1) == 0 else None,
                        None,
                        None,
                    )
                    attend = jax.shard_map(
                        attend,
                        mesh=self.mesh,
                        in_specs=(spec, spec, spec),
                        out_specs=spec,
                        check_vma=False,  # pallas_call outputs carry no vma
                    )
                out = attend(q, k, v)
            else:
                out = dense_attention(q, k, v, causal=True)
        proj = model_mm(self)(
            out.transpose(0, 2, 1, 3).reshape(n, s, d).astype(x.dtype),
            blk.wo,
            x.dtype,
        )
        if return_kv:
            return proj, kv_raw
        return proj

    def _moe(self, i: int):
        return self.moe_layers[i] if self.moe_layers else None

    def __call__(self, tokens):
        """(B, S) int tokens → (B, S, V) float32 logits."""
        return self.forward_with_aux(tokens)[0]

    def backbone(self, tokens):
        """(final hidden states (B, S, d) pre-logits, MoE aux loss) —
        the forward minus the tied-logits projection, so losses can
        choose how (or whether) to materialize logits."""
        cdt = jnp.dtype(self.compute_dtype)
        x = _embed(self, tokens, cdt)

        def block_fn(x, blk, moe):
            out, _, moe_aux = _block_apply(
                x, blk, cdt,
                lambda y, b: (self._attention(y, b), None),
                moe=moe,
                mm_fn=model_mm(self),
            )
            return out, moe_aux

        if self.remat:
            block_fn = remat_wrap(block_fn, self.remat_policy)
        aux = jnp.float32(0)
        for i, blk in enumerate(self.blocks):
            x, moe_aux = block_fn(x, blk, self._moe(i))
            aux = aux + moe_aux
        return x, aux

    def forward_with_aux(self, tokens):
        """(logits (B, S, V) f32, total MoE load-balance aux loss)."""
        x, aux = self.backbone(tokens)
        cdt = jnp.dtype(self.compute_dtype)
        return _tied_logits(x, self.embed, cdt), aux

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
        capacity_factor: float = 1.25,
        pos_encoding: str = "learned",
        num_kv_heads: int = 0,
    ) -> "TransformerLM":
        """``moe_every=k`` replaces the dense FFN of every k-th block with
        a top-2 routed :class:`~keystone_tpu.ops.moe.MoELayer` of
        ``num_experts`` experts (0 = dense everywhere).
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
        # the split count and per-block stride must not depend on
        # moe_every: dense models seeded before MoE existed must keep
        # bit-identical weights, so MoE keys are folded in separately
        keys = jax.random.split(key, 2 + 6 * depth)

        def init(k, shape, fan_in):
            return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

        blocks = []
        moes = []
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
                )
            )
            if is_moe:
                from keystone_tpu.ops.moe import MoELayer

                moes.append(
                    MoELayer.create(
                        jax.random.fold_in(key, 1_000_003 + i),
                        dim, ff_mult * dim, num_experts, capacity_factor,
                    )
                )
            else:
                moes.append(None)
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
            moe_layers=tuple(moes) if moe_every else (),
            pos_encoding=pos_encoding,
            num_kv_heads=num_kv_heads,
        )

    def num_params(self) -> int:
        return sum(
            int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(self)
        )


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
    """Analytic FLOPs of one train step: ~6·P_active·tokens for the matmul
    work plus the attention score/value terms (12·L·d·S²·B fwd+bwd). MoE
    expert gemms execute over ALL E·C static capacity slots (drops included
    — that's the static-shape trade), so expert params count at C/G weight,
    not the idealized 2/E."""
    p = model.num_params()
    tokens = batch * seq
    for m in model.moe_layers:
        if m is not None:
            expert_p = int(np.prod(m.w1.shape)) + int(np.prod(m.w2.shape))
            slots = m.num_experts * m._capacity(tokens)
            p -= expert_p * (1.0 - min(slots / (tokens * m.num_experts), 1.0))
    d = model.embed.shape[-1]
    attn = 12 * len(model.blocks) * d * seq * seq * batch
    return 6.0 * p * tokens + attn
