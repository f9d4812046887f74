"""KV-cache serving for the transformer LM: prefill → decode → sample.

Static shapes are the design rule throughout — the whole generate loop
compiles to ONE program (prefill + a lax.scan of decode steps) with
in-place `dynamic_update_slice` cache writes, no retracing as the
sequence grows. Weight-only int8 (:func:`quantize_for_decode`) and the
int8 KV cache attack the two HBM streams that bound decode rate on TPU:
the parameters and, at long context, the cache itself.

The reference serves f64 BLAS models and has no autoregressive path;
this module is beyond-reference serving capability (SURVEY §5).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from keystone_tpu.core.treenode import treenode
from keystone_tpu.models.lm.model import (
    TransformerLM,
    _block_apply,
    _embed,
    _gather_embed,
    model_mm,
    output_logits,
)
from keystone_tpu.ops.quantization import quantize_int8


@treenode
class KVCache:
    """Preallocated decode cache: static (L, B, KV_heads, S_max, hd)
    buffers (KV_heads < num_heads under GQA — that ratio IS the cache
    saving) plus the number of valid positions. Static shapes are the point — the whole
    generate loop compiles to ONE program (prefill + a lax.scan of decode
    steps) with in-place `dynamic_update_slice` writes, no retracing as
    the sequence grows (the XLA analog of the reference's nothing: it has
    no autoregressive models).

    With ``kv_dtype="int8"`` the buffers hold per-position symmetric int8
    with (L, B, KV_heads, S_max, 1) scales: at long context the cache, not the
    weights, dominates each decode step's HBM reads, and the scales pull
    OUT of both dots exactly (scores = (q·k_q^T)·scale_k; out =
    (p·scale_v)·v_q), so nothing dequantized ever materializes."""

    k: jnp.ndarray
    v: jnp.ndarray
    pos: jnp.ndarray  # scalar int32
    k_scale: jnp.ndarray | None = None
    v_scale: jnp.ndarray | None = None


def _kv_quant(t):
    """(..., hd) → (int8 codes, f32 scale (..., 1)) per-position — the
    shared symmetric recipe pooling over the head dim."""
    from keystone_tpu.ops.quantization import symmetric_int8

    return symmetric_int8(t, (-1,))


def refuse_unservable(model: TransformerLM) -> None:
    """The KV-cache path holds one (L, B, KV, S_max, hd) buffer pair and
    attends over all of it: a model with window layers, a head count per
    layer, gated heads, another rotary scheme or an untied head is
    refused by name, not served as something else (ROADMAP: the window
    layers' cache)."""
    why = model.uniform_decode_reason()
    if why is not None:
        raise NotImplementedError(
            f"prefill/decode cannot serve this model: {why}"
        )


def prefill(model: TransformerLM, tokens, s_max: int,
            kv_dtype: str | None = None, lengths=None):
    """Run the prompt through the model once, capturing per-layer K/V into
    an ``s_max``-long cache (optionally int8 — see :class:`KVCache`).
    Returns (last-position logits (B, V), cache). Local attention only
    (sequence-parallel decode shards the cache — use ring/Ulysses for
    training, gather to local for decode).

    ``lengths`` ((B,) int32) admits a batch of unequal-length prompts
    right-padded to a common width: logits are gathered at each
    sequence's own last real token (``lengths - 1``) and the cache comes
    back with a *per-sequence* ``pos`` vector, so decode resumes each
    row at its own position. Causal attention already keeps right-pad
    K/V out of every real token's view, and decode overwrites the pad
    region before its positions ever become valid — no mask plumbing
    needed (the positions past ``pos`` are excluded by
    :func:`decode_step`'s validity mask)."""
    if model.seq_mode != "local":
        raise ValueError("prefill/decode require seq_mode='local'")
    refuse_unservable(model)
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"kv_dtype={kv_dtype!r}; expected None|'int8'")
    cdt = jnp.dtype(model.compute_dtype)
    n, s = tokens.shape
    x = _embed(model, tokens, cdt)

    ks, vs = [], []
    for blk in model.blocks:
        x, (k, v), _, _ = _block_apply(
            x, blk, cdt,
            lambda y, b: model._attention(y, b, return_kv=True),
            mm_fn=model_mm(model),
        )
        ks.append(k)
        vs.append(v)
    if lengths is None:
        logits = output_logits(model, x[:, -1:], cdt)[:, 0]
        pos = jnp.asarray(s, jnp.int32)
    else:
        lengths = jnp.asarray(lengths, jnp.int32)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
        )  # (B, 1, d) — each row's own final real token
        logits = output_logits(model, last, cdt)[:, 0]
        pos = lengths
    pad = [(0, 0), (0, 0), (0, s_max - s), (0, 0)]
    k_stack = jnp.stack([jnp.pad(k, pad) for k in ks])
    v_stack = jnp.stack([jnp.pad(v, pad) for v in vs])
    if kv_dtype == "int8":
        kq, ksc = _kv_quant(k_stack)
        vq, vsc = _kv_quant(v_stack)
        cache = KVCache(k=kq, v=vq, pos=pos, k_scale=ksc, v_scale=vsc)
    else:
        cache = KVCache(k=k_stack, v=v_stack, pos=pos)
    return logits, cache


def decode_step(model: TransformerLM, token, cache: KVCache):
    """One autoregressive step: (B,) token at position ``cache.pos`` →
    ((B, V) logits, updated cache). Attention reads the full static-shape
    cache with positions ≥ pos masked — compiler-friendly in exchange for
    O(S_max) work per step.

    ``cache.pos`` may be the classic scalar (every row at the same
    position — one in-place 5-D slice write per buffer, the cheapest
    path, kept bit-identical) or a **(B,) vector**: each row decodes at
    its own position, which is what continuous batching needs — slots
    join and retire independently, so the pool's rows are never aligned.
    The vector path writes via a one-hot select over the position axis
    (O(S_max) per layer — the same order as the attention read that
    follows, so nothing asymptotically new)."""
    refuse_unservable(model)
    cdt = jnp.dtype(model.compute_dtype)
    d = model.embed.shape[-1]
    h = model.num_heads
    hd = d // h
    n = token.shape[0]
    pos = cache.pos
    s_cap = cache.k.shape[3]
    vec = getattr(pos, "ndim", 0) >= 1  # per-row positions
    x = _gather_embed(model.embed, token)[:, None] * math.sqrt(d)
    if model.pos_encoding == "learned":
        if vec:
            x = x + jnp.take(model.pos_embed, pos, axis=0)[:, None]
        else:
            x = x + jax.lax.dynamic_slice_in_dim(model.pos_embed, pos, 1)
    x = x.astype(cdt)

    if vec:
        valid = (jnp.arange(s_cap)[None, :] <= pos[:, None])[:, None, None, :]
        hit = (jnp.arange(s_cap)[None, :] == pos[:, None])[:, None, :, None]
    else:
        valid = (jnp.arange(s_cap) <= pos)[None, None, None, :]
    quantized = cache.k_scale is not None
    new_k, new_v = cache.k, cache.v
    new_ks, new_vs = cache.k_scale, cache.v_scale

    kvh = model.kv_heads
    g = h // kvh  # query heads per K/V head (1 = plain MHA)

    def write(buf, i, val):
        """Write the (B, KV_heads, 1, *) new-position slab into layer
        ``i`` of a (L, B, KV_heads, S_max, *) buffer at ``pos``."""
        if not vec:
            return jax.lax.dynamic_update_slice(
                buf, val[None].astype(buf.dtype), (i, 0, 0, pos, 0)
            )
        layer = jnp.where(hit, val.astype(buf.dtype), buf[i])
        return jax.lax.dynamic_update_slice(buf, layer[None], (i, 0, 0, 0, 0))

    def cached_attn(i):
        def attn(y, blk):
            nonlocal new_k, new_v, new_ks, new_vs
            # the shared split+rope helper, at the new token's global
            # position; cached keys were stored rotated by prefill /
            # earlier steps
            q, k1, v1 = model._qkv_heads(
                y, blk, positions=pos[:, None] if vec else pos[None]
            )
            if quantized:
                k1, k1s = _kv_quant(k1)
                v1, v1s = _kv_quant(v1)
                new_ks = write(new_ks, i, k1s)
                new_vs = write(new_vs, i, v1s)
            # one 5-D in-place update per buffer — not gather + rewrite,
            # which XLA may lower to an O(L·S_max) cache copy per layer
            new_k = write(new_k, i, k1)
            new_v = write(new_v, i, v1)
            layer_k, layer_v = new_k[i], new_v[i]
            # grouped attention (MHA is the g=1 special case): q heads
            # regroup as (KV, G) against the KV-head cache — no repeated
            # K/V ever materializes, which is GQA's decode point
            qg = q.reshape(n, kvh, g, 1, hd).astype(cdt)
            scores = jnp.einsum(
                "bkgqd,bksd->bkgqs", qg, layer_k.astype(cdt),
                preferred_element_type=jnp.float32,
            ) / math.sqrt(hd)
            if quantized:
                # per-position scales pull out of the contraction exactly
                scores = scores * new_ks[i][..., 0][:, :, None, None, :]
            scores = jnp.where(valid[:, :, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            if quantized:
                probs = probs * new_vs[i][..., 0][:, :, None, None, :]
            out = jnp.einsum(
                "bkgqs,bksd->bkgqd", probs.astype(cdt),
                layer_v.astype(cdt),
                preferred_element_type=jnp.float32,
            )
            proj = mm_fn(
                out.reshape(n, h, 1, hd).transpose(0, 2, 1, 3).reshape(
                    n, 1, d
                ).astype(cdt),
                blk.wo,
                cdt,
            )
            return proj, None

        return attn

    mm_fn = model_mm(model)
    for i, blk in enumerate(model.blocks):
        x, _, _, _ = _block_apply(x, blk, cdt, cached_attn(i), mm_fn=mm_fn)
    logits = output_logits(model, x, cdt)[:, 0]
    # past-capacity poison: at pos >= S_max the cache write would clamp
    # onto S_max-1 and return plausible-but-wrong logits; pos is traced,
    # so the honest device-side failure is loud NaNs, not an exception
    in_cap = (pos < s_cap)[:, None] if vec else pos < s_cap
    logits = jnp.where(in_cap, logits, jnp.nan)
    return logits, KVCache(
        k=new_k, v=new_v, pos=pos + 1, k_scale=new_ks, v_scale=new_vs
    )


def _filter_logits(logits, top_k: int, top_p: float):
    """Top-k then nucleus filtering on (B, V) logits (already temperature
    -scaled — the nucleus mass is meaningful only on the distribution
    actually sampled): everything outside the keep-set drops to -inf.
    Static-shape throughout, one descending sort shared by both filters.
    """
    v = logits.shape[-1]
    if top_k < 0 or top_k > v:
        raise ValueError(f"top_k={top_k} outside [0, vocab={v}]")
    sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
    if top_k:
        kth = sorted_l[:, top_k - 1][:, None]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
        # the nucleus below must see the top-k-filtered distribution
        sorted_l = jnp.where(
            jnp.arange(v)[None, :] < top_k, sorted_l, -jnp.inf
        )
    if top_p:
        probs = jax.nn.softmax(sorted_l, axis=-1)
        # exclusive cumulative mass BEFORE each token: a token stays while
        # the mass above it is < top_p (the first token always stays)
        csum = jnp.cumsum(probs, axis=-1) - probs
        keep = csum < top_p
        # smallest kept logit per row = the threshold
        thresh = jnp.min(
            jnp.where(keep, sorted_l, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits >= thresh, logits, -jnp.inf)
    return logits


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_new", "temperature", "top_k", "top_p", "kv_dtype", "eos_id"
    ),
)
def generate(
    model: TransformerLM,
    prompt,
    *,
    max_new: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    kv_dtype: str | None = None,
    key=None,
    prompt_lens=None,
    eos_id: int | None = None,
):
    """Greedy (temperature=0) or sampled decode of ``max_new`` tokens after
    ``prompt`` (B, P). One jitted program: prefill + lax.scan over steps.
    ``top_k``/``top_p`` (nucleus) restrict sampling to the head of the
    distribution (0 = off; both compose); ``kv_dtype="int8"`` halves the
    cache stream at long context (see :class:`KVCache`). Returns
    (B, max_new) int32.

    ``prompt_lens`` ((B,) int32) admits unequal-length prompts
    right-padded to ``P``: each row's first pick comes from its own last
    real token and decode continues at its own position (per-row cache
    positions — see :func:`prefill` / :func:`decode_step`). ``eos_id``
    arms per-sequence early exit: a row that emits EOS is frozen (its
    remaining output is EOS-filled) and the whole loop stops — still one
    compiled program, as a ``lax.while_loop`` with a dynamic trip count
    — as soon as every row has finished, so a batch of short answers
    never pays ``max_new`` steps. With both arguments left at their
    defaults the program is the original scan, bit-identical."""
    if key is None:
        key = jax.random.key(0)
    s_max = prompt.shape[1] + max_new
    if model.pos_encoding == "learned" and s_max > model.pos_embed.shape[0]:
        raise ValueError(
            f"prompt+max_new={s_max} exceeds max_seq={model.pos_embed.shape[0]}"
        )
    if prompt_lens is not None:
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
    logits0, cache = prefill(
        model, prompt, s_max, kv_dtype=kv_dtype, lengths=prompt_lens
    )

    def pick(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # temperature FIRST: the nucleus cut must measure mass on the
        # distribution being sampled, not the unscaled one
        logits = _filter_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(k, logits).astype(jnp.int32)

    keys = jax.random.split(key, max_new)
    tok0 = pick(logits0, keys[0])
    if max_new == 1:
        return tok0[:, None]

    if eos_id is not None:
        # early-exit decode: a while_loop whose trip count is data-
        # dependent — per-step keys via fold_in (a scan's pre-split keys
        # can't be indexed ahead of a dynamic counter as cheaply)
        out0 = jnp.full(
            (prompt.shape[0], max_new), eos_id, jnp.int32
        ).at[:, 0].set(tok0)

        def cond(c):
            i, _, _, done, _ = c
            return (i < max_new) & ~jnp.all(done)

        def body(c):
            i, tok, cache, done, out = c
            logits, cache2 = decode_step(model, tok, cache)
            tok2 = pick(logits, jax.random.fold_in(key, i))
            tok2 = jnp.where(done, eos_id, tok2)
            out = jax.lax.dynamic_update_slice(out, tok2[:, None], (0, i))
            return (i + 1, tok2, cache2, done | (tok2 == eos_id), out)

        carry = (
            jnp.asarray(1, jnp.int32), tok0, cache, tok0 == eos_id, out0
        )
        return jax.lax.while_loop(cond, body, carry)[4]

    # scan max_new-1 steps: the token for step i is picked from step i-1's
    # logits, so the final logits need no decode step of their own
    def step(carry, k):
        tok, cache = carry
        logits, cache2 = decode_step(model, tok, cache)
        tok2 = pick(logits, k)
        return (tok2, cache2), tok2

    (_, _), rest = jax.lax.scan(step, (tok0, cache), keys[1:])
    return jnp.concatenate([tok0[:, None], rest.T], axis=1)  # (B, max_new)


def quantize_for_decode(model: TransformerLM) -> TransformerLM:
    """Weight-only int8 quantization for serving: every block matrix gets
    symmetric per-output-channel int8 (``ops/quantization.py``), the tied
    embedding per-row scales (serving both the gather and the logit
    transpose). Decode is HBM-bound — every step re-reads all params — so
    halving the weight stream is the decode-rate lever on TPU. Inference
    only: ``train`` rejects quantized models (gradients through rounding
    are silently zero). MoE experts and pos_embed stay full precision
    (experts want per-(expert, channel) scales; the table is tiny)."""

    def qmat(w):
        return quantize_int8(w) if w.size else w

    refuse_unservable(model)
    blocks = tuple(
        dataclasses.replace(
            b, wq=qmat(b.wq), wk=qmat(b.wk), wv=qmat(b.wv), wo=qmat(b.wo),
            w1=qmat(b.w1), w2=qmat(b.w2),
        )
        for b in model.blocks
    )
    return dataclasses.replace(
        model,
        embed=quantize_int8(model.embed, channel_axis=0),
        blocks=blocks,
    )
