"""Next-token losses for the transformer LM.

The numerically sensitive ``logsumexp − gold`` form lives ONCE here
(:func:`token_cross_entropy`); the chunked variant reduces the CE in
S-chunks so the (B, S, V) f32 logits never materialize — at long
context that tensor is the step's single largest HBM object
(S=16k × V=32k f32 = 2.1 GB, twice more with its gradient).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from keystone_tpu.models.lm.model import TransformerLM, output_logits


def token_cross_entropy(logits, targets) -> jnp.ndarray:
    """Mean next-token cross-entropy. logits: (B, S, V) f32; targets:
    (B, S) int. The single source of the numerically sensitive
    ``logsumexp - gold`` form, shared by training loss and evaluation."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def chunked_token_cross_entropy(x, model, targets, cdt, chunk: int):
    """Mean next-token CE from final hidden states (through the model's
    final norm and its head, tied or its own) without ever holding
    the (B, S, V) f32 logits: positions are processed in S-chunks — each
    chunk's logits are built, reduced to ``logsumexp − gold``, and
    dropped (``jax.checkpoint`` recomputes them in the backward),
    turning the full logits tensor into a ``chunk`` × V working set."""
    b, s, d = x.shape
    if chunk <= 0 or s % chunk:
        raise ValueError(
            f"logit_chunk={chunk} must be a positive divisor of the "
            f"sequence length {s}"
        )
    n_c = s // chunk
    xc = x.reshape(b, n_c, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n_c, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_sum(xx, tt):
        logits = output_logits(model, xx, cdt)  # (B, chunk, V) f32
        # token_cross_entropy stays the single source of the CE form;
        # mean × count turns it back into this chunk's sum exactly
        return token_cross_entropy(logits, tt) * tt.size

    total, _ = jax.lax.scan(
        lambda c, args: (c + chunk_sum(*args), None),
        jnp.float32(0),
        (xc, tc),
    )
    return total / (b * s)


def next_token_loss(
    model: TransformerLM, tokens, logit_chunk: int = 0
) -> jnp.ndarray:
    """The scalar of :func:`next_token_loss_and_counters`."""
    return next_token_loss_and_counters(model, tokens, logit_chunk)[0]


def next_token_loss_and_counters(
    model: TransformerLM, tokens, logit_chunk: int = 0
):
    """(mean cross-entropy of predicting ``tokens[:, 1:]`` from the
    prefix, the expert layers' counters): the model runs on the first S
    tokens of an S+1 window. The loss is the cross-entropy alone: no
    auxiliary term the model's description does not name.
    ``logit_chunk > 0`` computes the CE in S-chunks so the full (B, S, V)
    f32 logits never materialize (see chunked_token_cross_entropy). A
    model with an MTP module reads windows of S+2 tokens and adds its
    term (:func:`_loss_with_mtp`)."""
    if model.mtp is not None:
        return _loss_with_mtp(model, tokens, logit_chunk)
    x, counters = model.backbone(tokens[:, :-1])
    with jax.named_scope("loss"):
        return _cross_entropy(model, x, tokens[:, 1:], logit_chunk), counters


def _cross_entropy(model, x, targets, logit_chunk: int):
    """Mean CE of ``targets`` from hidden states through the model's
    final norm and head, chunked or dense."""
    cdt = jnp.dtype(model.compute_dtype)
    if logit_chunk:
        return chunked_token_cross_entropy(x, model, targets, cdt, logit_chunk)
    return token_cross_entropy(output_logits(model, x, cdt), targets)


def _loss_with_mtp(model: TransformerLM, tokens, logit_chunk: int):
    """``CE_1 + weight * CE_2`` over (B, S+2) windows: the main stack on
    the first S ids predicts ``t_(i+1)``; the MTP module reads its hidden
    states and the ids one ahead and predicts ``t_(i+2)`` through its own
    final norm and the model's head. The counters gain ``mtp_rows``
    (positions the second term covers) and ``mtp_ce`` (that term)."""
    s = tokens.shape[1] - 2
    x, counters = model.backbone(tokens[:, :s])
    h, counters = model.mtp_hidden(x, tokens[:, 1 : s + 1], counters)
    with jax.named_scope("loss"):
        ce = _cross_entropy(model, x, tokens[:, 1 : s + 1], logit_chunk)
        ahead = dataclasses.replace(model, final_norm=model.mtp.final_norm)
        ce_mtp = _cross_entropy(ahead, h, tokens[:, 2:], logit_chunk)
    counters = {
        **counters,
        "mtp_rows": jnp.int32(tokens.shape[0] * s),
        "mtp_ce": ce_mtp,
    }
    return ce + model.mtp.weight * ce_mtp, counters
