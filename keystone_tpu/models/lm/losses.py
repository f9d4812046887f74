"""Next-token losses for the transformer LM.

The numerically sensitive ``logsumexp − gold`` form lives ONCE here
(:func:`token_cross_entropy`); the chunked variant reduces the CE in
S-chunks so the (B, S, V) f32 logits never materialize — at long
context that tensor is the step's single largest HBM object
(S=16k × V=32k f32 = 2.1 GB, twice more with its gradient).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from keystone_tpu.models.lm.model import (
    TransformerLM,
    final_rows,
    head_logits,
    head_matrix,
    output_logits,
    scaled_product,
)
from keystone_tpu.ops.quantization import QTensor


def token_cross_entropy(logits, targets) -> jnp.ndarray:
    """Mean next-token cross-entropy. logits: (B, S, V) f32; targets:
    (B, S) int. The single source of the numerically sensitive
    ``logsumexp - gold`` form, shared by training loss and evaluation."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _chunks(a, chunk: int):
    """(B, S, ...) -> (S / chunk, B, chunk, ...): the scan's leading axis."""
    b, s = a.shape[:2]
    return a.reshape(b, s // chunk, chunk, *a.shape[2:]).swapaxes(0, 1)


def _chunk_sum(logits, targets):
    # token_cross_entropy stays the single source of the CE form;
    # mean × count turns it back into this chunk's sum exactly
    return token_cross_entropy(logits, targets) * targets.size


def _chunked_ce(logits_of, xn, targets, chunk: int):
    """Mean CE over S-chunks: each chunk's logits are made, reduced to
    ``logsumexp − gold`` and dropped. One head product a chunk."""
    total, _ = jax.lax.scan(
        lambda total, a: (total + _chunk_sum(logits_of(a[0]), a[1]), None),
        jnp.float32(0),
        (_chunks(xn, chunk), _chunks(targets, chunk)),
    )
    return total / targets.size


@jax.custom_vjp
def _formed_once(logits):
    """The identity, whose cotangent passes an optimization barrier: XLA
    then forms the gradient with respect to the logits once, and both of
    the head's gradient products read it, where it would otherwise form
    it again inside each product's fusion, tile by tile. It changes no
    value."""
    return logits


_formed_once.defvjp(
    lambda logits: (logits, None),
    lambda _, g: (jax.lax.optimization_barrier(g),),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ce(xn, w, targets, scale: float, chunk: int):
    """Mean CE of ``targets`` from the final norm's rows ``xn`` (B, S, d)
    through the float head ``w`` (d, V) times ``scale``. Without a
    gradient it is :func:`_chunked_ce`; under one, :func:`_fused_ce_fwd`
    forms the gradient as it makes each chunk's logits."""
    return _chunked_ce(
        lambda xx: scaled_product(xx, w, scale, xx.dtype), xn, targets, chunk
    )


def _fused_ce_fwd(xn, w, targets, scale: float, chunk: int):
    """One scan over the chunks that runs each chunk's forward and, at
    once, its backward for the cotangent ``1 / (B S)`` the mean gives
    it: autodiff's own, three head products a chunk (the logits, the
    rows' gradient, the head's), in the dtypes autodiff gives them. The
    rows' gradient is rounded to their dtype here and each chunk's head
    gradient to the head's compute dtype before the float32 sum, as
    autodiff of the chunked loss rounds them. The scan runs from the
    last chunk to the first, the order of autodiff's backward scan, so
    the head's gradient is summed in that order and the loss in the
    forward's. The loss's cotangent only scales both, so nothing is left
    for the backward but that."""
    n = targets.size
    wc = w.astype(xn.dtype)

    def body(dw, args):
        xx, tt = args
        chunk_sum, pull = jax.vjp(
            lambda xx, wc: _chunk_sum(
                _formed_once(scaled_product(xx, wc, scale, xx.dtype)), tt
            ),
            xx,
            wc,
        )
        dxx, dwc = pull(jnp.float32(1.0) / n)
        return dw + dwc.astype(jnp.float32), (chunk_sum, dxx)

    dw, (sums, dxc) = jax.lax.scan(
        body,
        jnp.zeros(w.shape, jnp.float32),
        (_chunks(xn, chunk), _chunks(targets, chunk)),
        reverse=True,
    )
    total = functools.reduce(jnp.add, sums, jnp.float32(0))
    dxn = dxc.swapaxes(0, 1).reshape(xn.shape)
    return total / n, (dxn, dw.astype(w.dtype))


def _fused_ce_bwd(scale, chunk, res, g):
    # the rows' gradient, already rounded, is rounded again after the
    # scaling where g is not 1 (the MTP term's 0.3): a last bit of a
    # term that autodiff would round once
    dxn, dw = res
    return (g * dxn).astype(dxn.dtype), (g * dw).astype(dw.dtype), None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def chunked_token_cross_entropy(x, model, targets, cdt, chunk: int):
    """Mean next-token CE from final hidden states (through the model's
    final norm and its head, tied or its own) without ever holding
    the (B, S, V) f32 logits: positions are processed in S-chunks — each
    chunk's logits are built, reduced to ``logsumexp − gold``, and
    dropped, turning the full logits tensor into a ``chunk`` × V working
    set. Under a gradient each chunk's gradient is formed as its logits
    are made (:func:`_fused_ce_fwd`), so no logits are made again; the
    final norm's backward is autodiff's. An int8 head trains nothing and
    takes the plain chunked CE."""
    s = x.shape[1]
    if chunk <= 0 or s % chunk:
        raise ValueError(
            f"logit_chunk={chunk} must be a positive divisor of the "
            f"sequence length {s}"
        )
    xn = final_rows(model, x, cdt)
    w = head_matrix(model)
    if isinstance(w, QTensor):
        return _chunked_ce(lambda xx: head_logits(model, xx, cdt), xn, targets, chunk)
    return _fused_ce(xn, w, targets, model.logits_scale, chunk)


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
