"""Held-out LM evaluation: cross-entropy, perplexity, bits per token.

The reference's evaluation layer scores classifiers
(``evaluation/*.scala``); this is the sequence-model member: slide
non-overlapping (S+1)-token windows over a held-out stream, run the
model's next-token loss in one jitted batch loop, and report the
standard aggregates (for byte-level corpora, bits_per_token IS
bits-per-byte, the enwik8 headline metric).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("logit_chunk",))
def _ce(model, toks, logit_chunk: int = 0):
    """The training loss itself, which is the cross-entropy alone
    (module-level so the jit cache persists across evaluate_perplexity
    calls). ``logit_chunk`` mirrors the training option — at long eval
    sequences the (B, S, V) f32 logits are the same HBM object to avoid."""
    from keystone_tpu.models.lm.losses import next_token_loss

    return next_token_loss(model, toks, logit_chunk)


def evaluate_perplexity(
    model,
    tokens: np.ndarray,
    *,
    seq: int,
    batch: int = 8,
    logit_chunk: int = 0,
) -> dict:
    """Mean next-token cross-entropy of ``model`` over ``tokens``.

    Non-overlapping windows of S+1 tokens (each token predicted once,
    except window-leading tokens which are conditioned on nothing from
    the previous window — the standard simple protocol); a ragged tail
    shorter than S+1 is dropped. Returns {loss, perplexity,
    bits_per_token, tokens_scored}. ``logit_chunk`` evaluates the CE in
    S-chunks (see ``models/lm``) — identical numbers up to FP order. A
    model's multi-token prediction module is left out: the perplexity
    is the next token's.
    """
    if getattr(model, "mtp", None) is not None:
        model = dataclasses.replace(model, mtp=None)
    window = seq + 1
    n_win = len(tokens) // window
    if n_win == 0:
        raise ValueError(
            f"held-out stream of {len(tokens)} tokens is shorter than one "
            f"window ({window})"
        )
    wins = np.asarray(tokens[: n_win * window], np.int32).reshape(
        n_win, window
    )

    total, count = 0.0, 0
    for i in range(0, n_win, batch):
        chunk = jnp.asarray(wins[i : i + batch])
        # next_token_loss averages over the chunk's predicted tokens;
        # re-weight by token count so uneven tail chunks don't skew
        n_tok = chunk.shape[0] * seq
        total += float(_ce(model, chunk, logit_chunk)) * n_tok
        count += n_tok
    loss = total / count
    return {
        "loss": loss,
        "perplexity": math.exp(loss),
        "bits_per_token": loss / math.log(2.0),
        "tokens_scored": count,
    }
