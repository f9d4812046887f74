"""Decoder-only transformer LM — stable import path and CLI.

The implementation lives in :mod:`keystone_tpu.models.lm`
(``model`` / ``train`` / ``decode``); this module re-exports that
surface (existing imports and pickled checkpoints keep resolving here)
and owns the config/CLI entry: ``python -m
keystone_tpu.models.lm_transformer``.

The reference has no sequence models at all (SURVEY §5: long-context
"absent"); the LM is the training/serving-side consumer of the
framework's sequence-parallel + pipeline-parallel + quantization stack —
a beyond-reference capability in the spirit of ``models/vit_ridge.py``.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from keystone_tpu.core.config import arg, parse_config
from keystone_tpu.core.logging import get_logger
from keystone_tpu.models.lm import (  # noqa: F401  (re-exported surface)
    KVCache,
    LMBlock,
    TransformerLM,
    chunked_token_cross_entropy,
    decode_step,
    generate,
    make_optimizer,
    make_pp_train_step,
    make_train_step,
    next_token_loss,
    next_token_loss_pp,
    pp_forward,
    prefill,
    quantize_for_decode,
    shard_params,
    synthetic_corpus,
    token_cross_entropy,
    train,
    train_step_flops,
)
from keystone_tpu.models.lm.decode import _filter_logits  # noqa: F401
from keystone_tpu.models.lm.model import (  # noqa: F401
    has_quantized_leaves as _has_quantized_leaves,
)
from keystone_tpu.models.lm.model import mtp_depth
from keystone_tpu.models.lm.train import (  # noqa: F401
    _step_batch,
    cca_layers,
    moe_latent,
    ssm_layers,
)

logger = get_logger("keystone_tpu.models.lm_transformer")


@dataclasses.dataclass
class LMConfig:
    steps: int = arg(default=60, help="training steps")
    batch: int = arg(default=8)
    seq: int = arg(default=256)
    dim: int = arg(default=256)
    depth: int = arg(default=4)
    num_heads: int = arg(default=8)
    num_kv_heads: int = arg(
        default=0,
        help="GQA: K/V heads (0 = num_heads/MHA, 1 = MQA); shrinks the "
        "decode cache by num_heads/num_kv_heads",
    )
    vocab: int = arg(default=256)
    lr: float = arg(default=3e-4)
    seq_mode: str = arg(
        default="local", help="attention strategy: local | ring | ulysses"
    )
    compute_dtype: str = arg(
        default="float32",
        help="matmul/activation dtype (params stay float32); "
        "bfloat16 is the TPU-native choice",
    )
    seed: int = arg(default=0)
    moe_every: int = arg(
        default=0,
        help="replace every k-th block's FFN with a top-2 MoE (0 = dense)",
    )
    num_experts: int = arg(default=8)
    pos_encoding: str = arg(
        default="learned", help="position encoding: learned | rope"
    )
    corpus: str = arg(
        default="",
        help="path to a text file/dir (byte-level tokens, vocab forced to "
        "256, 10%% held out for perplexity); default: synthetic Markov",
    )
    schedule: str = arg(
        default="constant", help="lr schedule: constant | cosine (warmup)"
    )
    grad_clip: float = arg(
        default=0.0, help="global-norm gradient clip (0 = off)"
    )
    checkpoint_dir: str = arg(
        default="",
        help="orbax checkpoint/resume directory (preemption-safe training)",
    )
    checkpoint_every: int = arg(
        default=0,
        help="steps between checkpoints (0 = steps//10, ~10 per run)",
    )
    config: str = arg(
        default="",
        help="a public architecture in place of the toy preset: the name "
        "of a config.json-shaped file kept in the package "
        "(models/lm/configs/<name>.json, e.g. laguna_xs2) or a path to "
        "one; its sizes replace --dim/--depth/--num-heads/--vocab",
    )
    remat: bool = arg(
        default=False,
        help="rematerialize each block in the backward pass",
    )
    logit_chunk: int = arg(
        default=0,
        help="compute the CE in this many-position chunks so the "
        "(B, S, V) f32 logits never materialize (0 = dense; must divide "
        "seq; the long-context memory/bandwidth lever)",
    )


def load_architecture(name_or_path: str) -> dict:
    """The ``config.json``-shaped description ``--config`` names: a file
    of ``models/lm/configs/`` by its stem, else a path."""
    import json
    import os

    packaged = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "lm", "configs",
        name_or_path + ".json",
    )
    with open(packaged if os.path.isfile(packaged) else name_or_path) as f:
        return json.load(f)


def build_model(conf: LMConfig, mesh=None) -> TransformerLM:
    """The model ``conf`` describes, from ``conf.seed``, laid out over
    ``mesh``: a public architecture when ``--config`` names one, else
    the toy preset of the size flags. One block definition serves both."""
    key = jax.random.key(conf.seed)
    if conf.config:
        model = TransformerLM.from_config(
            key,
            load_architecture(conf.config),
            # local attention needs the mesh too: on a TPU its Pallas
            # flash kernel is shard_mapped over the mesh the batch is
            # split on
            mesh=mesh,
            compute_dtype=conf.compute_dtype,
            remat=conf.remat,
        )
    else:
        model = TransformerLM.create(
            key,
            vocab=conf.vocab,
            max_seq=conf.seq,
            dim=conf.dim,
            depth=conf.depth,
            num_heads=conf.num_heads,
            seq_mode=conf.seq_mode,
            mesh=mesh,
            compute_dtype=conf.compute_dtype,
            moe_every=conf.moe_every,
            num_experts=conf.num_experts,
            pos_encoding=conf.pos_encoding,
            num_kv_heads=conf.num_kv_heads,
        )
        if conf.remat:
            model = dataclasses.replace(model, remat=True)
    return shard_params(model, mesh)


def fit(conf: LMConfig, mesh=None, history: dict | None = None):
    """One fit, as ``run`` makes it: the model from ``conf.seed``, the
    corpus, then ``conf.steps`` optimizer steps through :func:`train`.
    Returns (model, losses, held-out tokens or None, seconds in
    ``train``). While spans are on, the whole of it is one ``fit`` root
    span (``fit.init`` covers the model and the corpus)."""
    from keystone_tpu.observe.spans import force, span
    from keystone_tpu.parallel.mesh import create_mesh

    if conf.schedule not in ("constant", "cosine"):
        # fail before the (possibly minutes-long) corpus load / model init
        raise ValueError(
            f"--schedule {conf.schedule!r}; expected constant|cosine"
        )
    if mesh is None and len(jax.devices()) > 1:
        mesh = create_mesh()
    found: dict = {}  # what fit.init learns of the model it makes
    with span(
        "fit",
        parent=None,
        steps=conf.steps,
        tokens_per_step=conf.batch * conf.seq,
        chips=mesh.size if mesh is not None else 1,
        # known when the span closes: the model is made inside it
        ssm_layers=lambda: found.get("ssm_layers", 0),
        cca_layers=lambda: found.get("cca_layers", 0),
        mtp_depth=lambda: found.get("mtp_depth", 0),
        moe_latent=lambda: found.get("moe_latent", 0),
    ):
        valid = None
        with span("fit.init"):
            if conf.corpus:
                from keystone_tpu.loaders.text import (
                    BYTE_VOCAB,
                    load_text_corpus,
                )

                corpus, valid = load_text_corpus(conf.corpus)
                conf = dataclasses.replace(conf, vocab=BYTE_VOCAB)
            model = build_model(conf, mesh)
            found["ssm_layers"] = ssm_layers(model)
            found["cca_layers"] = cca_layers(model)
            found["mtp_depth"] = mtp_depth(model)
            found["moe_latent"] = moe_latent(model)
            if not conf.corpus:
                corpus = synthetic_corpus(
                    200_000, model.embed.shape[0], seed=conf.seed
                )
            force(model)
        t0 = time.time()
        model, losses = train(
            model,
            corpus,
            steps=conf.steps,
            batch=conf.batch,
            seq=conf.seq,
            lr=conf.lr,
            mesh=mesh,
            seed=conf.seed,
            log_every=max(conf.steps // 5, 1),
            checkpoint_dir=conf.checkpoint_dir,
            checkpoint_every=conf.checkpoint_every,
            schedule=conf.schedule,
            grad_clip=conf.grad_clip,
            logit_chunk=conf.logit_chunk,
            history=history,
        )
        return model, losses, valid, time.time() - t0


def run(conf: LMConfig, mesh=None) -> dict:
    model, losses, valid, dt = fit(conf, mesh)
    steps_ran = len(losses)
    if not losses:
        # a resume that found the run already complete trains 0 steps
        losses = [float("nan")]
    res = {
        # loss_first is the first loss of THIS segment; on a resumed run
        # (steps_ran < steps) it is not the run's true initial loss —
        # downstream records key off `resumed` to tell the cases apart
        "loss_first": losses[0],
        "loss_last": float(np.mean(losses[-5:])),
        "steps": conf.steps,
        "steps_ran": steps_ran,
        "resumed": steps_ran < conf.steps,
        "params": model.num_params(),
        "tokens_per_s": steps_ran * conf.batch * conf.seq / dt,
        "wall_s": dt,
    }
    if valid is not None:
        if len(valid) >= conf.seq + 1:
            from keystone_tpu.evaluation.perplexity import (
                evaluate_perplexity,
            )

            ev = evaluate_perplexity(
                model, valid, seq=conf.seq, batch=conf.batch,
                logit_chunk=conf.logit_chunk,
            )
            res["valid_loss"] = ev["loss"]
            res["valid_bits_per_token"] = ev["bits_per_token"]
            res["valid_perplexity"] = ev["perplexity"]
        else:
            logger.warning(
                "held-out tail (%d tokens) is shorter than one seq+1=%d "
                "window — skipping the perplexity evaluation the corpus "
                "flag promises; shorten --seq or grow the corpus",
                len(valid),
                conf.seq + 1,
            )
    logger.info(
        "lm: %d params, loss %.3f -> %.3f, %.0f tokens/s",
        res["params"],
        res["loss_first"],
        res["loss_last"],
        res["tokens_per_s"],
    )
    return res


def main(argv=None) -> dict:
    return run(parse_config(LMConfig, argv))


if __name__ == "__main__":
    main()
