"""Tensor-parallel weight layout for the transformer LM.

The layout IS the parallelism: annotate each weight's sharding over the
mesh ``model`` axis and XLA inserts exactly the two psums per block that
hand-written Megatron-style TP would (see shard_params). The same layout
feeds the pipeline-parallel path unchanged — gpipe leaves non-manual
mesh axes automatic, so these shardings propagate into stage bodies on a
3-axis (pipe, data, model) mesh.
"""

from __future__ import annotations

import dataclasses

import jax

from keystone_tpu.models.lm.model import TransformerLM


def shard_params(model: TransformerLM, mesh) -> TransformerLM:
    """Lay the weights out for tensor parallelism over the mesh ``model``
    axis: attention q/k/v column-sharded (head-parallel) with wo
    row-sharded, MLP column- then row-sharded, embedding vocab-sharded.
    XLA then inserts exactly the two psums per block that hand-written
    Megatron-style TP would — the layout IS the parallelism. A block's
    routed experts are left as they are, and so is a state-space mixer:
    its leaves stay whole on every device under ``model`` (its scan is
    split over ``data`` alone; no head-parallel layout of the mixer is
    written). Left whole too: a compressed-latent mixer (``b.cca``: its
    four projections, both convolutions and ``tau``; the attention in
    the latent is still split by head where ``model`` divides both head
    counts, as any local attention is), a carried router (``b.router``)
    and a block's joining rows (``scale1``, ``scale2``), and an MTP
    module (``model.mtp``). A block of one part alone has None for the
    absent part's weights, and None stays None.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None or mesh.shape.get("model", 1) == 1:
        return model
    n_model = mesh.shape["model"]

    def put(x, spec):
        # a dim not divisible by the axis (e.g. an unpadded vocab) is
        # replicated rather than rejected
        spec = P(
            *(
                a
                if a is None or x.shape[i] % n_model == 0
                else None
                for i, a in enumerate(spec)
            )
        )
        return jax.device_put(x, NamedSharding(mesh, spec))

    def opt(x, spec):
        return None if x is None else put(x, spec)

    blocks = tuple(
        dataclasses.replace(
            b,
            wq=opt(b.wq, P(None, "model")),
            wk=opt(b.wk, P(None, "model")),
            wv=opt(b.wv, P(None, "model")),
            wo=opt(b.wo, P("model", None)),
            w1=opt(b.w1, P(None, "model")),
            w2=opt(b.w2, P("model", None)),
            w3=opt(b.w3, P(None, "model")),
            # routed experts stay whole on every device (their layer
            # shard_maps its tokens over `data`): the exchange that
            # expert parallelism needs is not written yet (ROADMAP C8);
            # b.ssm and b.cca stay whole too: the zero-width wq..wo
            # above are their placeholders and nothing of either mixer's
            # weights is split over `model`; so do b.router and the rows
            # of scale1 / scale2
        )
        for b in model.blocks
    )
    return dataclasses.replace(
        model,
        embed=put(model.embed, P("model", None)),
        pos_embed=put(model.pos_embed, P()),
        blocks=blocks,
        head=opt(model.head, P(None, "model")),
    )


