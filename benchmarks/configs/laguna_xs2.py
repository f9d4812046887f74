"""Adapter of ``laguna_xs2``: how the harness reaches the program.

One fit is what ``python -m keystone_tpu lm --config <file>`` does:
``models/lm_transformer.py::fit`` makes the model and the Markov stream
from the seed and trains ``steps`` optimizer steps through ``train()``.
The check makes one more such fit, asks it for what its steps said of
themselves (``history``), and holds it to the plain reference, which
draws the stream and the windows itself and holds the program's
starting weights to the stated init: the windows, the losses of steps 0
and 1, the gradient norms of step 0 by group, and the decay of the
embedding rows no window touched, which a state kept in bfloat16 cannot
represent. ``_laguna_xs2_controls.py`` plants the faults each limit is
there to refuse."""

from __future__ import annotations

import functools
import json
import os
import tempfile

import numpy as np

from harness import find

ref = find.load_module("configs", "laguna_xs2_reference.py")
CFG = find.read_json("configs", "laguna_xs2.json")
TOL = CFG["tolerances"]
# what describes the benchmark's file, not the architecture
NOT_ARCHITECTURE = (
    "about", "train", "reduced", "reduced_why", "assumed", "tolerances",
    "toy", "programs",
)


def cell_sizes(sizes: dict) -> dict:
    """A row is one token position trained: the fit's steps times the
    tokens of a step."""
    sizes["train_rows"] = sizes["steps"] * sizes["batch"] * sizes["seq"]
    return sizes


def architecture(sizes: dict) -> dict:
    """The ``config.json``-shaped description of this cell: the file's
    architecture keys, with the cell's sizes (``toy`` in a rehearsal)
    laid over those they name."""
    arch = {k: v for k, v in CFG.items() if k not in NOT_ARCHITECTURE}
    arch.update({k: v for k, v in sizes.items() if k in arch})
    return arch


@functools.cache
def _architecture_file(text: str) -> str:
    """A file the program's ``--config`` can read, once per process."""
    fd, path = tempfile.mkstemp(prefix="bench_laguna_xs2_", suffix=".json")
    with os.fdopen(fd, "w") as f:
        f.write(text)
    return path


def _conf(seed: int, sizes: dict):
    from keystone_tpu.models.lm_transformer import LMConfig

    return LMConfig(
        config=_architecture_file(json.dumps(architecture(sizes), sort_keys=True)),
        steps=sizes["steps"],
        batch=sizes["batch"],
        seq=sizes["seq"],
        lr=sizes["lr"],
        seed=seed,
        compute_dtype=sizes["compute_dtype"],
        remat=sizes["remat"],
        logit_chunk=sizes["logit_chunk"],
    )


def one_fit(seed: int, sizes: dict) -> dict:
    from keystone_tpu.models.lm_transformer import fit

    model, losses, _valid, train_s = fit(_conf(seed, sizes))
    del model  # 11 GB of weights and moments: gone before the next fit
    return {"losses": losses, "train_s": train_s}


def _reference_params(model) -> dict:
    """The program's weights under the reference's names (no copy)."""
    layers = []
    for b in model.blocks:
        p = {k: getattr(b, k) for k in ("norm1", "wq", "wk", "wv", "wo", "norm2")}
        if b.wg is not None:
            p["wg"] = b.wg
        if b.moe is None:
            p.update(w1=b.w1, w3=b.w3, w2=b.w2)
        else:
            m = b.moe
            p.update(router=m.w_router, e1=m.w1, e3=m.w3, e2=m.w2)
            if m.shared_w1 is not None:
                p.update(s1=m.shared_w1, s3=m.shared_w3, s2=m.shared_w2)
        layers.append(p)
    return {
        "embed": model.embed, "head": model.head,
        "final_norm": model.final_norm, "layers": layers,
    }


def _norms_by_group(squared) -> dict:
    """``ref.group_norms`` of the step's ``grad_sq``: a tree of the
    model's shape whose leaves are squared norms already, so each leaf
    goes in as its root."""
    import jax

    return ref.group_norms(jax.tree_util.tree_map(np.sqrt, _reference_params(squared)))


def reference_readings(seed: int, sizes: dict) -> dict:
    """What the plain reference says of this seed's fit: its own stream
    and windows, the program's starting weights held to the init the
    configuration states, then the losses of steps 0 and 1 and step 0's
    gradient norms (a sequence at a time, layer by layer, at the timed
    sizes). Nothing is left on the device."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.models.lm_transformer import build_model

    arch = architecture(sizes)
    steps, batch, seq = sizes["steps"], sizes["batch"], sizes["seq"]
    stream = ref.markov_stream(arch["vocab_size"], seed)
    windows = [ref.step_windows(stream, seed, i, batch, seq) for i in range(steps)]
    # embedding rows no input position of any step touched
    quiet = np.setdiff1d(
        np.arange(arch["vocab_size"]), np.concatenate([w[:, :-1].ravel() for w in windows])
    )
    start = build_model(_conf(seed, sizes))
    # the reference is float32 whatever the program keeps its state in
    params = jax.tree_util.tree_map(
        lambda l: jnp.asarray(l, jnp.float32), _reference_params(start)
    )
    embed_before = np.asarray(start.embed[quiet], np.float32)
    del start
    init = ref.init_deviation(params)
    loss0, grads = ref.loss_and_grads_blocked(arch, params, jnp.asarray(windows[0]))
    norms = ref.group_norms(grads)
    params = ref.adamw_first_step(params, grads, sizes["lr"])
    del grads
    loss1, _ = ref.loss_and_grads_blocked(
        arch, params, jnp.asarray(windows[1]), want_grads=False
    )
    del params
    return {
        "windows": windows, "quiet": quiet, "embed_before": embed_before,
        "init": init, "loss0": float(loss0), "loss1": float(loss1), "norms": norms,
    }


def program_readings(seed: int, sizes: dict) -> dict:
    """One more fit through the program, with what its steps said of
    themselves (``history``). Nothing is left on the device."""
    import jax

    from keystone_tpu.models.lm_transformer import fit

    history: dict = {}
    model, losses, _valid, _s = fit(_conf(seed, sizes), history=history)
    return {
        "losses": losses,
        "windows": history["windows"],
        "norms": _norms_by_group(history["grad_sq"][0]),
        "embed_after": np.asarray(model.embed, np.float32),
        "state_dtypes": sorted({str(l.dtype) for l in jax.tree_util.tree_leaves(model)}),
    }


def compare(got: dict, want: dict, sizes: dict, fits: list[dict]):
    """(correct, detail): the program's readings held to the
    reference's, each under its limit of ``tolerances``."""
    losses, quiet = got["losses"], want["quiet"]
    before = want["embed_before"]
    detail = {
        "loss0": [losses[0], want["loss0"]],
        "loss1": [losses[1], want["loss1"]],
        "loss0_rel": abs(losses[0] - want["loss0"]) / want["loss0"],
        "loss1_rel": abs(losses[1] - want["loss1"]) / want["loss1"],
        "grad_norms_rel": {
            k: abs(got["norms"][k] - v) / v for k, v in want["norms"].items()
        },
        "quiet_embedding_rows": int(quiet.size),
        # how far the quiet rows' change over the fit lies from the
        # decoupled decay alone, over that change: a state that did not
        # move (weights kept in bfloat16 cannot, by 3e-6 of themselves
        # a step) reads 1
        "quiet_decay_rel": ref.distance(
            got["embed_after"][quiet] - before,
            ref.decayed(before, sizes["steps"], sizes["lr"]) - before,
        ),
        "init_z_max": want["init"]["z_max"],
        "init_worst": want["init"]["worst"],
        # steps whose windows are not the reference's own draw
        "windows_differ": sum(
            not np.array_equal(g, w) for g, w in zip(got["windows"], want["windows"])
        ) + abs(len(got["windows"]) - len(want["windows"])),
        "state_dtypes": got["state_dtypes"],
        "losses": losses,
    }
    detail["grad_norms_rel_max"] = max(detail["grad_norms_rel"].values())
    bad = [
        (key, detail[key], TOL[key])
        for key in (
            "loss0_rel", "loss1_rel", "grad_norms_rel_max", "quiet_decay_rel",
            "init_z_max",
        )
        if not detail[key] <= TOL[key]
    ]
    if detail["windows_differ"]:
        bad.append(("windows_differ", detail["windows_differ"], 0))
    if not want["init"]["norm_scales_are_one"]:
        bad.append(("norm_scales_are_one", False, True))
    if not quiet.size:
        bad.append(("quiet_embedding_rows", 0, "the decay check needs some"))
    for i, fit in enumerate(fits):
        if fit["losses"] != losses:
            bad.append((i, "differs from the checked fit", fit["losses"]))
    detail["mismatches"] = bad[:5]
    return not bad, detail


def check_fits(seed: int, sizes: dict, fits: list[dict]):
    """Outside the window: one more fit through the program, then, its
    state dropped, the reference on the same weights and its own
    windows."""
    got = program_readings(seed, sizes)
    return compare(got, reference_readings(seed, sizes), sizes, fits)


# ------------------------------------------------------ operations and bytes

def _layer_kinds(sizes: dict):
    arch = architecture(sizes)
    n = arch["num_hidden_layers"]
    return arch, list(
        zip(
            arch["layer_types"][:n],
            arch["num_attention_heads_per_layer"][:n],
            arch["mlp_layer_types"][:n],
        )
    )


def ops_and_bytes(sizes: dict) -> dict:
    """What the algorithm needs, from shapes, for one chip (recomputation
    not counted; a forward and its backward are three times the forward)."""
    arch, kinds = _layer_kinds(sizes)
    d, hd, kv = arch["hidden_size"], arch["head_dim"], arch["num_key_value_heads"]
    ff, eff = arch["intermediate_size"], arch["moe_intermediate_size"]
    sff = arch.get("shared_expert_intermediate_size", 0)
    routed = arch.get("published", arch)["num_experts"]
    held, top_k = arch["num_experts"], arch["num_experts_per_tok"]
    seq, window = sizes["seq"], arch["sliding_window"]
    tokens = sizes["batch"] * seq
    act = 2  # bytes of a bfloat16 activation

    touched = 0.0  # parameters a token multiplies (even routing)
    attn = {"full": 0.0, "window": 0.0}  # score and value products, forward
    attn_bytes = {"full": 0.0, "window": 0.0}
    for kind, heads, mlp in kinds:
        touched += d * hd * (2 * heads + 2 * kv) + d * heads
        if mlp == "sparse":
            touched += d * routed + 3 * d * sff + 3 * d * eff * top_k * held / routed
        else:
            touched += 3 * d * ff
        sliding = kind == "sliding_attention"
        # keys a causal query sees, summed over the sequence's queries
        pairs = sum(min(i + 1, window) if sliding else i + 1 for i in range(seq))
        name = "window" if sliding else "full"
        attn[name] += 2 * 2 * heads * hd * pairs * sizes["batch"]
        # q and the output once, K and V once a K/V head
        attn_bytes[name] += act * tokens * hd * (2 * heads + 2 * kv)
    touched += d * arch["vocab_size"]  # the head; the embedding is a gather
    attn_flops = attn["full"] + attn["window"]
    forward_runs = 2 if sizes["remat"] else 1  # remat runs a forward twice
    step = 6.0 * touched * tokens + 3.0 * attn_flops
    return {
        "train_flops_per_step": step,
        "train_flops_per_fit": step * sizes["steps"],
        # one routed row through one expert: three d x eff products
        "moe_flops_per_row": 2.0 * 3 * d * eff,
        # a row's input read (twice: two first products), its hidden
        # written and read, its output written, in bfloat16
        "moe_bytes_per_row": act * (2 * d + 3 * eff + d),
        # every held expert's three matrices read once a layer, bfloat16
        "moe_weight_bytes_per_layer": act * held * 3 * d * eff,
        "moe_layers": sum(m == "sparse" for _k, _h, m in kinds),
        # the grouped kernels' passes of that size: forward, the forward
        # again where remat recomputes it, and two backward
        "moe_passes": 3.0 + forward_runs - 1,
        # forward + backward of the window layers' score and value products
        "attn_window_flops_per_step": 3.0 * attn["window"],
        "attn_full_flops_per_step": 3.0 * attn["full"],
        # what the forward kernel itself runs a step
        "attn_window_kernel_flops_per_step": forward_runs * attn["window"],
        "attn_window_kernel_bytes_per_step": forward_runs * attn_bytes["window"],
        "steps": sizes["steps"],
    }
