"""Adapter of ``nemotron_3_super``: how the harness reaches the program.

One fit is what ``python -m keystone_tpu lm --config <file>`` does:
``models/lm_transformer.py::fit`` makes the model and the Markov stream
from the seed and trains ``steps`` optimizer steps through ``train()``
on windows of S + 2 ids (the multi-token prediction module's targets lie
two positions ahead). The check makes one more such fit, asks it for
what its steps said of themselves (``history``), and holds it to the
plain reference, which draws the stream and the windows itself and holds
the program's starting weights to the stated init: the windows; the
losses of steps 0 and 1 and step 0's MTP term; the gradient norms of
step 0 by group (a mixer's vectors of one entry a head under a limit of
their own, and each expert layer's router and held experts under
another: their sums run over the tokens by a discrete choice of 22 of
512, which rounding moves at its edge); the experts every token of
step 0 chose in every expert layer, the MTP module's included (what
``MoELayer.route`` returned inside the model's own forward, against the
reference's own choice); and, from a fit of one step, how far the size
of each entry's first AdamW move lies from the rate, and how far above
it. ``_nemotron_3_super_controls.py`` plants the faults each limit is
there to refuse."""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
import tempfile

import numpy as np

from harness import find

ref = find.load_module("configs", "nemotron_3_super_reference.py")
CFG = find.read_json("configs", "nemotron_3_super.json")
TOL = CFG["tolerances"]
# what describes the benchmark's file, not the architecture
NOT_ARCHITECTURE = (
    "about", "train", "reduced", "reduced_why", "assumed", "tolerances",
    "toy", "programs",
)
# the reference's names of a layer's leaves, by the program's part
OF_SSM = {
    "in": "w_in", "conv_w": "conv_w", "conv_b": "conv_b", "dt_bias": "dt_bias",
    "A_log": "A_log", "D": "D", "gnorm": "norm", "out": "w_out",
}
OF_EXPERTS = {
    "router": "w_router", "down": "latent_down", "up": "latent_up", "e1": "w1",
    "e2": "w2", "s1": "shared_w1", "s2": "shared_w2",
}
# how a gradient-norm group is held, by its last name: a mixer's vectors
# of one entry a head (sums of few bfloat16-rounded terms) and an expert
# layer's router and held experts (sums over tokens by their discrete
# choice) have limits of their own; every other group is "large"
CLASS_OF = {
    "A_log": "per_head", "dt_bias": "per_head", "D": "per_head",
    "router": "routed", "experts": "routed",
}
LIMITS = (
    "loss0_rel", "loss1_rel", "mtp0_rel", "grad_norms_rel_max",
    "grad_norms_per_head_rel_max", "grad_norms_routed_rel_max", "route_diff_share",
    "first_move_rel", "first_move_over", "init_z_max",
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
    fd, path = tempfile.mkstemp(prefix="bench_nemotron_3_super_", suffix=".json")
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
    del model  # 11.5 GB of weights and moments: gone before the next fit
    return {"losses": losses, "train_s": train_s}


def _layer(b) -> dict:
    """One block's leaves under the reference's names: a mixer's with
    its pre-norm, an expert layer's with its own."""
    if b.ssm is not None:
        return {"norm": b.norm1, **{k: getattr(b.ssm, f) for k, f in OF_SSM.items()}}
    if b.moe is not None:
        return {"norm": b.norm2, **{k: getattr(b.moe, f) for k, f in OF_EXPERTS.items()}}
    return {"norm": b.norm1, **{k: getattr(b, k) for k in ref.ATTENTION}}


def _reference_params(model) -> dict:
    """The program's weights under the reference's names (no copy)."""
    m = model.mtp
    return {
        "embed": model.embed, "head": model.head, "final_norm": model.final_norm,
        "layers": [_layer(b) for b in model.blocks],
        "mtp": {
            "enorm": m.enorm, "hnorm": m.hnorm, "eh": m.eh_proj,
            "final_norm": m.final_norm, "layers": [_layer(b) for b in m.blocks],
        },
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
    configuration states, then the experts every token of step 0 chose,
    the losses of steps 0 and 1, step 0's MTP term and its gradient
    norms (a sequence at a time, layer by layer, at the timed sizes).
    Nothing is left on the device."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.models.lm_transformer import build_model

    arch = architecture(sizes)
    steps, batch, seq = sizes["steps"], sizes["batch"], sizes["seq"]
    stream = ref.markov_stream(arch["vocab_size"], seed)
    windows = [ref.step_windows(stream, seed, i, batch, seq) for i in range(steps)]
    # the reference is float32 whatever the program keeps its state in
    params = jax.tree_util.tree_map(
        lambda l: jnp.asarray(l, jnp.float32),
        _reference_params(build_model(_conf(seed, sizes))),
    )
    init = ref.init_deviation(params)
    first = jnp.asarray(windows[0])
    choices = ref.chosen_experts(arch, params, first)
    loss0, mtp0, grads = ref.loss_and_grads_blocked(arch, params, first)
    norms = ref.group_norms(grads)
    params = ref.adamw_first_step(params, grads, sizes["lr"])
    del grads
    loss1 = ref.loss_and_grads_blocked(
        arch, params, jnp.asarray(windows[1]), want_grads=False
    )[0]
    del params
    return {
        "windows": windows, "init": init, "loss0": float(loss0), "mtp0": float(mtp0),
        "loss1": float(loss1), "norms": norms, "choices": choices,
    }


def chosen_experts(model, tokens):
    """(expert layers, B, S, k): the experts every token chose in every
    expert layer, the MTP module's last, sorted, as ``MoELayer.route``
    returned them inside the model's own forward on (B, S + 2) windows
    (without remat: nothing is differentiated, and what a recomputed
    block returns cannot leave it)."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.ops.moe import MoELayer

    route, picked = MoELayer.route, []
    s = tokens.shape[1] - 2

    def watched(self, xf, scores=()):
        weights, idx = route(self, xf, scores)
        picked.append(jnp.sort(idx, axis=-1).reshape(tokens.shape[0], s, -1))
        return weights, idx

    def forward(m, t):
        m = dataclasses.replace(m, remat=False)
        x, counters = m.backbone(t[:, :s])
        m.mtp_hidden(x, t[:, 1 : s + 1], counters)
        return jnp.stack(picked)

    MoELayer.route = watched
    try:
        return np.asarray(jax.jit(forward)(model, tokens))
    finally:
        MoELayer.route = route


@functools.cache
def _move_program():
    """One program a leaf shape for every plant and check in a process:
    (sum of ``|move - lr| / lr``, sum of ``max(move - lr, 0) / lr``) over
    each leading index."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def by_row(p0, p1, lr, weight_decay):
        p0, p1 = p0.astype(jnp.float32), p1.astype(jnp.float32)
        move = jnp.abs(p1 - p0 + lr * weight_decay * p0)
        rows = tuple(range(1, p0.ndim))
        return jnp.stack([
            jnp.sum(jnp.abs(move - lr) / lr, axis=rows),
            jnp.sum(jnp.maximum(move - lr, 0.0) / lr, axis=rows),
        ])

    return by_row


def _moves_by_row(p0, p1, lr: float, weight_decay: float):
    import jax.numpy as jnp

    return _move_program()(p0, p1, jnp.float32(lr), jnp.float32(weight_decay))


def _first_move(before, after, lr: float, busy, weight_decay: float = 0.01):
    """With ``move = |after - before + lr wd before|``, (the mean of
    ``|move - lr| / lr``, the mean of ``max(move - lr, 0) / lr``) over
    every entry of every leaf but the embedding (whose rows outside the
    windows see no gradient: the decay alone moves them) and the held
    experts no token of step 0 went to (``busy``: (expert layers, held)
    bool; no gradient reaches an idle expert). From zero moments AdamW
    moves an entry by ``lr g / (|g| + eps)`` and the decay: never by more
    than the rate, and by less where the gradient is near eps. So the
    first reads what share of the rate the gradients' size costs (a
    state left unchanged reads 1), and the second reads float32 rounding
    alone, unless the state cannot represent the move: in bfloat16 a
    move lands above the rate as often as below it."""
    import jax

    total, entries, layer = np.zeros(2), 0, {}
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(before), jax.tree_util.tree_leaves(after)
    ):
        name = jax.tree_util.keystr(path)
        if not a.size or name == ".embed":
            continue
        rows = np.asarray(_moves_by_row(a, b, lr, weight_decay)).reshape(2, -1)
        expert_of = re.fullmatch(r"(\.mtp)?\.blocks\[(\d+)\]\.moe\.w[12]", name)
        if expert_of:
            key = (expert_of[1] or "", int(expert_of[2]))
            layer.setdefault(key, len(layer))
            keep = busy[layer[key]]
        else:
            keep = np.ones(rows.shape[1], bool)
        total += rows[:, keep].sum(axis=1)
        entries += int(np.sum(keep)) * (a.size // rows.shape[1])
    return tuple(float(t) / entries for t in total)


def program_readings(seed: int, sizes: dict) -> dict:
    """One more fit through the program, with what its steps said of
    themselves (``history``); a fit of one step beside the weights it
    started from; and, from those weights, the experts every token of
    step 0 chose. Nothing is left on the device."""
    import jax

    from keystone_tpu.models.lm_transformer import build_model, fit

    history: dict = {}
    conf = _conf(seed, sizes)
    model, losses, _valid, _s = fit(conf, history=history)
    dtypes = sorted({str(l.dtype) for l in jax.tree_util.tree_leaves(model)})
    del model
    stepped, _l, _v, _s = fit(dataclasses.replace(conf, steps=1))
    start = build_model(conf)
    choices = chosen_experts(start, history["windows"][0])
    moe = start.blocks[-1].moe
    held = np.arange(moe.first_expert, moe.first_expert + moe.held)
    busy = (choices[..., None] == held).any(axis=(1, 2, 3))  # (expert layers, held)
    # against the stated rate, whatever rate the fit was given
    first_move, first_over = _first_move(start, stepped, sizes["lr"], busy)
    del stepped, start
    counters = history["counters"][0]
    return {
        "losses": losses,
        "windows": history["windows"],
        "norms": _norms_by_group(history["grad_sq"][0]),
        "mtp0": float(counters["mtp_ce"]),
        "mtp_rows": int(counters["mtp_rows"]),
        "ssm_rows": int(counters["ssm_rows"]),
        "ssm_kernel_rows": int(counters["ssm_kernel_rows"]),
        "choices": choices,
        "idle_experts": int(busy.size - busy.sum()),
        "first_move_rel": first_move,
        "first_move_over": first_over,
        "state_dtypes": dtypes,
    }


def compare(got: dict, want: dict, sizes: dict, fits: list[dict]):
    """(correct, detail): the program's readings held to the
    reference's, each under its limit of ``tolerances``."""
    losses = got["losses"]
    same_shape = got["choices"].shape == want["choices"].shape
    k = got["choices"].shape[-1]
    detail = {
        "loss0": [losses[0], want["loss0"]],
        "loss1": [losses[1], want["loss1"]],
        "mtp0": [got["mtp0"], want["mtp0"]],
        "loss0_rel": abs(losses[0] - want["loss0"]) / want["loss0"],
        "loss1_rel": abs(losses[1] - want["loss1"]) / want["loss1"],
        "mtp0_rel": abs(got["mtp0"] - want["mtp0"]) / want["mtp0"],
        "grad_norms_rel": {
            key: abs(got["norms"][key] - v) / v for key, v in want["norms"].items()
        },
        # of every (layer, token)'s k choices, the share that the program
        # and the reference do not share (half the symmetric difference)
        "route_diff_share": float(
            1.0 - np.mean([
                len(np.intersect1d(a, b, assume_unique=True)) / k
                for a, b in zip(got["choices"].reshape(-1, k), want["choices"].reshape(-1, k))
            ])
        ) if same_shape else 1.0,
        "first_move_rel": got["first_move_rel"],
        "first_move_over": got["first_move_over"],
        "idle_experts": got["idle_experts"],
        "init_z_max": want["init"]["z_max"],
        "init_worst": want["init"]["worst"],
        # steps whose windows are not the reference's own draw
        "windows_differ": sum(
            not np.array_equal(g, w) for g, w in zip(got["windows"], want["windows"])
        ) + abs(len(got["windows"]) - len(want["windows"])),
        "mtp_rows_per_step": got["mtp_rows"],
        "ssm_rows_per_step": got["ssm_rows"],
        "ssm_kernel_rows_per_step": got["ssm_kernel_rows"],
        "state_dtypes": got["state_dtypes"],
        "losses": losses,
    }
    for name, kind in (
        ("grad_norms", "large"), ("grad_norms_per_head", "per_head"),
        ("grad_norms_routed", "routed"),
    ):
        among = {
            key: v for key, v in detail["grad_norms_rel"].items()
            if CLASS_OF.get(key.rsplit(".", 1)[-1], "large") == kind
        }
        worst = max(among, key=lambda key: _nan_last(among[key]))
        detail[name + "_worst"] = worst
        detail[name + "_rel_max"] = among[worst]
    bad = [(key, detail[key], TOL[key]) for key in LIMITS if not detail[key] <= TOL[key]]
    if detail["windows_differ"]:
        bad.append(("windows_differ", detail["windows_differ"], 0))
    if not want["init"]["norm_scales_are_one"]:
        bad.append(("norm_scales_are_one", False, True))
    if not want["init"]["in_range"]:
        bad.append(("init_in_range", False, True))
    for i, fit in enumerate(fits):
        if fit["losses"] != losses:
            bad.append((i, "differs from the checked fit", fit["losses"]))
    detail["mismatches"] = bad[:len(LIMITS) + 3]  # every limit; the fits that differ cut short
    return not bad, detail


def _nan_last(x: float) -> float:
    """A reading that is not a number is the worst there is."""
    return float("inf") if x != x else x


def check_fits(seed: int, sizes: dict, fits: list[dict]):
    """Outside the window: one more fit through the program, then, its
    state dropped, the reference on the same weights and its own
    windows."""
    got = program_readings(seed, sizes)
    return compare(got, reference_readings(seed, sizes), sizes, fits)


# ------------------------------------------------------ operations and bytes

def ops_and_bytes(sizes: dict) -> dict:
    """What the algorithm needs, from shapes, for one chip (recomputation
    not counted; a forward and its backward are three times the forward)."""
    arch = architecture(sizes)
    kinds = arch["hybrid_override_pattern"][: arch["num_hidden_layers"]]
    mtp_kinds = arch["mtp_hybrid_override_pattern"] if arch["num_nextn_predict_layers"] else ""
    d, hd = arch["hidden_size"], arch["head_dim"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    h, p = arch["mamba_num_heads"], arch["mamba_head_dim"]
    g, n = arch["n_groups"], arch["ssm_state_size"]
    inner, half = h * p, arch["chunk_size"] / 2
    lat, eff = arch["moe_latent_size"], arch["moe_intermediate_size"]
    shared = (
        arch["moe_shared_expert_intermediate_size"]
        // arch.get("deployment", {}).get("tensor_parallel", 1)
    )
    routed = arch.get("published", arch)["n_routed_experts"]
    held, top_k = arch["n_routed_experts"], arch["num_experts_per_tok"]
    vocab = arch["vocab_size"]
    seq = sizes["seq"]
    tokens = sizes["batch"] * seq
    act = 2  # bytes of a bfloat16 activation

    # parameters a token multiplies, a layer (even routing: a token's 22
    # experts are held here held / routed of the time)
    layer = {
        "M": d * (2 * inner + 2 * g * n + h) + inner * d + (inner + 2 * g * n) * arch["conv_kernel"],
        "*": d * hd * (2 * heads + 2 * kv),
        "E": d * routed + 2 * d * lat + 2 * d * shared + 2 * lat * eff * top_k * held / routed,
    }
    every = kinds + mtp_kinds
    # the head twice (next token and the MTP's), the MTP's projection
    touched = (
        sum(layer[k] for k in every) + d * vocab * (2 if mtp_kinds else 1)
        + (2 * d * d if mtp_kinds else 0)
    )
    # score and value products of a causal layer, forward
    pairs = seq * (seq + 1) // 2
    attn = every.count("*") * 2 * 2 * heads * hd * pairs * sizes["batch"]
    # the scan, a position a layer, forward: the chunk's scores at their
    # causal half once a group, their product with x a head, and the
    # state's update and read-out
    scan_row = 2.0 * half * n * g + 2.0 * half * p * h + 4.0 * n * p * h
    rows = every.count("M") * tokens
    forward_runs = 2 if sizes["remat"] else 1  # remat runs a forward twice
    step = 6.0 * touched * tokens + 3.0 * attn + 3.0 * scan_row * rows
    return {
        "train_flops_per_step": step,
        "train_flops_per_fit": step * sizes["steps"],
        "attn_full_flops_per_step": 3.0 * attn,
        "ssm_scan_flops_per_row": scan_row,
        # x read and y written, dt in float32, B and C: once a run
        "ssm_scan_bytes_per_row": act * (2 * inner + 2 * g * n) + 4 * h,
        "ssm_scan_runs": forward_runs,
        "ssm_rows_per_step": rows,
        # one routed row through one expert: two lat x eff products
        "moe_flops_per_row": 2.0 * 2 * lat * eff,
        # a row's latent input read, its hidden written and read, its
        # output written, in bfloat16
        "moe_bytes_per_row": act * (lat + 2 * eff + lat),
        # every held expert's two matrices read once a layer, bfloat16
        "moe_weight_bytes_per_layer": act * held * 2 * lat * eff,
        "moe_layers": every.count("E"),
        # the grouped kernels' passes of that size: forward, the forward
        # again where remat recomputes it, and two backward
        "moe_passes": 3.0 + forward_runs - 1,
        "mtp_rows_per_step": tokens if mtp_kinds else 0,
        "steps": sizes["steps"],
    }
