"""Adapter of ``zaya1_8b``: how the harness reaches the program.

One fit is what ``python -m keystone_tpu lm --config <file>`` does:
``models/lm_transformer.py::fit`` makes the model and the Markov stream
from the seed and trains ``steps`` optimizer steps through ``train()``.
The check makes one more such fit, asks it for what its steps said of
themselves (``history``), and holds it to the plain reference, which
draws the stream and the windows itself and holds the program's
starting weights to the stated init: the windows, the losses of steps 0
and 1, and the gradient norms of step 0 by group (the convolutions, the
joining rows, the router and the experts each a group of their own).
One expert a token is a discrete choice: the program's forward from the
starting weights says which expert every token of step 0 went to in
every layer (``chosen_experts``: what ``MoELayer.route`` returned, layer
by layer, inside the model's own ``backbone``), the reference says the
same of its own forward, and the share of tokens on which they differ is
a reading with a limit. A token that chooses otherwise changes its whole
term in every sum over tokens that the choice enters, so the gradient
norms of each layer's router and experts are held to a limit of their
own. ``tau`` and ``gamma``
are one or two entries, each a sum over every position of terms of
either sign: the reference says how large those terms are (their root
sum of squares), and the distance of the program's norm from the
reference's is held as a share of that, which is what rounding moves it
by, where a share of the sum itself can read anything. The embedding is
tied to the head, so, as in ``granite_4_0_h_micro``, a fit of one step
says how far the size of each entry's first AdamW move lies from the
rate, and how far above it; beside the mean over every entry, each leaf
outside the embedding and the experts is read alone, so that one leaf
left where it was shows. ``_zaya1_8b_controls.py`` plants the faults
each limit is there to refuse."""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
import tempfile

import numpy as np

from harness import find

ref = find.load_module("configs", "zaya1_8b_reference.py")
CFG = find.read_json("configs", "zaya1_8b.json")
TOL = CFG["tolerances"]
# what describes the benchmark's file, not the architecture
NOT_ARCHITECTURE = (
    "about", "train", "reduced", "reduced_why", "assumed", "tolerances",
    "toy", "programs",
)
# the reference's names of a block's leaves, by where the program keeps them
OF_BLOCK = {"norm1": "norm1", "norm2": "norm2", "scale1": "scale1", "scale2": "scale2"}
OF_CCA = {k: k for k in (*ref.ATTENTION, *ref.CONVS, "tau")}
OF_ROUTER = {
    "rd": "w_down", "rd_b": "b_down", "gamma": "gamma", "rnorm": "norm",
    "r1": "w1", "r1_b": "b1", "r2": "w2", "r2_b": "b2", "r3": "w3", "r3_b": "b3",
    "beta": "beta",
}
OF_EXPERTS = {"e1": "w1", "e3": "w3", "e2": "w2"}
# how a gradient-norm group is held, by its last name. A layer's router
# and its experts sum over tokens by their one discrete choice (the
# router sees every token through it, an expert only the tokens that
# chose it): a token that chooses otherwise in bfloat16 replaces its
# whole term, so they are "routed" and have a limit of their own.
# ``tau`` and ``gamma`` are one or two entries, each a sum over every
# position of terms of either sign, held by the distance of the norms
# over the root sum of squares of those terms (``tau`` feels rounding,
# ``gamma`` also the tokens that chose otherwise: a limit each). Every
# other group is "large"
CLASS_OF = {"router": "routed", "experts": "routed", "tau": "sum", "gamma": "sum"}
# leaves that no gradient reaches: the balancing bias, and the weight
# of the state that the first layer reads, which is zero
NO_GRADIENT = re.compile(r"\.router\.beta$|^\.blocks\[0\]\.router\.gamma$")
LIMITS = (
    "loss0_rel", "loss1_rel", "grad_norms_rel_max", "grad_norms_routed_rel_max",
    "grad_sums_tau_over_terms_max", "grad_sums_gamma_over_terms_max",
    "first_move_rel", "first_move_over",
    "first_move_leaf_max", "init_z_max", "route_flip_share",
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
    fd, path = tempfile.mkstemp(prefix="bench_zaya1_8b_", suffix=".json")
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
    del model  # 7 GB of weights and moments: gone before the next fit
    return {"losses": losses, "train_s": train_s}


def _reference_params(model) -> dict:
    """The program's weights under the reference's names (no copy)."""
    layers = []
    for b in model.blocks:
        p = {}
        for names, node in (
            (OF_BLOCK, b), (OF_CCA, b.cca), (OF_ROUTER, b.router), (OF_EXPERTS, b.moe),
        ):
            p.update({k: getattr(node, field) for k, field in names.items()})
        layers.append(p)
    return {"embed": model.embed, "final_norm": model.final_norm, "layers": layers}


def _norms_by_group(squared) -> dict:
    """``ref.group_norms`` of the step's ``grad_sq``: a tree of the
    model's shape whose leaves are squared norms already, so each leaf
    goes in as its root."""
    import jax

    return ref.group_norms(jax.tree_util.tree_map(np.sqrt, _reference_params(squared)))


def reference_readings(seed: int, sizes: dict) -> dict:
    """What the plain reference says of this seed's fit: its own stream
    and windows, the program's starting weights held to the init the
    configuration states, then the losses of steps 0 and 1, step 0's
    gradient norms (with the size of the terms that ``tau``'s and
    ``gamma``'s sum) and the expert every token of step 0 went to (a
    sequence at a time, layer by layer, at the timed sizes). Nothing is
    left on the device."""
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
    init = ref.init_deviation(params, CFG["assumed"]["gamma_init"])
    first = jnp.asarray(windows[0])
    choices = ref.chosen_experts(arch, params, first[:, :-1])
    loss0, grads, terms = ref.loss_and_grads_blocked(arch, params, first)
    norms = ref.group_norms(grads)
    params = ref.adamw_first_step(params, grads, sizes["lr"])
    del grads
    loss1 = ref.loss_and_grads_blocked(
        arch, params, jnp.asarray(windows[1]), want_grads=False
    )[0]
    del params
    return {
        "windows": windows, "init": init, "loss0": float(loss0),
        "loss1": float(loss1), "norms": norms, "terms": terms, "choices": choices,
    }


def chosen_experts(model, tokens):
    """(routed layers, B, S): the expert every token of (B, S) ids went
    to in every layer, as ``MoELayer.route`` returned it inside the
    model's own ``backbone`` (without remat: nothing is differentiated,
    and what a recomputed block returns cannot leave it)."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.ops.moe import MoELayer

    route, picked = MoELayer.route, []

    def watched(self, xf, scores=()):
        weights, idx = route(self, xf, scores)
        picked.append(idx[:, 0].reshape(tokens.shape))
        return weights, idx

    def forward(m, t):
        dataclasses.replace(m, remat=False).backbone(t)
        return jnp.stack(picked)

    MoELayer.route = watched
    try:
        return np.asarray(jax.jit(forward)(model, tokens))
    finally:
        MoELayer.route = route


def _first_move(before, after, lr: float, busy, weight_decay: float = 0.01):
    """With ``move = |after - before + lr wd before|``, (the mean of
    ``|move - lr| / lr``, the mean of ``max(move - lr, 0) / lr``) over
    every entry of every leaf but the embedding (whose rows outside the
    windows see gradients no larger than AdamW's epsilon) and the held
    experts no token of step 0 went to (``busy``: (layers, held) bool;
    no gradient reaches an idle expert); then the first mean taken over
    one leaf alone, by the leaf's name, for the leaves outside the
    embedding and the experts that a gradient reaches. From
    zero moments AdamW moves an entry by ``lr g / (|g| + eps)`` and the
    decay: never by more than the rate, and by less where the gradient
    is near eps, which with 32 768 tokens a step and a gate near a tenth
    an expert's entries are. So the first reads what share of the rate
    the gradients' size costs (a state left unchanged reads 1), and the
    second reads float32 rounding alone, unless the state cannot
    represent the move: in bfloat16 a move lands above the rate as often
    as below it. The third is what one leaf that lost its gradient, or
    was left out of the update, reads 1 in, however small the leaf."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def by_row(p0, p1):
        p0, p1 = p0.astype(jnp.float32), p1.astype(jnp.float32)
        move = jnp.abs(p1 - p0 + lr * weight_decay * p0)
        rows = tuple(range(1, p0.ndim))
        return jnp.stack([
            jnp.sum(jnp.abs(move - lr) / lr, axis=rows),
            jnp.sum(jnp.maximum(move - lr, 0.0) / lr, axis=rows),
        ])

    total, entries = np.zeros(2), 0
    by_leaf = {}
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(before), jax.tree_util.tree_leaves(after)
    ):
        name = jax.tree_util.keystr(path)
        if not a.size or "embed" in name:
            continue
        rows = np.asarray(by_row(a, b)).reshape(2, -1)  # (2, leading indices)
        expert_of = re.fullmatch(r"\.blocks\[(\d+)\]\.moe\.w[123]", name)
        keep = busy[int(expert_of[1])] if expert_of else np.ones(rows.shape[1], bool)
        total += rows[:, keep].sum(axis=1)
        entries += int(np.sum(keep)) * (a.size // rows.shape[1])
        if not expert_of and not NO_GRADIENT.search(name):
            by_leaf[name] = float(rows[0].sum()) / a.size
    return (*(float(t) / entries for t in total), by_leaf)


def program_readings(seed: int, sizes: dict) -> dict:
    """One more fit through the program, with what its steps said of
    themselves (``history``); a fit of one step beside the weights it
    started from; and, from those weights, the expert every token of
    step 0 went to. Nothing is left on the device."""
    import jax

    from keystone_tpu.models.lm_transformer import build_model, fit

    history: dict = {}
    conf = _conf(seed, sizes)
    model, losses, _valid, _s = fit(conf, history=history)
    dtypes = sorted({str(l.dtype) for l in jax.tree_util.tree_leaves(model)})
    del model
    stepped, _l, _v, _s = fit(dataclasses.replace(conf, steps=1))
    start = build_model(conf)
    choices = chosen_experts(start, history["windows"][0][:, :-1])
    moe = start.blocks[0].moe
    loads = np.stack([np.bincount(c.ravel(), minlength=moe.num_experts) for c in choices])
    busy = loads[:, moe.first_expert : moe.first_expert + moe.held] > 0
    # against the stated rate, whatever rate the fit was given
    first_move, first_over, move_of = _first_move(start, stepped, sizes["lr"], busy)
    leaf = max(move_of, key=move_of.get)
    del stepped, start
    counters = history["counters"][0]
    return {
        "losses": losses,
        "windows": history["windows"],
        "norms": _norms_by_group(history["grad_sq"][0]),
        "cca_rows": int(counters.get("cca_rows", 0)),
        "gate_mean": float(counters.get("gate_sum", 0.0)) / max(int(counters["routed_rows"]), 1),
        "choices": choices,
        "idle_experts": int(busy.size - busy.sum()),
        "first_move_rel": first_move,
        "first_move_over": first_over,
        "first_move_leaf_max": move_of[leaf],
        "first_move_leaf_worst": leaf,
        "state_dtypes": dtypes,
    }


def compare(got: dict, want: dict, sizes: dict, fits: list[dict]):
    """(correct, detail): the program's readings held to the
    reference's, each under its limit of ``tolerances``."""
    losses = got["losses"]
    same_shape = got["choices"].shape == want["choices"].shape
    detail = {
        "loss0": [losses[0], want["loss0"]],
        "loss1": [losses[1], want["loss1"]],
        "loss0_rel": abs(losses[0] - want["loss0"]) / want["loss0"],
        "loss1_rel": abs(losses[1] - want["loss1"]) / want["loss1"],
        "grad_norms_rel": {
            k: abs(got["norms"][k] - v) / v for k, v in want["norms"].items()
        },
        # tau and gamma: the norms' distance over the root sum of squares
        # of the terms the reference's gradient sums, and (reported, not
        # held) how much of those terms the sum itself is
        "grad_sums_over_terms": {
            k: abs(got["norms"][k] - want["norms"][k]) / t
            for k, t in want["terms"].items()
        },
        "grad_sums_size_over_terms": {
            k: want["norms"][k] / t for k, t in want["terms"].items()
        },
        "first_move_rel": got["first_move_rel"],
        "first_move_over": got["first_move_over"],
        "first_move_leaf_max": got["first_move_leaf_max"],
        "first_move_leaf_worst": got["first_move_leaf_worst"],
        # held experts no token of step 0 went to, left out of it
        "idle_experts": got["idle_experts"],
        "init_z_max": want["init"]["z_max"],
        "init_worst": want["init"]["worst"],
        # tokens of step 0, over the layers, that the program sent to
        # another expert than the reference did
        "route_flip_share": float(np.mean(got["choices"] != want["choices"]))
        if same_shape else 1.0,
        # steps whose windows are not the reference's own draw
        "windows_differ": sum(
            not np.array_equal(g, w) for g, w in zip(got["windows"], want["windows"])
        ) + abs(len(got["windows"]) - len(want["windows"])),
        "cca_rows_per_step": got["cca_rows"],
        "router_gate_mean_step0": got["gate_mean"],
        "state_dtypes": got["state_dtypes"],
        "losses": losses,
    }
    for name, kind in (("grad_norms", "large"), ("grad_norms_routed", "routed")):
        among = {
            k: v for k, v in detail["grad_norms_rel"].items()
            if CLASS_OF.get(k.rsplit(".", 1)[-1], "large") == kind
        }
        worst = max(among, key=lambda k: _nan_last(among[k]))
        detail[name + "_worst"] = worst
        detail[name + "_rel_max"] = among[worst]
    sums = detail["grad_sums_over_terms"]
    assert set(sums) == {
        k for k in want["norms"] if CLASS_OF.get(k.rsplit(".", 1)[-1]) == "sum"
    }
    for leaf in ("tau", "gamma"):
        among = {k: v for k, v in sums.items() if k.endswith("." + leaf)}
        worst = max(among, key=lambda k: _nan_last(among[k]))
        detail[f"grad_sums_{leaf}_worst"] = worst
        detail[f"grad_sums_{leaf}_over_terms_max"] = among[worst]
    bad = [(key, detail[key], TOL[key]) for key in LIMITS if not detail[key] <= TOL[key]]
    if detail["windows_differ"]:
        bad.append(("windows_differ", detail["windows_differ"], 0))
    if not want["init"]["exact"]:
        bad.append(("init_exact", False, True))
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
    layers = arch["num_hidden_layers"]
    d, hd = arch["hidden_size"], arch["head_dim"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    eff, rh = arch["moe_intermediate_size"], arch["router_hidden_size"]
    routed = arch.get("published", arch)["num_experts"]
    held, top_k = arch["num_experts"], arch["num_experts_per_tok"]
    seq = sizes["seq"]
    tokens = sizes["batch"] * seq
    act = 2  # bytes of a bfloat16 activation

    # parameters a token multiplies, a layer (even routing: a token's one
    # expert is held here held / routed of the time. With a fresh router
    # and no balancing a fit's share is 0.29 to 0.64 by seed, 0.49 in the
    # mean of eight (my chip runs, PR 35): the count is the mean's, and a
    # fit's own experts' FLOPs lie up to 40 % off it, its step's up to 3 %;
    # the harness hands this function no counters to count them from)
    latent = (heads + kv) * hd
    layer = (
        d * hd * (2 * heads + 2 * kv)  # wq, wo, wk, wv
        + latent * arch["cca_time0"] + latent * hd * arch["cca_time1"]  # the convolutions
        + d * rh + 2 * rh * rh + rh * routed  # the router
        + 3 * d * eff * top_k * held / routed
    )
    touched = layers * layer + d * arch["vocab_size"]  # the tied table as the head
    # score and value products of a causal layer in the latent, forward
    pairs = seq * (seq + 1) // 2
    attn = layers * 2 * 2 * heads * hd * pairs * sizes["batch"]
    forward_runs = 2 if sizes["remat"] else 1  # remat runs a forward twice
    step = 6.0 * touched * tokens + 3.0 * attn
    return {
        "train_flops_per_step": step,
        "train_flops_per_fit": step * sizes["steps"],
        "attn_full_flops_per_step": 3.0 * attn,
        # one routed row through one expert: three d x eff products
        "moe_flops_per_row": 2.0 * 3 * d * eff,
        # a row's input read (twice: two first products), its hidden
        # written and read, its output written, in bfloat16
        "moe_bytes_per_row": act * (2 * d + 3 * eff + d),
        # every held expert's three matrices read once a layer, bfloat16
        "moe_weight_bytes_per_layer": act * held * 3 * d * eff,
        "moe_layers": layers,
        # the grouped kernels' passes of that size: forward, the forward
        # again where remat recomputes it, and two backward
        "moe_passes": 3.0 + forward_runs - 1,
        "cca_rows_per_step": layers * tokens,
        "steps": sizes["steps"],
    }
