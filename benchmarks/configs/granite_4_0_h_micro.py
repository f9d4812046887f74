"""Adapter of ``granite_4_0_h_micro``: how the harness reaches the program.

One fit is what ``python -m keystone_tpu lm --config <file>`` does:
``models/lm_transformer.py::fit`` makes the model and the Markov stream
from the seed and trains ``steps`` optimizer steps through ``train()``.
The check makes one more such fit, asks it for what its steps said of
themselves (``history``), and holds it to the plain reference, which
draws the stream and the windows itself and holds the program's
starting weights to the stated init: the windows, the losses of steps 0
and 1, and the gradient norms of step 0 by group, every leaf of every
state-space mixer a group of its own (those of one entry a head under a
limit of their own). The embedding is tied to the head,
so no row of it is left without a gradient and the decay of quiet rows
(``laguna_xs2``'s reading of a state kept in bfloat16) has nothing to
read here: instead a fit of one step says how far the size of each
entry's first AdamW move lies from the rate, which is what that move is
whatever the gradient's sign. ``_granite_4_0_h_micro_controls.py`` plants
the faults each limit is there to refuse."""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import tempfile

import numpy as np

from harness import find

ref = find.load_module("configs", "granite_4_0_h_micro_reference.py")
CFG = find.read_json("configs", "granite_4_0_h_micro.json")
TOL = CFG["tolerances"]
# what describes the benchmark's file, not the architecture
NOT_ARCHITECTURE = (
    "about", "train", "reduced", "reduced_why", "assumed", "tolerances",
    "toy", "programs",
)
# the reference's names of a mixer's leaves that are not the program's
MIXER_FIELD = {"in": "w_in", "out": "w_out"}
# a mixer's vectors of one entry a head: their gradients are sums of few
# bfloat16-rounded terms and read ten times further from the reference
# than every other group, so they are held to a limit of their own
PER_HEAD = ("A_log", "dt_bias", "D")
LIMITS = (
    "loss0_rel", "loss1_rel", "grad_norms_rel_max", "grad_norms_per_head_rel_max",
    "first_move_rel", "init_z_max",
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
    fd, path = tempfile.mkstemp(prefix="bench_granite_4_0_h_micro_", suffix=".json")
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
    del model  # 12 GB of weights and moments: gone before the next fit
    return {"losses": losses, "train_s": train_s}


def _reference_params(model) -> dict:
    """The program's weights under the reference's names (no copy)."""
    layers = []
    for b in model.blocks:
        p = {k: getattr(b, k) for k in ("norm1", "norm2", "w1", "w3", "w2")}
        if b.ssm is None:
            p.update({k: getattr(b, k) for k in ("wq", "wk", "wv", "wo")})
        else:
            p.update({k: getattr(b.ssm, MIXER_FIELD.get(k, k)) for k in ref.SSM_LEAVES})
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
    # the reference is float32 whatever the program keeps its state in
    params = jax.tree_util.tree_map(
        lambda l: jnp.asarray(l, jnp.float32),
        _reference_params(build_model(_conf(seed, sizes))),
    )
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
        "windows": windows, "init": init, "loss0": float(loss0),
        "loss1": float(loss1), "norms": norms,
    }


def _first_move_rel(before, after, lr: float, weight_decay: float = 0.01) -> float:
    """Over every entry of every leaf but the embedding (whose rows
    outside the windows see gradients no larger than AdamW's epsilon):
    the mean of ``| |after - before + lr wd before| - lr | / lr``. From
    zero moments AdamW moves an entry by ``lr g / (|g| + eps)`` and the
    decay, so a float32 state reads the few entries whose gradient is
    near eps; a state in bfloat16 cannot represent the move; a state
    left unchanged reads 1."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def leaf(p0, p1):
        p0, p1 = p0.astype(jnp.float32), p1.astype(jnp.float32)
        move = jnp.abs(p1 - p0 + lr * weight_decay * p0)
        return jnp.sum(jnp.abs(move - lr) / lr)

    pairs = [
        (a, b)
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(before), jax.tree_util.tree_leaves(after)
        )
        if a.size and "embed" not in jax.tree_util.keystr(path)
    ]
    total = sum(float(leaf(a, b)) for a, b in pairs)
    return total / sum(a.size for a, _b in pairs)


def program_readings(seed: int, sizes: dict) -> dict:
    """One more fit through the program, with what its steps said of
    themselves (``history``), and a fit of one step beside the weights
    it started from. Nothing is left on the device."""
    import jax

    from keystone_tpu.models.lm_transformer import build_model, fit

    history: dict = {}
    conf = _conf(seed, sizes)
    model, losses, _valid, _s = fit(conf, history=history)
    dtypes = sorted({str(l.dtype) for l in jax.tree_util.tree_leaves(model)})
    del model
    stepped, _l, _v, _s = fit(dataclasses.replace(conf, steps=1))
    # against the stated rate, whatever rate the fit was given
    first_move = _first_move_rel(build_model(conf), stepped, sizes["lr"])
    del stepped
    return {
        "losses": losses,
        "windows": history["windows"],
        "norms": _norms_by_group(history["grad_sq"][0]),
        "ssm_rows": int(history["counters"][0].get("ssm_rows", 0)),
        "first_move_rel": first_move,
        "state_dtypes": dtypes,
    }


def compare(got: dict, want: dict, sizes: dict, fits: list[dict]):
    """(correct, detail): the program's readings held to the
    reference's, each under its limit of ``tolerances``."""
    losses = got["losses"]
    detail = {
        "loss0": [losses[0], want["loss0"]],
        "loss1": [losses[1], want["loss1"]],
        "loss0_rel": abs(losses[0] - want["loss0"]) / want["loss0"],
        "loss1_rel": abs(losses[1] - want["loss1"]) / want["loss1"],
        "grad_norms_rel": {
            k: abs(got["norms"][k] - v) / v for k, v in want["norms"].items()
        },
        "first_move_rel": got["first_move_rel"],
        "init_z_max": want["init"]["z_max"],
        "init_worst": want["init"]["worst"],
        # steps whose windows are not the reference's own draw
        "windows_differ": sum(
            not np.array_equal(g, w) for g, w in zip(got["windows"], want["windows"])
        ) + abs(len(got["windows"]) - len(want["windows"])),
        "ssm_rows_per_step": got["ssm_rows"],
        "state_dtypes": got["state_dtypes"],
        "losses": losses,
    }
    for name, per_head in (("grad_norms", False), ("grad_norms_per_head", True)):
        among = {
            k: v for k, v in detail["grad_norms_rel"].items()
            if (k.rsplit(".", 1)[-1] in PER_HEAD) == per_head
        }
        worst = max(among, key=lambda k: _nan_last(among[k]))
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
    detail["mismatches"] = bad[:5]
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
    kinds = arch["layer_types"][: arch["num_hidden_layers"]]
    d, ff = arch["hidden_size"], arch["shared_intermediate_size"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = d // heads
    h, p = arch["mamba_n_heads"], arch["mamba_d_head"]
    g, n = arch["mamba_n_groups"], arch["mamba_d_state"]
    inner, half = h * p, arch["mamba_chunk_size"] / 2
    seq = sizes["seq"]
    tokens = sizes["batch"] * seq
    act = 2  # bytes of a bfloat16 activation

    # parameters a token multiplies; the tied table once, as the head
    mamba_layer = (
        d * (2 * inner + 2 * g * n + h) + inner * d
        + (inner + 2 * g * n) * arch["mamba_d_conv"] + 3 * d * ff
    )
    attention_layer = d * hd * (2 * heads + 2 * kv) + 3 * d * ff
    n_ssm = sum(k == "mamba" for k in kinds)
    touched = (
        n_ssm * mamba_layer + (len(kinds) - n_ssm) * attention_layer
        + d * arch["vocab_size"]
    )
    # score and value products of a causal layer, forward
    pairs = seq * (seq + 1) // 2
    attn = (len(kinds) - n_ssm) * 2 * 2 * heads * hd * pairs * sizes["batch"]
    # the scan, a position a layer, forward: the chunk's scores at their
    # causal half once a group, their product with x a head, and the
    # state's update and read-out
    scan_row = 2.0 * half * n * g + 2.0 * half * p * h + 4.0 * n * p * h
    rows = n_ssm * tokens
    step = 6.0 * touched * tokens + 3.0 * attn + 3.0 * scan_row * rows
    return {
        "train_flops_per_step": step,
        "train_flops_per_fit": step * sizes["steps"],
        "attn_full_flops_per_step": 3.0 * attn,
        "ssm_scan_flops_per_row": scan_row,
        # x read and y written, dt in float32, B and C: once a run
        "ssm_scan_bytes_per_row": act * (2 * inner + 2 * g * n) + 4 * h,
        # forward runs of the scan a step: remat runs the forward twice
        "ssm_scan_runs": 2 if sizes["remat"] else 1,
        "ssm_rows_per_step": rows,
        "steps": sizes["steps"],
    }
