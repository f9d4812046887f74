"""Controls of ``nemotron_3_super``'s check: the faults each limit is
there to refuse, planted in the program from outside and run through the
cell's own ``program_readings`` and ``compare``. A sound run has to come
out correct and every plant not correct; the readings printed here are
the upper readings of ``tolerances`` in ``nemotron_3_super.json``.

    python3 benchmarks/configs/_nemotron_3_super_controls.py --seed N \
        [--plants sound,state_dropped,...] [--steps 2] [--rehearse-cpu]

One JSON line a plant: ``{"plant", "correct", "refused_by", readings}``.
On the chip this is one process (the chip is its alone). The reference's
readings are made once, from the sound starting weights, and every plant
that leaves those weights as they are is held to them;
``bfloat16_state`` rounds them, so its reference starts from the rounded
ones, as the cell's check would. A plant swaps a function of the program,
so the step program is traced and compiled anew for each. ``--steps``
runs the check's fits at fewer steps than the cell's (it reads steps 0
and 1 alone, and the windows of the steps it ran).
``tests/test_nemotron_3_super.py`` runs every plant at the toy sizes."""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

READINGS = (
    "loss0_rel", "loss1_rel", "mtp0_rel", "grad_norms_rel_max", "grad_norms_worst",
    "grad_norms_per_head_rel_max", "grad_norms_per_head_worst",
    "grad_norms_routed_rel_max", "grad_norms_routed_worst", "route_diff_share",
    "first_move_rel", "first_move_over", "init_z_max", "init_worst", "windows_differ",
    "state_dtypes",
)
# plants whose starting weights are not the sound ones
OWN_REFERENCE = ("bfloat16_state",)


def plants(adapter) -> dict:
    """name -> [(object, attribute, replacement)]: what is swapped while
    that plant's fits run."""
    import jax
    import jax.numpy as jnp

    import keystone_tpu.models.lm_transformer as entry

    ssm = importlib.import_module("keystone_tpu.ops.ssm")
    moe = importlib.import_module("keystone_tpu.ops.moe")
    losses = importlib.import_module("keystone_tpu.models.lm.losses")
    build, conf_of, scan, norm, act = (
        entry.build_model, adapter._conf, ssm.ssd_scan, ssm.gated_rms_norm, moe._act,
    )

    def in_bfloat16(conf, mesh=None):
        # weights, and so AdamW's moments, kept in bfloat16
        return jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), build(conf, mesh))

    def mtp_shift_one(model, tokens, logit_chunk):
        # the MTP module reads the id at its own position and predicts
        # the next one, as the main head does
        s = tokens.shape[1] - 2
        x, counters = model.backbone(tokens[:, :s])
        h, counters = model.mtp_hidden(x, tokens[:, :s], counters)
        ce = losses._cross_entropy(model, x, tokens[:, 1 : s + 1], logit_chunk)
        ahead = dataclasses.replace(model, final_norm=model.mtp.final_norm)
        ce_mtp = losses._cross_entropy(ahead, h, tokens[:, 1 : s + 1], logit_chunk)
        counters = {**counters, "mtp_rows": jnp.int32(tokens.shape[0] * s), "mtp_ce": ce_mtp}
        return ce + model.mtp.weight * ce_mtp, counters

    def state_dropped(x, dt, a, b, c, chunk=256):
        # every chunk starts from a zero state: each is a sequence of its own
        n, s = x.shape[:2]
        n_l = min(chunk, s)

        def cut(t):
            return t.reshape(n * (s // n_l), n_l, *t.shape[2:])

        return scan(cut(x), cut(dt), a, cut(b), cut(c), chunk).reshape(x.shape)

    def groups_shared(x, dt, a, b, c, chunk=256):
        # every head reads group 0's B and C
        g = b.shape[2]
        return scan(
            x, dt, a, jnp.repeat(b[:, :, :1], g, axis=2), jnp.repeat(c[:, :, :1], g, axis=2),
            chunk,
        )

    def norm_ungrouped(y, z, scale, eps, groups=1):
        return norm(y, z, scale, eps)

    def relu_unsquared(h1, h3, activation=""):
        return jax.nn.relu(h1) if activation == "relu2" else act(h1, h3, activation)

    def experts_changed(model, **change):
        def blocks(bs):
            return tuple(
                b if b.moe is None
                else dataclasses.replace(b, moe=dataclasses.replace(b.moe, **change))
                for b in bs
            )

        return {
            "blocks": blocks(model.blocks),
            "mtp": dataclasses.replace(model.mtp, blocks=blocks(model.mtp.blocks)),
        }

    def never_steps(seed, sizes):
        # AdamW at rate 0: neither the update nor the decay moves a weight
        return dataclasses.replace(conf_of(seed, sizes), lr=0.0)

    return {
        "sound": [],
        "mtp_shift_one": [(losses, "_loss_with_mtp", mtp_shift_one)],
        "mtp_dropped": [(entry, "build_model", _with(
            build, lambda m: {"mtp": dataclasses.replace(m.mtp, weight=0.0)}))],
        "norm_ungrouped": [(ssm, "gated_rms_norm", norm_ungrouped)],
        "groups_shared": [(ssm, "ssd_scan", groups_shared)],
        "relu_unsquared": [(moe, "_act", relu_unsquared)],
        "routed_scale_one": [(entry, "build_model", _with(
            build, lambda m: experts_changed(m, routed_scale=1.0)))],
        "state_dropped": [(ssm, "ssd_scan", state_dropped)],
        "bfloat16_state": [(entry, "build_model", in_bfloat16)],
        "no_update": [(adapter, "_conf", never_steps)],
    }


def _with(build, change):
    """``build_model`` with ``change(model)``'s fields replaced."""

    def build_with(conf, mesh=None):
        model = build(conf, mesh)
        return dataclasses.replace(model, **change(model))

    return build_with


@contextlib.contextmanager
def planted(swaps):
    """The swaps in place, and no step program traced before them (or
    under them) answering for another: jax keys the step by its
    arguments, not by the functions it calls."""
    step = importlib.import_module("keystone_tpu.models.lm.train")._train_step
    kept = [(obj, name, getattr(obj, name)) for obj, name, _new in swaps]
    for obj, name, new in swaps:
        setattr(obj, name, new)
    step.clear_cache()
    try:
        yield
    finally:
        for obj, name, old in kept:
            setattr(obj, name, old)
        step.clear_cache()


def run_plant(adapter, name: str, seed: int, sizes: dict, want: dict | None = None):
    """One plant's line. ``want`` is the reference's readings from the
    sound weights, made here when not given."""
    with planted(plants(adapter)[name]):
        got = adapter.program_readings(seed, sizes)
        if want is None or name in OWN_REFERENCE:
            want = adapter.reference_readings(seed, sizes)
    correct, detail = adapter.compare(got, want, sizes, [])
    return {
        "plant": name,
        "correct": correct,
        "refused_by": [m[0] for m in detail["mismatches"]],
        **{k: detail[k] for k in READINGS},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--plants", default="")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from harness import device, find

    cfg, adapter = find.config("nemotron_3_super")
    cell = find.cell("nemotron_3_super.train_8k")
    device.bring_up(cell["chips"], args.rehearse_cpu)
    sizes = find.load_module("run.py").sizes_of(cfg, cell, adapter, args.rehearse_cpu)
    if args.steps:
        sizes = {**sizes, "steps": args.steps}
    names = args.plants.split(",") if args.plants else list(plants(adapter))
    want = adapter.reference_readings(args.seed, sizes)
    wrong = 0
    for name in names:
        line = run_plant(adapter, name, args.seed, sizes, want)
        print(json.dumps(line), flush=True)
        wrong += line["correct"] != (name == "sound")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
