"""Controls of ``granite_4_0_h_micro``'s check: the faults each limit is
there to refuse, planted in the program from outside and run through the
cell's own ``program_readings`` and ``compare``. A sound run has to come
out correct and every plant not correct; the readings printed here are
the upper readings of ``tolerances`` in ``granite_4_0_h_micro.json``.

    python3 benchmarks/configs/_granite_4_0_h_micro_controls.py --seed N \
        [--plants sound,state_dropped,...] [--rehearse-cpu]

One JSON line a plant: ``{"plant", "correct", "refused_by", readings}``.
On the chip this is one process (the chip is its alone). The reference's
readings are made once, from the sound starting weights, and every plant
that leaves those weights as they are is held to them;
``bfloat16_state`` rounds them, so its reference starts from the rounded
ones, as the cell's check would. A plant swaps a function of the program,
so the step program is traced and compiled anew for each.
``tests/test_granite_4_0_h_micro.py`` runs every plant at the toy sizes."""

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
    "loss0_rel", "loss1_rel", "grad_norms_rel_max", "grad_norms_worst",
    "grad_norms_per_head_rel_max", "grad_norms_per_head_worst", "first_move_rel", "init_z_max", "init_worst", "windows_differ", "state_dtypes",
)
# plants whose starting weights are not the sound ones
OWN_REFERENCE = ("bfloat16_state", "init_scale")


def plants(adapter) -> dict:
    """name -> [(object, attribute, replacement)]: what is swapped while
    that plant's fits run."""
    import jax
    import jax.numpy as jnp

    import keystone_tpu.models.lm_transformer as entry

    ssm = importlib.import_module("keystone_tpu.ops.ssm")
    build, conf_of, scan = entry.build_model, adapter._conf, ssm.ssd_scan

    def in_bfloat16(conf, mesh=None):
        # weights, and so AdamW's moments, kept in bfloat16
        return jax.tree_util.tree_map(
            lambda l: l.astype(jnp.bfloat16), build(conf, mesh)
        )

    def state_dropped(x, dt, a, b, c, chunk=256):
        # every chunk starts from a zero state: each is a sequence of its own
        n, s = x.shape[:2]
        n_l = min(chunk, s)
        if s % n_l:
            raise ValueError("the plant wants whole chunks")

        def cut(t):
            return t.reshape(n * (s // n_l), n_l, *t.shape[2:])

        y = scan(cut(x), cut(dt), a, cut(b), cut(c), chunk)
        return y.reshape(x.shape)

    def no_conv(x, w, b=None):
        return x.astype(jnp.float32)

    def no_softplus(dt, bias):
        return dt.astype(jnp.float32) + bias.astype(jnp.float32)

    def gate_after_norm(y, z, scale, eps):
        yf = y.astype(jnp.float32)
        yf = yf * jax.lax.rsqrt(jnp.mean(yf * yf, axis=-1, keepdims=True) + eps)
        out = yf * scale.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return out.astype(y.dtype)

    def attention_scale_eighth(conf, mesh=None):
        # the attention layer at 1/sqrt(head_dim), not the config's 1/64
        model = build(conf, mesh)
        return dataclasses.replace(
            model,
            blocks=tuple(
                b if b.ssm is not None
                else dataclasses.replace(b, spec=dataclasses.replace(b.spec, scale=None))
                for b in model.blocks
            ),
        )

    def embedding_twice_as_wide(conf, mesh=None):
        model = build(conf, mesh)
        return dataclasses.replace(model, embed=2.0 * model.embed)

    def residual_one(conf, mesh=None):
        return dataclasses.replace(build(conf, mesh), residual_multiplier=1.0)

    def never_steps(seed, sizes):
        # AdamW at rate 0: neither the update nor the decay moves a weight
        return dataclasses.replace(conf_of(seed, sizes), lr=0.0)

    return {
        "sound": [],
        "state_dropped": [(ssm, "ssd_scan", state_dropped)],
        "no_conv": [(ssm, "causal_conv", no_conv)],
        "no_softplus": [(ssm, "step_size", no_softplus)],
        "gate_after_norm": [(ssm, "gated_rms_norm", gate_after_norm)],
        "attention_scale_eighth": [(entry, "build_model", attention_scale_eighth)],
        "residual_one": [(entry, "build_model", residual_one)],
        "bfloat16_state": [(entry, "build_model", in_bfloat16)],
        "no_update": [(adapter, "_conf", never_steps)],
        "init_scale": [(entry, "build_model", embedding_twice_as_wide)],
    }


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
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from harness import device, find

    cfg, adapter = find.config("granite_4_0_h_micro")
    cell = find.cell("granite_4_0_h_micro.train_8k")
    device.bring_up(cell["chips"], args.rehearse_cpu)
    sizes = find.load_module("run.py").sizes_of(cfg, cell, adapter, args.rehearse_cpu)
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
