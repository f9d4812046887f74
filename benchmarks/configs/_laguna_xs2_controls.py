"""Controls of ``laguna_xs2``'s check: the faults each limit is there to
refuse, planted in the program from outside and run through
``check_fits`` as the cell runs it. A sound run has to come out correct
and every plant not correct; the readings printed here are the upper
readings of ``tolerances`` in ``laguna_xs2.json``.

    python3 benchmarks/configs/_laguna_xs2_controls.py --seed N \
        [--plants sound,bfloat16_state,...] [--rehearse-cpu]

One JSON line a plant: ``{"plant", "correct", "refused_by", readings}``.
On the chip this is one process (the chip is its alone); a plant that
changes the step program's types or optimizer compiles it once more.
``tests/test_laguna_xs2.py`` runs every plant at the toy sizes."""

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
    "loss0_rel", "loss1_rel", "grad_norms_rel_max", "quiet_decay_rel",
    "init_z_max", "init_worst", "windows_differ", "state_dtypes",
)


def plants(adapter) -> dict:
    """name -> [(object, attribute, replacement)]: what is swapped while
    that plant's check runs."""
    import jax
    import jax.numpy as jnp

    import keystone_tpu.models.lm_transformer as entry

    # the module: the package exports its ``train`` function by that name
    train = importlib.import_module("keystone_tpu.models.lm.train")

    build, conf_of, windows_of = entry.build_model, adapter._conf, train._step_batch
    stream_of = entry.synthetic_corpus

    def in_bfloat16(conf, mesh=None):
        # weights, and so AdamW's moments, kept in bfloat16
        return jax.tree_util.tree_map(
            lambda l: l.astype(jnp.bfloat16), build(conf, mesh)
        )

    def embedding_twice_as_wide(conf, mesh=None):
        model = build(conf, mesh)
        return dataclasses.replace(model, embed=2.0 * model.embed)

    def no_window(conf, mesh=None):
        # the window layers see every earlier key
        model = build(conf, mesh)
        return dataclasses.replace(
            model,
            blocks=tuple(
                dataclasses.replace(b, spec=dataclasses.replace(b.spec, window=0))
                for b in model.blocks
            ),
        )

    def first_half_twice(corpus, seed, i, batch, seq):
        # half of the batch left out, the other half in its place
        rows = windows_of(corpus, seed, i, batch, seq)
        rows[batch // 2 :] = rows[: batch - batch // 2]
        return rows

    def never_steps(seed, sizes):
        # AdamW at rate 0: neither the update nor the decay moves a weight
        return dataclasses.replace(conf_of(seed, sizes), lr=0.0)

    def ids_of_twice_the_slice(n, vocab, seed=0):
        return stream_of(n, 2 * vocab, seed=seed)

    return {
        "sound": [],
        "bfloat16_state": [(entry, "build_model", in_bfloat16)],
        "half_batch": [(train, "_step_batch", first_half_twice)],
        "no_update": [(adapter, "_conf", never_steps)],
        "no_window": [(entry, "build_model", no_window)],
        "init_scale": [(entry, "build_model", embedding_twice_as_wide)],
        "ids_outside_slice": [(entry, "synthetic_corpus", ids_of_twice_the_slice)],
    }


@contextlib.contextmanager
def planted(swaps):
    kept = [(obj, name, getattr(obj, name)) for obj, name, _new in swaps]
    for obj, name, new in swaps:
        setattr(obj, name, new)
    try:
        yield
    finally:
        for obj, name, old in kept:
            setattr(obj, name, old)


def run_plant(adapter, name: str, seed: int, sizes: dict):
    with planted(plants(adapter)[name]):
        correct, detail = adapter.check_fits(seed, sizes, [])
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

    cfg, adapter = find.config("laguna_xs2")
    cell = find.cell("laguna_xs2.train_8k")
    device.bring_up(cell["chips"], args.rehearse_cpu)
    sizes = find.load_module("run.py").sizes_of(cfg, cell, adapter, args.rehearse_cpu)
    names = args.plants.split(",") if args.plants else list(plants(adapter))
    wrong = 0
    for name in names:
        line = run_plant(adapter, name, args.seed, sizes)
        print(json.dumps(line), flush=True)
        wrong += line["correct"] != (name == "sound")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
