"""Controls of ``zaya1_8b``'s check: the faults each limit is there to
refuse, planted in the program from outside and run through the cell's
own ``program_readings`` and ``compare``. A sound run has to come out
correct and every plant not correct; the readings printed here are the
upper readings of ``tolerances`` in ``zaya1_8b.json``.

    python3 benchmarks/configs/_zaya1_8b_controls.py --seeds N[,M...] \
        [--plants sound,no_conv,...] [--rehearse-cpu]

One JSON line a plant and seed: ``{"plant", "seed", "correct",
"refused_by", readings}``. On the chip this is one process (the chip is
its alone). The reference's readings are made once a seed, from the
sound starting weights, and every plant that leaves those weights as
they are is held to them; ``bfloat16_state`` rounds them and
``embedding_doubled`` scales them, so their reference starts from the
planted ones, as the cell's check would. A plant swaps a function of the
program, so the step program is traced and compiled anew for each, once
for all its seeds. ``tests/benchmarks/test_zaya1_8b_cell.py`` runs every
plant at the toy sizes."""

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
    "grad_norms_routed_rel_max", "grad_norms_routed_worst",
    "grad_sums_tau_over_terms_max", "grad_sums_tau_worst",
    "grad_sums_gamma_over_terms_max", "grad_sums_gamma_worst", "grad_sums_size_over_terms",
    "first_move_rel", "first_move_over",
    "first_move_leaf_max", "first_move_leaf_worst", "idle_experts", "init_z_max",
    "init_worst", "route_flip_share", "router_gate_mean_step0", "windows_differ",
    "state_dtypes",
)
# plants whose starting weights are not the sound ones
OWN_REFERENCE = ("bfloat16_state", "embedding_doubled")


def plants(adapter) -> dict:
    """name -> [(object, attribute, replacement)]: what is swapped while
    that plant's fits run."""
    import jax
    import jax.numpy as jnp

    import keystone_tpu.models.lm_transformer as entry

    cca = importlib.import_module("keystone_tpu.ops.cca")
    moe = importlib.import_module("keystone_tpu.ops.moe")
    build, conf_of = entry.build_model, adapter._conf
    stream, route = entry.synthetic_corpus, moe.CarriedRouter.__call__

    def with_experts(model, **changed):
        return dataclasses.replace(
            model,
            blocks=tuple(
                dataclasses.replace(b, moe=dataclasses.replace(b.moe, **changed))
                for b in model.blocks
            ),
        )

    def gate_renormalised(conf, mesh=None):
        return with_experts(build(conf, mesh), renormalize=True)

    def wrong_share(conf, mesh=None):
        model = build(conf, mesh)
        return with_experts(model, first_expert=model.blocks[0].moe.held)

    def in_bfloat16(conf, mesh=None):
        # weights, and so AdamW's moments, kept in bfloat16
        return jax.tree_util.tree_map(
            lambda l: l.astype(jnp.bfloat16), build(conf, mesh)
        )

    def embedding_doubled(conf, mesh=None):
        model = build(conf, mesh)
        return dataclasses.replace(model, embed=2.0 * model.embed)

    def half_a_batch(seed, sizes):
        conf = conf_of(seed, sizes)
        return dataclasses.replace(conf, batch=conf.batch // 2)

    def never_steps(seed, sizes):
        # AdamW at rate 0: neither the update nor the decay moves a weight
        return dataclasses.replace(conf_of(seed, sizes), lr=0.0)

    def ids_outside_the_slice(n, vocab, seed=0):
        # the stream of a table twice as long: half its ids are no row held
        return stream(n, 2 * vocab, seed=seed)

    def as_it_came(x, *_weights):
        return x.astype(jnp.float32)

    def no_means(q, k):
        return jnp.zeros_like(q), jnp.zeros_like(k)

    def state_dropped(self, y, r_prev=None):
        return route(self, y, None)

    def without_gradient(node, leaf):
        # the node as it is, but for no gradient reaching that leaf
        call = node.__call__

        def planted(self, *args, **kwargs):
            still = jax.lax.stop_gradient(getattr(self, leaf))
            return call(dataclasses.replace(self, **{leaf: still}), *args, **kwargs)

        return planted

    return {
        "sound": [],
        "bfloat16_state": [(entry, "build_model", in_bfloat16)],
        "half_a_batch": [(adapter, "_conf", half_a_batch)],
        "no_update": [(adapter, "_conf", never_steps)],
        "ids_outside_the_slice": [(entry, "synthetic_corpus", ids_outside_the_slice)],
        "embedding_doubled": [(entry, "build_model", embedding_doubled)],
        # c2 = c: neither convolution
        "no_conv": [(cca, "causal_conv", as_it_came), (cca, "head_conv", as_it_came)],
        # both halves of the values from the current position
        "no_value_shift": [(cca, "shift_values", lambda v, _from: v)],
        "no_qk_mean": [(cca, "group_means", no_means)],
        "no_l2_norm": [(cca, "unit_heads", as_it_came)],
        # r_prev = 0 in every layer
        "router_state_dropped": [(moe.CarriedRouter, "__call__", state_dropped)],
        # every gate 1
        "gate_renormalised": [(entry, "build_model", gate_renormalised)],
        # experts 8-15's tokens through the matrices of 0-7
        "wrong_share": [(entry, "build_model", wrong_share)],
        # one small leaf alone cut off from the loss
        "tau_gradient_stopped": [
            (cca.CCAMixer, "__call__", without_gradient(cca.CCAMixer, "tau"))
        ],
        "gamma_gradient_stopped": [
            (moe.CarriedRouter, "__call__", without_gradient(moe.CarriedRouter, "gamma"))
        ],
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


def read_plant(adapter, name: str, seed: int, sizes: dict, want: dict | None = None):
    """One plant's line at one seed, the plant's swaps in place already.
    ``want`` is the reference's readings from the sound weights, made
    here when not given."""
    got = adapter.program_readings(seed, sizes)
    if want is None or name in OWN_REFERENCE:
        want = adapter.reference_readings(seed, sizes)
    correct, detail = adapter.compare(got, want, sizes, [])
    return {
        "plant": name,
        "seed": seed,
        "correct": correct,
        "refused_by": [m[0] for m in detail["mismatches"]],
        **{k: detail[k] for k in READINGS},
    }


def run_plant(adapter, name: str, seed: int, sizes: dict, want: dict | None = None):
    with planted(plants(adapter)[name]):
        return read_plant(adapter, name, seed, sizes, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--plants", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from harness import device, find

    cfg, adapter = find.config("zaya1_8b")
    cell = find.cell("zaya1_8b.train_8k")
    device.bring_up(cell["chips"], args.rehearse_cpu)
    sizes = find.load_module("run.py").sizes_of(cfg, cell, adapter, args.rehearse_cpu)
    seeds = [int(s) for s in args.seeds.split(",")]
    names = args.plants.split(",") if args.plants else list(plants(adapter))
    wants = {seed: adapter.reference_readings(seed, sizes) for seed in seeds}
    wrong = 0
    for name in names:
        with planted(plants(adapter)[name]):
            for seed in seeds:
                line = read_plant(adapter, name, seed, sizes, wants[seed])
                print(json.dumps(line), flush=True)
                wrong += line["correct"] != (name == "sound")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
