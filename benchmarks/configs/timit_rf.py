"""Adapter of ``timit_rf``: how the harness reaches the program.

Fits call ``models/timit_pipeline.py::run``, the function ``python -m
keystone_tpu timit`` calls. ``run()`` returns error rates only, so the
check makes one more fit through the program's own ``--checkpoint-dir``
(one chunk of all the epochs, which ``core/checkpoint.py`` documents as
identical to the plain fit), reads the solver's weights back from that
checkpoint and holds them to the plain reference's."""

from __future__ import annotations

import shutil
import tempfile

import numpy as np

from harness import find

ref = find.load_module("configs", "timit_rf_reference.py")
TOL = find.read_json("configs", "timit_rf.json")["tolerances"]
RESULT_KEYS = ("train_error", "test_error", "n_train", "n_test")


def cell_sizes(sizes: dict) -> dict:
    """Training rows are given per chip and grow with the cell's chips."""
    if "train_rows_per_chip" in sizes:
        sizes["train_rows"] = sizes["train_rows_per_chip"] * sizes["chips"]
    return sizes


def one_fit(seed: int, sizes: dict, checkpoint_dir: str = "") -> dict:
    from keystone_tpu.models.timit_pipeline import TimitConfig, run

    out = run(
        TimitConfig(
            synthetic=sizes["train_rows"],
            num_cosines=sizes["num_cosines"],
            cosine_features=sizes["cosine_features"],
            gamma=sizes["gamma"],
            rf_type=sizes["rf_type"],
            lam=sizes["lam"],
            num_epochs=sizes["num_epochs"],
            seed=seed,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=sizes["num_epochs"],
        )
    )
    # featurize_s / fit_s go to an earlier line only, as the host's view
    # of where a slow fit was slow: run() takes t_feat without a
    # block_until_ready, so featurize work leaks into fit_s
    walls = ("featurize_s", "fit_s", "total_s")
    return {k: float(out[k]) for k in (*RESULT_KEYS, *walls)}


def fitted_weights(seed: int, sizes: dict) -> tuple[dict, dict]:
    """(what ``run()`` returned, the solver's weights) of one fit saved
    by the program: the leaves of its ``BlockLinearMapper`` as
    ``core/checkpoint.py`` writes them, told apart by shape."""
    import orbax.checkpoint as ocp

    tmp = tempfile.mkdtemp(prefix="bench_timit_rf_")
    try:
        out = one_fit(seed, sizes, checkpoint_dir=tmp)
        mgr = ocp.CheckpointManager(tmp)
        try:
            leaves = mgr.restore(
                mgr.latest_step(), args=ocp.args.StandardRestore()
            )["leaves"]
        finally:
            mgr.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    leaves = [np.asarray(a) for a in leaves]
    width, k = sizes["cosine_features"], sizes["num_classes"]
    (intercept,) = [a for a in leaves if a.shape == (k,)]
    weights = {
        "xs": [a for a in leaves if a.shape == (width, k)],
        "means": [a for a in leaves if a.shape == (width,)],
        "intercept": intercept,
    }
    assert len(weights["xs"]) == len(weights["means"]) == sizes["num_cosines"]
    return out, weights


def check_fits(seed: int, sizes: dict, fits: list[dict]):
    """Outside the window. The checked fit's test-row scores lie within
    ``fit_scores_rel`` of the reference's (both sets of weights applied
    to the reference's test blocks at full precision; the distance is
    taken from the intercept, the score of a model that learnt
    nothing), and every fit of the window returned what the checked fit
    returned."""
    checked, weights = fitted_weights(seed, sizes)
    want = ref.fit(seed, sizes)
    n_test = want["n_test"]
    got_scores = ref.scores(want, weights)[:n_test]
    want_scores = ref.scores(want, want)[:n_test]
    origin = np.asarray(want["intercept"])
    detail = {
        "scores_rel": ref.distance(got_scores, want_scores, origin),
        "weights_rel": ref.distance(
            np.stack(weights["xs"]), np.stack([np.asarray(x) for x in want["xs"]])
        ),
        "labels_agree": float(
            np.mean(got_scores.argmax(-1) == want_scores.argmax(-1))
        ),
        "checked": checked,
        "reference": {k: want[k] for k in RESULT_KEYS},
    }
    bad = []
    if not detail["scores_rel"] <= TOL["fit_scores_rel"]:
        bad.append(("scores_rel", detail["scores_rel"]))
    for i, got in enumerate([checked, *fits]):
        for key in ("train_error", "test_error"):
            if abs(got[key] - want[key]) > TOL["fit_error_abs"]:
                bad.append((i, key, got[key], want[key]))
        if got["test_error"] > TOL["fit_test_error_max"]:
            bad.append((i, "test_error_max", got["test_error"]))
        if any(got[k] != checked[k] for k in RESULT_KEYS):
            bad.append((i, "differs from the checked fit", got))
    detail["mismatches"] = bad[:5]
    return not bad, detail


def ops_and_bytes(sizes: dict) -> dict:
    """What the algorithm needs, from shapes (per chip)."""
    n = sizes["train_rows_per_chip"]
    d, k, banks = (
        sizes["cosine_features"], sizes["num_classes"], sizes["num_cosines"]
    )
    # BCD: one Gram per block (2 N d^2), and per block and epoch the two
    # N x d x K products (A^T R and A (x_new - x)), 2 N d K each
    solve_gemm = banks * (2 * n * d * d + sizes["num_epochs"] * 2 * 2 * n * d * k)
    return {"solve_gemm_flops_per_fit": solve_gemm}
