"""The TIMIT fit's three device programs (``cosine_features``,
``standard_scale``, ``score`` of ``models/timit_pipeline.py``) are made
once per process: a later ``run()`` traces, lowers and compiles none of
them, whatever its seed, and captures nothing of the fit before it.
Toy sizes on the CPU; no clock is read."""

import tempfile

import numpy as np
import pytest

from keystone_tpu.models import timit_pipeline
from keystone_tpu.models.timit_pipeline import TimitConfig, run
from keystone_tpu.observe import events
from keystone_tpu.observe import spans as spans_mod

PROGRAMS = ("cosine_features", "standard_scale", "score")

# path -> what it adds to the toy config (KEYSTONE_PLAN is an environment
# name: the ``path`` fixture sets it)
PATHS = {
    "classic": {},
    "checkpoint_dir": {},
    "lam_sweep": {"lam_sweep": "0.1,1.0"},
    "keystone_plan": {},
}


def _fit(seed, tmp_path, path):
    kw = dict(PATHS[path])
    if path == "checkpoint_dir":  # a fresh one per fit, or the fit resumes
        kw["checkpoint_dir"] = tempfile.mkdtemp(dir=tmp_path)
    conf = TimitConfig(synthetic=256, num_cosines=2, cosine_features=32,
                       num_epochs=2, seed=seed, **kw)
    return run(conf), conf


def _cache_sizes():
    return [getattr(timit_pipeline, name)._cache_size() for name in PROGRAMS]


@pytest.fixture
def path(request, monkeypatch):
    if request.param == "keystone_plan":
        monkeypatch.setenv("KEYSTONE_PLAN", "1")
    else:
        monkeypatch.delenv("KEYSTONE_PLAN", raising=False)
    return request.param


@pytest.mark.parametrize("path", list(PATHS), indirect=True)
def test_second_fit_of_a_process_makes_no_program(path, tmp_path):
    """Under an event sink, the second run() (another seed than the
    first) has no ``jit.*`` span under its ``fit`` root. The streamed
    path keeps one compile that is not the fit's: the planner costs the
    bank on a probe (``plan/costs.py::sample_chain``, a ``jit(<lambda>)``
    per plan, under ``fit.solve``)."""
    _fit(11, tmp_path, path)
    with events.run(str(tmp_path / "observe")) as log:
        _fit(12, tmp_path, path)
        run_dir = log.run_dir
    recs = spans_mod.read_spans(run_dir)
    by_id = {r["span"]: r for r in recs}
    (root,) = [r for r in recs if r["name"] == "fit"]
    jit = [r for r in recs if r["name"].startswith("jit.")]
    assert all(r["trace"] == root["trace"] for r in jit)
    if path != "keystone_plan":
        assert jit == []
        return
    assert {by_id[r["parent"]]["name"] for r in jit} == {"fit.solve"}
    made = {r["fun"] for r in jit if r["name"] != "jit.trace"}
    assert made == {"jit(<lambda>)"}
    assert not any(name in r["fun"] for r in jit for name in PROGRAMS)


@pytest.mark.parametrize("path", ["classic", "keystone_plan"], indirect=True)
def test_program_caches_do_not_grow_after_the_second_fit(path, tmp_path):
    _fit(21, tmp_path, path)
    _fit(22, tmp_path, path)
    second = _cache_sizes()
    _fit(23, tmp_path, path)
    assert _cache_sizes() == second


def _restored_leaves(conf):
    import orbax.checkpoint as ocp

    mgr = ocp.CheckpointManager(conf.checkpoint_dir)
    try:
        leaves = mgr.restore(mgr.latest_step(), args=ocp.args.StandardRestore())["leaves"]
    finally:
        mgr.close()
    return [np.asarray(a) for a in leaves]


def test_a_later_fit_captures_nothing_of_an_earlier_one(tmp_path):
    """Seed A, seed B, seed A again: the two A fits return the same error
    rates and save bit-equal weights; B's weights are others."""
    fits = [_fit(seed, tmp_path, "checkpoint_dir") for seed in (31, 32, 31)]
    (out_a, conf_a), (_out_b, conf_b), (out_a2, conf_a2) = fits
    keys = ("train_error", "test_error", "n_train", "n_test")
    assert [out_a[k] for k in keys] == [out_a2[k] for k in keys]
    a, b, a2 = (_restored_leaves(c) for c in (conf_a, conf_b, conf_a2))
    assert [x.shape for x in a] == [x.shape for x in a2] == [x.shape for x in b]
    assert all(np.array_equal(x, y) for x, y in zip(a, a2))
    # (the intercept is the labels' mean: the corpus is the same, the
    # seed draws the features)
    blocks = [i for i, x in enumerate(a) if x.ndim == 2]
    assert len(blocks) == conf_a.num_cosines
    assert not any(np.array_equal(a[i], b[i]) for i in blocks)


def test_fitted_nodes_are_arguments_of_the_programs():
    """Nothing of a fit is closed over, and the names are those a profile
    shows (``jit_cosine_features`` is what the benchmark's reader finds)."""
    for name in PROGRAMS:
        fun = getattr(timit_pipeline, name)
        assert fun.__name__ == name and fun.__wrapped__.__closure__ is None
