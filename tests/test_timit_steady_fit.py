"""The TIMIT fit's three device programs (``cosine_features``,
``standard_scale``, ``score`` of ``models/timit_pipeline.py``) are made
once per process: a later ``run()`` traces, lowers and compiles none of
them, whatever its seed, and captures nothing of the fit before it.
So is the synthetic corpus (``_synthetic``): its seeds are fixed, the
first fit of a size draws it and every later one is handed the same
read-only arrays. Toy sizes on the CPU; no clock is read."""

import hashlib
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


def _fit(seed, tmp_path, path, synthetic=256):
    kw = dict(PATHS[path])
    if path == "checkpoint_dir":  # a fresh one per fit, or the fit resumes
        kw["checkpoint_dir"] = tempfile.mkdtemp(dir=tmp_path)
    conf = TimitConfig(synthetic=synthetic, num_cosines=2, cosine_features=32,
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


# ---------------------------------------------------------------------------
# the synthetic corpus, made once per process

ERRORS = ("train_error", "test_error")


@pytest.fixture
def cold_memo():
    timit_pipeline._synthetic.cache_clear()
    timit_pipeline._synthetic_centres.cache_clear()


def _todays_formula(which, n):
    """``_load``'s synthetic branch as it stood before the memo."""
    rng = np.random.default_rng(0 if which == "train" else 1)
    k = min(timit_pipeline.NUM_CLASSES, 12)
    labels = rng.integers(0, k, size=n).astype(np.int32)
    centers = np.random.default_rng(42).normal(
        size=(k, timit_pipeline.TIMIT_DIMENSION)
    )
    data = (
        centers[labels] * 2 + rng.normal(size=(n, timit_pipeline.TIMIT_DIMENSION))
    ).astype(np.float32)
    return labels, data


def _digest():
    """One hash over every array the memo holds (256 train, 51 test)."""
    h = hashlib.sha256()
    for which, n in (("train", 256), ("test", 51)):
        for a in timit_pipeline._synthetic(which, n):
            h.update(a.tobytes())
    return h.hexdigest()


def test_only_the_first_fit_of_a_size_draws_the_corpus(cold_memo, tmp_path, monkeypatch):
    seeds, loaded = [], []
    real_rng, real_load = np.random.default_rng, timit_pipeline._load
    monkeypatch.setattr(
        np.random, "default_rng", lambda *a, **k: seeds.append(a) or real_rng(*a, **k)
    )
    monkeypatch.setattr(
        timit_pipeline, "_load", lambda *a: loaded.append(real_load(*a)) or loaded[-1]
    )
    _fit(41, tmp_path, "classic")
    assert sorted(seeds) == [(0,), (1,), (42,)]
    _fit(42, tmp_path, "classic")  # another seed: the features' alone
    assert len(seeds) == 3
    train_1, test_1, train_2, test_2 = loaded
    assert train_2.data is train_1.data and train_2.labels is train_1.labels
    assert test_2.data is test_1.data and test_2.labels is test_1.labels
    assert timit_pipeline._synthetic.cache_info()[:2] == (2, 2)  # hits, misses


@pytest.mark.parametrize("which, n", [("train", 256), ("test", 51), ("train", 1)])
def test_the_memoised_corpus_is_todays_bit_for_bit_and_read_only(cold_memo, which, n):
    conf = TimitConfig(synthetic=n if which == "train" else n * 5)
    got = timit_pipeline._load(conf, which)
    labels, data = _todays_formula(which, n)
    assert got.labels.dtype == np.int32 and got.data.dtype == np.float32
    assert np.array_equal(got.labels, labels) and np.array_equal(got.data, data)
    assert not got.labels.flags.writeable and not got.data.flags.writeable
    with pytest.raises(ValueError):
        got.data[0, 0] = 1.0
    assert timit_pipeline._load(conf, which).data is got.data


@pytest.mark.parametrize("path", ["classic", "checkpoint_dir"], indirect=True)
def test_no_fit_writes_its_rows(path, tmp_path):
    """Also where ``device_put`` aliases host memory (the CPU backend)."""
    before = _digest()
    _fit(51, tmp_path, path)
    _fit(52, tmp_path, path)
    assert _digest() == before


def test_another_size_gets_its_own_corpus_and_the_memo_stays_small(cold_memo, tmp_path):
    first, _ = _fit(61, tmp_path, "classic")
    rows = timit_pipeline._synthetic("train", 256)[1]
    other, _ = _fit(61, tmp_path, "classic", synthetic=320)
    assert (other["n_train"], other["n_test"]) == (320, 64)
    bigger = timit_pipeline._synthetic("train", 320)[1]
    assert bigger.shape == (320, 440) and not np.array_equal(bigger[:256], rows)
    assert timit_pipeline._synthetic("train", 256)[1] is rows
    again, _ = _fit(61, tmp_path, "classic")
    assert [again[k] for k in ERRORS] == [first[k] for k in ERRORS]
    # a third size evicts the oldest pair: memory held is bounded
    _fit(61, tmp_path, "classic", synthetic=384)
    info = timit_pipeline._synthetic.cache_info()
    assert info.currsize == info.maxsize == 4
    assert timit_pipeline._synthetic("train", 384)[1].shape == (384, 440)


def test_cold_and_warm_memo_fit_the_same(cold_memo, tmp_path):
    cold, _ = _fit(71, tmp_path, "classic")
    assert timit_pipeline._synthetic.cache_info().hits == 0
    warm, _ = _fit(71, tmp_path, "classic")
    assert timit_pipeline._synthetic.cache_info().hits == 2
    assert [warm[k] for k in ERRORS] == [cold[k] for k in ERRORS]
    assert np.isfinite([cold[k] for k in ERRORS]).all()
