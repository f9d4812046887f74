"""Resilience subsystem: the fault matrix the Spark substrate used to
absorb for free — injected tar IOErrors, NaN batches, preemption,
checkpoint-IO flakes, hangs — each survived deterministically, plus the
retry-policy and fault-grammar unit tests. All CPU, and the backoff
clock is injected wherever a schedule is under test (no real sleeping
beyond sub-second IO-policy retries)."""

import io
import json
import os
import signal
import tarfile
import tempfile
import time

import numpy as np
import pytest

from keystone_tpu.observe import events, metrics
from keystone_tpu.resilience import (
    AcceleratorDrop,
    GuardConfig,
    LossGuard,
    NumericalHealthError,
    RetryExhausted,
    RetryPolicy,
    SimulatedPreemption,
    Watchdog,
    faults,
    guards,
    is_transient,
)


@pytest.fixture(autouse=True)
def _clean_resilience(monkeypatch):
    """Every test starts and ends with no fault plan and no output
    guard — global flags must not leak across tests."""
    monkeypatch.delenv("KEYSTONE_FAULTS", raising=False)
    monkeypatch.delenv("KEYSTONE_GUARD_OUTPUTS", raising=False)
    faults.reset()
    guards.set_output_guard(None)
    yield
    faults.reset()
    guards.set_output_guard(None)


def _counter_value(name, **labels) -> float:
    return metrics.get_registry().counter(name, **labels).value


# ---------------------------------------------------------------- retry


def test_retry_backoff_schedule_deterministic():
    p = RetryPolicy(
        max_attempts=5, base_delay_s=1.0, multiplier=2.0, max_delay_s=5.0,
        jitter=0.1, seed=3,
    )
    delays = [p.delay_s(i) for i in range(5)]
    # exponential with cap, jittered within ±10%
    for i, (d, raw) in enumerate(zip(delays, [1.0, 2.0, 4.0, 5.0, 5.0])):
        assert 0.9 * raw <= d <= 1.1 * raw, (i, d)
    # pure function of (seed, attempt): replays exactly
    assert delays == [p.delay_s(i) for i in range(5)]
    assert RetryPolicy(jitter=0.0, base_delay_s=1.0).delay_s(0) == 1.0


def test_retry_succeeds_after_transient_no_real_sleep():
    sleeps = []
    p = RetryPolicy(
        max_attempts=4, base_delay_s=1.0, jitter=0.0,
        sleep=sleeps.append, monotonic=lambda: 0.0,
    )
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise IOError("transient")
        return 42

    assert p.call(flaky, label="t") == 42
    assert calls["n"] == 3
    assert sleeps == [1.0, 2.0]


def test_retry_nontransient_passes_through_immediately():
    sleeps = []
    p = RetryPolicy(max_attempts=5, sleep=sleeps.append)
    calls = {"n": 0}

    def broken():
        calls["n"] += 1
        raise ValueError("permanent")

    with pytest.raises(ValueError, match="permanent"):
        p.call(broken)
    assert calls["n"] == 1 and sleeps == []


def test_retry_exhausted_carries_cause():
    p = RetryPolicy(
        max_attempts=2, base_delay_s=1.0, jitter=0.0,
        sleep=lambda s: None, monotonic=lambda: 0.0,
    )
    with pytest.raises(RetryExhausted) as ei:
        p.call(lambda: (_ for _ in ()).throw(IOError("flaky")))
    assert isinstance(ei.value.__cause__, IOError)


def test_retry_deadline_stops_early():
    clock = {"t": 0.0}
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        clock["t"] += s

    p = RetryPolicy(
        max_attempts=10, base_delay_s=4.0, multiplier=1.0, jitter=0.0,
        deadline_s=10.0, sleep=sleep, monotonic=lambda: clock["t"],
    )
    with pytest.raises(RetryExhausted) as ei:
        p.call(lambda: (_ for _ in ()).throw(IOError("x")))
    # 4s + 4s spent; a third delay would cross the 10s deadline
    assert sleeps == [4.0, 4.0]
    # the error reports what actually happened, not the configured cap
    assert "3/10 attempts" in str(ei.value)
    assert "deadline exceeded" in str(ei.value)


def test_transient_classifier():
    assert is_transient(IOError("x"))
    assert is_transient(ConnectionError("x"))
    assert is_transient(TimeoutError("x"))
    # corruption doesn't heal on retry — straight to the skip path
    assert not is_transient(tarfile.ReadError("corrupt header"))
    # neither does a typo'd path: the user needs the real error, fast
    assert not is_transient(FileNotFoundError("no such file"))
    assert not is_transient(PermissionError("denied"))
    assert is_transient(RuntimeError("UNAVAILABLE: connection dropped"))
    assert is_transient(RuntimeError("DEADLINE_EXCEEDED: barrier"))
    assert not is_transient(RuntimeError("RESOURCE_EXHAUSTED: OOM"))
    assert not is_transient(ValueError("shape mismatch"))


def test_retry_emits_events_and_metrics():
    before = _counter_value("retries", label="evt")
    p = RetryPolicy(
        max_attempts=2, base_delay_s=1.0, jitter=0.0,
        sleep=lambda s: None, monotonic=lambda: 0.0,
    )
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise IOError("once")
        return 1

    with events.run() as log:
        p.call(flaky, label="evt")
    recs = [r for r in log.records if r.get("event") == "resilience"]
    assert recs and recs[0]["action"] == "retry"
    assert recs[0]["phase"] == "resilience"
    assert _counter_value("retries", label="evt") == before + 1


# ---------------------------------------------------------------- faults


def test_fault_spec_grammar():
    specs = faults.parse_spec("tar.read:@0:0, train.nan:0.5:3:2")
    assert specs[0].at == 0 and specs[0].p is None
    assert specs[1].p == 0.5 and specs[1].seed == 3
    assert specs[1].max_fires == 2
    # seed defaults to 0
    assert faults.parse_spec("train.preempt:@12")[0].seed == 0
    with pytest.raises(ValueError, match="unknown site"):
        faults.parse_spec("no.such.site:0.5:0")
    with pytest.raises(ValueError, match="outside"):
        faults.parse_spec("tar.read:1.5:0")
    with pytest.raises(ValueError, match="expected site"):
        faults.parse_spec("tar.read")


def test_fault_keyed_firing_is_deterministic():
    faults.configure("train.nan:@7:0")
    fired = [faults.fire("train.nan", key=i) for i in range(10)]
    assert fired == [i == 7 for i in range(10)]
    # re-deriving the same keys gives the same schedule (resume safety)
    assert [faults.fire("train.nan", key=i) for i in range(10)] == fired


def test_fault_probability_schedule_replays():
    faults.configure("tar.read:0.3:5")
    a = [faults.fire("tar.read", key=i) for i in range(50)]
    faults.configure("tar.read:0.3:5")
    assert [faults.fire("tar.read", key=i) for i in range(50)] == a
    assert 2 <= sum(a) <= 30  # ~15 expected; loose bounds, no flake


def test_fault_counter_keys_and_max_fires():
    faults.configure("tar.read:@0:0")
    assert faults.fire("tar.read") is True  # counter key 0
    assert faults.fire("tar.read") is False  # counter key 1
    faults.configure("idx.read:1.0:0:2")  # always fire, capped at 2
    assert [faults.fire("idx.read") for _ in range(4)] == [
        True, True, False, False,
    ]


def test_fault_env_activation(monkeypatch):
    monkeypatch.setenv("KEYSTONE_FAULTS", "tar.read:@0:0")
    faults.reset()
    assert faults.active() is not None
    assert faults.fire("tar.read") is True
    monkeypatch.delenv("KEYSTONE_FAULTS")
    faults.reset()
    assert faults.active() is None
    assert faults.fire("tar.read") is False


def test_fault_poison_and_emission():
    faults.configure("batch.nan:@0:0")
    before = _counter_value("faults_fired", site="batch.nan")
    with events.run() as log:
        out = faults.poison("batch.nan", np.ones((4, 3), np.float32))
    assert np.isnan(out[0]).all() and np.isfinite(out[1:]).all()
    assert _counter_value("faults_fired", site="batch.nan") == before + 1
    recs = [r for r in log.records if r.get("event") == "resilience"]
    assert recs and recs[0]["action"] == "fault"
    # int batches pass through untouched even when the site fires
    faults.configure("batch.nan:@0:0")
    ints = np.ones((4, 3), np.int32)
    assert faults.poison("batch.nan", ints) is ints


def test_faults_cli(capsys):
    from keystone_tpu.__main__ import main

    main(["faults", "--list"])
    out = capsys.readouterr().out
    assert "tar.read" in out and "train.preempt" in out
    main(["faults", "--validate", "tar.read:@0:0,ckpt.save:0.1:2"])
    out = capsys.readouterr().out
    assert out.count("ok:") == 2
    with pytest.raises(SystemExit, match="invalid"):
        main(["faults", "--validate", "bogus.site:0.5"])


# ------------------------------------------------------------- loaders


def _make_tar(path, entries):
    from PIL import Image

    with tarfile.open(path, "w") as tf:
        for name, arr in entries:
            buf = io.BytesIO()
            Image.fromarray(arr).save(buf, format="JPEG")
            data = buf.getvalue()
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))


@pytest.fixture
def good_tars(tmp_path, rng):
    paths = []
    for t in range(2):
        entries = [
            (f"n{t}_{i}.jpg", rng.integers(0, 255, (16, 16, 3)).astype(np.uint8))
            for i in range(4)
        ]
        p = tmp_path / f"part{t}.tar"
        _make_tar(p, entries)
        paths.append(str(p))
    return paths


def test_corrupt_tar_skipped_stream_completes(good_tars, tmp_path):
    """The fault-matrix headline: one dead archive costs its own
    entries, never the stream — N-1 archives' images still arrive."""
    from keystone_tpu.loaders.streaming import iter_tar_image_batches

    bad = tmp_path / "corrupt.tar"
    bad.write_bytes(b"this is not a tar archive at all")
    before = _counter_value("ingest_archives_skipped", reason="unreadable")
    batches = list(
        iter_tar_image_batches(
            [good_tars[0], str(bad), good_tars[1]],
            batch_size=64, target_size=8,
        )
    )
    names = [n for b in batches for n in b[0]]
    assert len(names) == 8  # both good archives fully ingested
    assert (
        _counter_value("ingest_archives_skipped", reason="unreadable")
        == before + 1
    )


def test_injected_transient_tar_error_retried(good_tars):
    """tar.read:@0 fires on the first open attempt; the retry's next
    check (counter key 1) passes — no archive is lost."""
    from keystone_tpu.loaders.streaming import iter_tar_image_batches

    faults.configure("tar.read:@0:0")
    batches = list(
        iter_tar_image_batches(good_tars, batch_size=64, target_size=8)
    )
    assert len([n for b in batches for n in b[0]]) == 8


def test_decode_failure_counted(good_tars, tmp_path):
    from keystone_tpu.loaders.streaming import iter_tar_image_batches

    bad = tmp_path / "garbled.tar"
    with tarfile.open(bad, "w") as tf:
        info = tarfile.TarInfo("oops.jpg")
        payload = b"not a jpeg"
        info.size = len(payload)
        tf.addfile(info, io.BytesIO(payload))
    before = _counter_value("ingest_decode_failures", loader="streaming")
    batches = list(
        iter_tar_image_batches(
            [good_tars[0], str(bad)], batch_size=64, target_size=8
        )
    )
    assert len([n for b in batches for n in b[0]]) == 4
    assert (
        _counter_value("ingest_decode_failures", loader="streaming")
        == before + 1
    )


def test_eager_loader_strict_on_corrupt_tar(tmp_path):
    """load_tar_images (eager, often single-archive) must RAISE on a
    corrupt tar, not silently return an empty dataset — skip-and-
    continue is the streaming path's contract only."""
    from keystone_tpu.loaders.image_loaders import load_tar_images

    bad = tmp_path / "only.tar"
    bad.write_bytes(b"definitely not a tar")
    with pytest.raises((tarfile.ReadError, OSError)):
        load_tar_images([str(bad)], target_size=8)


def test_missing_file_fails_fast_not_retried(tmp_path):
    from keystone_tpu.loaders.idx import load_idx

    t0 = time.monotonic()
    with pytest.raises(FileNotFoundError):
        load_idx(str(tmp_path / "nope-idx3-ubyte"))
    assert time.monotonic() - t0 < 1.0  # no backoff burned on a typo


def _write_idx(path, arr):
    import struct

    code = {np.uint8: 0x08}[arr.dtype.type]
    with open(path, "wb") as f:
        f.write(struct.pack(">HBB", 0, code, arr.ndim))
        f.write(struct.pack(f">{arr.ndim}i", *arr.shape))
        f.write(arr.tobytes())


def test_idx_transient_error_retried(tmp_path):
    from keystone_tpu.loaders.idx import load_idx

    p = tmp_path / "train-images-idx3-ubyte"
    arr = np.arange(24, dtype=np.uint8).reshape(2, 3, 4)
    _write_idx(p, arr)
    faults.configure("idx.read:@0:0")
    np.testing.assert_array_equal(load_idx(str(p)), arr)
    # corruption (bad magic) is NOT transient: fails without retries
    bad = tmp_path / "bad-idx"
    bad.write_bytes(b"\xff\xff\xff\xff garbage")
    faults.configure("idx.read:@99:0")  # armed but never firing
    with pytest.raises(ValueError, match="not an IDX"):
        load_idx(str(bad))


# ---------------------------------------------------------- checkpoint


def test_checkpoint_save_and_restore_retried(rng, tmp_path):
    import dataclasses

    import jax.numpy as jnp

    from keystone_tpu.core.checkpoint import resumable_fit
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator

    n, d, c = 40, 8, 3
    a = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    ck = str(tmp_path / "ck")
    est = BlockLeastSquaresEstimator(block_size=4, num_iter=4, lam=0.1)
    before = _counter_value("retries", label="ckpt.save")
    # first save attempt raises (injected) → retried → fit completes
    faults.configure("ckpt.save:@0:0")
    resumable_fit(
        dataclasses.replace(est, num_iter=2), a, y,
        checkpoint_dir=ck, every=2,
    )
    assert _counter_value("retries", label="ckpt.save") == before + 1
    # resume with the first restore attempt failing (injected)
    faults.configure("ckpt.restore:@0:0")
    model = resumable_fit(est, a, y, checkpoint_dir=ck, every=2)
    direct = est.fit(a, y)
    for x1, x2 in zip(model.xs, direct.xs):
        np.testing.assert_allclose(
            np.asarray(x1), np.asarray(x2), atol=1e-4
        )


# -------------------------------------------------------------- guards


def test_guard_config_validation():
    with pytest.raises(ValueError, match="off|skip|halt"):
        GuardConfig(mode="explode")
    with pytest.raises(ValueError, match="check_every"):
        GuardConfig(mode="skip", check_every=0)
    assert guards.resolve_guard("skip").mode == "skip"
    assert guards.resolve_guard(None).mode == "off"
    assert guards.resolve_guard(GuardConfig(mode="halt")).mode == "halt"


def test_loss_guard_skip_records_and_halt_raises():
    import jax.numpy as jnp

    g = LossGuard(GuardConfig(mode="skip", check_every=4))
    vals = [1.0, 0.9, float("nan"), 0.8, 0.7]
    for i, v in enumerate(vals):
        g.note(i, jnp.float32(v))
    g.flush()
    assert g.skipped == [2]

    h = LossGuard(GuardConfig(mode="halt", check_every=2))
    h.note(0, jnp.float32(1.0))
    with pytest.raises(NumericalHealthError, match="non-finite"):
        h.note(1, jnp.float32(float("inf")))


def test_loss_guard_spike_detection():
    import jax.numpy as jnp

    g = LossGuard(
        GuardConfig(mode="halt", check_every=3, spike_factor=5.0)
    )
    for i, v in enumerate([1.0, 1.1, 0.9]):
        g.note(i, jnp.float32(v))
    with pytest.raises(NumericalHealthError, match="spike"):
        for i, v in enumerate([1.0, 50.0, 1.0], start=3):
            g.note(i, jnp.float32(v))
        g.flush()


def test_output_guard_warn_and_raise_modes():
    import jax.numpy as jnp

    from keystone_tpu.core.pipeline import Pipeline, transformer

    nan_node = transformer(
        lambda x: jnp.where(x > 0, jnp.float32(np.nan), x), name="nanify"
    )
    pipe = Pipeline.of(transformer(lambda x: x * 2, name="dbl"), nan_node)
    x = jnp.ones((4, 3), jnp.float32)

    guards.set_output_guard("warn")
    before = _counter_value("guard_events", action="nonfinite_output")
    with events.run() as log:
        out = pipe(x)  # degrade-don't-crash: completes with a warning
    assert np.isnan(np.asarray(out)).all()
    assert (
        _counter_value("guard_events", action="nonfinite_output")
        == before + 1
    )
    recs = [
        r for r in log.records
        if r.get("action") == "nonfinite_output"
    ]
    assert recs and recs[0]["node"].endswith("nanify")

    guards.set_output_guard("raise")
    with pytest.raises(NumericalHealthError, match="nanify"):
        pipe(x)

    guards.set_output_guard("")
    assert guards.output_guard_mode() == ""


def test_output_guard_env_rejects_bad_mode(monkeypatch):
    """A typo'd KEYSTONE_GUARD_OUTPUTS (e.g. 'halt', which belongs to
    KEYSTONE_GUARD) must fail fast, not silently downgrade to warn."""
    monkeypatch.setenv("KEYSTONE_GUARD_OUTPUTS", "halt")
    guards.set_output_guard(None)
    with pytest.raises(ValueError, match="KEYSTONE_GUARD_OUTPUTS"):
        guards.output_guard_mode()
    monkeypatch.setenv("KEYSTONE_GUARD_OUTPUTS", "1")
    guards.set_output_guard(None)
    assert guards.output_guard_mode() == "warn"


def test_output_guard_skipped_under_jit():
    import jax
    import jax.numpy as jnp

    from keystone_tpu.core.pipeline import Pipeline, transformer

    guards.set_output_guard("raise")
    pipe = Pipeline.of(transformer(lambda x: x * jnp.float32(np.nan)))
    # under tracing there is no value to check; the guard must not
    # touch tracers (and the jitted call must still compile)
    out = jax.jit(lambda x: pipe(x))(jnp.ones((2, 2), jnp.float32))
    assert np.isnan(np.asarray(out)).all()


# ------------------------------------------------- pipeline fault sites


def test_accelerator_drop_injected_into_chained_fit(rng):
    import jax.numpy as jnp

    from keystone_tpu.core.pipeline import label_estimator, transformer

    est = transformer(lambda x: x, name="feat").then(
        label_estimator(lambda d, l: transformer(lambda x: x))
    )
    a = jnp.asarray(rng.normal(size=(8, 3)).astype(np.float32))
    y = jnp.zeros((8,), jnp.int32)
    faults.configure("accel.fit:@0:0")
    with pytest.raises(AcceleratorDrop, match="UNAVAILABLE"):
        est.fit(a, y)
    # the injected error reads as transient to the retry classifier,
    # exactly like a real lost-device XlaRuntimeError
    faults.configure("accel.fit:@0:0")
    try:
        est.fit(a, y)
    except AcceleratorDrop as e:
        assert is_transient(e)


def test_batch_nan_poison_reaches_chained_fit(rng):
    from keystone_tpu.core.pipeline import label_estimator, transformer

    seen = {}

    def fit(d, l):
        seen["data"] = np.asarray(d)
        return transformer(lambda x: x)

    est = transformer(lambda x: x, name="feat").then(label_estimator(fit))
    a = rng.normal(size=(8, 3)).astype(np.float32)
    faults.configure("batch.nan:@0:0")
    est.fit(a, np.zeros((8,), np.int32))
    assert np.isnan(seen["data"][0]).all()
    assert np.isfinite(seen["data"][1:]).all()


# ------------------------------------------------------------ watchdog


def test_watchdog_flags_stall_and_rearms():
    stalls = []
    dog = Watchdog(
        timeout_s=0.05, label="t", on_stall=lambda: stalls.append(1),
        poll_s=0.01,
    )
    with dog:
        time.sleep(0.12)  # stalled: no pet
        first = dog.stalls
        dog.pet()  # recover + re-arm
        time.sleep(0.12)  # stall again
    assert first == 1
    assert dog.stalls == 2 and len(stalls) == 2


def test_watchdog_quiet_when_petted():
    dog = Watchdog(timeout_s=0.2, label="t", poll_s=0.01)
    with dog:
        for _ in range(10):
            time.sleep(0.01)
            dog.pet()
    assert dog.stalls == 0


def test_watchdog_rejects_nonpositive_timeout():
    with pytest.raises(ValueError):
        Watchdog(timeout_s=0.0)


# ------------------------------------------------------- LM fault matrix


def _lm():
    import jax

    from keystone_tpu.models import lm_transformer as lm

    corpus = lm.synthetic_corpus(3_000, 31, seed=5)

    def fresh():
        return lm.TransformerLM.create(
            jax.random.key(5), vocab=31, max_seq=32, dim=32, depth=2,
            num_heads=2,
        )

    kw = dict(steps=20, batch=4, seq=16, lr=1e-3, seed=5)
    return lm, corpus, fresh, kw


def _models_bit_equal(m1, m2) -> bool:
    import jax

    return all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(m1), jax.tree_util.tree_leaves(m2)
        )
    )


def test_nan_batch_skipped_and_training_converges():
    lm, corpus, fresh, kw = _lm()
    faults.configure("train.nan:@7:0")
    with events.run() as log:
        model, losses = lm.train(fresh(), corpus, **kw, guard="skip")
    assert np.isnan(losses[7])  # the poisoned step's loss IS NaN...
    finite = [l for l in losses if np.isfinite(l)]
    assert len(finite) == 19
    assert finite[-1] < finite[0]  # ...but training converged anyway
    skips = [r for r in log.records if r.get("action") == "guard_skip"]
    assert [r["step"] for r in skips] == [7]


def test_nan_batch_without_guard_corrupts():
    """With the NaN fault armed but NO guard mode, the injection must
    corrupt like a real bad batch — the baseline the guard is measured
    against (poison scales loss AND grads, so the update goes NaN)."""
    lm, corpus, fresh, kw = _lm()
    faults.configure("train.nan:@2:0")
    _, losses = lm.train(fresh(), corpus, **{**kw, "steps": 6})
    assert np.isfinite(losses[:2]).all()
    assert np.isnan(losses[2:]).all()  # NaN params poison every step after


def test_preemption_resume_bit_exact():
    """The acceptance gate: with a NaN batch AND a preemption injected,
    the resumed trajectory (losses and final params) is bit-identical
    to the uninterrupted run with the same NaN fault."""
    lm, corpus, fresh, kw = _lm()
    faults.configure("train.nan:@7:0")
    m_base, base = lm.train(fresh(), corpus, **kw, guard="skip")

    d = tempfile.mkdtemp()
    faults.configure("train.nan:@7:0,train.preempt:@12:0")
    with events.run() as log:
        with pytest.raises(SimulatedPreemption):
            lm.train(
                fresh(), corpus, **kw, guard="skip", checkpoint_dir=d
            )
    # the finally path checkpointed the last completed step (13)
    final = [r for r in log.records if r.get("action") == "final_checkpoint"]
    assert final and final[0]["step"] == 13

    faults.configure("train.nan:@7:0")  # resume re-derives the schedule
    m_res, rest = lm.train(
        fresh(), corpus, **kw, guard="skip", checkpoint_dir=d
    )
    assert len(rest) == 7  # steps 13..19
    assert [float(a) for a in base[13:]] == [float(b) for b in rest]
    assert _models_bit_equal(m_base, m_res)


def test_guard_halt_returns_last_good_checkpoint():
    lm, corpus, fresh, kw = _lm()
    d = tempfile.mkdtemp()
    faults.configure("train.nan:@7:0")
    model, losses = lm.train(
        fresh(), corpus, **kw,
        guard=GuardConfig(mode="halt", check_every=10),
        checkpoint_dir=d, checkpoint_every=2,
    )
    # the NaN at step 7 is seen at the step-9 interval check; the last
    # checkpoint before it is step 8 — that state comes back (the loss
    # trace keeps step 7's NaN: the guard skips the UPDATE, the record
    # stays honest)
    assert len(losses) == 8
    assert all(np.isfinite(losses[:7])) and np.isnan(losses[7])
    # without a checkpoint dir the halt propagates
    faults.configure("train.nan:@7:0")
    with pytest.raises(NumericalHealthError):
        lm.train(
            fresh(), corpus, **kw,
            guard=GuardConfig(mode="halt", check_every=10),
        )


def test_sigterm_checkpoints_and_resume_matches():
    """Satellite: SIGTERM mid-train writes a final checkpoint and
    returns early; resuming completes the identical trajectory. The
    signal is REAL (raise_signal via the train.sigterm fault site), so
    the handler path is exercised end to end."""
    lm, corpus, fresh, kw = _lm()
    prev_handler = signal.getsignal(signal.SIGTERM)
    m_base, base = lm.train(fresh(), corpus, **kw)

    d = tempfile.mkdtemp()
    faults.configure("train.sigterm:@5:0")
    m_int, part = lm.train(fresh(), corpus, **kw, checkpoint_dir=d)
    assert len(part) < kw["steps"]  # stopped early
    stopped_at = len(part)

    faults.reset()
    m_res, rest = lm.train(fresh(), corpus, **kw, checkpoint_dir=d)
    assert len(rest) == kw["steps"] - stopped_at
    assert [float(a) for a in base[stopped_at:]] == [
        float(b) for b in rest
    ]
    assert _models_bit_equal(m_base, m_res)
    # the loop restored the pre-train handler on every exit path
    assert signal.getsignal(signal.SIGTERM) is prev_handler


def test_sigterm_fault_without_handler_is_ignored():
    """train.sigterm with no checkpoint_dir (no handler installed) must
    NOT kill the process — a real SIGTERM would, which tests nothing."""
    lm, corpus, fresh, kw = _lm()
    faults.configure("train.sigterm:@2:0")
    _, losses = lm.train(fresh(), corpus, **{**kw, "steps": 5})
    assert len(losses) == 5  # ran to completion, process alive


def test_hostile_env_mnist_style_fit_completes(rng, tmp_path):
    """Acceptance scenario, pipeline side: with the full hostile
    KEYSTONE_FAULTS (transient tar error + NaN batch + preemption
    armed), an idx-ingested MNIST-style chained fit completes — ingest
    retries absorb the IO fault and the train-only sites never touch
    the solver path."""
    import jax.numpy as jnp

    from keystone_tpu.core.pipeline import label_estimator, transformer
    from keystone_tpu.loaders.idx import load_labeled_idx
    from keystone_tpu.ops.linear import BlockLeastSquaresEstimator

    imgs = rng.integers(0, 255, (32, 6, 6)).astype(np.uint8)
    labs = rng.integers(0, 3, (32,)).astype(np.uint8)
    _write_idx(tmp_path / "train-images-idx3-ubyte", imgs)
    _write_idx(tmp_path / "train-labels-idx1-ubyte", labs)

    faults.configure(
        "tar.read:@0:0,idx.read:@0:0,train.nan:@7:0,train.preempt:@12:0"
    )
    data = load_labeled_idx(
        str(tmp_path / "train-images-idx3-ubyte"),
        str(tmp_path / "train-labels-idx1-ubyte"),
    )
    y = -np.ones((32, 3), np.float32)
    y[np.arange(32), data.labels] = 1.0
    est = transformer(lambda x: x / 255.0, name="scale").then(
        label_estimator(
            lambda d, l: BlockLeastSquaresEstimator(
                block_size=36, num_iter=2, lam=0.1
            ).fit(d, l)
        )
    )
    pipe = est.fit(jnp.asarray(data.data), jnp.asarray(y))
    out = np.asarray(pipe(jnp.asarray(data.data)))
    assert out.shape == (32, 3) and np.isfinite(out).all()


# ----------------------------------------------------------- multihost


def test_multihost_init_timeout_fails_fast(tmp_path, free_tcp_port):
    """A missing coordinator fails in seconds with the address in the
    message, not an infinite hang (run in a subprocess: a failed
    distributed init must not pollute this process's jax runtime)."""
    import subprocess
    import sys

    port = free_tcp_port
    code = (
        "from keystone_tpu.parallel import multihost\n"
        "try:\n"
        f"    multihost.initialize('127.0.0.1:{port}', 2, 1,"
        " init_timeout_s=2)\n"
        "    print('NO-ERROR')\n"
        "except RuntimeError as e:\n"
        f"    assert '127.0.0.1:{port}' in str(e), str(e)\n"
        "    print('TIMEOUT-OK')\n"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert "TIMEOUT-OK" in proc.stdout, proc.stdout + proc.stderr


def test_preflight_zero_timeout_still_probes_once(free_tcp_port):
    """A live coordinator must never be reported unreachable unprobed,
    even with the timeout set to 0."""
    import socket
    import threading

    from keystone_tpu.parallel.multihost import _preflight_coordinator

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", free_tcp_port))
    srv.listen(1)
    t = threading.Thread(target=lambda: srv.accept(), daemon=True)
    t.start()
    try:
        _preflight_coordinator(f"127.0.0.1:{free_tcp_port}", 0.0, 1)
    finally:
        srv.close()
    # and an unparseable address defers to jax's own validation
    _preflight_coordinator("not-an-address", 0.0, 1)


def test_multihost_env_timeout_override(monkeypatch):
    from keystone_tpu.parallel import multihost

    monkeypatch.setenv(multihost.ENV_INIT_TIMEOUT, "17")
    seen = {}

    def fake_init(**kw):
        seen.update(kw)

    monkeypatch.setattr(
        multihost.jax.distributed, "initialize", fake_init
    )
    multihost.initialize()
    assert seen == {"initialization_timeout": 17}


# ------------------------------------------------------------ no-overhead


def test_hot_paths_do_one_read_when_disabled():
    """With KEYSTONE_FAULTS unset the fault plan is None and fire() is
    a single global read returning False — the acceptance criterion's
    no-per-batch-overhead contract."""
    assert faults.active() is None
    assert faults.fire("train.nan", key=0) is False
    arr = np.ones((2, 2), np.float32)
    assert faults.poison("batch.nan", arr) is arr
    assert guards.output_guard_mode() == ""
