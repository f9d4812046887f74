"""Self-tuning runtime tests: the autotuner controller (zero-sleep,
injected clock), the persisted plan store, the async ingest frontier,
the end-to-end host-bound pin, and the observe diff / --learned CLIs."""

import json
import os

import numpy as np
import pytest

from keystone_tpu.observe import events as observe_events
from keystone_tpu.observe import metrics as observe_metrics
from keystone_tpu.plan import store as plan_store
from keystone_tpu.plan import tune as tune_mod
from keystone_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _fresh_tuner():
    tune_mod.reset()
    yield
    tune_mod.reset()
    faults.configure(None)


def _counter(name: str, **labels) -> float:
    key = observe_metrics._series_key(name, labels)
    return observe_metrics.get_registry().snapshot().get(key, 0)


def make_tuner(knobs=(), clock=None, **cfg):
    defaults = dict(
        window_s=1.0,
        cooldown_s=5.0,
        revert_tolerance=0.05,
        min_share=0.2,
    )
    defaults.update(cfg)
    t = tune_mod.Autotuner(
        tune_mod.TuneConfig(**defaults),
        clock=clock or (lambda: 0.0),
    )
    for k in knobs:
        t.register(k)
    return t


def window(tuner, t, buckets=None, rows=10):
    """Feed one window's observations and advance the injected clock
    past the window boundary — zero sleeps."""
    tuner.observe(rows=rows, buckets=buckets or {})
    t[0] += 1.0
    tuner.tick()


# ---------------------------------------------------------------------------
# controller units


def test_knob_steps_and_bounds():
    k = tune_mod.value_knob("w", 4, lo=1, hi=8, scale=2)
    assert k.next_value(+1) == 8
    k.set(8)
    assert k.next_value(+1) is None  # at the ceiling
    assert k.next_value(-1) == 4
    k.set(1)
    assert k.next_value(-1) is None
    add = tune_mod.value_knob("d", 2, lo=1, hi=4, scale=None, step=1)
    assert add.next_value(+1) == 3 and add.next_value(-1) == 1


def test_wait_host_adjusts_ingest_workers_then_staging():
    t = [0.0]
    tuner = make_tuner(
        [
            tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2),
            tune_mod.value_knob(
                "stage_depth", 2, lo=1, hi=8, scale=None, step=1
            ),
        ],
        clock=lambda: t[0],
    )
    before = _counter("tune_adjusts", knob="ingest_workers")
    window(tuner, t, {"wait_host": 0.8})
    assert tuner.value("ingest_workers") == 4  # the first candidate moved
    assert tuner.value("stage_depth") == 2
    assert tuner.history[-1]["action"] == "adjust"
    assert tuner.history[-1]["stall"] == "wait_host"
    assert _counter("tune_adjusts", knob="ingest_workers") == before + 1
    # with ingest_workers cooling down, the SECOND candidate (staging
    # depth) takes the next wait_host window
    window(tuner, t, {"wait_host": 0.8}, rows=20)  # commit the first
    window(tuner, t, {"wait_host": 0.8}, rows=20)
    assert tuner.value("stage_depth") == 3


def test_wait_device_shrinks_chunk_rows():
    t = [0.0]
    tuner = make_tuner(clock=lambda: t[0])
    tuner.bind_chunk(4096)
    window(tuner, t, {"wait_device": 0.7})
    assert tuner.value("chunk_rows") == 2048
    assert tuner.history[-1]["stall"] == "wait_device"


def test_queue_widens_serve_bucket():
    t = [0.0]
    tuner = make_tuner(
        [tune_mod.value_knob("serve_bucket", 8, lo=1, hi=64, scale=2)],
        clock=lambda: t[0],
    )
    window(tuner, t, {"queue": 0.5, "wait_host": 0.1})
    assert tuner.value("serve_bucket") == 16
    assert tuner.history[-1]["stall"] == "queue"


def test_hold_when_no_dominant_stall():
    t = [0.0]
    tuner = make_tuner(
        [tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2)],
        clock=lambda: t[0],
    )
    window(tuner, t, {"wait_host": 0.05, "compute": 0.9})
    assert tuner.value("ingest_workers") == 2
    assert tuner.history[-1]["action"] == "hold"
    assert tuner.history[-1]["reason"] == "no_dominant_stall"


def test_idle_window_judges_nothing():
    t = [0.0]
    tuner = make_tuner(
        [tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2)],
        clock=lambda: t[0],
    )
    window(tuner, t, {"wait_host": 0.8})  # adjust -> pending
    assert tuner.value("ingest_workers") == 4
    window(tuner, t, rows=0)  # idle: no verdict, no revert
    assert tuner.value("ingest_workers") == 4
    assert len(tuner.history) == 1  # the idle window left no summary
    window(tuner, t, rows=20)  # real data -> commit
    assert tuner.history[-1]["action"] == "commit"


def test_regression_reverts_the_knob():
    t = [0.0]
    tuner = make_tuner(
        [tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2)],
        clock=lambda: t[0],
    )
    before = _counter("tune_reverts", knob="ingest_workers")
    window(tuner, t, {"wait_host": 0.8}, rows=10)  # adjust 2 -> 4
    window(tuner, t, {"wait_host": 0.8}, rows=5)  # goodput halved
    assert tuner.value("ingest_workers") == 2  # walked back
    assert tuner.history[-1]["action"] == "revert"
    assert _counter("tune_reverts", knob="ingest_workers") == before + 1


def test_improvement_commits():
    t = [0.0]
    tuner = make_tuner(
        [tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2)],
        clock=lambda: t[0],
    )
    window(tuner, t, {"wait_host": 0.8}, rows=10)
    window(tuner, t, {"wait_host": 0.2}, rows=30)
    assert tuner.value("ingest_workers") == 4
    assert tuner.history[-1]["action"] == "commit"


def test_cooldown_blocks_immediate_readjust():
    t = [0.0]
    tuner = make_tuner(
        [tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2)],
        clock=lambda: t[0],
        cooldown_s=2.5,
    )
    window(tuner, t, {"wait_host": 0.8}, rows=10)  # adjust at t=1 (cool→3.5)
    window(tuner, t, {"wait_host": 0.8}, rows=20)  # commit at t=2
    window(tuner, t, {"wait_host": 0.8}, rows=20)  # t=3 < 3.5: cooling
    assert tuner.value("ingest_workers") == 4
    assert tuner.history[-1]["action"] == "hold"
    assert tuner.history[-1]["reason"] == "cooldown_or_bounds"
    window(tuner, t, {"wait_host": 0.8}, rows=20)  # t=4 >= 3.5
    assert tuner.value("ingest_workers") == 8  # cooldown elapsed


def test_chunk_knob_scoped_to_its_pipeline_fingerprint():
    """Pipeline B must not inherit a chunk tuned for pipeline A's
    working set: the knob answers only for the fingerprint that bound
    it, and a different pipeline re-seeds it from its own plan."""
    tuner = make_tuner()
    tuner.bind_chunk(1024, fingerprint="fp-a")
    assert tuner.chunk_value_for("fp-a") == 1024
    assert tuner.chunk_value_for("fp-b") is None
    tuner.bind_chunk(256, fingerprint="fp-b")  # B re-seeds, not inherits
    assert tuner.chunk_value_for("fp-b") == 256
    assert tuner.chunk_value_for("fp-a") is None


def test_revert_backoff_blocks_immediate_reapply():
    """A knob whose adjustment regressed must not be re-tried at the
    very next cooldown expiry — the revert doubles the knob's cooldown
    so the climb can't oscillate adjust/revert forever."""
    t = [0.0]
    tuner = make_tuner(
        [tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2)],
        clock=lambda: t[0],
        cooldown_s=1.0,
    )
    window(tuner, t, {"wait_host": 0.8}, rows=10)  # adjust at t=1
    window(tuner, t, {"wait_host": 0.8}, rows=2)  # revert at t=2 (→4.0)
    assert tuner.history[-1]["action"] == "revert"
    window(tuner, t, {"wait_host": 0.8}, rows=10)  # t=3 < 4: backed off
    assert tuner.history[-1]["action"] == "hold"
    assert tuner.value("ingest_workers") == 2
    window(tuner, t, {"wait_host": 0.8}, rows=10)  # t=4: retry allowed
    assert tuner.history[-1]["action"] == "adjust"


def test_bad_knob_drill_forced_then_walked_back():
    """tune.bad_knob forces a knob to its worst bound at the keyed
    evaluation; the revert guard must walk it back on the regressed
    window — the deterministic drill."""
    faults.configure("tune.bad_knob:@0:0")
    t = [0.0]
    tuner = make_tuner(
        [tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2)],
        clock=lambda: t[0],
    )
    before = _counter("faults_fired", site="tune.bad_knob")
    window(tuner, t, {"compute": 0.9}, rows=10)  # eval 0: drill fires
    assert tuner.value("ingest_workers") == 16  # forced to the bound
    assert tuner.history[-1].get("injected") is True
    assert _counter("faults_fired", site="tune.bad_knob") == before + 1
    window(tuner, t, {"compute": 0.9}, rows=2)  # regressed -> revert
    assert tuner.value("ingest_workers") == 2
    assert tuner.history[-1]["action"] == "revert"


def test_every_decision_is_a_declared_tune_event():
    from keystone_tpu.observe import schema

    assert "tune" in schema.declared()
    t = [0.0]
    with observe_events.run() as log:
        tuner = make_tuner(
            [tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2)],
            clock=lambda: t[0],
        )
        window(tuner, t, {"wait_host": 0.8}, rows=10)
        window(tuner, t, {"wait_host": 0.8}, rows=5)
        events = [r for r in log.records if r.get("event") == "tune"]
    assert [e["action"] for e in events] == ["adjust", "revert"]
    # every event carries the full knob snapshot for the dashboard
    assert all("ingest_workers" in e["knobs"] for e in events)


def test_knob_gauges_reach_prometheus_exposition():
    tuner = make_tuner(
        [tune_mod.value_knob("ingest_workers", 3, lo=1, hi=16, scale=2)]
    )
    tuner.bind_chunk(1024)
    text = observe_metrics.get_registry().to_prometheus()
    assert "tune_ingest_workers 3" in text
    assert "tune_chunk_rows 1024" in text


def test_bad_knob_site_registered():
    assert "tune.bad_knob" in faults.SITES
    faults.parse_spec("tune.bad_knob:@3:0")  # grammar accepts it


# ---------------------------------------------------------------------------
# plan store


def test_store_round_trip(tmp_path):
    fp = plan_store.fingerprint(["00:Scale", "01:center"])
    path = plan_store.save(
        fp,
        {"knobs": {"ingest_workers": 4, "stage_depth": 3},
         "plan": {"chunk_size": 2048}},
        device_kind="cpu",
        base=str(tmp_path),
    )
    assert path and os.path.isfile(path)
    rec = plan_store.load(fp, device_kind="cpu", base=str(tmp_path))
    assert rec["knobs"] == {"ingest_workers": 4, "stage_depth": 3}
    assert rec["plan"]["chunk_size"] == 2048
    assert rec["fingerprint"] == fp
    # different device kind: its own record slot
    assert plan_store.load(fp, device_kind="v5 lite", base=str(tmp_path)) is None


def test_store_fingerprint_mismatch_refused(tmp_path):
    fp = plan_store.fingerprint(["00:A"])
    path = plan_store.save(fp, {"knobs": {}}, device_kind="cpu", base=str(tmp_path))
    payload = json.loads(open(path).read())
    payload["fingerprint"] = "0" * 16
    open(path, "w").write(json.dumps(payload))
    before = _counter("plan_store_mismatch")
    with pytest.raises(plan_store.PlanStoreError):
        plan_store.load(fp, device_kind="cpu", base=str(tmp_path))
    assert _counter("plan_store_mismatch") == before + 1
    assert isinstance(plan_store.PlanStoreError("x"), ValueError)


def test_store_corrupt_record_degrades(tmp_path):
    fp = plan_store.fingerprint(["00:A"])
    path = plan_store.save(fp, {"knobs": {}}, device_kind="cpu", base=str(tmp_path))
    open(path, "w").write("{not json")
    assert plan_store.load(fp, device_kind="cpu", base=str(tmp_path)) is None


def test_store_disabled_is_a_noop(monkeypatch):
    monkeypatch.delenv(plan_store.ENV_STORE, raising=False)
    assert plan_store.store_dir() is None
    assert plan_store.save("ab", {}) is None
    assert plan_store.load("ab") is None


def test_tuner_commit_persists_and_second_run_starts_from_it(tmp_path):
    """The learned-plan round trip: a commit saves (knobs + plan) under
    the bound fingerprint; a FRESH tuner binding the same identity
    starts from the stored knob values."""
    base = str(tmp_path)
    fp = plan_store.fingerprint(["00:Scale"])
    t = [0.0]
    tuner = make_tuner(
        [tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2)],
        clock=lambda: t[0],
    )
    tuner._store = (fp, "cpu", {"chunk_size": 512, "stage_depth": 2})
    tuner._store_loaded = True  # binding without a load (fresh store)
    window(tuner, t, {"wait_host": 0.8}, rows=10)  # adjust 2 -> 4
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(plan_store.ENV_STORE, base)
        window(tuner, t, {"wait_host": 0.4}, rows=20)  # commit -> save
    rec = plan_store.load(fp, device_kind="cpu", base=base)
    assert rec["knobs"]["ingest_workers"] == 4
    assert rec["plan"]["chunk_size"] == 512
    assert rec["provenance"]["goodput"] == 20.0

    fresh = make_tuner(
        [tune_mod.value_knob("ingest_workers", 2, lo=1, hi=16, scale=2)]
    )
    fresh.bind_store(fp, "cpu", {"chunk_size": 512}, base=base)
    assert fresh.value("ingest_workers") == 4  # started where we left off


def test_plan_pipeline_seeds_from_store(tmp_path, monkeypatch):
    """plan_pipeline consults KEYSTONE_PLAN_STORE: the stored chunk size
    and stage depth seed the new plan with source=store decisions."""
    import jax.numpy as jnp

    from keystone_tpu import plan as plan_mod
    from keystone_tpu.core.pipeline import transformer
    from keystone_tpu.plan.ir import chain_from

    pipe = transformer(lambda b: b * 2.0, name="dbl") >> transformer(
        lambda b: b + 1.0, name="inc"
    )
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 8)).astype(np.float32))
    fp = plan_store.fingerprint([pn.label for pn in chain_from(pipe)])
    plan_store.save(
        fp,
        {"knobs": {"stage_depth": 4}, "plan": {"chunk_size": 32}},
        device_kind=plan_mod._device_kind(),
        base=str(tmp_path),
    )
    monkeypatch.setenv(plan_store.ENV_STORE, str(tmp_path))
    monkeypatch.delenv("KEYSTONE_STAGE_DEPTH", raising=False)
    plan = plan_mod.plan_pipeline(pipe, sample=x, n_rows=64)
    assert plan.chunk_size == 32
    assert plan.stage_depth == 4
    by_action = {d["action"]: d for d in plan.decisions}
    assert by_action["chunk"]["source"] == "store"
    assert by_action["stage"]["source"] == "store"
    assert by_action["learned"]["fingerprint"] == fp
    # planned execution with the stored knobs stays bit-exact
    out = plan.execute(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2.0 + 1.0)


# ---------------------------------------------------------------------------
# ingest frontier


def test_ingest_frontier_bit_exact_vs_serial():
    from keystone_tpu.loaders.streaming import ingest_frontier

    items = list(range(200))
    fn = lambda i: i * 3 + 1  # noqa: E731
    for workers in (1, 2, 7):
        assert list(ingest_frontier(items, fn, workers=workers)) == [
            fn(i) for i in items
        ]
    assert list(ingest_frontier([], fn, workers=4)) == []


def test_ingest_frontier_exception_reraises_in_order():
    from keystone_tpu.loaders.streaming import ingest_frontier

    def boom(i):
        if i == 5:
            raise ValueError("decode died")
        return i

    got = []
    with pytest.raises(ValueError, match="decode died"):
        for v in ingest_frontier(range(10), boom, workers=4):
            got.append(v)
    assert got == [0, 1, 2, 3, 4]  # everything before the failure, in order


def test_ingest_frontier_polls_live_worker_count():
    from keystone_tpu.loaders.streaming import ingest_frontier

    calls = []

    def workers():
        calls.append(1)
        return 2

    assert list(ingest_frontier(range(8), lambda i: i, workers=workers)) == list(
        range(8)
    )
    assert len(calls) >= 8  # polled at every refill, not once


def test_tar_batches_unchanged_through_frontier(tmp_path):
    """The tar iterator's batch grouping survived the frontier rewrite:
    boundaries every batch_size entries, same contents, same order."""
    import io
    import tarfile

    from PIL import Image

    from keystone_tpu.loaders.streaming import iter_tar_image_batches

    p = tmp_path / "imgs.tar"
    rng = np.random.default_rng(0)
    with tarfile.open(p, "w") as tf:
        for i in range(7):
            img = Image.fromarray(
                rng.integers(0, 255, size=(8, 8, 3), dtype=np.uint8)
            )
            buf = io.BytesIO()
            img.save(buf, format="PNG")
            info = tarfile.TarInfo(f"n{i:02d}_img.png")
            info.size = buf.tell()
            buf.seek(0)
            tf.addfile(info, buf)
    batches = list(
        iter_tar_image_batches([str(p)], batch_size=3, target_size=8)
    )
    assert [len(b[0]) for b in batches] == [3, 3, 1]
    assert [n for b in batches for n in b[0]] == [
        f"n{i:02d}_img.png" for i in range(7)
    ]


def test_host_bound_stream_drops_wait_host_share_under_tune(monkeypatch):
    """The end-to-end pin: a synthetic host-bound stream under
    KEYSTONE_TUNE=1 — the autotuner raises ingest workers, the measured
    wait_host share drops, and tuned throughput beats the static serial
    path."""
    import time

    from keystone_tpu.loaders.streaming import ingest_frontier

    monkeypatch.setenv("KEYSTONE_TUNE", "1")
    monkeypatch.setenv("KEYSTONE_TUNE_WINDOW_S", "0.03")
    monkeypatch.setenv("KEYSTONE_TUNE_COOLDOWN_S", "0.03")
    monkeypatch.setenv("KEYSTONE_INGEST_WORKERS", "1")
    monkeypatch.setenv("KEYSTONE_STAGE_DEPTH", "2")

    decode_s, compute_s, n = 0.004, 0.0005, 60

    def decode(i):
        time.sleep(decode_s)
        return i

    def drive(workers):
        t0 = time.perf_counter()
        for _ in ingest_frontier(
            range(n), decode, workers=workers, span_name=None
        ):
            time.sleep(compute_s)
        return time.perf_counter() - t0

    # static: tuning disabled so the serial baseline is untouched
    tune_mod.configure(None)
    static_wall = drive(workers=1)
    tune_mod.reset()  # re-arm env activation for the tuned pass

    tuned_wall = drive(workers=None)  # follows the live knob
    tuner = tune_mod.active()
    assert tuner is not None  # env-activated, starting from 1 worker
    tuner.tick(force=True)  # close out the final partial window

    assert tuner.value("ingest_workers") > 1  # the controller scaled up
    waits = [
        h["shares"].get("wait_host", 0.0)
        for h in tuner.history
        if h.get("shares")
    ]
    assert len(waits) >= 2
    assert waits[-1] < waits[0]  # wait_host share dropped
    assert tuned_wall < static_wall  # tuned throughput >= static


# ---------------------------------------------------------------------------
# rendering: observe top / report / diff


def _tune_event(action, knobs, **fields):
    return {
        "event": "tune",
        "ts": 1.0,
        "action": action,
        "knobs": knobs,
        **fields,
    }


def test_top_renders_autotuner_panel():
    from keystone_tpu.observe import top as observe_top

    state = observe_top.summarize(
        [],
        [
            _tune_event("adjust", {"ingest_workers": 2}, knob="ingest_workers",
                        to=2, stall="wait_host"),
            _tune_event("commit", {"ingest_workers": 2, "stage_depth": 3},
                        knob="ingest_workers", value=2),
            _tune_event("hold", {"ingest_workers": 2, "stage_depth": 3},
                        reason="no_dominant_stall"),
        ],
    )
    assert state["tune"]["decisions"] == 3
    assert state["tune"]["knobs"] == {"ingest_workers": 2, "stage_depth": 3}
    assert state["tune"]["last"]["action"] == "commit"
    screen = observe_top.render(state, "/tmp/run")
    assert "autotuner:" in screen
    assert "stage_depth=3" in screen and "ingest_workers=2" in screen
    assert "last: commit" in screen


def test_report_autotuner_section(tmp_path):
    from keystone_tpu.observe import report

    with observe_events.run(str(tmp_path)) as log:
        run_dir = log.run_dir
        log.emit("tune", action="adjust", knob="ingest_workers",
                 knobs={"ingest_workers": 4}, stall="wait_host")
        log.emit("tune", action="commit", knob="ingest_workers",
                 knobs={"ingest_workers": 4}, value=4)
    text = report.render(run_dir)
    assert "autotuner (self-tuning decisions)" in text
    assert "adjust=1" in text and "commit=1" in text
    assert "ingest_workers=4" in text


def _write_run(base, name, *, wait_host, steps_ms, tune_events=0):
    run_dir = os.path.join(base, name)
    os.makedirs(run_dir)
    with open(os.path.join(run_dir, "events.jsonl"), "w") as f:
        f.write(json.dumps({"event": "run_start", "ts": 1.0, "run": name}) + "\n")
        for i in range(tune_events):
            f.write(
                json.dumps(
                    {"event": "tune", "ts": 2.0 + i, "action": "adjust",
                     "knob": "ingest_workers"}
                )
                + "\n"
            )
        f.write(
            json.dumps(
                {"event": "run_end", "ts": 9.0, "wall_s": 8.0, "status": "ok"}
            )
            + "\n"
        )
    with open(os.path.join(run_dir, "steps.jsonl"), "w") as f:
        for i, ms in enumerate(steps_ms):
            f.write(
                json.dumps(
                    {"ts": 2.0 + i, "source": "train", "step": i + 1,
                     "wall_s": ms / 1e3, "tokens": 100,
                     "tokens_per_s": 100 / (ms / 1e3)}
                )
                + "\n"
            )
    with open(os.path.join(run_dir, "spans.jsonl"), "w") as f:
        f.write(
            json.dumps(
                {"ts": 2.0, "trace": "t1", "span": "s1",
                 "name": "ingest.wait_host", "wall_s": wait_host,
                 "bucket": "wait_host"}
            )
            + "\n"
        )
        f.write(
            json.dumps(
                {"ts": 2.1, "trace": "t1", "span": "s2",
                 "name": "train.compute", "wall_s": 1.0, "bucket": "compute"}
            )
            + "\n"
        )
    return run_dir


def test_observe_diff_renders_shares_steps_and_counters(tmp_path, capsys):
    from keystone_tpu.observe import report

    a = _write_run(str(tmp_path), "static", wait_host=3.0,
                   steps_ms=[20, 22, 21], tune_events=0)
    b = _write_run(str(tmp_path), "tuned", wait_host=0.5,
                   steps_ms=[12, 11, 13], tune_events=4)
    report.main(["diff", a, b])
    out = capsys.readouterr().out
    assert "goodput shares" in out
    assert "wait_host" in out and "pp" in out  # the share delta column
    assert "wall p50" in out
    assert "tune.adjust" in out and "(+4)" in out


def test_observe_diff_usage(capsys):
    from keystone_tpu.observe import report

    with pytest.raises(SystemExit):
        report.main(["diff", "only-one-dir"])


# ---------------------------------------------------------------------------
# plan CLI --learned


def test_plan_cli_learned_round_trip(tmp_path, monkeypatch, capsys):
    from keystone_tpu import plan as plan_mod
    from keystone_tpu.plan import cli as plan_cli
    from keystone_tpu.plan.ir import chain_from

    pipe, _ = plan_cli.BUILDERS["cifar-random-patch"]()
    fp = plan_store.fingerprint([pn.label for pn in chain_from(pipe)])
    plan_store.save(
        fp,
        {
            "knobs": {"ingest_workers": 8, "stage_depth": 3},
            "plan": {"chunk_size": 1024},
            "provenance": {"run": "r-123", "goodput": 1234.5, "evals": 7},
        },
        device_kind=plan_mod._device_kind(),
        base=str(tmp_path),
    )
    monkeypatch.setenv(plan_store.ENV_STORE, str(tmp_path))
    plan_cli.main(["cifar-random-patch", "--learned"])
    out = capsys.readouterr().out
    assert fp in out
    assert "ingest_workers=8" in out
    assert "chunk_size=1024" in out
    assert "run=r-123" in out


def test_plan_cli_learned_requires_store(monkeypatch):
    from keystone_tpu.plan import cli as plan_cli

    monkeypatch.delenv(plan_store.ENV_STORE, raising=False)
    with pytest.raises(SystemExit, match="KEYSTONE_PLAN_STORE"):
        plan_cli.main(["cifar-random-patch", "--learned"])
