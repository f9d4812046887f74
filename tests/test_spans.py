"""PR-9 observability tests: end-to-end span tracing (propagation
across the micro-batcher worker thread, the staging thread, and the
decode loop), the request critical-path acceptance, goodput
summaries, the rolling-baseline anomaly monitor and its deterministic
fault drills, size-based stream rotation, the event-schema drift
check, Prometheus exposition, and the ``observe trace`` CLI."""

import json
import math
import os
import pathlib
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from keystone_tpu.observe import events, health, metrics
from keystone_tpu.observe import spans as spans_mod
from keystone_tpu.resilience import faults
from keystone_tpu.serve.queue import MicroBatcher


class Clock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


class FakeExported:
    """A serve dispatch stub shaped like ExportedApply: buckets attr +
    row-indexed __call__ (optionally with a deliberate device wall)."""

    buckets = (8,)

    def __init__(self, wall_s: float = 0.0, buckets=(8,)):
        self.wall_s = wall_s
        self.buckets = tuple(buckets)

    def __call__(self, batch):
        if self.wall_s:
            time.sleep(self.wall_s)
        return np.asarray(batch) * 2.0


def _rows(n: int, d: int = 3) -> np.ndarray:
    return np.ones((n, d), np.float32)


# ---------------------------------------------------------------------------
# span primitives


def test_span_nesting_trace_and_parent_ids(tmp_path):
    with events.run(str(tmp_path)) as log:
        with spans_mod.span("outer", kind="unit") as octx:
            assert spans_mod.current() == octx
            with spans_mod.span("inner", bucket="compute") as ictx:
                assert ictx.trace == octx.trace
            assert spans_mod.current() == octx
        assert spans_mod.current() is None
        run_dir = log.run_dir
        sl = spans_mod.active_span_log()
    recs = spans_mod.read_spans(run_dir)
    by_name = {r["name"]: r for r in recs}
    assert by_name["inner"]["parent"] == octx.span
    assert by_name["inner"]["trace"] == octx.trace == by_name["outer"]["trace"]
    assert by_name["inner"]["bucket"] == "compute"
    assert "bucket" not in by_name["outer"]  # structural
    assert by_name["outer"]["wall_s"] >= by_name["inner"]["wall_s"] >= 0
    # the run's sink closes with the event log
    assert sl is not None and sl._sink is None


def test_span_records_failed_status(tmp_path):
    with events.run(str(tmp_path)) as log:
        with pytest.raises(ValueError):
            with spans_mod.span("doomed"):
                raise ValueError("boom")
        run_dir = log.run_dir
    recs = spans_mod.read_spans(run_dir)
    assert recs[0]["name"] == "doomed" and recs[0]["status"] == "failed"


def test_request_hot_path_exactly_one_global_read_no_sink(monkeypatch):
    """Acceptance: with no sink active the request hot path pays exactly
    ONE global read — the request span gate. Submission costs zero, and
    the batch dispatch adds a constant two reads per BATCH (step + span
    log lookups), never per request."""
    assert events.active() is None  # suite invariant
    health.reset_monitor()
    reads: list[int] = []
    monkeypatch.setattr(events, "active", lambda: reads.append(1) or None)

    def boom(self, *a, **k):
        raise AssertionError("span/step log built with no sink active")

    monkeypatch.setattr(spans_mod.SpanLog, "__init__", boom)

    clock = Clock()
    mb = MicroBatcher(
        FakeExported(), buckets=(8,), deadline_ms=10.0, clock=clock,
        start=False,
    )
    futs = []
    for rid in range(4):
        # what ServeApp.predict does per request: one span gate + submit
        with spans_mod.span("serve.request", rid=rid):
            futs.append(mb.submit(_rows(1), rid=rid))
    assert len(reads) == 4  # exactly one global read per request
    clock.t = 1.0
    assert mb.pump(now=1.0) == 1
    assert len(reads) == 4 + 2  # two more per BATCH, not per request
    assert all(f.done() for f in futs)


# ---------------------------------------------------------------------------
# propagation across thread boundaries


def test_batcher_spans_cross_worker_thread_scheduler_form(tmp_path):
    """Deterministic (injected clock, no threads): each request's spans
    land in ITS trace even though _run_batch runs outside the request
    context, and the dispatch spans link to one shared batch trace."""
    clock = Clock()
    with events.run(str(tmp_path)) as log:
        mb = MicroBatcher(
            FakeExported(), buckets=(8,), deadline_ms=10.0, clock=clock,
            start=False,
        )
        ctxs = []
        for rid in range(2):
            with spans_mod.span("serve.request", rid=rid) as ctx:
                mb.submit(_rows(2), rid=rid)
                ctxs.append(ctx)
        clock.t = 0.010
        assert mb.pump(now=0.010) == 1
        run_dir = log.run_dir
    recs = spans_mod.read_spans(run_dir)
    for rid, ctx in enumerate(ctxs):
        mine = [r for r in recs if r.get("trace") == ctx.trace]
        names = {r["name"] for r in mine}
        assert {"serve.request", "serve.queue_wait", "serve.dispatch",
                "serve.device_compute"} <= names
        qw = next(r for r in mine if r["name"] == "serve.queue_wait")
        assert qw["parent"] == ctx.span and qw["bucket"] == "queue"
        disp = next(r for r in mine if r["name"] == "serve.dispatch")
        assert disp["parent"] == ctx.span and disp["requests"] == 2
    # both dispatches link to the SAME batch-level trace, which holds
    # the serve.batch span the model actually ran under
    batch_traces = {
        r["batch_trace"] for r in recs if r["name"] == "serve.dispatch"
    }
    assert len(batch_traces) == 1
    batch = [r for r in recs if r.get("trace") in batch_traces]
    assert any(r["name"] == "serve.batch" for r in batch)
    # the classified device wall is counted ONCE per batch (the
    # serve.compute span) — the per-request device_compute copies are
    # structural, so a full bucket can't inflate the goodput shares
    # batch-fill times over
    compute = [r for r in recs if r.get("bucket") == "compute"]
    assert len(compute) == 1 and compute[0]["name"] == "serve.compute"
    assert all(
        "bucket" not in r
        for r in recs
        if r["name"] == "serve.device_compute"
    )


def test_batcher_slice_failure_fans_out_not_thread_death():
    """A failure AFTER dispatch (while materializing per-request
    slices) must fail the batch's futures like a dispatch failure —
    never escape and kill the batching thread."""
    clock = Clock()
    mb = MicroBatcher(
        lambda batch: 1.0,  # scalar result: per-request slicing raises
        buckets=(8,), deadline_ms=10.0, clock=clock, start=False,
    )
    f1 = mb.submit(_rows(2))
    f2 = mb.submit(_rows(1))
    clock.t = 0.010
    assert mb.pump(now=0.010) == 1  # does not raise
    for f in (f1, f2):
        with pytest.raises(TypeError):
            f.result(0)


def test_staging_spans_cross_staging_thread(tmp_path):
    from keystone_tpu.core.staging import run_staged

    chunks = [(np.full((4, 2), i, np.float32), 4) for i in range(4)]
    with events.run(str(tmp_path)) as log:
        with spans_mod.span("plan.segment") as octx:
            outs = list(
                run_staged(iter(chunks), lambda x: x * 2, stage_depth=2)
            )
        run_dir = log.run_dir
    assert len(outs) == 4
    recs = spans_mod.read_spans(run_dir)
    h2d = [r for r in recs if r["name"] == "staging.h2d"]
    waits = [r for r in recs if r["name"] == "staging.wait_device"]
    assert len(h2d) == 4 and len(waits) == 4
    # the worker thread's placements parent on the consumer's ambient
    # span, captured at stream creation
    assert all(
        r["trace"] == octx.trace and r["parent"] == octx.span
        and r["bucket"] == "wait_host" and r["bytes"] > 0
        for r in h2d
    )
    assert all(
        r["trace"] == octx.trace and r["bucket"] == "wait_device"
        for r in waits
    )


def test_plan_executor_segment_spans_nest_staging(tmp_path):
    import jax.numpy as jnp

    from keystone_tpu.core.pipeline import FnTransformer, Pipeline
    from keystone_tpu.plan.executor import run_plan
    from keystone_tpu.plan.ir import Plan, chain_from

    pipe = Pipeline.of(FnTransformer(fn=lambda x: x * 2.0))
    x = np.ones((32, 4), np.float32)
    expect = np.asarray(pipe(jnp.asarray(x)))
    with events.run(str(tmp_path)) as log:
        got = np.asarray(
            run_plan(Plan(prefix=chain_from(pipe), chunk_size=8), x)
        )
        run_dir = log.run_dir
    assert np.array_equal(got, expect)
    recs = spans_mod.read_spans(run_dir)
    seg = [r for r in recs if r["name"] == "plan.segment"]
    assert seg and seg[0]["chunked"] is True and "bucket" not in seg[0]
    children = [r for r in recs if r.get("parent") == seg[0]["span"]]
    names = {r["name"] for r in children}
    assert {"staging.h2d", "staging.wait_device"} <= names


def test_decode_loop_slot_spans(tmp_path):
    import jax

    from keystone_tpu.models.lm.model import TransformerLM
    from keystone_tpu.serve.decode_loop import DecodeLoop

    model = TransformerLM.create(
        jax.random.key(0), vocab=32, max_seq=32, dim=32, depth=1,
        num_heads=2,
    )
    with events.run(str(tmp_path)) as log:
        loop = DecodeLoop(
            model, slots=2, s_max=32, max_new=4, prefill_buckets=(8,)
        )
        with spans_mod.span("serve.request", rid=7) as rctx:
            fut = loop.submit([1, 2, 3], rid=7)
        while not fut.done():
            loop.step()
        out = fut.result(timeout=0)
        run_dir = log.run_dir
    assert out.shape[0] == 4
    recs = spans_mod.read_spans(run_dir)
    gen = next(r for r in recs if r["name"] == "serve.generate")
    pre = next(r for r in recs if r["name"] == "decode.prefill")
    # request → generation → prefill, across the decode schedule
    assert gen["trace"] == rctx.trace and gen["parent"] == rctx.span
    assert pre["trace"] == rctx.trace and pre["parent"] == gen["span"]
    assert gen["tokens"] == 4 and gen["rid"] == 7


# ---------------------------------------------------------------------------
# the /predict acceptance: span tree vs measured wall


def test_predict_span_tree_critical_path_within_10pct(tmp_path):
    """Acceptance: a served /predict request's span tree covers
    queue-wait, dispatch, and device-compute, and its critical-path sum
    is within 10% of the measured request wall."""
    from keystone_tpu.serve.server import ServeApp

    health.reset_monitor()
    with events.run(str(tmp_path)) as log:
        app = ServeApp(
            exported=FakeExported(wall_s=0.02), deadline_ms=150.0
        )
        t0 = time.perf_counter()
        out = app.predict(_rows(2))
        wall = time.perf_counter() - t0
        app.shutdown()
        run_dir = log.run_dir
    assert out.shape == (2, 3)
    recs = spans_mod.read_spans(run_dir)
    trees = spans_mod.build_trees(recs)
    req = None
    for roots in trees.values():
        for r in roots:
            if r["rec"]["name"] == "serve.request":
                req = roots
    assert req is not None
    names = {n["rec"]["name"] for n in spans_mod._walk(req)}
    assert {"serve.request", "serve.queue_wait", "serve.dispatch",
            "serve.device_compute"} <= names
    cp = spans_mod.trace_critical_path(req)
    assert wall > 0 and abs(cp - wall) / wall < 0.10, (cp, wall)


# ---------------------------------------------------------------------------
# goodput


def test_goodput_summary_buckets_and_critical_path():
    sl = spans_mod.SpanLog()  # memory-only
    root = sl.record_span("train.step", wall_s=1.0, step=1)
    sl.record_span(
        "train.host_batch", wall_s=0.25, bucket="wait_host", parent=root
    )
    sl.record_span(
        "train.compute", wall_s=0.75, bucket="compute", parent=root
    )
    g = spans_mod.goodput_summary(list(sl.records))
    assert g["total_s"] == pytest.approx(1.0)
    assert g["buckets"]["compute"]["share"] == pytest.approx(0.75)
    assert g["buckets"]["wait_host"]["share"] == pytest.approx(0.25)
    # the structural root is not a bucket, but IS the critical path
    assert g["critical_path_s"] == pytest.approx(1.0)
    assert g["traces"] == 1 and g["spans"] == 3


# ---------------------------------------------------------------------------
# anomaly monitor units (injected clock, zero sleeps)


def _cfg(**kw) -> health.HealthConfig:
    base = dict(
        baseline_steps=4, window=8, step_p95_factor=2.0,
        loss_spike_factor=3.0, loss_warmup=3, hbm_growth_factor=1.5,
        deadline_miss_rate=0.5, shed_rate=0.05, rate_min_requests=10,
        cooldown_steps=0, cooldown_s=30.0, slow_request_s=0.01,
    )
    base.update(kw)
    return health.HealthConfig(**base)


def test_health_nan_and_spike_alerts():
    mon = health.HealthMonitor(_cfg(), emit=False)
    mon.note_step(step=1, loss=float("nan"))
    assert [a["kind"] for a in mon.alerts] == ["train.nan_loss"]
    for i in range(2, 8):
        mon.note_step(step=i, loss=1.0)
    mon.note_step(step=8, loss=10.0)  # > 3x the EMA
    assert [a["kind"] for a in mon.alerts][-1] == "train.loss_spike"


def test_health_step_time_drift_vs_frozen_baseline():
    mon = health.HealthMonitor(_cfg(), emit=False)
    # step 1 (compile) is excluded from the baseline by design
    mon.note_step(step=1, wall_s=9.0)
    for i in range(2, 6):  # steps 2..5 freeze the baseline at ~10 ms
        mon.note_step(step=i, wall_s=0.010)
    assert not mon.alerts
    for i in range(6, 14):  # sustained 5x drift
        mon.note_step(step=i, wall_s=0.050)
    kinds = [a["kind"] for a in mon.alerts]
    assert "train.step_time_drift" in kinds


def test_health_hbm_growth_ratchets():
    mon = health.HealthMonitor(_cfg(), emit=False)
    mon.note_step(step=1, hbm_peak_bytes=100)
    mon.note_step(step=2, hbm_peak_bytes=120)  # < 1.5x: quiet
    assert not mon.alerts
    mon.note_step(step=3, hbm_peak_bytes=200)  # 2x: alert + ratchet
    mon.note_step(step=4, hbm_peak_bytes=250)  # < 1.5x of the NEW base
    mon.note_step(step=5, hbm_peak_bytes=350)  # past the ratchet again
    assert [a["kind"] for a in mon.alerts] == [
        "train.hbm_growth", "train.hbm_growth",
    ]


def test_health_request_side_rates_and_slow_with_cooldown():
    clock = Clock()
    mon = health.HealthMonitor(_cfg(), emit=False, clock=clock)
    mon.note_request(0.02)  # > slow_request_s=0.01
    assert [a["kind"] for a in mon.alerts] == ["serve.slow_request"]
    mon.note_request(0.02)  # cooldown_s suppresses the repeat
    assert len(mon.alerts) == 1
    clock.t = 31.0
    mon.note_request(0.02)
    assert len(mon.alerts) == 2
    # shed rate: 2 sheds in 12 requests > 5%
    for _ in range(8):
        mon.note_request(0.0)
    mon.note_request(0.0, shed=True)
    mon.note_request(0.0, shed=True)
    assert [a["kind"] for a in mon.alerts][-1] == "serve.shed_rate"
    # deadline-miss rate over dispatches
    mon2 = health.HealthMonitor(_cfg(), emit=False, clock=clock)
    mon2.note_dispatch(requests=10, misses=6)
    assert [a["kind"] for a in mon2.alerts] == ["serve.deadline_miss"]


def test_health_rates_slide_not_lifetime():
    """The miss rate is a sliding window: hours of healthy traffic must
    not bury an SLO collapse, and a cold-start burst must age out."""
    clock = Clock()
    mon = health.HealthMonitor(
        _cfg(rate_window=32, cooldown_s=0.0), emit=False, clock=clock
    )
    # long healthy history — lifetime ratio would need thousands of
    # misses to cross 0.5; the window needs at most one window's worth
    for _ in range(20):
        mon.note_dispatch(requests=10, misses=0)
    assert not mon.alerts
    mon.note_dispatch(requests=20, misses=20)  # collapse: 20/32 window
    assert [a["kind"] for a in mon.alerts] == ["serve.deadline_miss"]
    # ...and healthy traffic ages the burst out: once the misses have
    # slid out of the window, the alert stops re-firing
    for _ in range(2):
        mon.note_dispatch(requests=10, misses=0)  # burst still in-window
    mon.alerts.clear()
    for _ in range(10):
        mon.note_dispatch(requests=10, misses=0)
    assert not mon.alerts


def test_failed_request_still_reaches_the_monitor():
    """A request that raises (dispatch error, timeout) must still be
    noted — the slowest requests are exactly the failing ones."""
    from keystone_tpu.serve.server import ServeApp

    class Exploding(FakeExported):
        def __call__(self, batch):
            raise RuntimeError("device on fire")

    health.reset_monitor()
    app = ServeApp(exported=Exploding(), deadline_ms=1.0)
    before = health.get_monitor()._req_total
    with pytest.raises(RuntimeError):
        app.predict(_rows(1))
    assert health.get_monitor()._req_total == before + 1
    app.shutdown()


def test_events_run_resets_health_baselines(tmp_path):
    health.reset_monitor()
    health.get_monitor().note_step(step=2, wall_s=123.0)  # stale baseline
    stale = health.get_monitor()
    with events.run(str(tmp_path)):
        assert health.get_monitor() is not stale  # fresh per run


def test_health_check_run_offline_replay(tmp_path):
    from keystone_tpu.observe import telemetry

    health.reset_monitor()
    with events.run(str(tmp_path)) as log:
        sl = telemetry.active_step_log()
        sl.record("train", step=1, loss=1.0)
        sl.record("train", step=2, loss=float("nan"))
        run_dir = log.run_dir
    alerts = health.check_run(run_dir)
    assert [a["kind"] for a in alerts] == ["train.nan_loss"]


# ---------------------------------------------------------------------------
# deterministic fault drills → alert events → observe top


def test_train_nan_fault_fires_alert_visible_in_top_once(tmp_path, capsys):
    import jax

    from keystone_tpu.models import lm_transformer as lm
    from keystone_tpu.observe import top

    health.reset_monitor()
    faults.configure("train.nan:@2:0")
    try:
        corpus = lm.synthetic_corpus(512, 64, seed=0)
        model = lm.TransformerLM.create(
            jax.random.key(0), vocab=64, max_seq=16, dim=32, depth=1,
            num_heads=2,
        )
        with events.run(str(tmp_path)) as log:
            lm.train(model, corpus, steps=4, batch=4, seq=16, lr=1e-3)
            run_dir = log.run_dir
    finally:
        faults.reset()
    alerts = [
        e for e in events.read_events(run_dir) if e.get("event") == "alert"
    ]
    assert [a["action"] for a in alerts] == ["train.nan_loss"]
    assert alerts[0]["step"] == 3  # the step AFTER the @2-keyed poison
    # step spans recorded alongside
    recs = spans_mod.read_spans(run_dir)
    assert {"train.step", "train.host_batch", "train.compute"} <= {
        r["name"] for r in recs
    }
    top.main([run_dir, "--once"])
    out = capsys.readouterr().out
    assert "ALERTS" in out and "train.nan_loss=1" in out
    # ...and the report renders alert + goodput sections from the same dir
    from keystone_tpu.observe import report

    txt = report.render(run_dir)
    assert "alerts: train.nan_loss=1" in txt
    assert "goodput (where the time went" in txt


def test_serve_slow_request_fault_fires_alert(tmp_path, monkeypatch):
    from keystone_tpu.serve.server import ServeApp

    health.reset_monitor()
    monkeypatch.setenv("KEYSTONE_SERVE_SLOW_MS", "5")
    faults.configure("serve.slow_request:@0:0")
    try:
        with events.run(str(tmp_path)) as log:
            app = ServeApp(exported=FakeExported(), deadline_ms=5.0)
            app.predict(_rows(1))
            app.shutdown()
            run_dir = log.run_dir
    finally:
        faults.reset()
    alerts = [
        e for e in events.read_events(run_dir) if e.get("event") == "alert"
    ]
    assert any(a["action"] == "serve.slow_request" for a in alerts)
    snap = metrics.get_registry().snapshot()
    assert snap.get("alerts{kind=serve.slow_request}", 0) >= 1


# ---------------------------------------------------------------------------
# stream rotation under KEYSTONE_OBSERVE_MAX_MB


def test_steps_and_spans_rotate_under_size_cap(tmp_path, monkeypatch):
    from keystone_tpu.observe import telemetry

    monkeypatch.setenv("KEYSTONE_OBSERVE_MAX_MB", "0.002")  # ~2 KiB
    health.reset_monitor()
    with events.run(str(tmp_path)) as log:
        sl = telemetry.active_step_log()
        spl = spans_mod.active_span_log()
        for i in range(200):
            sl.record("train", step=i, filler="x" * 64)
            spl.record_span("unit", wall_s=0.001, bucket="compute", idx=i)
        run_dir = log.run_dir
    for name in ("steps.jsonl", "spans.jsonl"):
        path = os.path.join(run_dir, name)
        assert os.path.isfile(path) and os.path.isfile(path + ".1")
        # current generation stays under the cap (+1 record of slack)
        assert os.path.getsize(path) <= 2.5 * 1024
        cur = events.read_jsonl(path)
        old = events.read_jsonl(path + ".1")
        assert cur and old  # both generations parse
    # the newest record survived rotation
    last = events.read_jsonl(os.path.join(run_dir, "steps.jsonl"))[-1]
    assert last["step"] == 199
    # read_spans stitches rotated + current in order
    idxs = [r["idx"] for r in spans_mod.read_spans(run_dir)]
    assert idxs[-1] == 199 and idxs == sorted(idxs)


def test_rotation_env_parse():
    assert events.max_bytes_from_env() is None
    os.environ["KEYSTONE_OBSERVE_MAX_MB"] = "1.5"
    try:
        assert events.max_bytes_from_env() == int(1.5 * 2**20)
        os.environ["KEYSTONE_OBSERVE_MAX_MB"] = "garbage"
        assert events.max_bytes_from_env() is None
        os.environ["KEYSTONE_OBSERVE_MAX_MB"] = "-1"
        assert events.max_bytes_from_env() is None
    finally:
        del os.environ["KEYSTONE_OBSERVE_MAX_MB"]


# ---------------------------------------------------------------------------
# event-schema registry: the drift check


def test_event_schema_registry_covers_every_emit_site():
    """Grep every ``.emit("<kind>"`` call and ``event_kind="<kind>"``
    argument in the source tree; any kind not declared in
    observe/schema.py fails — the one-home rule, enforced."""
    from keystone_tpu.observe import schema

    root = pathlib.Path(__file__).resolve().parents[1]
    pat_emit = re.compile(r'\.emit\(\s*"([a-z_]+)"')
    pat_kind = re.compile(r'event_kind\s*[:=]\s*(?:str\s*=\s*)?"([a-z_]+)"')
    found: dict[str, list[str]] = {}
    for path in (root / "keystone_tpu").rglob("*.py"):
        text = path.read_text()
        for pat in (pat_emit, pat_kind):
            for kind in pat.findall(text):
                found.setdefault(kind, []).append(str(path))
    assert found, "no emit sites found — the grep went stale"
    undeclared = {
        k: v for k, v in found.items() if k not in schema.declared()
    }
    assert not undeclared, (
        f"event kinds emitted but not declared in observe/schema.py: "
        f"{undeclared}"
    )
    # the known core kinds really are being picked up by the grep
    assert {"node", "optimize", "serve", "alert"} <= set(found)


def test_schema_note_warns_once_on_unknown_kind(caplog):
    from keystone_tpu.observe import schema

    assert schema.note("run_start") is True
    schema._warned.discard("totally_unknown")
    assert schema.note("totally_unknown") is False
    assert schema.note("totally_unknown") is False  # warn-once


# ---------------------------------------------------------------------------
# Prometheus exposition


def test_metrics_to_prometheus_exposition():
    reg = metrics.MetricsRegistry()
    reg.counter("reqs", route="/predict").inc(2)
    reg.gauge("depth").set(1.5)
    t = reg.timer("lat")
    for v in (0.01, 0.02, 0.03):
        t.observe(v)
    reg.counter("weird", label='a"b\\c\nd').inc()
    reg.describe("depth", "queue depth right now")
    text = reg.to_prometheus()
    # counters expose under the conformant _total suffix; every family
    # carries HELP + TYPE (described or auto-generated)
    assert "# TYPE reqs_total counter" in text
    assert "# HELP reqs_total " in text
    assert 'reqs_total{route="/predict"} 2' in text
    assert "# TYPE depth gauge" in text and "depth 1.5" in text
    assert "# HELP depth queue depth right now" in text
    assert "# TYPE lat summary" in text
    assert "lat_count 3" in text
    assert "lat_sum 0.06" in text
    assert 'lat{quantile="0.5"} 0.02' in text
    assert 'weird_total{label="a\\"b\\\\c\\nd"} 1' in text
    # a name already ending in _total is not doubled
    reg.counter("already_total").inc()
    assert "already_total 1" in reg.to_prometheus()
    assert "already_total_total" not in reg.to_prometheus()
    # every line is exposition-shaped
    for line in text.strip().splitlines():
        assert line.startswith(("# TYPE", "# HELP")) or re.match(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? \S+$", line
        ), line
    # the JSON negotiation path is byte-compatible: snapshot keys stay
    # the bare registry series keys, no _total anywhere
    assert 'reqs{route=/predict}' in reg.snapshot()
    assert not any("_total" in k for k in reg.snapshot() if k != "already_total")


def test_metrics_endpoint_content_negotiation(free_tcp_port):
    from http.server import ThreadingHTTPServer

    from keystone_tpu.serve.server import ServeApp, _handler_for

    health.reset_monitor()
    app = ServeApp(exported=FakeExported(), deadline_ms=5.0)
    httpd = ThreadingHTTPServer(
        ("127.0.0.1", free_tcp_port), _handler_for(app)
    )
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        base = f"http://127.0.0.1:{free_tcp_port}"
        metrics.get_registry().counter("serve_requests").inc(0)
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "# TYPE" in body and "serve_requests" in body
        req = urllib.request.Request(
            f"{base}/metrics", headers={"Accept": "application/json"}
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.headers["Content-Type"] == "application/json"
            payload = json.load(r)
        assert "metrics" in payload and "serve_requests" in payload["metrics"]
    finally:
        httpd.shutdown()
        httpd.server_close()
        app.shutdown()


# ---------------------------------------------------------------------------
# observe trace CLI


def test_observe_trace_cli_smoke(tmp_path, capsys):
    from keystone_tpu.observe import report

    health.reset_monitor()
    clock = Clock()
    with events.run(str(tmp_path)) as log:
        mb = MicroBatcher(
            FakeExported(), buckets=(8,), deadline_ms=10.0, clock=clock,
            start=False,
        )
        with spans_mod.span("serve.request", rid=0):
            mb.submit(_rows(2), rid=0)
        clock.t = 0.010
        mb.pump(now=0.010)
        run_dir = log.run_dir
    report.main(["trace", run_dir])
    out = capsys.readouterr().out
    assert "trace " in out and "critical path" in out
    assert "serve.request" in out and "serve.queue_wait" in out
    assert "goodput (where the time went" in out
    # --request filters to the request's trace AND follows its batch link
    report.main(["trace", run_dir, "--request", "0"])
    out = capsys.readouterr().out
    assert "serve.request" in out and "serve.batch" in out
    report.main(["trace", run_dir, "--request", "nope"])
    out = capsys.readouterr().out
    assert "no trace with a root span rid" in out


def test_sparkline_survives_all_nan_window():
    from keystone_tpu.observe.top import SPARK, sparkline

    nan = float("nan")
    # mixed: non-finite renders as the full bar
    s = sparkline([1.0, 2.0, nan, 3.0])
    assert len(s) == 4 and s[2] == SPARK[-1]
    # an ENTIRELY non-finite window still renders (divergence that
    # stuck) instead of vanishing mid-incident
    s = sparkline([nan] * 10)
    assert s == SPARK[-1] * 10


def test_observe_trace_cli_usage():
    from keystone_tpu.observe import spans as spans_cli

    with pytest.raises(SystemExit):
        spans_cli.main([])
    with pytest.raises(SystemExit):
        spans_cli.main(["--help"])


# ---------------------------------------------------------------------------
# PR 26: spans of the fit path, on the profiler's clock. None of these
# times the CPU: they hold ids, nesting, one clock against another, and
# hand-worked tables.

from types import SimpleNamespace as NS  # noqa: E402

FIT_SPANS = {
    # name -> parent's name
    "fit.load": "fit",
    "fit.h2d": "fit",
    "fit.featurize_init": "fit",
    "fit.featurize": "fit",
    "featurize.cosine": "fit.featurize",
    "featurize.scale_fit": "fit.featurize",
    "featurize.scale_apply": "fit.featurize",
    "fit.labels": "fit",
    "fit.featurize_wait": "fit",
    "fit.solve": "fit",
    "fit.score": "fit",
    "score.train": "fit.score",
    "score.test": "fit.score",
}


def _toy_fit():
    from keystone_tpu.models.timit_pipeline import TimitConfig, run

    return run(TimitConfig(synthetic=256, num_cosines=2, cosine_features=32,
                           num_epochs=2))


def _start_profile(directory):
    import jax

    options = jax.profiler.ProfileOptions()  # the benchmark harness's own
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=options)


def _planes(directory):
    import jax

    from keystone_tpu.observe import idle

    return list(
        jax.profiler.ProfileData.from_file(idle.newest_profile(str(directory))).planes
    )


@pytest.fixture(scope="module")
def profiled_fit(tmp_path_factory):
    """One toy fit inside a profiler session and no event sink: (what
    run() returned, the session's span records, the profile's dir). A
    steady fit compiles nothing, so the session also holds one compile
    made on purpose, under a root of its own."""
    import jax
    import jax.numpy as jnp

    def on_purpose(x):
        return x * 2.0 + 1.0

    directory = tmp_path_factory.mktemp("profile")
    assert events.active() is None
    _toy_fit()  # the process's first fit makes the programs: the next is steady
    x = jnp.ones(3)
    _start_profile(directory)
    try:
        out = _toy_fit()
        with spans_mod.span("compiled.on.purpose", parent=None):
            jax.jit(on_purpose)(x)
    finally:
        jax.profiler.stop_trace()
    return out, spans_mod.profiled_spans(), directory


def test_fit_path_off_records_nothing_and_forces_nothing_extra(monkeypatch, profiled_fit):
    """No sink, no session: span() yields None after two reads, builds
    nothing, and run() waits for the device exactly where it did before
    it had spans (the solve's own block_until_ready)."""
    import jax

    assert events.active() is None and not jax.profiler.TraceAnnotation.is_enabled()
    before = spans_mod.profiled_spans()
    forced: list[int] = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: forced.append(1) or real(x))
    monkeypatch.setattr(
        spans_mod.SpanLog, "__init__",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("built a SpanLog")),
    )
    with spans_mod.span("anything", rows=1) as ctx:
        assert ctx is None and spans_mod.current() is None
        assert spans_mod.force(7) == 7
    assert forced == []
    _toy_fit()
    assert forced == [1]
    assert spans_mod.active_span_log() is None
    assert spans_mod.profiled_spans() == before


def test_fit_spans_on_by_profiler_session_alone(profiled_fit):
    out, recs, _dir = profiled_fit
    by_id = {r["span"]: r for r in recs}
    (root,) = [r for r in recs if r["name"] == "fit"]
    assert "parent" not in root
    import jax

    assert {k: root[k] for k in ("blocks", "epochs", "chips")} == {
        "blocks": 2, "epochs": 2, "chips": len(jax.devices())}
    (purpose,) = [r for r in recs if r["name"] == "compiled.on.purpose"]
    assert {r["trace"] for r in recs} == {root["trace"], purpose["trace"]}
    names = [r["name"] for r in recs]
    for name, parent in FIT_SPANS.items():
        mine = [r for r in recs if r["name"] == name]
        assert mine, name
        assert {by_id[r["parent"]]["name"] for r in mine} == {parent}, name
    assert names.count("fit.featurize") == 2
    assert sorted(r["bank"] for r in recs if r["name"] == "fit.featurize") == [0, 1]
    (h2d,) = [r for r in recs if r["name"] == "fit.h2d"]
    # rows ride the first span that opens once the load has said them
    assert (h2d["rows"], h2d["bytes"]) == (256, (256 + 51) * 440 * 4)
    for r in recs:
        assert r["t0_ns"] <= r["t1_ns"]
        assert r["wall_s"] == pytest.approx((r["t1_ns"] - r["t0_ns"]) / 1e9, abs=1e-6)
        if r["name"].startswith("jit."):
            continue  # post-hoc: start = emission - duration, may round out
        p = by_id.get(r.get("parent"))
        if p is not None:
            assert p["t0_ns"] <= r["t0_ns"] and r["t1_ns"] <= p["t1_ns"], r["name"]
    # the phase keys come from the same boundaries as the spans
    wait = next(r for r in recs if r["name"] == "fit.featurize_wait")
    assert out["featurize_s"] == pytest.approx(
        (wait["t1_ns"] - root["t0_ns"]) / 1e9, abs=5e-3)
    # the fit's programs are made once per process: this second fit traced,
    # lowered and compiled nothing, and every jit.* record of the session
    # belongs to the compile made on purpose
    jit = [r for r in recs if r["name"].startswith("jit.")]
    assert {r["parent"] for r in jit} == {purpose["span"]}
    assert [r["fun"] for r in jit if r["name"] == "jit.backend_compile"] == [
        "jit(on_purpose)"]
    # a session that is over records nothing more, and stays readable
    _toy_fit()
    assert spans_mod.profiled_spans() == recs


def test_live_spans_have_twins_on_the_profilers_clock(profiled_fit):
    """Every live span has a host-plane event of its name whose ``span``
    stat is its id; anchored on the root, starts and durations agree
    within 1 ms: the .xplane.pb is the shared clock."""
    from keystone_tpu.observe import idle

    _out, recs, directory = profiled_fit
    twins = {s["span"]: s for s in idle.host_spans(_planes(directory))}
    live = [r for r in recs if not r["name"].startswith("jit.")]
    (root,) = [r for r in live if r["name"] == "fit"]
    offset = twins[root["span"]]["start"] - root["t0_ns"]
    assert len(live) >= len(FIT_SPANS) + 1
    for r in live:
        twin = twins[r["span"]]
        assert twin["label"] == r["name"]
        assert twin["parent"] == r.get("parent", "")
        assert abs(twin["start"] - (r["t0_ns"] + offset)) < 1e6, r["name"]
        assert abs((twin["end"] - twin["start"]) - (r["t1_ns"] - r["t0_ns"])) < 1e6
    # post-hoc spans have no twin; `observe idle` places them by the offset
    assert not any(r["span"] in twins for r in recs if r["name"].startswith("jit."))
    placed = {s["span"]: s for s in idle.place_recorded(list(twins.values()), recs)}
    (compile_,) = [r for r in recs if r["name"] == "jit.backend_compile"]
    assert placed[compile_["span"]]["label"] == f"jit.backend_compile fun={compile_['fun']}"
    assert placed[compile_["span"]]["start"] == pytest.approx(
        compile_["t0_ns"] + offset, abs=1e6)


def test_span_ids_never_read_as_numbers(monkeypatch):
    """The profiler guesses the type of an annotation's stats: a twin
    whose id was ``68401e457669`` came back as ``inf``, one in 250."""
    worst = ["68401e457669", "000123456789", "123456789012", "1e5000000000", "0" * 12]
    hexes = iter(w + "0" * 20 for w in worst)
    monkeypatch.setattr(spans_mod.uuid, "uuid4", lambda: NS(hex=next(hexes)))
    for w in worst:
        got = spans_mod._new_id()
        assert re.fullmatch("[a-f][0-9a-f]{11}", got) and got[1:] == w[:11]
        with pytest.raises(ValueError):
            float(got)


def test_compile_listener_names_the_call_that_compiled(tmp_path):
    import jax
    import jax.numpy as jnp

    def fresh(tag):
        def twice_plus(x):
            return x * 2.0 + tag
        twice_plus.__name__ = f"twice_plus_{tag}"
        return jax.jit(twice_plus)

    x = jnp.ones(3)
    fresh(1)(x)  # off: nothing recorded
    with events.run(str(tmp_path)) as log:
        with spans_mod.span("caller") as ctx:
            fresh(2)(x)
        fresh(3)(x)  # on, but under no span: a child needs a parent
        run_dir = log.run_dir
    fresh(4)(x)  # off again
    recs = spans_mod.read_spans(run_dir)
    jit = [r for r in recs if r["name"].startswith("jit.")]
    assert len(jit) == len(recs) - 1
    assert {r["name"] for r in jit} == {"jit.trace", "jit.lower", "jit.backend_compile"}
    assert all(r["parent"] == ctx.span and r["trace"] == ctx.trace for r in jit)
    # (the jnp ops inside it may be traced too, as its own children in time)
    assert {"twice_plus_2", "jit(twice_plus_2)"} <= {r["fun"] for r in jit}
    assert [r["fun"] for r in jit if r["name"] == "jit.backend_compile"] == [
        "jit(twice_plus_2)"]
    caller = next(r for r in recs if r["name"] == "caller")
    for r in jit:  # start = emission - duration, inside the caller
        assert caller["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= caller["t1_ns"]


def test_a_cache_answered_compile_is_one_cache_read_span(tmp_path):
    """jax emits the cache-retrieval event inside the backend-compile
    bracket: the pair becomes one ``jit.cache_read`` with the ``fun``."""
    with events.run(str(tmp_path)) as log:
        with spans_mod.span("caller"):
            spans_mod._on_jit_duration(spans_mod._CACHE_READ_EVENT, 0.25)
            spans_mod._on_jit_duration(
                "/jax/core/compile/backend_compile_duration", 0.3, fun_name="jit(f)")
            spans_mod._on_jit_duration(
                "/jax/core/compile/backend_compile_duration", 0.1, fun_name="jit(g)")
            spans_mod._on_jit_duration("/jax/some/other_duration", 9.0)
        run_dir = log.run_dir
    got = [(r["name"], r.get("fun"), r["wall_s"]) for r in spans_mod.read_spans(run_dir)]
    assert got == [("jit.cache_read", "jit(f)", 0.3),
                   ("jit.backend_compile", "jit(g)", 0.1), ("caller", None, got[-1][2])]


def test_a_new_profiler_session_starts_a_new_list(tmp_path, profiled_fit):
    """New means seen on after seen off: the look between the sessions
    (here profiled_spans()) is what resets; without one they share."""
    import jax

    def session(directory, name):
        _start_profile(directory)
        try:
            with spans_mod.span(name):
                pass
        finally:
            jax.profiler.stop_trace()

    _out, recs, _dir = profiled_fit
    assert spans_mod.profiled_spans() == recs
    session(tmp_path / "2", "second.session")
    during = spans_mod.profiled_spans()
    assert [r["name"] for r in during] == ["second.session"]
    session(tmp_path / "3", "third.session")
    session(tmp_path / "4", "no.look.before.this.one")
    assert [r["name"] for r in spans_mod.profiled_spans()] == [
        "third.session", "no.look.before.this.one"]
    assert spans_mod._session_log.records.maxlen == spans_mod._MAX_MEMORY_SPANS == 8192


def test_fit_load_says_how_many_corpora_the_memo_answered(tmp_path, profiled_fit):
    """``cached`` on ``fit.load``: 0 in a process's first fit at a size,
    2 in the next; the copy to the device stays in every fit."""
    import jax

    from keystone_tpu.models.timit_pipeline import TimitConfig, run

    _out, recs, _dir = profiled_fit
    (load,) = [r for r in recs if r["name"] == "fit.load"]
    assert load["cached"] == 2  # the fixture's fit came second
    conf = TimitConfig(synthetic=260, num_cosines=2, cosine_features=32, num_epochs=2)
    _start_profile(tmp_path)
    try:
        run(conf)
        run(conf)
    finally:
        jax.profiler.stop_trace()
    mine = spans_mod.profiled_spans()
    assert [r["cached"] for r in mine if r["name"] == "fit.load"] == [0, 2]
    h2d = [r for r in mine if r["name"] == "fit.h2d"]
    assert [r["bytes"] for r in h2d] == [(260 + 52) * 440 * 4] * 2
    assert all(r["t1_ns"] > r["t0_ns"] for r in h2d)
    assert "cached=2" in spans_mod.render_traces(mine)


def _xplane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[
            NS(name=n, start_ns=s, duration_ns=d, stats=list(stats.items()))
            for n, s, d, stats in evs])
        for ln, evs in lines.items()])


def test_observe_idle_on_a_hand_built_trace(capsys):
    """A 1000 ns window. Host: fit 0-1000 holding fit.load 0-300 and
    fit.score 600-950, which holds score.test 700-900. Chip 0 runs ops
    300-400, 450-600 and 800-850; chip 1 runs 0-500."""
    from keystone_tpu.observe import idle

    host = _xplane("/host:CPU", {"python": [
        ("fit", 0, 1000, {"span": "r", "trace": "t"}),
        ("fit.load", 0, 300, {"span": "a", "parent": "r", "trace": "t"}),
        ("fit.score", 600, 350, {"span": "b", "parent": "r", "trace": "t"}),
        ("score.test", 700, 200, {"span": "c", "parent": "b", "trace": "t"}),
        ("not a span", 0, 1000, {}),
    ]})
    chip0 = _xplane("/device:TPU:0", {
        "XLA Modules": [("jit_x(1)", 300, 300, {})],
        "XLA Ops": [("fusion.1", 300, 100, {}), ("fusion.2", 450, 150, {}),
                    ("fusion.3", 800, 50, {})]})
    chip1 = _xplane("/device:TPU:1", {"XLA Ops": [("fusion.1", 0, 500, {})]})
    one = idle.idle_by_span([host, chip0])
    assert (one["chips"], one["window_s"]) == (1, pytest.approx(1000e-9))
    assert one["busy_s"] == pytest.approx(300e-9) and one["idle_s"] == pytest.approx(700e-9)
    # gaps: 0-300 fit.load; 400-450 fit; 600-800 = fit.score 100 + score.test
    # 100; 850-1000 = score.test 50 + fit.score 50 + fit 50
    assert {k: (round(s * 1e9), g) for k, s, g in one["rows"]} == {
        "fit.load": (300, 1), "fit.score": (150, 2), "score.test": (150, 2), "fit": (100, 2)}
    assert [r[0] for r in one["rows"]][0] == "fit.load"
    # a compile recorded after the fact, placed through the shared spans:
    # the process clock runs 5000 ns ahead of the profile's
    records = [
        {"name": "fit", "span": "r", "trace": "t", "t0_ns": 5000, "t1_ns": 6000},
        {"name": "score.test", "span": "c", "parent": "b", "trace": "t",
         "t0_ns": 5700, "t1_ns": 5900},
        {"name": "jit.backend_compile", "span": "j", "parent": "c", "trace": "t",
         "fun": "jit(score)", "t0_ns": 5710, "t1_ns": 5790},
        {"name": "jit.trace", "span": "k", "parent": "zz", "trace": "other",
         "t0_ns": 5000, "t1_ns": 6000},
        {"name": "older record", "span": "old", "trace": "t"},
    ]
    with_jit = idle.idle_by_span([host, chip0], records)
    rows = {k: round(s * 1e9) for k, s, _g in with_jit["rows"]}
    assert rows["jit.backend_compile fun=jit(score)"] == 80
    assert rows["score.test"] == 70 and "jit.trace" not in rows
    # two chips: chip 1 idles 500-1000 (fit 100, fit.score 150, score.test
    # 200, and 50 of fit after 950); the table is the mean
    two = idle.idle_by_span([host, chip0, chip1])
    assert two["chips"] == 2 and two["busy_s"] == pytest.approx(400e-9)
    rows = {k: round(s * 1e9) for k, s, _g in two["rows"]}
    assert rows == {"fit.load": 150, "fit.score": 150, "score.test": 175, "fit": 125}
    # what no span covers is the last row
    bare = idle.idle_by_span([_xplane("/host:CPU", {"python": [
        ("fit.load", 0, 300, {"span": "a", "trace": "t"})]}), chip0])
    assert [(k, round(s * 1e9)) for k, s, _g in bare["rows"]] == [
        ("fit.load", 300), ("(no span)", 250)]
    out = idle.render(bare)
    assert "idle 0.0000 s (64.71 %)" in out and out.splitlines()[-1].startswith("(no span)")
    assert idle.idle_by_span([host]) is None


def test_observe_idle_cli_on_a_cpu_trace_says_it_has_no_device_plane(
        profiled_fit, tmp_path, capsys):
    from keystone_tpu.observe import report

    _out, _recs, directory = profiled_fit
    report.main(["idle", str(directory)])
    out = capsys.readouterr().out
    assert "no /device:TPU:<n> plane" in out and "fit.solve" in out
    for bad in ([], ["--help"], [str(tmp_path)], [str(directory), str(tmp_path / "nope")]):
        with pytest.raises(SystemExit):
            report.main(["idle", *bad])
    with pytest.raises(SystemExit) as e:
        report.main(["--help"])
    assert "observe idle <profile-dir>" in str(e.value)


# ---------------------------------------------------------------------------
# the startup period (PR 38): from init_backend to the end of the first fit


@pytest.fixture
def no_startup(monkeypatch):
    """A process whose startup period has not opened yet (and whatever a
    test opens is gone after it)."""
    monkeypatch.setattr(spans_mod, "_startup", None)
    monkeypatch.setattr(spans_mod, "_startup_open", None)
    assert events.active() is None


def _fresh_jit(tag):
    import jax

    def plus(x):
        return x * 3.0 + tag
    plus.__name__ = f"startup_plus_{tag}"
    return jax.jit(plus)


def test_the_startup_period_opens_when_asked_and_not_at_import(no_startup):
    assert spans_mod.active_span_log() is None and spans_mod.startup_spans() == []
    with spans_mod.span("before.the.period") as ctx:
        assert ctx is None
    said = []
    process = spans_mod.open_startup(attrs=dict, report=said.append)
    sl = spans_mod.active_span_log()
    assert isinstance(sl, spans_mod.SpanLog) and sl._sink is None  # memory only
    # a second call opens nothing and hands back the same root
    assert spans_mod.open_startup(attrs=dict, report=said.append) == process
    assert spans_mod.active_span_log() is sl
    # a span with a parent of its own is no unit of work: the period stays open
    with spans_mod.span("runtime.init_backend", parent=process):
        pass
    assert spans_mod.active_span_log() is sl and said == []
    assert [r["name"] for r in spans_mod.startup_spans()] == ["runtime.init_backend"]


def test_a_first_fit_in_the_startup_period_is_one_tree_under_process(no_startup, monkeypatch):
    import jax.numpy as jnp

    said = []
    process = spans_mod.open_startup(
        attrs=lambda: {"platform": "cpu", "chips": 8, "compile_cache": None},
        report=said.append)
    with spans_mod.span("runtime.init_backend", parent=process, platform="cpu"):
        pass
    x = jnp.ones(3)
    _fresh_jit(101)(x)  # outside any span: a child of `process`
    assert spans_mod.force(5) == 5  # no span around it: nothing to wait for
    with spans_mod.span("fit", parent=None, steps=1) as fit_ctx:
        assert fit_ctx.trace == process.trace
        with spans_mod.span("fit.solve"):
            _fresh_jit(102)(x)
        with spans_mod.span("second.root", parent=None):
            pass  # parentless while the unit is in flight: closes nothing
        with spans_mod.span("fit", parent=None):
            pass  # and so does a second fit inside the first
        assert spans_mod.active_span_log() is not None and said == []
    # closed, once, at the root's end
    assert spans_mod.active_span_log() is None and len(said) == 1
    recs = spans_mod.startup_spans()
    by_id = {r["span"]: r for r in recs}
    (root,) = [r for r in recs if r["name"] == "process"]
    assert "parent" not in root and root["span"] == process.span
    assert (root["platform"], root["chips"], root["closed_by"]) == ("cpu", 8, "unit")
    assert root["t0_source"] == "proc" and "compile_cache" not in root
    (backend,) = [r for r in recs if r["name"] == "runtime.init_backend"]
    inner_fit, fit = [r for r in recs if r["name"] == "fit"]
    (second,) = [r for r in recs if r["name"] == "second.root"]
    assert backend["parent"] == fit["parent"] == second["parent"] == root["span"]
    assert inner_fit["parent"] == root["span"] and fit["span"] == fit_ctx.span
    assert root["t0_ns"] <= spans_mod._T_IMPORT_NS < backend["t0_ns"]
    assert root["t1_ns"] == fit["t1_ns"]
    jit = [r for r in recs if r["name"].startswith("jit.")]
    outside = [r for r in jit if "startup_plus_101" in r["fun"]]
    inside = [r for r in jit if "startup_plus_102" in r["fun"]]
    assert {r["name"] for r in outside} == {r["name"] for r in inside} >= {
        "jit.trace", "jit.lower"}
    assert {r["parent"] for r in outside} == {root["span"]}
    assert {by_id[r["parent"]]["name"] for r in inside} == {"fit.solve"}
    assert {r["trace"] for r in recs} == {process.trace}
    summary = said[0]
    assert summary["total_s"] == pytest.approx(root["wall_s"], abs=1e-3)
    assert summary["programs"] == sum(
        r["name"] in ("jit.backend_compile", "jit.cache_read") for r in jit) >= 2
    # after it: a second fit records nothing, span() builds nothing
    monkeypatch.setattr(
        spans_mod.SpanLog, "__init__",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("built a SpanLog")))
    with spans_mod.span("fit", parent=None) as ctx:
        assert ctx is None
        _fresh_jit(103)(x)
    assert spans_mod.startup_spans() == recs and len(said) == 1


def test_a_toy_fit_after_open_startup_keeps_its_own_spans(no_startup):
    said = []
    spans_mod.open_startup(attrs=dict, report=said.append)
    _toy_fit()
    recs = spans_mod.startup_spans()
    by_id = {r["span"]: r for r in recs}
    assert recs[-1]["name"] == "process" and len(said) == 1
    (fit,) = [r for r in recs if r["name"] == "fit"]
    assert fit["parent"] == recs[-1]["span"] and fit["blocks"] == 2
    for name, parent in FIT_SPANS.items():
        mine = [r for r in recs if r["name"] == name]
        assert mine and {by_id[r["parent"]]["name"] for r in mine} == {parent}, name
    n = len(recs)
    _toy_fit()
    assert len(spans_mod.startup_spans()) == n and spans_mod.active_span_log() is None


@pytest.mark.parametrize("bound, closed_by", [
    ("_MAX_STARTUP_SPANS", "records"), ("_STARTUP_STEPS", "steps")])
def test_a_bound_closes_the_startup_period_in_mid_fit(no_startup, monkeypatch, bound, closed_by):
    """A trainer's first `fit` is its whole run: after five steps' worth
    the period closes inside the open root, the steps after it are
    neither recorded nor waited for, and the summary counts the root and
    the phase in flight up to there."""
    monkeypatch.setattr(spans_mod, bound, 5)
    waited = []
    monkeypatch.setattr(spans_mod.jax, "block_until_ready", waited.append)
    said = []
    process = spans_mod.open_startup(attrs=dict, report=said.append)
    with spans_mod.span("fit", parent=None):
        time.sleep(0.003)
        with spans_mod.span("fit.solve"):
            for i in range(9):
                with spans_mod.span("train.step", step=i):
                    spans_mod.force(i)
    recs = spans_mod.startup_spans()
    # five steps, then the bound: the root, and what was in flight
    assert [r["name"] for r in recs] == ["train.step"] * 5 + ["process", "fit.solve", "fit"]
    assert recs[5]["closed_by"] == closed_by and recs[5]["span"] == process.span
    assert recs[5]["t1_ns"] < recs[6]["t1_ns"] <= recs[7]["t1_ns"]
    assert len(said) == 1 and spans_mod.active_span_log() is None
    # force() waited in the recorded steps only (the records' bound is seen
    # by the sixth step's own span(), the steps' bound at the fifth's end)
    assert waited == [0, 1, 2, 3, 4]
    # the root and its phase were in flight: counted up to the close
    assert said[0]["first_run_s"] >= 0.003
    assert said[0]["total_s"] == pytest.approx(recs[5]["wall_s"], abs=1e-3)


def test_force_waits_only_while_what_is_around_it_is_being_recorded(no_startup, monkeypatch):
    waited = []
    monkeypatch.setattr(spans_mod.jax, "block_until_ready", waited.append)
    spans_mod.force("no span")
    spans_mod.open_startup(attrs=dict, report=lambda s: None)
    spans_mod.force("no span, period open")
    with spans_mod.span("fit", parent=None):
        spans_mod.force("recorded")
        # the period closes under the open root (a bound, here by hand)
        spans_mod._close_startup(spans_mod._startup_open, 0, "records")
        assert spans_mod.current() is not None
        spans_mod.force("root open, nothing on")
    assert waited == ["recorded"]


@pytest.mark.parametrize("first", ["plan.segment", "plan.fit_stream", "serve.stream",
                                   "multihost.rollup_gather", "fleet.request"])
def test_a_parentless_span_that_is_no_unit_of_work_closes_nothing(no_startup, first):
    said = []
    process = spans_mod.open_startup(attrs=dict, report=said.append)
    with spans_mod.span(first):
        with spans_mod.span("fit"):
            pass  # a fit under another span is no unit either
    assert said == [] and spans_mod.active_span_log() is not None
    with spans_mod.span("fit", parent=None):
        pass
    assert len(said) == 1 and spans_mod.active_span_log() is None
    names = [r["name"] for r in spans_mod.startup_spans()]
    assert names == ["fit", first, "fit", "process"]
    by_name = {r["name"]: r for r in spans_mod.startup_spans()}
    assert by_name[first]["parent"] == by_name["fit"]["parent"] == process.span
    assert by_name["process"]["t1_ns"] == by_name["fit"]["t1_ns"]


@pytest.mark.parametrize("upstream", [False, True])
def test_the_first_served_request_closes_the_startup_period(no_startup, upstream):
    """Behind the fleet's router a request brings its parent from another
    process: it is this process's unit of work all the same."""
    said = []
    process = spans_mod.open_startup(attrs=dict, report=said.append)
    with spans_mod.span("serve.batch"):  # the server's own warm-up
        pass
    kw = {"parent": spans_mod.make_context()} if upstream else {}
    with spans_mod.span("serve.request", rid="r1", **kw) as ctx:
        assert (ctx.trace == process.trace) is not upstream
    assert len(said) == 1 and spans_mod.active_span_log() is None
    recs = spans_mod.startup_spans()
    assert [r["name"] for r in recs] == ["serve.batch", "serve.request", "process"]
    assert recs[2]["closed_by"] == "unit" and recs[2]["t1_ns"] == recs[1]["t1_ns"]
    assert (recs[1]["parent"] == process.span) is not upstream


def test_requests_on_many_threads_close_the_startup_period_once(no_startup):
    """More threads than cores, each a few requests with steps inside,
    a short switch interval: one `process` root, one report, every span
    that began in the period recorded once, none after it."""
    import sys

    said, failed = [], []
    process = spans_mod.open_startup(attrs=dict, report=said.append)
    go = threading.Event()

    def client(k):
        try:
            go.wait(10)
            for i in range(20):
                with spans_mod.span("serve.request", rid=f"{k}.{i}"):
                    with spans_mod.span("train.step", step=i):
                        spans_mod.force(i)
        except Exception as e:  # noqa: BLE001 - the test reports it
            failed.append(repr(e))

    threads = [threading.Thread(target=client, args=(k,)) for k in range(4 * (os.cpu_count() or 4))]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        go.set()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads) and failed == []
    assert len(said) == 1 and spans_mod.active_span_log() is None
    recs = spans_mod.startup_spans()
    roots = [r for r in recs if r["name"] == "process"]
    assert len(roots) == 1 and roots[0]["span"] == process.span
    assert roots[0]["closed_by"] in ("unit", "steps")
    assert len({r["span"] for r in recs}) == len(recs)
    assert spans_mod._startup.in_flight == {}
    assert len(recs) < 3 * len(threads) + 2 * spans_mod._STARTUP_STEPS


def test_with_a_sink_active_the_startup_records_go_to_its_file(no_startup, tmp_path):
    import jax.numpy as jnp

    said = []
    with events.run(str(tmp_path)) as log:
        process = spans_mod.open_startup(attrs=dict, report=said.append)
        _fresh_jit(104)(jnp.ones(3))
        with spans_mod.span("fit", parent=None):
            pass
        run_dir = log.run_dir
    assert spans_mod.startup_spans() == [] and spans_mod._startup_open is None
    recs = spans_mod.read_spans(run_dir)
    names = [r["name"] for r in recs]
    assert names[-2:] == ["fit", "process"] and "jit.trace" in names
    assert {r.get("parent") for r in recs[:-1]} == {process.span}
    assert said[0]["total_s"] > 0 and said[0]["programs"] >= 1


def test_process_start_falls_back_to_the_import_when_proc_is_unreadable(monkeypatch):
    t0, source = spans_mod._process_start_ns()
    assert source == "proc" and t0 <= spans_mod._T_IMPORT_NS
    import builtins

    real = builtins.open

    def no_proc(path, *a, **k):
        if str(path).startswith("/proc/"):
            raise OSError("no /proc here")
        return real(path, *a, **k)

    monkeypatch.setattr(builtins, "open", no_proc)
    assert spans_mod._process_start_ns() == (spans_mod._T_IMPORT_NS, "import")


def test_startup_summary_on_hand_built_records():
    """A process of 10 s: the backend 2-3 s, a compile outside any span
    3.2-3.4 s, a fit 4-10 s whose `fit.init` 4-7 s holds a trace 4.5-6.5
    (an inner trace 5-5.5 inside it), a lowering 6.5-6.9 and a cache read
    6.9-7; a foreign root beside it."""
    s = 1_000_000_000

    def rec(name, span, parent, t0, t1, **attrs):
        r = {"name": name, "span": span, "trace": "t", "t0_ns": int(t0 * s),
             "t1_ns": int(t1 * s), **attrs}
        if parent:
            r["parent"] = parent
        return r

    recs = [
        rec("runtime.init_backend", "b", "p", 2, 3),
        rec("jit.backend_compile", "c0", "p", 3.2, 3.4, fun="jit(iota)"),
        rec("fit.init", "i", "f", 4, 7),
        rec("jit.trace", "t1", "i", 4.5, 6.5, fun="_train_step"),
        rec("jit.trace", "t2", "i", 5, 5.5, fun="gmm"),
        rec("jit.lower", "l1", "i", 6.5, 6.9, fun="jit(_train_step)"),
        rec("jit.cache_read", "r1", "i", 6.9, 7, fun="jit(_train_step)"),
        rec("fit", "f", "p", 4, 10),
        rec("serve.request", "x", None, 0, 10),
        rec("jit.trace", "t9", "x", 0, 10, fun="other"),
        rec("process", "p", None, 0, 10),
    ]
    assert spans_mod.startup_summary(recs[:-1]) is None
    got = spans_mod.startup_summary(recs)
    assert got == {
        "import_s": 2.0, "backend_s": 1.0, "trace_s": 2.0, "lower_s": 0.4,
        "cache_read_s": 0.1, "compile_s": 0.2, "first_run_s": 3.5, "programs": 2,
        "total_s": 10.0,
        "top": [{"fun": "_train_step", "s": 2.0}, {"fun": "gmm", "s": 0.5},
                {"fun": "iota", "s": 0.2}],
    }
    mine = spans_mod.self_ns(recs, recs[-1])
    assert sum(mine.values()) == 10 * s and "x" not in mine
    assert mine["p"] == int(2.0 * s) + int(0.2 * s) + int(0.6 * s)  # what only the root covers


def test_init_backend_opens_the_period_and_logs_device_startup_and_compile(tmp_path):
    """One subprocess: nothing is on at import; `init_backend` opens the
    period; the first parentless span closes it and the `startup` line
    follows; the `compile` line at exit keeps its three fields."""
    import subprocess
    import sys

    code = (
        "from keystone_tpu.observe import spans\n"
        "from keystone_tpu.core import runtime\n"
        "assert spans.active_span_log() is None and spans.startup_spans() == []\n"
        "runtime.init_backend()\n"
        "assert spans.active_span_log() is not None\n"
        "assert [r['name'] for r in spans.startup_spans()] == ['runtime.init_backend']\n"
        "import jax, jax.numpy as jnp\n"
        "with spans.span('fit', parent=None):\n"
        "    jax.jit(lambda x: x + 1.0)(jnp.ones(3))\n"
        "assert spans.active_span_log() is None\n"
        "names = [r['name'] for r in spans.startup_spans()]\n"
        "assert names[0] == 'runtime.init_backend' and names[-2:] == ['fit', 'process']\n"
        "from jax._src import monitoring\n"
        "print('listeners', sum(f.__module__.startswith('keystone_tpu') for f in "
        "monitoring.get_event_duration_listeners()))\n"
    )
    root = str(pathlib.Path(__file__).resolve().parents[1])
    env = {**os.environ, "PYTHONPATH": root, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    # the program registers one duration listener where it had two
    assert r.stdout.split() == ["listeners", "1"]
    said = {}
    for line in r.stderr.splitlines():
        for kind in ("device", "startup", "compile"):
            if f" keystone_tpu.runtime: {kind} {{" in line:
                said.setdefault(kind, []).append(json.loads(line.split(f": {kind} ", 1)[1]))
    assert {k: len(v) for k, v in said.items()} == {"device": 1, "startup": 1, "compile": 1}
    assert set(said["device"][0]) == {"platform", "device_kind", "count", "compile_cache"}
    assert set(said["compile"][0]) == {"backend_compile_s", "cache_hits", "cache_misses"}
    assert said["compile"][0]["backend_compile_s"] > 0
    startup = said["startup"][0]
    assert list(startup) == ["import_s", "backend_s", "trace_s", "lower_s", "cache_read_s",
                             "compile_s", "first_run_s", "programs", "total_s", "top"]
    assert startup["programs"] >= 1 and startup["compile_s"] > 0
    assert startup["import_s"] > 0 and startup["total_s"] >= startup["import_s"]
    assert len(startup["top"]) <= 3 and all(set(t) == {"fun", "s"} for t in startup["top"])
