"""Observability subsystem tests (observe/*, core logging/profiling
satellites, and the ``observe`` CLI path).

Reference: KeystoneML's optimizer consumes per-operator runtime profiles;
these tests pin the TPU rebuild's substrate for that — metrics registry,
JSONL event log, pipeline instrumentation, and compiler cost profiles.
"""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import LabelEstimator, Pipeline, transformer
from keystone_tpu.observe import events, metrics
from keystone_tpu.observe.cost import CostProfileRegistry, analyze, load_profiles
from keystone_tpu.observe.instrument import instrument


def three_node_pipe():
    return (
        transformer(lambda b: b + 1.0, "add1")
        >> transformer(lambda b: b * 2.0, "mul2")
        >> transformer(lambda b: b - 0.5, "sub")
    )


# ---------------------------------------------------------------- metrics


def test_counter_gauge_timer_and_labels():
    reg = metrics.MetricsRegistry()
    reg.counter("calls", node="a").inc()
    reg.counter("calls", node="a").inc(2)
    reg.counter("calls", node="b").inc()
    reg.gauge("hbm").set(42.5)
    t = reg.timer("secs", node="a")
    t.observe(0.25)
    t.observe(0.75)
    snap = reg.snapshot()
    assert snap["calls{node=a}"] == 3
    assert snap["calls{node=b}"] == 1
    assert snap["hbm"] == 42.5
    summary = snap["secs{node=a}"]
    assert summary["count"] == 2
    assert summary["total_s"] == pytest.approx(1.0)
    assert summary["min_s"] == 0.25 and summary["max_s"] == 0.75
    # same key, different kind → error, not silent aliasing
    with pytest.raises(ValueError):
        reg.gauge("calls", node="a")


def test_timer_time_context_counts_failures_too():
    reg = metrics.MetricsRegistry()
    t = reg.timer("bracket")
    with pytest.raises(RuntimeError):
        with t.time():
            raise RuntimeError("boom")
    assert t.count == 1


def test_metrics_thread_safety():
    reg = metrics.MetricsRegistry()
    n_threads, n_incs = 8, 2000

    def work():
        c = reg.counter("hammer", src="t")
        timer = reg.timer("hammer_s", src="t")
        for _ in range(n_incs):
            c.inc()
            timer.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert reg.counter("hammer", src="t").value == n_threads * n_incs
    assert reg.timer("hammer_s", src="t").count == n_threads * n_incs


# ----------------------------------------------------------------- events


def test_event_log_jsonl_roundtrip(tmp_path):
    with events.run(str(tmp_path), workload="unit") as log:
        log.emit("node", node="00:x", phase="apply", wall_s=0.5, status="ok")
        with log.node("01:y", "fit"):
            pass
        run_dir = log.run_dir
    evs = events.read_events(run_dir)
    kinds = [e["event"] for e in evs]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert evs[0]["workload"] == "unit"
    nodes = [e for e in evs if e["event"] == "node"]
    assert len(nodes) == 2
    assert nodes[1]["node"] == "01:y" and nodes[1]["phase"] == "fit"
    assert nodes[1]["status"] == "ok" and nodes[1]["wall_s"] >= 0
    assert all(e["run"] == evs[0]["run"] for e in evs)
    # base-dir resolution picks this run
    assert events.resolve_run_dir(str(tmp_path)) == run_dir


def test_event_node_bracket_records_failure(tmp_path):
    with events.run(str(tmp_path)) as log:
        with pytest.raises(ValueError):
            with log.node("00:bad", "apply"):
                raise ValueError("nope")
        run_dir = log.run_dir
    nodes = [e for e in events.read_events(run_dir) if e["event"] == "node"]
    assert nodes[0]["status"] == "failed" and "nope" in nodes[0]["error"]
    # the run itself completed
    end = [e for e in events.read_events(run_dir) if e["event"] == "run_end"]
    assert end[0]["status"] == "ok"


def test_env_gated_activation(tmp_path, monkeypatch):
    try:
        monkeypatch.setenv(events.ENV_DIR, str(tmp_path))
        events.reset()
        log = events.active()
        assert log is not None and log.run_dir.startswith(str(tmp_path))
        assert events.active() is log  # cached, not re-created
    finally:
        monkeypatch.delenv(events.ENV_DIR, raising=False)
        events.reset()
    assert events.active() is None


def test_run_restores_previous_sink(tmp_path):
    assert events.active() is None
    with events.run(str(tmp_path)) as outer:
        with events.run(str(tmp_path)) as inner:
            assert events.active() is inner
        assert events.active() is outer
    assert events.active() is None


# ------------------------------------------------------- instrumentation


def test_instrument_preserves_outputs_bit_exactly_and_records(tmp_path):
    pipe = three_node_pipe()
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(32, 8)).astype(np.float32)
    )
    expect = np.asarray(pipe(x))
    with events.run(str(tmp_path)) as log:
        inst = instrument(pipe, sync=True)
        got1 = np.asarray(inst(x))
        got2 = np.asarray(inst(x))
        run_dir = log.run_dir
    assert np.array_equal(got1, expect) and np.array_equal(got2, expect)
    nodes = [e for e in events.read_events(run_dir) if e["event"] == "node"]
    per_label = {}
    for e in nodes:
        per_label[e["node"]] = per_label.get(e["node"], 0) + 1
    # one entry per node per call — 3 nodes × 2 calls, no double counting
    # from the Pipeline.__call__ hook (instrumented nodes self-record)
    assert per_label == {"00:add1": 2, "01:mul2": 2, "02:sub": 2}
    assert all("wall_s" in e and e["status"] == "ok" for e in nodes)
    # metrics registry saw the same calls
    snap = metrics.get_registry().snapshot()
    assert snap["node_calls{node=00:add1}"] >= 2


def test_instrument_is_idempotent_but_honors_sync_change():
    pipe = three_node_pipe()
    once = instrument(pipe, sync=False)
    twice = instrument(once, sync=False)
    assert all(a is b for a, b in zip(once.nodes, twice.nodes))
    resynced = instrument(once, sync=True)
    assert all(n.sync for n in resynced.nodes)
    assert [n.inner for n in resynced.nodes] == [n.inner for n in once.nodes]


def test_pipeline_call_hook_emits_per_node_events(tmp_path):
    pipe = three_node_pipe()
    x = jnp.ones((4, 4))
    with events.run(str(tmp_path)) as log:
        pipe(x)
        run_dir = log.run_dir
    labels = [
        e["node"] for e in events.read_events(run_dir) if e["event"] == "node"
    ]
    assert labels == ["00:add1", "01:mul2", "02:sub"]
    # disabled: no sink, no events, same output
    out = pipe(x)
    assert np.asarray(out).shape == (4, 4)


def test_jitted_instrumented_pipeline_records_compile_phase(tmp_path):
    pipe = three_node_pipe()
    x = jnp.ones((8, 4))
    expect = np.asarray(pipe(x))
    with events.run(str(tmp_path)) as log:
        inst = instrument(pipe)
        jit_apply = jax.jit(lambda p, b: p(b))
        got = np.asarray(jit_apply(inst, x))
        run_dir = log.run_dir
    assert np.array_equal(got, expect)
    phases = {
        e["phase"] for e in events.read_events(run_dir) if e["event"] == "node"
    }
    assert "compile" in phases


def test_chained_fit_hooks_emit_fit_events(tmp_path):
    class MeanEst(LabelEstimator):
        def fit(self, data, labels):
            mu = jnp.mean(labels)
            return transformer(lambda b, mu=mu: b * mu, name="scaled")

    data = jnp.ones((8, 3))
    labels = jnp.full((8,), 2.0)
    chained = transformer(lambda b: b + 1.0, "shift") >> MeanEst()
    with events.run(str(tmp_path)) as log:
        chained.fit(data, labels)
        run_dir = log.run_dir
    nodes = [e for e in events.read_events(run_dir) if e["event"] == "node"]
    by_phase = {e["phase"]: e["node"] for e in nodes}
    assert by_phase.get("fit") == "MeanEst"
    assert by_phase.get("apply") == "shift"


# ------------------------------------------------------------------ cost


def test_cost_profile_of_jitted_matmul():
    a = jnp.ones((128, 256), jnp.float32)
    b = jnp.ones((256, 64), jnp.float32)
    profile = analyze(lambda a, b: a @ b, a, b)
    assert "error" not in profile
    # 2*M*K*N FLOPs for the matmul, as modeled by cost_analysis()
    assert profile["flops"] == pytest.approx(2 * 128 * 256 * 64, rel=0.01)
    assert profile["bytes_accessed"] > 0
    if "peak_bytes" in profile:  # memory_analysis available on this backend
        assert profile["output_bytes"] == 128 * 64 * 4


def test_cost_registry_pipeline_profiles_roundtrip(tmp_path):
    pipe = transformer(lambda b: b @ jnp.ones((8, 16)), "proj") >> transformer(
        lambda b: jnp.maximum(b, 0.0), "relu"
    )
    reg = CostProfileRegistry()
    profiles = reg.profile_pipeline(pipe, jnp.ones((32, 8)))
    assert set(profiles) == {"00:proj", "01:relu"}
    assert profiles["00:proj"]["flops"] > 0
    assert profiles["00:proj"]["input_shapes"] == ["float32[32, 8]"]
    path = reg.save(str(tmp_path))
    loaded = load_profiles(str(tmp_path))
    assert loaded["profiles"]["00:proj"]["flops"] == profiles["00:proj"]["flops"]
    assert loaded["device_kind"] == "cpu"
    assert os.path.basename(path) == "cost_profiles.json"
    # unanalyzable node degrades to an error profile, not an exception
    bad = transformer(lambda b: np.asarray(b).tolist(), "host_op")
    assert "error" in CostProfileRegistry().profile_node(bad, jnp.ones(3))


# -------------------------------------------------------- report and CLI


def _make_run(tmp_path):
    pipe = three_node_pipe()
    x = jnp.ones((64, 32))
    with events.run(str(tmp_path)) as log:
        instrument(pipe, sync=True)(x)
        reg = CostProfileRegistry()
        reg.profile_pipeline(pipe, x)
        reg.save(log.run_dir)
        return log.run_dir


def test_observe_cli_renders_per_node_summary(tmp_path, capsys):
    run_dir = _make_run(tmp_path)
    from keystone_tpu.__main__ import main as cli_main

    cli_main(["observe", run_dir])
    out = capsys.readouterr().out
    assert "00:add1" in out and "01:mul2" in out and "02:sub" in out
    assert "GFLOP" in out and "MB_acc" in out  # cost_analysis columns
    assert "calls" in out
    # base-dir form resolves to the newest run
    cli_main(["observe", str(tmp_path)])
    assert "00:add1" in capsys.readouterr().out


def test_observe_cli_usage_and_missing_dir(tmp_path):
    from keystone_tpu.__main__ import main as cli_main

    with pytest.raises(SystemExit):
        cli_main(["observe"])
    with pytest.raises(SystemExit):
        cli_main(["observe", str(tmp_path / "nowhere")])


def test_summarize_counts_each_node_of_a_memory_only_log():
    pipe = three_node_pipe()
    from keystone_tpu.observe.report import summarize

    with events.run() as log:  # memory-only: no dir
        instrument(pipe, sync=True)(jnp.ones((16, 4)))
        nodes = summarize(log.records)["nodes"]
    assert set(nodes) == {"00:add1", "01:mul2", "02:sub"}
    assert all(v["calls"] == 1 and v["total_s"] >= 0 for v in nodes.values())


# ------------------------------------------- logging/profiling satellites


def test_log_time_emits_duration_on_failure(tmp_path):
    from keystone_tpu.core.logging import log_time

    with events.run(str(tmp_path)) as log:
        with pytest.raises(KeyError):
            with log_time("doomed step"):
                raise KeyError("x")
        with log_time("fine step"):
            pass
        run_dir = log.run_dir
    spans = [e for e in events.read_events(run_dir) if e["event"] == "span"]
    assert len(spans) == 2
    assert spans[0]["label"] == "doomed step" and spans[0]["status"] == "failed"
    assert spans[1]["status"] == "ok"
    assert all(e["wall_s"] >= 0 for e in spans)


def test_get_logger_honors_env_level_and_is_idempotent(monkeypatch):
    import keystone_tpu.core.logging as klog

    root = __import__("logging").getLogger("keystone_tpu")
    saved_level, saved_handlers = root.level, list(root.handlers)
    try:
        root.handlers = []
        monkeypatch.setattr(klog, "_CONFIGURED", False)
        monkeypatch.setenv("KEYSTONE_LOG_LEVEL", "DEBUG")
        results = []

        def configure():
            results.append(klog.get_logger("keystone_tpu.test"))

        threads = [threading.Thread(target=configure) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert root.level == 10  # DEBUG
        assert len(root.handlers) == 1  # concurrent first calls: ONE handler
    finally:
        root.level = saved_level
        root.handlers = saved_handlers


def test_trace_env_gate_and_degraded_start(monkeypatch, tmp_path):
    from keystone_tpu.core import profiling

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls.append(d)
    )
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    # kill switch: explicit dir is still a no-op
    monkeypatch.setenv(profiling.ENV_TRACE_DIR, "0")
    with profiling.trace(str(tmp_path)):
        pass
    assert calls == []
    # env provides the default dir when enabled
    monkeypatch.setenv(profiling.ENV_TRACE_DIR, str(tmp_path))
    with profiling.trace():
        pass
    assert calls == [str(tmp_path)]
    # a failing start_trace degrades to a warning, not an abort
    def boom(d):
        raise RuntimeError("dir not writable")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    ran = []
    with profiling.trace(str(tmp_path)):
        ran.append(True)
    assert ran == [True]


def test_fusion_pass_records_rewrite(tmp_path):
    from keystone_tpu.core.fusion import optimize
    from keystone_tpu.ops.images import (
        Convolver,
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )

    rng = np.random.default_rng(0)
    filters = jnp.asarray(rng.normal(size=(4, 27)).astype(np.float32))
    means = jnp.asarray(rng.normal(size=(27,)).astype(np.float32))
    pipe = (
        Convolver(
            filters=filters,
            whitener_means=means,
            patch_size=3,
            normalize_patches=True,
        )
        >> SymmetricRectifier(alpha=0.25)
        >> Pooler(stride=13, pool_size=14)
        >> ImageVectorizer()
    )
    before = metrics.get_registry().counter(
        "fusion_rewrites", rule="conv_rectify_pool"
    ).value
    with events.run(str(tmp_path)) as log:
        optimize(pipe)
        run_dir = log.run_dir
    after = metrics.get_registry().counter(
        "fusion_rewrites", rule="conv_rectify_pool"
    ).value
    assert after == before + 1
    opt = [e for e in events.read_events(run_dir) if e["event"] == "optimize"]
    assert opt and opt[0]["nodes_before"] == 4 and opt[0]["nodes_after"] == 2


def test_events_file_lines_are_valid_json(tmp_path):
    run_dir = _make_run(tmp_path)
    with open(os.path.join(run_dir, events.EVENTS_FILE)) as f:
        for line in f:
            json.loads(line)


# ------------------------------------------------- live telemetry (PR 5)


def test_steplog_writes_steps_jsonl_and_derives_rates(tmp_path):
    from keystone_tpu.observe import telemetry

    with events.run(str(tmp_path)) as log:
        sl = telemetry.active_step_log()
        assert sl is not None and telemetry.active_step_log() is sl  # bound once
        sl.step(step=1, loss=2.5, tokens=1000, wall_s=0.5, flops=1e9)
        sl.step(step=2, loss=2.0, tokens=1000, wall_s=0.25,
                hbm_peak_bytes=123456)
        run_dir = log.run_dir
    recs = events.read_jsonl(os.path.join(run_dir, "steps.jsonl"))
    assert [r["step"] for r in recs] == [1, 2]
    assert recs[0]["loss"] == 2.5
    assert recs[0]["tokens_per_s"] == pytest.approx(2000.0)
    assert recs[0]["tflops_per_s"] == pytest.approx(2e-3)
    assert "mfu" not in recs[0]  # a CPU run records no utilization
    assert recs[1]["hbm_peak_bytes"] == 123456
    assert all(r["run"] == recs[0]["run"] for r in recs)
    # the stream also feeds the metrics registry for dashboards
    snap = metrics.get_registry().snapshot()
    assert snap["telemetry_last_step{source=train}"] == 2.0


def test_steplog_no_sink_one_global_read_no_io(monkeypatch):
    from keystone_tpu.observe import telemetry

    assert events.active() is None  # suite invariant: no ambient sink
    reads = []
    monkeypatch.setattr(
        telemetry._events, "active", lambda: reads.append(1) or None
    )

    def boom(self, *a, **k):  # constructing a StepLog would mean file I/O
        raise AssertionError("StepLog built with no sink active")

    monkeypatch.setattr(telemetry.StepLog, "__init__", boom)
    assert telemetry.active_step_log() is None
    assert len(reads) == 1  # exactly ONE global read on the hot path


def test_lm_train_emits_step_telemetry(tmp_path):
    """Acceptance: an LM run with a sink active produces per-step
    loss/tokens-per-sec/MFU records in steps.jsonl."""
    import jax

    from keystone_tpu.models import lm_transformer as lm

    corpus = lm.synthetic_corpus(512, 64, seed=0)
    model = lm.TransformerLM.create(
        jax.random.key(0), vocab=64, max_seq=16, dim=32, depth=1,
        num_heads=2,
    )
    with events.run(str(tmp_path)) as log:
        model, losses = lm.train(
            model, corpus, steps=3, batch=4, seq=16, lr=1e-3
        )
        run_dir = log.run_dir
    recs = [
        r
        for r in events.read_jsonl(os.path.join(run_dir, "steps.jsonl"))
        if r.get("source") == "train"
    ]
    assert [r["step"] for r in recs] == [1, 2, 3]
    assert [r["loss"] for r in recs] == pytest.approx(losses)
    assert all(
        r["tokens"] == 64 and r["tokens_per_s"] > 0 and r["wall_s"] > 0
        and "mfu" not in r  # a CPU run records no utilization
        for r in recs
    )


def test_plan_chunked_execution_records_stream_telemetry(tmp_path):
    from keystone_tpu.observe import telemetry
    from keystone_tpu.plan.ir import Plan, chain_from
    from keystone_tpu.plan.executor import run_plan

    pipe = three_node_pipe()
    x = jnp.ones((32, 4))
    expect = np.asarray(pipe(x))
    plan = Plan(prefix=chain_from(pipe), chunk_size=8)
    with events.run(str(tmp_path)) as log:
        got = np.asarray(run_plan(plan, x))
        run_dir = log.run_dir
    assert np.array_equal(got, expect)
    recs = [
        r
        for r in events.read_jsonl(os.path.join(run_dir, "steps.jsonl"))
        if r.get("source") == "plan"
    ]
    assert recs and recs[0]["rows"] == 32 and recs[0]["chunks"] == 4
    assert recs[0]["chunk_size"] == 8 and recs[0]["rows_per_s"] > 0
    snap = metrics.get_registry().snapshot()
    assert snap.get("plan_stage_depth") is not None


def test_timer_percentiles_from_bounded_reservoir():
    t = metrics.Timer()
    for ms in range(1, 101):  # 1..100 ms
        t.observe(ms / 1e3)
    s = t.summary()
    assert s["p50_s"] == pytest.approx(0.050, abs=0.002)
    assert s["p95_s"] == pytest.approx(0.095, abs=0.002)
    assert s["p99_s"] == pytest.approx(0.099, abs=0.002)
    assert t.percentile(50) == s["p50_s"]
    # reservoir stays bounded on long runs
    for _ in range(5000):
        t.observe(0.01)
    assert len(t.samples) <= metrics._RESERVOIR_CAP
    assert t.count == 5100


def test_series_key_escapes_label_values_roundtrip():
    hostile = "Node{f=g, h}, x=1"
    key = metrics._series_key("calls", {"node": hostile, "k": "plain"})
    name, labels = metrics.parse_series_key(key)
    assert name == "calls"
    assert labels == {"node": hostile, "k": "plain"}
    # two hostile values that would collide unescaped stay distinct
    k1 = metrics._series_key("c", {"a": "x,b=y"})
    k2 = metrics._series_key("c", {"a": "x", "b": "y"})
    assert k1 != k2
    # plain keys are unchanged (snapshot stability)
    assert metrics._series_key("calls", {"node": "00:add1"}) == (
        "calls{node=00:add1}"
    )
    reg = metrics.MetricsRegistry()
    reg.counter("calls", node=hostile).inc()
    assert reg.counter("calls", node=hostile).value == 1


def test_read_events_tolerates_torn_final_line(tmp_path):
    import logging

    with events.run(str(tmp_path)) as log:
        log.emit("node", node="00:x", wall_s=0.1, status="ok")
        run_dir = log.run_dir
    path = os.path.join(run_dir, events.EVENTS_FILE)
    whole = open(path).read()
    # SIGKILL mid-write: the final record is torn mid-JSON, no newline
    with open(path, "w") as f:
        f.write(whole + '{"ts": 123456.0, "run": "abc", "event": "nod')
    records = []
    handler = logging.Handler()
    handler.emit = lambda rec: records.append(rec)
    logger = logging.getLogger("keystone_tpu.observe")
    logger.addHandler(handler)
    try:
        evs = events.read_events(run_dir)
    finally:
        logger.removeHandler(handler)
    kinds = [e["event"] for e in evs]
    assert kinds[0] == "run_start" and "node" in kinds  # intact records kept
    assert len(evs) == len(whole.splitlines())  # torn tail: skipped, not raised
    assert not any(e.get("run") == "abc" for e in evs)
    assert any("unparseable" in r.getMessage() for r in records)  # warned


def test_device_memory_sampler_degrades_on_cpu_and_tracks_watermarks(
    monkeypatch,
):
    from keystone_tpu.observe import devices as obs_devices

    # CPU backend: memory_stats() is None -> empty sample, no crash
    mon = obs_devices.DeviceMemoryMonitor()
    assert obs_devices.sample_device_memory() == []
    assert mon.sample() == []
    assert mon.peak_bytes() is None and mon.maybe_sample() is None

    # fake accelerator stats: watermark ratchets up, never down
    current = {"v": 100}

    def fake_stats(dev):
        v = current["v"]
        return {"bytes_in_use": v, "peak_bytes_in_use": v, "bytes_limit": 1000}

    monkeypatch.setattr(obs_devices, "_device_stats", fake_stats)
    mon = obs_devices.DeviceMemoryMonitor(emit_events=False)
    mon.sample()
    assert mon.peak_bytes() == 100
    current["v"] = 900
    mon.sample()
    assert mon.peak_bytes() == 900
    current["v"] = 300
    mon.sample()
    assert mon.peak_bytes() == 900  # a lower sample can't lower the peak
    dev0 = next(iter(mon.watermarks))
    snap = metrics.get_registry().snapshot()
    assert snap[f"hbm_peak_bytes{{device={dev0}}}"] == 900.0


def test_observe_top_once_cli_smoke(tmp_path, capsys):
    from keystone_tpu.__main__ import main as cli_main
    from keystone_tpu.observe import telemetry

    with events.run(str(tmp_path)) as log:
        sl = telemetry.active_step_log()
        for i in range(5):
            sl.step(step=i + 1, loss=3.0 - 0.1 * i, tokens=256,
                    wall_s=0.01, flops=1e9)
        log.emit(
            "device_memory",
            devices=[{
                "device": "tpu:0", "kind": "TPU v5 lite",
                "bytes_in_use": 2 << 30, "peak_bytes_in_use": 3 << 30,
                "bytes_limit": 16 << 30,
            }],
            peak_bytes=3 << 30,
        )
        from keystone_tpu.resilience.emit import decision

        decision("retry", label="unit")
        run_dir = log.run_dir
    cli_main(["observe", "top", run_dir, "--once"])
    out = capsys.readouterr().out
    assert "steps 5" in out
    assert "loss" in out and "2.6" in out  # last loss rendered
    assert "tpu:0" in out and "peak" in out  # HBM watermark line
    assert "retry=1" in out  # resilience counter
    # base-dir form resolves to the newest run
    cli_main(["observe", "top", str(tmp_path), "--once"])
    assert "steps 5" in capsys.readouterr().out
    # usage
    with pytest.raises(SystemExit):
        cli_main(["observe", "top"])


def test_top_and_report_keep_plan_stream_out_of_step_stats(tmp_path):
    """Plan chunk-stream records (source="plan") ride a process-lifetime
    sequence and whole-stream walls — they must not pollute the train
    step rate/percentiles in `observe top` or the report."""
    from keystone_tpu.observe import report as observe_report
    from keystone_tpu.observe import telemetry
    from keystone_tpu.observe.top import summarize as top_summarize

    with events.run(str(tmp_path)) as log:
        sl = telemetry.active_step_log()
        for i in range(4):
            sl.step(step=i + 1, loss=2.0 - 0.1 * i, tokens=128, wall_s=0.01)
        # a plan stream lands between train steps: huge wall, global seq
        sl.step(step=9001, source="plan", wall_s=30.0, rows=4096,
                rows_per_s=136.5, chunks=8, chunk_size=512)
        run_dir = log.run_dir
    steps = events.read_jsonl(os.path.join(run_dir, "steps.jsonl"))
    state = top_summarize(steps, events.read_events(run_dir))
    assert state["last_step"] == 4  # not the plan stream's 9001
    assert state["n_steps"] == 4
    assert state["plan_streams"] == 1
    assert len(state["losses"]) == 4
    text = observe_report.render(run_dir)
    assert "4 step record(s), last step 4" in text
    # per-step p99 stays in the per-step regime (ms), not the plan
    # stream's 30 s wall
    assert "p99 10.0 ms" in text
    assert "plan chunk streams: 1 record(s), 4096 row(s)" in text


def test_step_tracer_env_windows_and_sigusr2(monkeypatch, tmp_path):
    from keystone_tpu.observe import tracing

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: calls.append(("stop",))
    )
    monkeypatch.setenv(tracing.ENV_PROFILE_STEPS, "3:2")
    tracer = tracing.StepTracer.from_env(log_dir=str(tmp_path))
    assert tracer is not None
    for i in range(8):
        tracer.step(i)
    assert [c[0] for c in calls] == ["start", "stop"]
    assert calls[0][1] == os.path.join(str(tmp_path), "step_3")
    # SIGUSR2-style on-demand window: armed flag fires at the next step
    calls.clear()
    tracer.request(steps=1)
    tracer.step(8)
    tracer.step(9)
    assert [c[0] for c in calls] == ["start", "stop"]
    assert calls[0][1] == os.path.join(str(tmp_path), "step_8")
    # a request landing MID-window stays armed and fires at the first
    # free step boundary instead of being silently dropped
    calls.clear()
    tracer.request(steps=2)
    tracer.step(10)  # starts the on-demand window (steps 10-11)
    tracer.request(steps=1)  # arrives while the window is active
    tracer.step(11)
    tracer.step(12)  # first free boundary: pending request fires here
    tracer.step(13)
    assert [c[0] for c in calls] == ["start", "stop", "start", "stop"]
    assert calls[2][1] == os.path.join(str(tmp_path), "step_12")
    tracer.close()
    # malformed spec: windows dropped with a warning, not a crash
    monkeypatch.setenv(tracing.ENV_PROFILE_STEPS, "nonsense")
    assert tracing.StepTracer.from_env(log_dir=str(tmp_path)) is None
    with pytest.raises(ValueError):
        tracing.parse_windows("12")
    with pytest.raises(ValueError):
        tracing.parse_windows("5:0")
    assert tracing.parse_windows("120:10,5:1") == [(5, 1), (120, 10)]


def test_step_tracer_degrades_when_profiler_unavailable(monkeypatch, tmp_path):
    from keystone_tpu.observe import tracing

    def broken(d):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", broken)
    tracer = tracing.StepTracer(windows=[(0, 2)], log_dir=str(tmp_path))
    for i in range(4):
        tracer.step(i)  # must not raise
    tracer.close()


def test_metrics_dump_merge_cluster_totals():
    from keystone_tpu.parallel.multihost import merge_metric_dumps

    a, b = metrics.MetricsRegistry(), metrics.MetricsRegistry()
    a.counter("rows").inc(100)
    b.counter("rows").inc(200)
    a.gauge("hbm_peak").set(1000.0)
    b.gauge("hbm_peak").set(2000.0)
    for k in range(10):
        a.timer("step_s").observe(0.010 + 0.001 * k)
        b.timer("step_s").observe(0.020 + 0.001 * k)
    merged = merge_metric_dumps([a.dump(), b.dump()])
    assert merged["rows"] == 300  # counters sum
    assert merged["hbm_peak"] == 2000.0  # gauges: cluster max (watermark)
    t = merged["step_s"]
    assert t["count"] == 20
    assert t["min_s"] == pytest.approx(0.010)
    assert t["max_s"] == pytest.approx(0.029)
    # percentiles come from POOLED samples: p95 must sit in host b's range
    assert 0.020 <= t["p95_s"] <= 0.029


def test_rollup_metrics_single_host_writes_cluster_file(tmp_path):
    from keystone_tpu.parallel.multihost import rollup_metrics

    metrics.get_registry().counter("rollup_unit_rows").inc(7)
    with events.run(str(tmp_path)) as log:
        merged = rollup_metrics(log.run_dir)
        run_dir = log.run_dir
    assert merged is not None and merged["hosts"] == 1
    assert merged["metrics"]["rollup_unit_rows"] == 7
    with open(os.path.join(run_dir, "metrics_cluster.json")) as f:
        on_disk = json.load(f)
    assert on_disk["metrics"]["rollup_unit_rows"] == 7
    rolls = [
        e for e in events.read_events(run_dir)
        if e["event"] == "metrics_rollup"
    ]
    assert rolls and rolls[0]["hosts"] == 1
    # the report renders the roll-up section
    from keystone_tpu.observe.report import render

    assert "cluster metrics roll-up" in render(run_dir)


@pytest.mark.multihost
def test_multihost_metrics_rollup_two_processes(tmp_path, free_tcp_port):
    """Two real processes: each records host-local metrics, host 0
    gathers over the coordination service and writes cluster totals
    (reuses the multihost_worker.py launch harness)."""
    import subprocess
    import sys as _sys
    from pathlib import Path

    worker = Path(__file__).with_name("multihost_metrics_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p
        for p in (str(worker.parent.parent), env.get("PYTHONPATH"))
        if p
    )
    procs = [
        subprocess.Popen(
            [_sys.executable, str(worker), str(pid), "2",
             str(free_tcp_port), str(tmp_path)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    logs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        logs.append(stdout)
    if any(p.returncode == 42 for p in procs):
        pytest.skip(
            "rig cannot join a 2-process jax.distributed runtime:\n"
            + "\n".join(logs)
        )
    assert all(p.returncode == 0 for p in procs), "\n".join(logs)
    with open(os.path.join(str(tmp_path), "metrics_cluster.json")) as f:
        merged = json.load(f)
    assert merged["hosts"] == 2
    m = merged["metrics"]
    assert m["mh_rows"] == 300  # 100 (host 0) + 200 (host 1)
    assert m["mh_calls{host=0}"] == 1 and m["mh_calls{host=1}"] == 2
    assert m["mh_hbm_peak"] == 2000.0  # max across hosts
    t = m["mh_step_seconds"]
    assert t["count"] == 20 and "p95_s" in t
