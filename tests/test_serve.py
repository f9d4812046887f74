"""serve/ subsystem tests: SLO micro-batching (injected clock — no
sleeps), AOT export pad/trim equivalence, continuous-batching decode
parity vs single-stream generate, fitted-pipeline serialization with
loud spec-drift failure, the serve fault sites, the serving panel in
``observe top``, and the HTTP server CLI smoke (real request + clean
SIGTERM drain)."""

import json
import math
import os
import pickle
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.core.pipeline import jit_apply
from keystone_tpu.core.serialization import (
    PipelineSpecError,
    load_fitted,
    load_pipeline,
    save_fitted,
    _MAGIC_FITTED,
)
from keystone_tpu.models.lm.decode import generate
from keystone_tpu.models.lm.model import TransformerLM
from keystone_tpu.observe import metrics as observe_metrics
from keystone_tpu.resilience import faults
from keystone_tpu.serve.decode_loop import DecodeLoop
from keystone_tpu.serve.export import ExportedApply, export_pipeline
from keystone_tpu.serve.queue import (
    DEFAULT_BUCKETS,
    DEFAULT_DEADLINE_MS,
    MicroBatcher,
    RequestShed,
    buckets_from_env,
    deadline_ms_from_env,
)


def _counter(name: str) -> float:
    return observe_metrics.get_registry().snapshot().get(name, 0)


class Clock:
    """Injected clock: the batcher's scheduling is a pure function of
    (pending set, now) — tests advance time explicitly, never sleep."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


class Recorder:
    """Dispatch stub: records every batch shape, returns rows doubled."""

    def __init__(self):
        self.shapes = []

    def __call__(self, batch):
        self.shapes.append(tuple(batch.shape))
        return np.asarray(batch) * 2.0


def _rows(n: int, d: int = 3, fill: float = 1.0) -> np.ndarray:
    return np.full((n, d), fill, np.float32)


# ---------------------------------------------------------------------------
# micro-batcher: injected-clock scheduling


def test_batcher_holds_until_deadline_never_past_it():
    """The SLO contract: a sub-bucket batch waits for more traffic but
    the batcher itself NEVER plans to hold a request past its deadline."""
    clock = Clock()
    disp = Recorder()
    mb = MicroBatcher(
        disp, buckets=(8,), deadline_ms=10.0, clock=clock, start=False
    )
    mb.submit(_rows(2))
    clock.t = 0.004
    mb.submit(_rows(3))
    # before the oldest request's deadline: nothing is due
    assert mb.pump(now=0.0099) == 0
    assert disp.shapes == []
    # the planned sleep is exactly to the OLDEST deadline, never past it
    assert mb.wait_s(now=0.004) == pytest.approx(0.006)
    # at the deadline the coalesced batch ships as ONE dispatch
    clock.t = 0.010
    assert mb.pump(now=0.010) == 1
    assert disp.shapes == [(8, 3)]
    assert mb.wait_s() is None


def test_batcher_full_bucket_never_waits():
    clock = Clock()
    disp = Recorder()
    mb = MicroBatcher(
        disp, buckets=(4,), deadline_ms=1000.0, clock=clock, start=False
    )
    futs = [mb.submit(_rows(1, fill=float(i))) for i in range(4)]
    # bucket filled: due immediately, deadline irrelevant
    assert mb.pump(now=0.0) == 1
    assert disp.shapes == [(4, 3)]
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result(0), _rows(1, fill=i) * 2)


def test_batcher_bucket_padding_trimmed_from_responses():
    clock = Clock()
    disp = Recorder()
    mb = MicroBatcher(
        disp, buckets=(2, 8), deadline_ms=5.0, clock=clock, start=False
    )
    names = ("serve_batches", "serve_rows", "serve_pad_rows")
    before = {name: _counter(name) for name in names}
    f1 = mb.submit(_rows(3, fill=1.0))
    f2 = mb.submit(_rows(2, fill=5.0))
    clock.t = 0.005
    assert mb.pump(now=0.005) == 1
    # 5 rows pad to the 8-bucket; each requester gets ONLY its own rows,
    # values exact, pad rows never leak
    assert disp.shapes == [(8, 3)]
    np.testing.assert_array_equal(f1.result(0), _rows(3, fill=1.0) * 2)
    np.testing.assert_array_equal(f2.result(0), _rows(2, fill=5.0) * 2)
    # the counters a batch fill is read from: one batch, 5 rows of 8
    batches, rows, pad = (_counter(name) - before[name] for name in names)
    assert (batches, rows, pad) == (1, 5, 3)


def test_batcher_burst_coalesces_to_ceil_n_over_bucket():
    clock = Clock()
    disp = Recorder()
    mb = MicroBatcher(
        disp, buckets=(8,), deadline_ms=10.0, clock=clock, start=False
    )
    n = 27
    futs = [mb.submit(_rows(1)) for _ in range(n)]
    clock.t = 0.010
    ran = mb.pump(now=0.010)
    assert ran <= math.ceil(n / 8)
    assert len(disp.shapes) == ran
    assert all(f.done() for f in futs)


def test_batcher_never_splits_a_request():
    clock = Clock()
    disp = Recorder()
    mb = MicroBatcher(
        disp, buckets=(8,), deadline_ms=1.0, clock=clock, start=False
    )
    f1 = mb.submit(_rows(5, fill=1.0))
    f2 = mb.submit(_rows(6, fill=2.0))
    clock.t = 0.001
    assert mb.pump(now=0.001) == 2  # 5+6 > 8: two dispatches, no split
    assert disp.shapes == [(8, 3), (8, 3)]
    np.testing.assert_array_equal(f1.result(0), _rows(5, fill=1.0) * 2)
    np.testing.assert_array_equal(f2.result(0), _rows(6, fill=2.0) * 2)


def test_batcher_oversized_request_ships_solo():
    clock = Clock()
    disp = Recorder()
    mb = MicroBatcher(
        disp, buckets=(4,), deadline_ms=0.0, clock=clock, start=False
    )
    f = mb.submit(_rows(10))
    assert mb.pump(now=0.0) == 1
    # bigger than every bucket: dispatched alone, unpadded (the exported
    # apply streams it through bucket-size chunks downstream)
    assert disp.shapes == [(10, 3)]
    assert f.result(0).shape == (10, 3)


def test_batcher_dispatch_error_fans_out_to_every_request():
    clock = Clock()

    def boom(batch):
        raise RuntimeError("device fell over")

    mb = MicroBatcher(
        boom, buckets=(8,), deadline_ms=0.0, clock=clock, start=False
    )
    f1, f2 = mb.submit(_rows(1)), mb.submit(_rows(2))
    mb.pump(now=0.0)
    with pytest.raises(RuntimeError, match="fell over"):
        f1.result(0)
    with pytest.raises(RuntimeError, match="fell over"):
        f2.result(0)


def test_batcher_survives_uncoalescable_rows():
    """A request whose row shape won't concatenate with its batch mates
    fails ITS futures — the batching machinery stays alive and serves
    the next well-formed batch (a dead batch thread would hang every
    later request while /healthz still said ok)."""
    clock = Clock()
    disp = Recorder()
    mb = MicroBatcher(
        disp, buckets=(8,), deadline_ms=0.0, clock=clock, start=False
    )
    f1 = mb.submit(np.ones((1, 3), np.float32))
    f2 = mb.submit(np.ones((1, 7), np.float32))  # width mismatch
    mb.pump(now=0.0)
    with pytest.raises(ValueError):
        f1.result(0)
    with pytest.raises(ValueError):
        f2.result(0)
    # the batcher is still functional afterwards
    f3 = mb.submit(_rows(2))
    assert mb.pump(now=0.0) == 1
    np.testing.assert_array_equal(f3.result(0), _rows(2) * 2)


def test_batcher_close_drains_then_sheds():
    clock = Clock()
    disp = Recorder()
    mb = MicroBatcher(
        disp, buckets=(8,), deadline_ms=1000.0, clock=clock, start=False
    )
    f = mb.submit(_rows(2))
    mb.close(drain=True)
    np.testing.assert_array_equal(f.result(0), _rows(2) * 2)
    late = mb.submit(_rows(1))
    with pytest.raises(RequestShed):
        late.result(0)


def test_batcher_close_without_drain_sheds_pending():
    clock = Clock()
    disp = Recorder()
    mb = MicroBatcher(
        disp, buckets=(8,), deadline_ms=1000.0, clock=clock, start=False
    )
    f = mb.submit(_rows(2))
    mb.close(drain=False)
    with pytest.raises(RequestShed):
        f.result(0)
    assert disp.shapes == []


def test_batcher_threaded_end_to_end():
    """The daemon-thread form against the real clock: concurrent submits
    coalesce and resolve (the only wall-clock test — bounded by the
    5 ms deadline, not polling sleeps)."""
    disp = Recorder()
    mb = MicroBatcher(disp, buckets=(8,), deadline_ms=5.0)
    futs = []

    def client(i):
        futs.append(mb.submit(_rows(1, fill=float(i))))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    outs = [f.result(timeout=30.0) for f in futs]
    assert all(o.shape == (1, 3) for o in outs)
    mb.close()
    assert len(disp.shapes) <= 8


def test_env_knobs_parse_and_reject_garbage(monkeypatch):
    monkeypatch.setenv("KEYSTONE_SERVE_DEADLINE_MS", "7.5")
    monkeypatch.setenv("KEYSTONE_SERVE_BUCKETS", "16,4,32")
    assert deadline_ms_from_env() == 7.5
    assert buckets_from_env() == (4, 16, 32)
    monkeypatch.setenv("KEYSTONE_SERVE_DEADLINE_MS", "not-a-number")
    monkeypatch.setenv("KEYSTONE_SERVE_BUCKETS", "8,-1")
    assert deadline_ms_from_env() == DEFAULT_DEADLINE_MS
    assert buckets_from_env() == DEFAULT_BUCKETS


# ---------------------------------------------------------------------------
# fitted-pipeline serialization: round-trip + loud spec drift


@pytest.fixture(scope="module")
def demo_pipe():
    """One small fitted mnist-demo pipeline shared across the module
    (fit once — every consumer treats it as read-only)."""
    from keystone_tpu.serve.server import _fit_mnist_demo

    pipe, sample = _fit_mnist_demo(96, num_ffts=2)
    return pipe, np.asarray(sample)


def test_save_fitted_round_trip_bit_exact(tmp_path, demo_pipe, rng):
    pipe, sample = demo_pipe
    path = str(tmp_path / "fitted.kst")
    spec = save_fitted(pipe, path, corpus="synthetic-96")
    assert spec["leaves"], spec
    loaded, meta = load_fitted(path, with_meta=True)
    assert meta == {"corpus": "synthetic-96"}
    x = rng.normal(size=(4, sample.shape[1])).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(jit_apply(pipe, x)), np.asarray(jit_apply(loaded, x))
    )


def test_load_fitted_spec_drift_is_loud(tmp_path, demo_pipe):
    pipe, _ = demo_pipe
    path = str(tmp_path / "fitted.kst")
    save_fitted(pipe, path)
    # simulate code drift: the stored spec no longer matches what the
    # current classes reconstruct (a leaf changed shape)
    with open(path, "rb") as f:
        f.read(len(_MAGIC_FITTED))
        payload = pickle.load(f)
    payload["spec"]["leaves"][0]["shape"] = [1, 2, 3]
    with open(path, "wb") as f:
        f.write(_MAGIC_FITTED)
        pickle.dump(payload, f)
    with pytest.raises(PipelineSpecError, match="spec drift"):
        load_fitted(path)
    assert issubclass(PipelineSpecError, ValueError)


def test_load_fitted_formats(tmp_path, demo_pipe):
    pipe, sample = demo_pipe
    path = str(tmp_path / "fitted.kst")
    save_fitted(pipe, path)
    # load_pipeline accepts the fitted format (spec still verified)
    loaded = load_pipeline(path)
    np.testing.assert_array_equal(
        np.asarray(jit_apply(pipe, sample)),
        np.asarray(jit_apply(loaded, sample)),
    )
    # a bare non-checkpoint file refuses loudly
    bad = tmp_path / "junk.kst"
    bad.write_bytes(b"not a checkpoint")
    with pytest.raises(ValueError, match="not a keystone_tpu"):
        load_fitted(str(bad))


# ---------------------------------------------------------------------------
# decode satellites: unequal-length prompts + per-sequence EOS early exit


@pytest.fixture(scope="module")
def lm():
    return TransformerLM.create(
        jax.random.key(0), vocab=64, max_seq=96, dim=32, depth=2,
        num_heads=2,
    )


def test_generate_default_path_equals_explicit_full_lengths(lm):
    """prompt_lens covering every row exactly is the identity: the
    classic scan path stays bit-identical with the new arguments off."""
    p = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    base = generate(lm, p, max_new=6)
    full = generate(
        lm, p, max_new=6, prompt_lens=jnp.asarray([4], jnp.int32)
    )
    np.testing.assert_array_equal(np.asarray(base), np.asarray(full))


#: unequal-length prompt set shared by the batched-generate parity test
#: and the decode-pool parity test, so the per-width solo ``generate``
#: programs compile ONCE for the module (tier-1 wall budget).
PROMPTS = [[7, 3, 9], [11, 5, 2, 8, 4], [6, 1, 2]]


def _solo(lm, p, max_new: int = 5) -> np.ndarray:
    return np.asarray(
        generate(lm, jnp.asarray([p], jnp.int32), max_new=max_new)
    )[0]


def test_generate_unequal_length_batch_matches_singles(lm):
    """Right-padded unequal prompts with per-row lengths: every row's
    output is bit-identical to decoding that prompt alone."""
    width = max(len(p) for p in PROMPTS)
    padded = np.zeros((len(PROMPTS), width), np.int32)
    for i, p in enumerate(PROMPTS):
        padded[i, : len(p)] = p
    lens = jnp.asarray([len(p) for p in PROMPTS], jnp.int32)
    batched = np.asarray(
        generate(lm, jnp.asarray(padded), max_new=5, prompt_lens=lens)
    )
    for i, p in enumerate(PROMPTS):
        np.testing.assert_array_equal(batched[i], _solo(lm, p))


def test_generate_eos_early_exit_freezes_finished_rows(lm):
    p = jnp.asarray([[1, 2, 3], [9, 8, 7]], jnp.int32)
    base = np.asarray(generate(lm, p, max_new=8))
    # an eos_id that never appears: the early-exit program must match
    # the classic scan bit-exactly (greedy ignores the key schedule)
    never = int(np.setdiff1d(np.arange(64), base.ravel())[0])
    with_eos = np.asarray(generate(lm, p, max_new=8, eos_id=never))
    np.testing.assert_array_equal(base, with_eos)
    # an eos_id the greedy decode actually emits: the row freezes at its
    # first EOS (EOS-filled after), rows before it are untouched
    hit = int(base[0, 2])
    out = np.asarray(generate(lm, p, max_new=8, eos_id=hit))
    row = out[0]
    k = int(np.argmax(row == hit))
    np.testing.assert_array_equal(row[: k + 1], base[0, : k + 1])
    assert (row[k:] == hit).all()


# ---------------------------------------------------------------------------
# continuous-batching decode loop


def test_decode_loop_matches_single_stream_generate(lm):
    """THE continuous-batching correctness claim: prompts joining and
    retiring mid-flight through the shared slot pool produce exactly the
    tokens each would get decoded alone (greedy)."""
    loop = DecodeLoop(lm, slots=2, s_max=96, max_new=5)
    outs = loop.run(PROMPTS, max_new=5)
    assert len(outs) == len(PROMPTS)
    for p, got in zip(PROMPTS, outs):
        np.testing.assert_array_equal(np.asarray(got), _solo(lm, p))
    # 3 sequences through 2 slots: the pool was reused, and aggregate
    # accounting saw more than one slot active on average
    assert _counter("serve_decode_finished") >= 3
    assert loop.tokens_out == len(PROMPTS) * 5


def test_decode_loop_eos_retires_early(lm):
    base = np.asarray(
        generate(lm, jnp.asarray([[7, 3, 9]], jnp.int32), max_new=8)
    )[0]
    eos = int(base[3])
    loop = DecodeLoop(lm, slots=2, s_max=96, max_new=8, eos_id=eos)
    (out,) = loop.run([[7, 3, 9]], max_new=8)
    out = np.asarray(out)
    # retired at its first EOS: a strict prefix of the unbounded decode,
    # ending in EOS, shorter than max_new
    assert out[-1] == eos and len(out) <= 8
    np.testing.assert_array_equal(out, base[: len(out)])


def test_decode_loop_default_prefill_buckets_cover_s_max(lm):
    """The default bucket ladder reaches s_max: every admissible prompt
    length maps to a pre-compiled prefill width, so warm() really does
    compile everything the loop can need (no per-length recompiles on
    the request path)."""
    loop = DecodeLoop(lm, slots=1, s_max=96, max_new=8)
    assert loop.prefill_buckets[-1] >= 96
    assert all(
        any(w >= n for w in loop.prefill_buckets)
        for n in range(1, loop.max_prompt_len() + 1)
    )


def test_decode_loop_rejects_oversized_prompt(lm):
    loop = DecodeLoop(lm, slots=1, s_max=16, max_new=8)
    fut = loop.submit(np.arange(1, 12, dtype=np.int32))
    with pytest.raises(ValueError, match="s_max"):
        fut.result(0)


def test_decode_loop_int8_kv_pool(lm):
    loop = DecodeLoop(lm, slots=2, s_max=96, max_new=4, kv_dtype="int8")
    assert loop.cache.k.dtype == jnp.int8
    outs = loop.run([[5, 6], [7, 8, 9]], max_new=4)
    assert [len(np.asarray(o)) for o in outs] == [4, 4]


# ---------------------------------------------------------------------------
# AOT export: pad/trim equivalence over buckets


def test_exported_apply_matches_plain_pipeline(demo_pipe, rng):
    pipe, sample = demo_pipe
    exported = ExportedApply(pipe, sample, buckets=(2, 8), optimize=False)
    assert set(exported._compiled) == {2, 8}
    for n in (1, 2, 3, 8):
        x = rng.normal(size=(n, sample.shape[1])).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(exported(x)), np.asarray(jit_apply(pipe, x))
        )


def test_exported_apply_oversized_batch_streams(demo_pipe, rng):
    pipe, sample = demo_pipe
    exported = ExportedApply(pipe, sample, buckets=(4,), optimize=False)
    before = _counter("serve_stream_batches")
    x = rng.normal(size=(11, sample.shape[1])).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(exported(x)), np.asarray(jit_apply(pipe, x))
    )
    assert _counter("serve_stream_batches") == before + 1


def test_exported_apply_rejects_wrong_row_shape(demo_pipe):
    pipe, sample = demo_pipe
    exported = ExportedApply(pipe, sample, buckets=(2,), optimize=False)
    with pytest.raises(ValueError, match="row shape"):
        exported(np.zeros((2, 5), np.float32))


def test_export_pipeline_from_fitted_checkpoint(tmp_path, demo_pipe):
    pipe, sample = demo_pipe
    path = str(tmp_path / "fitted.kst")
    save_fitted(pipe, path)
    exported = export_pipeline(path, sample, buckets=(2,), optimize=False)
    np.testing.assert_array_equal(
        np.asarray(exported(sample)), np.asarray(jit_apply(pipe, sample))
    )


# ---------------------------------------------------------------------------
# serve fault sites: deterministic overload / tail-latency drills


@pytest.fixture
def serve_app(demo_pipe):
    from keystone_tpu.serve.server import ServeApp

    pipe, sample = demo_pipe
    exported = ExportedApply(pipe, sample, buckets=(8,), optimize=False)
    app = ServeApp(exported=exported, deadline_ms=1.0)
    yield app
    app.shutdown()


def test_serve_drop_fault_sheds_exactly_the_keyed_request(serve_app):
    from keystone_tpu.serve.server import OverloadShed

    faults.configure("serve.drop:@1:0")
    try:
        shed_before = _counter("serve_shed")
        ok0 = serve_app.predict(_rows(1, d=784))  # rid 0: admitted
        assert ok0.shape[0] == 1
        with pytest.raises(OverloadShed):  # rid 1: the keyed drop
            serve_app.predict(_rows(1, d=784))
        ok2 = serve_app.predict(_rows(1, d=784))  # rid 2: admitted again
        assert ok2.shape[0] == 1
        assert _counter("serve_shed") == shed_before + 1
    finally:
        faults.reset()


def test_serve_slow_request_injects_tail_latency(serve_app, monkeypatch):
    monkeypatch.setenv("KEYSTONE_SERVE_SLOW_MS", "1")
    faults.configure("serve.slow_request:@0:0")
    try:
        slow_before = _counter("serve_slowed")
        out = serve_app.predict(_rows(1, d=784))
        assert out.shape[0] == 1
        assert _counter("serve_slowed") == slow_before + 1
    finally:
        faults.reset()


def test_serve_fault_sites_registered():
    assert "serve.drop" in faults.SITES
    assert "serve.slow_request" in faults.SITES


# ---------------------------------------------------------------------------
# observe: the serving panel


def test_observe_top_serving_panel(tmp_path):
    from keystone_tpu.observe import top

    run = tmp_path / "run"
    run.mkdir()
    steps = [
        {"ts": 1.0, "source": "serve", "rows": 6, "bucket": 8,
         "batch_fill": 0.75, "wall_s": 0.01, "requests": 3},
        {"ts": 2.0, "source": "serve", "kind": "decode", "tokens": 32,
         "wall_s": 0.2, "slots": 8},
        {"ts": 3.0, "source": "train", "step": 1, "loss": 1.0},
    ]
    events = [
        {"ts": 0.5, "event": "serve", "action": "start", "model": "mnist",
         "port": 8123, "cold_start_s": 0.9},
    ]
    (run / "steps.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in steps)
    )
    (run / "events.jsonl").write_text(
        "".join(json.dumps(e) + "\n" for e in events)
    )
    state = top.summarize(steps, events)
    assert state["serve"] == {
        "batches": 1, "rows": 6, "batch_fill": 0.75, "generations": 1,
        "tokens": 32, "model": "mnist", "port": 8123, "cold_start_s": 0.9,
        "status": "serving",
    }
    screen = top.render(state, str(run))
    assert "serving: mnist @ :8123" in screen
    assert "1 batch(es)  6 row(s)  fill 0.75" in screen
    assert "1 generation(s)  32 tok" in screen
    # serve rows never pollute the train step math
    assert state["n_steps"] == 1 and state["last_step"] == 1


def test_report_renders_serving_sections(tmp_path):
    from keystone_tpu.observe import events as ev_mod
    from keystone_tpu.observe import report, telemetry

    with ev_mod.run(base_dir=str(tmp_path), workload="serve_report") as log:
        log.emit("serve", action="start", model="mnist", port=1)
        sl = telemetry.active_step_log()
        sl.record("serve", rows=6, bucket=8, batch_fill=0.75,
                  wall_s=0.01, requests=3)
        sl.record("serve", kind="decode", tokens=16, wall_s=0.1)
        log.emit("serve", action="stop")
    text = report.render(str(tmp_path))
    assert "serving (request path lifecycle):" in text
    assert "start: model=mnist" in text
    assert "serving stream: 1 batch(es), 6 row(s), mean fill 0.75; " \
           "1 generation(s), 16 token(s)" in text
    # dispatch and generation walls are NOT pooled: a whole-generation
    # wall must never inflate the per-dispatch percentiles
    assert "dispatch wall p50 10.0 ms  p95 10.0 ms" in text
    assert "generation wall p50 100.0 ms" in text


# ---------------------------------------------------------------------------
# the serve CLI smoke: real server, real request, clean SIGTERM drain


def test_serve_cli_smoke_mnist(tmp_path, free_tcp_port, capsys):
    obs = tmp_path / "obs"
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "KEYSTONE_OBSERVE_DIR": str(obs),
        "KEYSTONE_SERVE_DEADLINE_MS": "5",
    }
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "keystone_tpu", "serve", "mnist",
            "--port", str(free_tcp_port), "--synthetic", "96",
            "--buckets", "1,4",
        ],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    base = f"http://127.0.0.1:{free_tcp_port}"
    try:
        # poll /healthz until the server is up (fit + AOT compile first)
        deadline = time.time() + 180
        health = None
        while time.time() < deadline:
            if proc.poll() is not None:
                pytest.fail(
                    "server died: " + proc.stderr.read()[-2000:]
                )
            try:
                with urllib.request.urlopen(
                    base + "/healthz", timeout=5
                ) as r:
                    health = json.loads(r.read())
                break
            except OSError:
                time.sleep(0.25)
        assert health is not None, "server never came up"
        assert health["status"] == "ok"
        # one real request through the mnist pipeline
        rows = np.zeros((2, 784), np.float32).tolist()
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps({"rows": rows}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            payload = json.loads(r.read())
        assert len(payload["predictions"]) == 2
        # clean SIGTERM shutdown: drain and exit 0
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    # the run directory carries the serve lifecycle: the live dashboard
    # (same entry as `python -m keystone_tpu observe top`) renders the
    # serving panel for the run the server just wrote
    runs = list(obs.iterdir()) if obs.is_dir() else []
    assert runs, "no observe run dir written"
    from keystone_tpu.observe import top

    top.main([str(obs), "--once"])
    screen = capsys.readouterr().out
    assert "serving: mnist" in screen, screen
