"""Tests of the eight ``setup_*`` readers
(``benchmarks/layer_metrics/_startup.py``): hand-built startup records
give hand-worked numbers that add up to the ``process`` root's wall,
nothing gives None, the manifest names every cell, and a real process
(``init_backend``, one toy fit through the adapter, an empty compile
cache) answers every reader. Nothing here times anything.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "layer_metrics")]

from harness import find  # noqa: E402

from keystone_tpu.observe import spans  # noqa: E402

# metric -> its part in _startup.parts
STARTUP_METRICS = {
    "setup_import_s": "import", "setup_backend_s": "backend", "setup_trace_s": "trace",
    "setup_lower_s": "lower", "setup_cache_read_s": "cache_read",
    "setup_compile_s": "compile", "setup_first_run_s": "first_run",
    "setup_programs": "programs",
}
MS = 1_000_000  # ns


def rec(name, span, parent, t0_ms, t1_ms, trace="t", **attrs):
    r = {"name": name, "span": span, "trace": trace,
         "t0_ns": int(t0_ms * MS), "t1_ns": int(t1_ms * MS), **attrs}
    if parent:
        r["parent"] = parent
    return r


def hand_built_startup():
    """A process of 10 000 ms. The backend 3000-4000; the adapter's small
    program compiles 4100-4300 under no span; a gap; the fit 5000-10 000:
    `fit.init` 5000-8000 holds the step's trace 5200-7200 (an inner
    function's trace 6000-6500 inside it, and a lowering 7000-7600 that
    starts before the outer trace has ended), a cache read 7600-7900;
    `fit.solve` 8000-10 000 holds one step 8000-9000 with a late compile
    8100-8200. A profiled fit's root and an older record lie beside it."""
    return [
        rec("runtime.init_backend", "b", "p", 3000, 4000, platform="tpu"),
        rec("jit.trace", "a0", "p", 4100, 4150, fun="iota"),
        rec("jit.backend_compile", "a1", "p", 4150, 4300, fun="jit(iota)"),
        rec("fit.init", "i", "f", 5000, 8000),
        rec("jit.trace", "j1", "i", 5200, 7200, fun="_train_step"),
        rec("jit.trace", "j2", "i", 6000, 6500, fun="gmm"),
        rec("jit.lower", "j3", "i", 7000, 7600, fun="jit(_train_step)"),
        rec("jit.cache_read", "j4", "i", 7600, 7900, fun="jit(_train_step)"),
        rec("train.step", "s1", "so", 8000, 9000, trace="train-1", step=1),
        rec("jit.backend_compile", "j5", "s1", 8100, 8200, trace="train-1", fun="jit(add)"),
        rec("fit.solve", "so", "f", 8000, 10000),
        rec("fit", "f", "p", 5000, 10000, steps=8),
        rec("process", "p", None, 0, 10000, t0_source="proc", platform="tpu"),
        rec("fit", "later", None, 20000, 21000, trace="t2"),
        rec("jit.trace", "j9", "later", 20000, 20500, trace="t2"),
        {"name": "written before spans had a clock", "span": "q", "trace": "t"},
    ]


def measured(trace):
    return {"trace": trace, "facts": {"traced_fits": 1}, "sizes": {}, "work": {},
            "programs": {}, "peaks": None}


def test_startup_readers_on_hand_built_records_add_up_to_the_roots_wall(monkeypatch):
    monkeypatch.setattr(spans, "startup_spans", hand_built_startup, raising=False)
    m = measured({"programs_s": {}})
    got = {n: find.layer_metric(n).read(m) for n in STARTUP_METRICS}
    assert got == {
        "setup_import_s": 3.0,
        "setup_backend_s": 1.0,
        # 50 outside a span; the step's 2000 less the inner 500 and less the
        # 200 the lowering (started later) takes; the inner 500
        "setup_trace_s": pytest.approx(0.05 + 1.3 + 0.5),
        "setup_lower_s": pytest.approx(0.6),
        "setup_cache_read_s": pytest.approx(0.3),
        "setup_compile_s": pytest.approx(0.15 + 0.1),
        # the fit's 5000 less its jit time (2000 + 400 + 300 + 100)
        "setup_first_run_s": pytest.approx(2.2),
        "setup_programs": 3,
    }
    helper = find.layer_metric("_startup")
    parts = helper.parts(helper.records(m))
    # what only the root covers after the backend: 4000-4100, 4300-5000
    assert parts["uncovered"] == pytest.approx(0.8)
    seconds = [v for k, v in parts.items() if k not in ("wall", "programs")]
    assert sum(seconds) == pytest.approx(parts["wall"], abs=1e-12) and parts["wall"] == 10.0
    assert sum(v for n, v in got.items() if n != "setup_programs") + parts[
        "uncovered"] == pytest.approx(10.0)


def test_startup_readers_find_nothing_without_a_trace_a_closed_period_or_the_function(monkeypatch):
    monkeypatch.setattr(spans, "startup_spans", hand_built_startup, raising=False)
    for n in STARTUP_METRICS:  # an untraced run reports no per-layer metric
        assert find.layer_metric(n).read(measured(None)) is None
    m = measured({"programs_s": {}})
    # a period that is still open has no `process` root yet
    monkeypatch.setattr(spans, "startup_spans", lambda: [
        r for r in hand_built_startup() if r["name"] != "process"])
    for n in STARTUP_METRICS:
        assert find.layer_metric(n).read(m) is None
    monkeypatch.setattr(spans, "startup_spans", list)  # never opened
    for n in STARTUP_METRICS:
        assert find.layer_metric(n).read(m) is None
    monkeypatch.delattr(spans, "startup_spans")  # a program from before PR 38
    for n in STARTUP_METRICS:
        assert find.layer_metric(n).read(m) is None


def test_a_startup_record_without_a_backend_span_or_with_spans_past_the_root(monkeypatch):
    """No `runtime.init_backend` (an entry point that opened the period
    another way): nothing is called import; a record that ends after the
    root (the bound closed the period in mid-fit) is cut at the root's end."""
    monkeypatch.setattr(spans, "startup_spans", cut_short_startup, raising=False)
    helper = find.layer_metric("_startup")
    parts = helper.parts(helper.records(measured({"programs_s": {}})))
    assert (parts["import"], parts["backend"], parts["uncovered"]) == (0.0, 0.0, 0.1)
    assert (parts["first_run"], parts["lower"], parts["wall"]) == (0.2, 0.2, 0.5)


def cut_short_startup():
    """A period that a bound closed in mid-fit: the fit and a lowering
    end after the root, and nothing is called the backend."""
    return [
        rec("fit", "f", "p", 100, 900),
        rec("jit.lower", "j", "f", 300, 2000, fun="jit(f)"),
        rec("process", "p", None, 0, 500),
    ]


@pytest.mark.parametrize("built", [hand_built_startup, cut_short_startup])
def test_the_operators_line_and_the_readers_say_the_same_of_the_same_records(monkeypatch, built):
    """One sweep (`spans.self_ns`) is behind both, and each keeps its own
    table of names: the `startup {json}` line a process logs and the
    metrics that decide PRs agree part by part."""
    monkeypatch.setattr(spans, "startup_spans", built, raising=False)
    helper = find.layer_metric("_startup")
    parts = helper.parts(helper.records(measured({"programs_s": {}})))
    said = spans.startup_summary(built())
    assert {k: said[k] for k in said if k.endswith("_s") and k != "total_s"} == {
        f"{part}_s": round(parts[part], 3) for part in (
            "import", "backend", "trace", "lower", "cache_read", "compile", "first_run")}
    assert (said["programs"], said["total_s"]) == (parts["programs"], parts["wall"])
    # the same table of names on both sides
    assert set(helper.KEYS) == {"runtime.init_backend", *spans._JIT_EVENTS.values(),
                                "jit.cache_read"}


def test_every_startup_reader_is_in_the_manifest_for_every_cell():
    man = find.manifest()
    cells = [w["name"] for w in man["workloads"]]
    per_layer = {m["name"]: m for m in man["per_layer"]}
    for n in STARTUP_METRICS:
        m = per_layer[n]
        assert m["workloads"] == cells and len(cells) == 5
        assert (m["moves"], m["better"], m["layer"]) == ("setup_s", "lower", "Startup")
        assert m["source"] == "program_span"
        assert m["unit"] == ("n" if n == "setup_programs" else "s")
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    # setup_s is reported by every cell, which is what the driver's rule asks
    (setup,) = [m for m in man["end_to_end"] if m["name"] == "setup_s"]
    assert "workloads" not in setup
    # appended, in ISSUE 38's order, after everything the benchmark had
    assert list(per_layer)[-8:] == list(STARTUP_METRICS)
    assert list(per_layer)[:4] == [
        "device_idle_share.fit", "solve_device_ms_per_fit",
        "nonsolve_device_ms_per_fit", "solve_gemm_roofline"]


IN_A_PROCESS = """
import json, os, sys
sys.path[:0] = [{bench!r}, os.path.join({bench!r}, "layer_metrics")]
from harness import find
from keystone_tpu.core.runtime import init_backend
from keystone_tpu.observe import spans

init_backend()
_cfg, timit = find.config("timit_rf")
toy = {{**find.read_json("configs", "timit_rf.json"), "train_rows": 256,
       "num_cosines": 2, "cosine_features": 32}}
timit.one_fit(3, toy)
before = len(spans.startup_spans())
timit.one_fit(3, toy)  # a fit of the window: the period records nothing of it
m = {{"trace": {{"programs_s": {{}}}}, "facts": {{}}, "sizes": {{}}, "work": {{}},
     "programs": {{}}, "peaks": None}}
helper = find.layer_metric("_startup")
recs = helper.records(m)
print(json.dumps({{
    "metrics": {{n: find.layer_metric(n).read(m) for n in {names!r}}},
    "parts": helper.parts(recs), "root": recs[0],
    "records": [before, len(spans.startup_spans())],
    "names": sorted({{r["name"] for r in recs}}),
}}))
"""


def test_startup_readers_in_a_cold_process_after_one_toy_fit(tmp_path):
    """The program's own record through its own function, in a process
    of its own with an empty compile cache: every reader answers, the
    programs were compiled and none read, and the parts make the root's
    wall."""
    code = IN_A_PROCESS.format(bench=BENCH, names=list(STARTUP_METRICS))
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "empty_cache"),
           "TMPDIR": str(tmp_path)}
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    got, parts, root = out["metrics"], out["parts"], out["root"]
    assert all(v is not None for v in got.values()), got
    assert got["setup_compile_s"] > 0 and got["setup_cache_read_s"] == 0
    assert got["setup_programs"] >= 1 and got["setup_import_s"] > 0
    assert got["setup_backend_s"] > 0 and got["setup_first_run_s"] > 0
    assert got["setup_trace_s"] > 0 and got["setup_lower_s"] > 0
    wall = (root["t1_ns"] - root["t0_ns"]) / 1e9
    seconds = sum(v for n, v in got.items() if n != "setup_programs")
    assert seconds + parts["uncovered"] == pytest.approx(wall, abs=1e-9)
    assert parts["wall"] == wall and 0 <= parts["uncovered"] < wall
    assert (root["name"], root["t0_source"], root["platform"], root["chips"]) == (
        "process", "proc", "cpu", 1)
    assert root["closed_by"] == "unit" and root["compile_cache"].endswith("empty_cache")
    assert {"process", "runtime.init_backend", "fit", "fit.solve", "jit.trace",
            "jit.lower", "jit.backend_compile"} <= set(out["names"])
    assert out["records"][0] == out["records"][1]
    # the operator's line says the same of the same records
    (line,) = [ln for ln in r.stderr.splitlines() if ": startup {" in ln]
    said = json.loads(line.split(": startup ", 1)[1])
    assert said["total_s"] == pytest.approx(wall, abs=1e-3)
    for name, key in (("setup_import_s", "import_s"), ("setup_backend_s", "backend_s"),
                      ("setup_trace_s", "trace_s"), ("setup_lower_s", "lower_s"),
                      ("setup_compile_s", "compile_s"), ("setup_first_run_s", "first_run_s")):
        assert said[key] == pytest.approx(got[name], abs=2e-3), name
    assert said["programs"] == got["setup_programs"]
