"""Tests of the per-layer readers that read the program's spans
(``benchmarks/layer_metrics/_spans.py``): hand-built span records give
hand-worked numbers, nothing gives None, and a real profiled toy fit on
the CPU adds up to its wall. Nothing here times anything.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "layer_metrics")]

from harness import find  # noqa: E402

from keystone_tpu.observe import spans  # noqa: E402

SPAN_METRICS = (
    "load_host_ms_per_fit", "featurize_host_ms_per_fit", "solve_host_ms_per_fit",
    "score_host_ms_per_fit", "compile_host_ms_per_fit", "compiles_per_fit",
    "host_uncovered_share.fit",
)
MS = 1_000_000  # ns


def rec(name, span, parent, t0_ms, t1_ms, trace="t", **attrs):
    r = {"name": name, "span": span, "trace": trace,
         "t0_ns": int(t0_ms * MS), "t1_ns": int(t1_ms * MS), **attrs}
    if parent:
        r["parent"] = parent
    return r


def hand_built_fit():
    """A fit of 1000 ms. load 0-300, h2d 300-350, one bank 400-600 whose
    cosine child 400-500 compiled for 60 ms (a trace 410-430 with an
    inner trace 415-420 inside it, a cache read 440-480), solve 650-800,
    score 820-1000 whose test child 850-1000 compiled 900-950 and held an
    unknown span 960-970. An older fit and a foreign trace lie beside it."""
    return [
        rec("fit", "old", None, -2000, -1000, trace="t0"),
        rec("fit.load", "old1", "old", -2000, -1500, trace="t0"),
        rec("fit", "r", None, 0, 1000, rows=8),
        rec("fit.load", "a", "r", 0, 300),
        rec("fit.h2d", "b", "r", 300, 350),
        rec("fit.featurize", "c", "r", 400, 600, bank=0),
        rec("featurize.cosine", "d", "c", 400, 500),
        rec("jit.trace", "j1", "d", 410, 430, fun="cosine_features"),
        rec("jit.trace", "j2", "d", 415, 420, fun="cos"),
        rec("jit.cache_read", "j3", "d", 440, 480, fun="jit(cosine_features)"),
        rec("fit.solve", "e", "r", 650, 800),
        rec("fit.score", "f", "r", 820, 1000),
        rec("score.test", "g", "f", 850, 1000),
        rec("jit.backend_compile", "j4", "g", 900, 950, fun="jit(score)"),
        rec("staging.h2d", "s", "g", 960, 970),
        rec("serve.request", "x", None, 100, 200, trace="other"),
        rec("jit.trace", "j9", "x", 100, 200, trace="other"),
        {"name": "written before spans had a clock", "span": "q", "trace": "t"},
    ]


def measured(trace):
    return {"trace": trace, "facts": {"traced_fits": 1}, "sizes": {}, "work": {},
            "programs": {}, "peaks": None}


def test_span_readers_on_hand_built_records(monkeypatch):
    monkeypatch.setattr(spans, "profiled_spans", hand_built_fit)
    m = measured({"programs_s": {"jit_cosine_features": 0.031, "jit__bcd_fit": 0.16}})
    read = lambda name: find.layer_metric(name).read(m)  # noqa: E731
    assert read("load_host_ms_per_fit") == pytest.approx(350.0)
    # the bank's 200 less its compiles (20 + 40), the inner trace not twice
    assert read("featurize_host_ms_per_fit") == pytest.approx(140.0)
    assert read("solve_host_ms_per_fit") == pytest.approx(150.0)
    # 180 less the compile; the unknown span goes with its parent
    assert read("score_host_ms_per_fit") == pytest.approx(130.0)
    assert read("compile_host_ms_per_fit") == pytest.approx(110.0)
    assert read("compiles_per_fit") == 2
    # what only the root covers: 350-400, 600-650, 800-820
    assert read("host_uncovered_share.fit") == pytest.approx(12.0)
    assert read("featurize_device_ms_per_fit") == pytest.approx(31.0)
    ms = find.layer_metric("_spans").layer_ms(find.layer_metric("_spans").records(m))
    assert sum(v for k, v in ms.items() if k != "wall") == pytest.approx(ms["wall"])


def test_span_readers_find_nothing_without_a_trace_a_root_or_the_function(monkeypatch):
    names = [*SPAN_METRICS, "featurize_device_ms_per_fit"]
    monkeypatch.setattr(spans, "profiled_spans", hand_built_fit)
    for n in names:  # no traced run
        assert find.layer_metric(n).read(measured(None)) is None
    # a parent commit's program names its programs jit__lambda
    old = measured({"programs_s": {"jit__lambda": 0.05}})
    assert find.layer_metric("featurize_device_ms_per_fit").read(old) is None
    monkeypatch.setattr(spans, "profiled_spans", lambda: [
        rec("serve.request", "x", None, 0, 1), rec("fit", "y", "x", 0, 1)])
    for n in SPAN_METRICS:  # no fit root
        assert find.layer_metric(n).read(old) is None
    monkeypatch.delattr(spans, "profiled_spans")
    for n in SPAN_METRICS:  # a program from before PR 26
        assert find.layer_metric(n).read(old) is None


def test_every_span_reader_is_in_the_manifest_for_the_fit_cell():
    per_layer = {m["name"]: m for m in find.manifest()["per_layer"]}
    for n in (*SPAN_METRICS, "featurize_device_ms_per_fit"):
        m = per_layer[n]
        assert m["workloads"] == ["timit_rf.fit"] and m["better"] == "lower"
        assert m["moves"] == "fit_rows_per_s_per_chip"
    # it counts span records; the program keeps no counter of compiles
    assert per_layer["compiles_per_fit"]["source"] == "program_span"
    assert per_layer["featurize_device_ms_per_fit"]["source"] == "device_trace"
    # appended: the four metrics the benchmark had come first, unchanged
    assert list(per_layer)[:4] == [
        "device_idle_share.fit", "solve_device_ms_per_fit",
        "nonsolve_device_ms_per_fit", "solve_gemm_roofline"]


def test_span_readers_on_a_profiled_toy_fit_add_up_to_its_wall(tmp_path):
    """The program's own records, through its own function: the layers
    and the uncovered share make the traced fit's wall."""
    import jax

    _cfg, timit = find.config("timit_rf")
    toy = {**find.read_json("configs", "timit_rf.json"), "train_rows": 256,
           "num_cosines": 2, "cosine_features": 32}
    timit.one_fit(3, toy)
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = timit.one_fit(3, toy)
    finally:
        jax.profiler.stop_trace()
    m = measured({"programs_s": {}})
    got = {n: find.layer_metric(n).read(m) for n in SPAN_METRICS}
    assert all(v is not None for v in got.values()), got
    assert got["compiles_per_fit"] == 3
    wall = find.layer_metric("_spans").layer_ms(
        find.layer_metric("_spans").records(m))["wall"]
    parts = sum(v for n, v in got.items() if n.endswith("_ms_per_fit"))
    assert parts + got["host_uncovered_share.fit"] / 100 * wall == pytest.approx(wall)
    assert wall >= 1e3 * out["total_s"]  # the root span encloses run()'s own clock
