"""The two readers PR 39 added for the expert layer's window
(``ops/moe.py``): values on a known ``fit.counters`` record, nothing
without the counters, and their entries in the manifest."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "layer_metrics")]

from harness import find  # noqa: E402

METRICS = ("moe_dispatch_rows_over_routed", "moe_extra_windows_per_step")
CELLS = ["laguna_xs2.train_8k", "zaya1_8b.train_8k"]


def measured(monkeypatch, counters):
    # the readers import the helper by name when they are loaded, which
    # find.layer_metric does afresh at every call
    import _laguna

    monkeypatch.setattr(_laguna, "counters", lambda m: counters)
    return {"trace": None, "facts": {}, "sizes": {}, "work": {}, "programs": {}, "peaks": None}


def read(name, m):
    return find.layer_metric(name).read(m)


@pytest.mark.parametrize(
    "counters,rows_over_routed,extra_per_step",
    [
        # laguna's shape: 4 layers x 8 steps of one 32 768-row window,
        # 16 384 rows routed here a layer-step, one layer-step overflowing
        ({"steps": 8, "routed_rows": 32 * 16384, "dispatch_rows": 33 * 32768,
          "extra_windows": 1}, 33 * 32768 / (32 * 16384), 0.125),
        # every row moves (zaya's shape): T·k = 32 768 a layer-step
        ({"steps": 8, "routed_rows": 40 * 8192, "dispatch_rows": 40 * 32768,
          "extra_windows": 0}, 4.0, 0.0),
    ],
    ids=["window", "every_row"],
)
def test_the_readers_on_a_known_record(monkeypatch, counters, rows_over_routed, extra_per_step):
    m = measured(monkeypatch, counters)
    assert read(METRICS[0], m) == pytest.approx(rows_over_routed)
    assert read(METRICS[1], m) == pytest.approx(extra_per_step)


@pytest.mark.parametrize(
    "counters",
    [None, {"ssm_rows": 10, "steps": 2},
     {"steps": 2, "routed_rows": 96, "mm_rows": 512},
     {"steps": 2, "routed_rows": 0, "mm_rows": 0, "dispatch_rows": 0, "extra_windows": 0}],
    ids=["no_span", "no_experts", "parent_program", "nothing_routed"],
)
def test_the_readers_find_nothing_without_the_counters(monkeypatch, counters):
    """No span; a model that routes nothing; a program from before PR 39
    (its span has no ``dispatch_rows`` or ``extra_windows``, the parent's
    in the driver's traced runs); a fit that routed nothing."""
    m = measured(monkeypatch, counters)
    assert read(METRICS[0], m) is None
    if counters is None or "extra_windows" not in counters:
        assert read(METRICS[1], m) is None
    else:
        assert read(METRICS[1], m) == 0.0


@pytest.mark.parametrize("name", METRICS)
def test_both_are_in_the_manifest_for_the_two_routed_cells(name):
    man = find.manifest()
    per_layer = {m["name"]: m for m in man["per_layer"]}
    assert per_layer[name] == {
        "name": name, "unit": "rows/row" if name == METRICS[0] else "windows/step",
        "better": "lower", "source": "program_counter", "layer": "Experts",
        "moves": "fit_rows_per_s_per_chip", "workloads": CELLS}
    # appended after everything the benchmark had, in ISSUE 39's order
    assert list(per_layer)[-2:] == list(METRICS)
    assert {w["name"] for w in man["workloads"]} >= set(CELLS)
