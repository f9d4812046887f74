"""Tests of the benchmark itself (``benchmarks/``, ``BENCHMARK.json``).

None of them times anything: they hold the manifest to its rules, the
harness's arithmetic to hand-worked numbers, and ``run.py`` to its last
line, at toy size on an asked-for CPU (``--rehearse-cpu``).
"""

import ast
import json
import os
import re
import shutil
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "layer_metrics")]

from harness import find, xplane  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def man():
    return find.manifest()


def run_cell(tmp_path, *args, bench=BENCH, devices=1):
    env = {
        **os.environ,
        "PYTHONPATH": ROOT,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
        "TMPDIR": str(tmp_path),
    }
    return subprocess.run(
        [sys.executable, os.path.join(bench, "run.py"), *args],
        env=env, capture_output=True, text=True, timeout=600,
    )


def test_every_per_layer_metric_names_cells_that_report_what_it_moves(man):
    """The rule that refused PR 22."""
    cells = {w["name"] for w in man["workloads"]}
    reported = {
        m["name"]: set(m.get("workloads", cells)) for m in man["end_to_end"]
    }
    assert reported["setup_s"] == cells
    for m in man["per_layer"]:
        assert m.get("workloads"), f"{m['name']} names no cell"
        assert set(m["workloads"]) <= cells
        assert m["moves"] in reported and m["moves"] != "setup_s"
        missing = set(m["workloads"]) - reported[m["moves"]]
        assert not missing, f"{m['name']} moves {m['moves']}, not reported on {missing}"
    for c in cells:  # every cell: setup_s, one more end-to-end, one per-layer
        assert sum(c in r for r in reported.values()) >= 2
        assert any(c in m["workloads"] for m in man["per_layer"])


def test_names_and_units_hold_only_the_allowed_characters(man):
    names = [c["name"] for c in man["configs"]]
    names += [k for c in man["configs"] for k in c["reduced"]]
    for w in man["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in man["end_to_end"] + man["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for n in names:
        assert NAME.match(n), n
    metric_names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    four = [w for w in man["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] in ([], ["timit_rf.fit_x4"])
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1


def test_every_name_in_the_manifest_has_its_files_and_every_file_its_entry(man):
    def stems(sub, ext):
        return {
            f[: -len(ext)]
            for f in os.listdir(os.path.join(BENCH, sub))
            if f.endswith(ext) and not f.startswith("_")
        }

    assert stems("workloads", ".json") == {w["name"] for w in man["workloads"]}
    assert stems("traffic", ".json") == {w["traffic"] for w in man["workloads"]}
    kinds = set()
    for w in man["workloads"]:
        cell = find.cell(w["name"])
        assert {k: cell[k] for k in w} == w
        kinds.add(find.read_json("traffic", w["traffic"] + ".json")["kind"])
    assert stems("traffic/kinds", ".py") == kinds
    names = {c["name"] for c in man["configs"]}
    assert names == {w["config"] for w in man["workloads"]} == stems("configs", ".json")
    assert stems("configs", ".py") == names | {n + "_reference" for n in names}
    for c in man["configs"]:
        cfg = find.read_json("configs", c["name"] + ".json")
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert (cfg["about"]["source"], cfg["reduced"]) == (c["source"], c["reduced"])
    assert stems("layer_metrics", ".py") == {m["name"] for m in man["per_layer"]}


def test_the_references_import_nothing_from_the_program():
    for f in os.listdir(os.path.join(BENCH, "configs")):
        if not f.endswith("_reference.py"):
            continue
        tree = ast.parse(open(os.path.join(BENCH, "configs", f)).read())
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            assert all(m.split(".")[0] in ("jax", "numpy", "__future__") for m in mods), (f, mods)


def fit_sizes(chips=1, rehearse=False):
    cfg, timit = find.config("timit_rf")
    run = find.load_module("run.py")
    cell = {**find.cell("timit_rf.fit"), "chips": chips}
    return cfg, timit, run.sizes_of(cfg, cell, timit, rehearse)


def test_operations_against_hand_worked_numbers():
    _cfg, timit, fit = fit_sizes()
    assert (fit["num_cosines"], fit["train_rows"]) == (4, 65536)
    # Gram 2 x 65536 x 4096^2 = 2.199e12 a block; the two products
    # 2 x 2 x 65536 x 4096 x 147 = 1.578e11 a block and epoch
    want = 4 * (2 * 65536 * 4096**2 + 5 * 4 * 65536 * 4096 * 147)
    assert timit.ops_and_bytes(fit) == {"solve_gemm_flops_per_fit": want}
    assert want == pytest.approx(11.95e12, rel=1e-3)
    # per chip: four chips hold four times the rows and need the same each
    assert timit.ops_and_bytes(fit_sizes(chips=4)[2]) == timit.ops_and_bytes(fit)


def test_sizes_follow_the_cell_rows_per_chip_and_toy():
    cfg, _timit, x4 = fit_sizes(chips=4)
    assert x4["train_rows"] == 4 * 65536 and x4["train_rows"] // 5 % 4 == 0
    assert (x4["cosine_features"], x4["num_epochs"], x4["num_classes"]) == (4096, 5, 147)
    assert (x4["input_dim"], x4["gamma"], x4["lam"]) == (440, 0.05555, 0.0)
    toy = fit_sizes(chips=4, rehearse=True)[2]
    assert toy["train_rows"] == 4 * cfg["toy"]["train_rows_per_chip"]
    assert toy["cosine_features"] == cfg["toy"]["cosine_features"]


def test_the_fit_check_passes_the_program_and_fails_a_shorter_solve(monkeypatch):
    """The gate itself, at toy size: the program's weights, read back
    from its own checkpoint, lie on the reference's; a solve of one
    epoch instead of five classifies the corpus as well and is refused."""
    _cfg, timit, toy = fit_sizes(rehearse=True)
    ok, detail = timit.check_fits(7, toy, [])
    assert ok and detail["scores_rel"] < 1e-4, detail
    assert detail["checked"]["test_error"] == 0.0
    one_fit = timit.one_fit
    monkeypatch.setattr(
        timit, "one_fit",
        lambda seed, sizes, checkpoint_dir="": one_fit(
            seed, {**sizes, "num_epochs": 1}, checkpoint_dir))
    ok, detail = timit.check_fits(7, toy, [])
    assert not ok and detail["checked"]["test_error"] == 0.0
    assert detail["scores_rel"] > timit.TOL["fit_scores_rel"]
    assert detail["mismatches"][0][0] == "scores_rel"
    # and a fit of the window that returned something else is refused
    monkeypatch.setattr(timit, "one_fit", one_fit)
    ok, detail = timit.check_fits(7, toy, [{**detail["checked"], "n_test": 1.0}])
    assert not ok and "differs" in detail["mismatches"][0][1]


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d) for n, s, d in evs])
        for ln, evs in lines.items()
    ])


def hand_built_trace():
    """Two chips over a 1000 ns window. Chip 0: a solve program 100-500
    (a while op 100-400 holding two fusions, then an all-reduce 450-500)
    and a score program 700-800; chip 1: busy 0-250."""
    chip0 = _plane("/device:TPU:0", {
        "XLA Modules": [("jit_solve(123)", 100, 400), ("jit_score(77)", 700, 100)],
        "XLA Ops": [("while.1", 100, 300), ("fusion.1", 100, 100),
                    ("fusion.2", 250, 100), ("all-reduce.3", 450, 50),
                    ("fusion.9", 700, 100)],
    })
    chip1 = _plane("/device:TPU:1", {
        "XLA Modules": [("jit_solve(123)", 0, 250)],
        "XLA Ops": [("fusion.1", 0, 250)],
    })
    host = _plane("/host:CPU", {"python": [("f", 0, 1000)]})
    return xplane.reduce_planes([host, chip0, chip1], window_s=1000e-9)


def test_trace_reduction_gives_known_busy_idle_programs_and_gaps():
    t = hand_built_trace()
    assert t["chips"] == 2
    # chip 0 busy 300 + 50 + 100 = 450 ns, chip 1 250 ns: mean 350
    assert t["busy_s"] == pytest.approx(350e-9)
    # solve: chip 0 holds 350 busy ns inside 100-500, chip 1 250: mean 300
    assert t["programs_s"]["jit_solve"] == pytest.approx(300e-9)
    assert t["programs_s"]["jit_score"] == pytest.approx(50e-9)
    assert t["program_runs"] == {"jit_solve": 1, "jit_score": 1}
    # self times: the while keeps 300 - 200 = 100 ns on chip 0
    assert t["ops_s"]["while.1"] == pytest.approx(50e-9)
    assert t["ops_s"]["fusion.1"] == pytest.approx((100 + 250) / 2 * 1e-9)
    assert xplane.ops_matching(t, "all-reduce") == pytest.approx(25e-9)
    # gaps on chip 0: 400-450 inside solve, 500-700 before score
    assert t["gaps_s"]["jit_solve -> jit_score"] == pytest.approx((50 + 200) / 2 * 1e-9)
    assert xplane.reduce_planes([_plane("/host:CPU", {})], 1.0) is None
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    busy = xplane.Covered([(0, 10), (20, 30), (40, 50)])
    assert (busy.within(5, 25), busy.within(12, 18), busy.within(0, 60)) == (10, 0, 30)


def test_per_layer_readers_on_a_known_run_and_on_nothing():
    m = {
        "trace": hand_built_trace(),
        "facts": {"traced_fits": 1}, "sizes": {},
        "work": {"solve_gemm_flops_per_fit": 100e-9 * 197e12},
        "programs": {"solve": ["jit_solve"]},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }
    read = lambda name: find.layer_metric(name).read(m)  # noqa: E731
    assert read("device_idle_share.fit") == pytest.approx(65.0)
    assert read("solve_device_ms_per_fit") == pytest.approx(300e-6)
    assert read("nonsolve_device_ms_per_fit") == pytest.approx(50e-6)
    # the gemms need 100 ns at peak; the solve took 300 ns
    assert read("solve_gemm_roofline") == pytest.approx(100 / 3)
    nothing = {**m, "trace": None, "peaks": None}
    for f in os.listdir(os.path.join(BENCH, "layer_metrics")):
        if f.endswith(".py") and not f.startswith("_"):
            assert find.layer_metric(f[:-3]).read(nothing) is None


LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def with_x4_cell(tmp_path, man):
    """A copy of the benchmark with ``timit_rf.fit_x4`` added as a later
    PR would add it: one workload file, one appended cell, and its name
    appended to the lists of the metrics it reports (PERF.md section 7:
    not proven on four chips yet, so not in the manifest)."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    cell = {**find.cell("timit_rf.fit"), "name": "timit_rf.fit_x4", "chips": 4}
    (bench / "workloads" / "timit_rf.fit_x4.json").write_text(json.dumps(cell))
    new = json.loads(json.dumps(man))
    new["workloads"].append({k: cell[k] for k in new["workloads"][0]})
    for m in new["end_to_end"] + new["per_layer"]:
        if "timit_rf.fit" in m.get("workloads", ()):
            m["workloads"].append(cell["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    return str(bench), new


@pytest.mark.parametrize("cell,chips", [("timit_rf.fit", 1), ("timit_rf.fit_x4", 4)])
def test_rehearsal_prints_the_contracts_last_line_and_no_metric_value(tmp_path, cell, chips, man):
    bench = BENCH
    if cell not in {w["name"] for w in man["workloads"]}:
        bench, man = with_x4_cell(tmp_path, man)
    r = run_cell(tmp_path, "--workload", cell, "--seed", str(2**31 + 17),
                 "--seconds", "1", "--trace", "0", "--rehearse-cpu", bench=bench,
                 devices=chips)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert set(line) == LAST_LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": chips}
    want = {m["name"] for m in find.metrics_of(man, "end_to_end", cell)}
    assert set(line["metrics"]) == want == {"fit_rows_per_s_per_chip", "setup_s"}
    assert all(v["value"] is None for v in line["metrics"].values())
    facts = {k: v for ln in lines[:-1] for k, v in json.loads(ln).items()}
    toy = find.read_json("configs", "timit_rf.json")["toy"]["train_rows_per_chip"]
    assert facts["fits"]["rows_per_fit"] == toy * chips
    assert facts["check"]["scores_rel"] < 1e-4 and not facts["check"]["mismatches"]


def test_no_tpu_and_no_rehearsal_flag_exits_nonzero_with_no_result(tmp_path):
    r = run_cell(tmp_path, "--workload", "timit_rf.fit", "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "correct" not in r.stdout and "metrics" not in r.stdout
    # and a cell that asks for other chips than jax sees is refused too
    r = run_cell(tmp_path, "--workload", "timit_rf.fit", "--seed", "1",
                 "--seconds", "1", "--trace", "0", "--rehearse-cpu", devices=2)
    assert r.returncode != 0 and "correct" not in r.stdout


THROWAWAY = {
    "configs/toy_cfg.json": json.dumps({
        "about": {"name": "toy_cfg", "source": "none"}, "reduced": [], "width": 3,
        "toy": {"width": 2}}),
    "configs/toy_cfg.py": (
        "def one_thing(seed, sizes):\n"
        "    import jax.numpy as jnp\n"
        "    return float(jnp.ones(sizes['width']).sum()) + seed\n"
        "def ops_and_bytes(sizes):\n"
        "    return {'answer': 40 + sizes['width']}\n"),
    "traffic/toy_mix.json": json.dumps({"kind": "toy_kind", "times": 3}),
    "traffic/kinds/toy_kind.py": (
        "def run(adapter, sizes, mix, win):\n"
        "    win.begin()\n"
        "    got = [adapter.one_thing(win.seed, sizes) for _ in range(mix['times'])]\n"
        "    win.end()\n"
        "    return {'attempted': len(got), 'failed': 0,\n"
        "            'correct': got == [sizes['width'] + win.seed] * mix['times'],\n"
        "            'metrics': {'toy_rate': len(got) / win.elapsed_s}, 'facts': {}}\n"),
    "layer_metrics/toy_metric.py": "def read(m):\n    return m['work']['answer']\n",
    "workloads/toy.cell.json": json.dumps({
        "name": "toy.cell", "config": "toy_cfg", "traffic": "toy_mix",
        "chips": 1, "why": "a throw-away cell"}),
}


def test_a_cell_configuration_kind_and_metric_are_added_as_files_alone(tmp_path, man):
    """A copy of benchmarks/ with new files and appended manifest entries
    runs the new cell; no file that was there is edited."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    for rel, text in THROWAWAY.items():
        assert not (bench / rel).exists()
        (bench / rel).write_text(text)
    new = json.loads(json.dumps(man))
    new["configs"].append({"name": "toy_cfg", "source": "none", "reduced": [],
                           "file": "benchmarks/configs/toy_cfg.json", "why": "toy"})
    new["workloads"].append({"name": "toy.cell", "config": "toy_cfg",
                             "traffic": "toy_mix", "chips": 1, "why": "toy"})
    new["end_to_end"].append({"name": "toy_rate", "unit": "1/s", "better": "higher",
                              "bound": 0.1, "source": "host_clock",
                              "workloads": ["toy.cell"]})
    new["per_layer"].append({"name": "toy_metric", "unit": "n", "better": "higher",
                             "source": "program_counter", "layer": "Toy",
                             "moves": "toy_rate", "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    for trace, want in (("0", {"toy_rate", "setup_s"}), ("1", {"toy_metric"})):
        r = run_cell(tmp_path, "--workload", "toy.cell", "--seed", "5",
                     "--seconds", "1", "--trace", trace, "--rehearse-cpu",
                     bench=str(bench))
        assert r.returncode == 0, r.stderr[-2000:]
        lines = r.stdout.strip().splitlines()
        line = json.loads(lines[-1])
        assert line["correct"] is True and line["attempted"] == 3
        assert set(line["metrics"]) == want
    assert json.loads(lines[-2])["rehearsal_cpu_clock_not_device_metrics"] == {"toy_metric": 42}
