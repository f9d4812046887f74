"""The cells PR 33 added: ``granite_4_0_h_micro.train_8k``'s readers on a
hand-built trace, the manifest's new entries and files, and the
rehearsal of both new cells at toy size on an asked-for CPU (no time is
taken)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "layer_metrics")]

from harness import find, xplane  # noqa: E402

CELL = "granite_4_0_h_micro.train_8k"
X4 = "timit_rf.fit_x4"


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d) for n, s, d in evs])
        for ln, evs in lines.items()
    ])


def traced_step(ops):
    chip = _plane("/device:TPU:0", {
        "XLA Modules": [("jit__train_step(1)", 0, 800)], "XLA Ops": ops})
    return xplane.reduce_planes([chip], window_s=1000e-9)


def measured(monkeypatch, counters, ops):
    # the readers import the helper by name when they are loaded, which
    # find.layer_metric does afresh at every call
    import _laguna

    monkeypatch.setattr(_laguna, "counters", lambda m: counters)
    return {
        "trace": traced_step(ops),
        "facts": {"traced_fits": 1}, "sizes": {},
        "work": {
            "steps": 2, "train_flops_per_fit": 250e-9 * 197e12,
            "ssm_scan_flops_per_row": 197e12 * 1e-9, "ssm_scan_bytes_per_row": 819e9 * 3e-9,
            "ssm_scan_runs": 2,
        },
        "programs": {}, "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


# named as the chip names them: a Pallas kernel after the scope it was
# called under, the rest %fusion.N; operands by name
OPS = [
    ("%ssd_chunk.3 = (bf16[8], f32[8]) custom-call(bf16[8] %fusion.1)", 0, 100),
    ("%jvp_ssd_chunk_.4 = (bf16[8], f32[8]) custom-call(bf16[8] %fusion.2)", 100, 200),
    ("%attn_full.2 = (bf16[8], f32[8]) custom-call(s32[3] %c)", 300, 150),
    ("%attn_bwd.5 = (bf16[8], f32[8]) custom-call(s32[3] %c)", 450, 50),
    ("%fusion.5 = bf16[8] fusion(bf16[8] %ssd_chunk.3)", 500, 100),
]


def test_the_new_readers_on_a_known_trace(monkeypatch):
    m = measured(monkeypatch, {"ssm_rows": 10, "steps": 2}, OPS)
    read = lambda name: find.layer_metric(name).read(m)  # noqa: E731
    # both runs of the kernel, not the fusion that reads its output
    assert read("ssm_scan_ms_per_step") == pytest.approx(300e-6 / 2)
    # 10 rows x 2 runs: 20 ns of FLOPs, 60 ns of bytes, over 300 ns
    assert read("ssm_scan_roofline") == pytest.approx(20.0)
    assert read("attn_full_ms_per_step") == pytest.approx(150e-6 / 2)
    assert read("attn_bwd_ms_per_step") == pytest.approx(50e-6 / 2)
    assert read("train_step_mfu") == pytest.approx(25.0)


def test_the_new_readers_find_nothing_on_a_program_without_the_kernel(monkeypatch):
    """What the parent gives: no such op name, no such counter."""
    plain = [("%fusion.1 = f32[8] fusion()", 0, 800)]
    for counters in (None, {"routed_rows": 5, "steps": 2}):
        m = measured(monkeypatch, counters, plain)
        for name in ("ssm_scan_ms_per_step", "ssm_scan_roofline"):
            assert find.layer_metric(name).read(m) is None, name
    # the kernel without the counter, or without the adapter's operations
    m = measured(monkeypatch, {"steps": 2}, OPS)
    assert find.layer_metric("ssm_scan_roofline").read(m) is None
    m = measured(monkeypatch, {"ssm_rows": 10, "steps": 2}, OPS)
    m["work"] = {"steps": 2}
    assert find.layer_metric("ssm_scan_roofline").read(m) is None
    # a one-chip trace has no all-reduce
    assert find.layer_metric("collective_ms_per_fit").read(m) is None


def test_the_collective_reader_on_a_known_trace():
    chips = [
        _plane(f"/device:TPU:{i}", {
            "XLA Modules": [("jit_solve(1)", 0, 500)],
            "XLA Ops": [("fusion.1", 0, 300), ("all-reduce.3", 300, 40 + 20 * i)]})
        for i in range(2)
    ]
    m = {"trace": xplane.reduce_planes(chips, window_s=1000e-9),
         "facts": {"traced_fits": 2}}
    # mean over the chips of 40 and 60 ns, over two traced fits
    assert find.layer_metric("collective_ms_per_fit").read(m) == pytest.approx(25e-6)


def test_the_manifest_adds_one_configuration_and_two_cells():
    man = find.manifest()
    assert [c["name"] for c in man["configs"]][-1] == "granite_4_0_h_micro"
    cells = {w["name"]: w for w in man["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"], cells[CELL]["chips"]) == (
        "granite_4_0_h_micro", "fit_loop", 1)
    assert (cells[X4]["config"], cells[X4]["traffic"], cells[X4]["chips"]) == (
        "timit_rf", "fit_loop_x4", 4)
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs))
    # at most a quarter of the cells, or one, may take four chips
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    lists = {m["name"]: m["workloads"] for m in man["end_to_end"] + man["per_layer"]
             if "workloads" in m}
    for name in ("fit_rows_per_s_per_chip", "device_idle_share.fit", "train_step_mfu",
                 "attn_full_ms_per_step", "attn_bwd_ms_per_step", "ssm_scan_ms_per_step",
                 "ssm_scan_roofline"):
        assert CELL in lists[name], name
    for name in ("fit_rows_per_s_per_chip", "device_idle_share.fit",
                 "solve_device_ms_per_fit", "nonsolve_device_ms_per_fit",
                 "solve_gemm_roofline", "collective_ms_per_fit"):
        assert X4 in lists[name], name
    assert lists["ssm_scan_roofline"] == [CELL] and lists["collective_ms_per_fit"] == [X4]
    # appended: what was there comes first
    assert lists["device_idle_share.fit"][:2] == ["timit_rf.fit", "laguna_xs2.train_8k"]
    assert CELL not in lists["attn_window_ms_per_step"]
    per_layer = {m["name"]: m for m in man["per_layer"]}
    assert per_layer["ssm_scan_ms_per_step"]["layer"] == "StateSpace"
    assert per_layer["collective_ms_per_fit"]["layer"] == "Mesh"
    mix = find.read_json("traffic", "fit_loop_x4.json")
    assert {k: mix[k] for k in ("kind", "traced_fit", "stop_after_failures")} == {
        k: find.read_json("traffic", "fit_loop.json")[k]
        for k in ("kind", "traced_fit", "stop_after_failures")}
    cfg = find.read_json("configs", "granite_4_0_h_micro.json")
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 40, "vocab_size": 100352}
    assert set(cfg["tolerances"]) >= {"loss0_rel", "loss1_rel", "grad_norms_rel_max",
                                      "grad_norms_per_head_rel_max", "first_move_rel",
                                      "init_z_max"}
    assert cfg["train"] == {"steps": 8, "batch": 1, "seq": 8192, "lr": 0.0003,
                            "logit_chunk": 1024, "compute_dtype": "bfloat16",
                            "remat": True}
    assert os.path.isfile(os.path.join(BENCH, "configs", "_granite_4_0_h_micro_controls.py"))


def _rehearse(tmp_path, cell, trace, devices):
    env = {
        **os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
        "TMPDIR": str(tmp_path),
    }
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2**31 + 33), "--seconds", "1", "--trace", trace,
         "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    facts = {k: v for ln in lines[:-1] for k, v in json.loads(ln).items()}
    return line, facts


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_state_space_cell(tmp_path, trace):
    line, facts = _rehearse(tmp_path, CELL, trace, 1)
    # no fit after the warm-up asks jax for a program
    assert facts["window"]["compiles_inside"] == {
        "traces": 0, "backend_compiles": 0, "cache_hits": 0}
    assert facts["fits"]["rows_per_fit"] == 2 * 2 * 64
    assert not facts["check"]["mismatches"]
    assert facts["check"]["state_dtypes"] == ["float32"]
    assert facts["check"]["ssm_rows_per_step"] == 2 * 128
    if trace == "0":
        assert set(line["metrics"]) == {"fit_rows_per_s_per_chip", "setup_s"}
        assert all(v["value"] is None for v in line["metrics"].values())
    else:
        # a CPU trace has no device plane: no device metric is printed
        assert not set(line["metrics"]) & {"ssm_scan_ms_per_step", "ssm_scan_roofline"}


def test_rehearsal_of_the_four_chip_fit_cell(tmp_path):
    line, facts = _rehearse(tmp_path, X4, "0", 4)
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    assert set(line["metrics"]) == {"fit_rows_per_s_per_chip", "setup_s"}
    toy = find.read_json("configs", "timit_rf.json")["toy"]["train_rows_per_chip"]
    assert facts["fits"]["rows_per_fit"] == 4 * toy
    assert facts["check"]["scores_rel"] < 1e-4 and not facts["check"]["mismatches"]
