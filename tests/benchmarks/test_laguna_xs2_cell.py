"""The cell PR 28 added: ``laguna_xs2.train_8k``'s readers on a
hand-built trace, and the cell's rehearsal at toy size on an asked-for
CPU (no time is taken)."""

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

CELL = "laguna_xs2.train_8k"


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d) for n, s, d in evs])
        for ln, evs in lines.items()
    ])


def traced_step():
    """One chip over 1000 ns: a step program 0-800 whose ops are named
    as the chip names them (chip run, PR 28): a Pallas kernel after the
    scope or function it was called under, the rest ``%fusion.N``, and
    operands by name (a fusion that reads a kernel's output is no
    kernel)."""
    ops = [
        ("%attn_window.3 = (bf16[8], f32[8]) custom-call(s32[3] %c)", 0, 100),
        ("%jvp_attn_window_.4 = (bf16[8], f32[8]) custom-call(s32[3] %c)", 100, 100),
        ("%attn_full.2 = (bf16[8], f32[8]) custom-call(s32[3] %c)", 200, 150),
        ("%gmm.7 = bf16[8] custom-call(bf16[8] %x)", 350, 150),
        ("%tgmm.2 = bf16[8] custom-call(bf16[8] %x)", 500, 50),
        ("%fusion.5 = bf16[8] fusion(bf16[8] %gmm.7, bf16[8] %attn_window.3)", 550, 50),
        ("%fusion.7 = f32[8] fusion(f32[8] %q)", 700, 100),
    ]
    chip = _plane("/device:TPU:0", {
        "XLA Modules": [("jit__train_step(1)", 0, 800)], "XLA Ops": ops})
    return xplane.reduce_planes([chip], window_s=1000e-9)


def measured(monkeypatch, counters):
    # the readers import the helper by name when they are loaded, which
    # find.layer_metric does afresh at every call
    import _laguna

    monkeypatch.setattr(_laguna, "counters", lambda m: counters)
    return {
        "trace": traced_step(),
        "facts": {"traced_fits": 1}, "sizes": {},
        "work": {
            "steps": 2, "train_flops_per_fit": 250e-9 * 197e12,
            "moe_flops_per_row": 197e12 * 1e-9, "moe_bytes_per_row": 1.0,
            "moe_weight_bytes_per_layer": 0.0, "moe_layers": 4, "moe_passes": 3.0,
            "attn_window_kernel_flops_per_step": 1.0,
            "attn_window_kernel_bytes_per_step": 819e9 * 25e-9,
        },
        "programs": {}, "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


def test_the_new_readers_on_a_known_trace(monkeypatch):
    m = measured(monkeypatch, {"routed_rows": 20, "steps": 2, "load_max_over_mean": 1.5})
    read = lambda name: find.layer_metric(name).read(m)  # noqa: E731
    # 250 ns of model FLOPs at the peak over a 1000 ns traced fit
    assert read("train_step_mfu") == pytest.approx(25.0)
    assert read("attn_window_ms_per_step") == pytest.approx(200e-6 / 2)
    assert read("attn_full_ms_per_step") == pytest.approx(150e-6 / 2)
    # gmm and tgmm, not the fusion that reads gmm's output
    assert read("moe_experts_ms_per_step") == pytest.approx(200e-6 / 2)
    # 20 rows x 3 passes x 1 ns of FLOPs each = 60 ns over 200 ns
    assert read("moe_grouped_mm_roofline") == pytest.approx(30.0)
    # bytes bound: 2 steps x 25 ns over 200 ns
    assert read("attn_window_roofline") == pytest.approx(25.0)
    assert read("expert_load_max_over_mean") == 1.5


def test_the_new_readers_find_nothing_on_a_program_without_the_scopes(monkeypatch):
    """What the parent gives: a trace with no such op name, no counter."""
    m = measured(monkeypatch, None)
    chip = _plane("/device:TPU:0", {
        "XLA Modules": [("jit_step(1)", 0, 800)],
        "XLA Ops": [("%fusion.1 = f32[8] fusion()", 0, 800)]})
    m["trace"] = xplane.reduce_planes([chip], window_s=1000e-9)
    for name in ("attn_window_ms_per_step",
                 "attn_full_ms_per_step", "moe_experts_ms_per_step",
                 "moe_grouped_mm_roofline", "attn_window_roofline",
                 "expert_load_max_over_mean"):
        assert find.layer_metric(name).read(m) is None, name
    m["work"] = {}
    assert find.layer_metric("train_step_mfu").read(m) is None


def test_the_manifest_adds_one_configuration_and_one_cell():
    man = find.manifest()
    assert [c["name"] for c in man["configs"]] == ["timit_rf", "laguna_xs2"]
    assert [(w["name"], w["chips"]) for w in man["workloads"]] == [
        ("timit_rf.fit", 1), (CELL, 1)]
    # timit_rf.fit_x4 is not here: its pair of configuration and traffic
    # is timit_rf.fit's, and the manifest takes a pair once (PERF.md
    # section 7)
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs))
    lists = {m["name"]: m["workloads"] for m in man["per_layer"]}
    for name in ("train_step_mfu", "moe_experts_ms_per_step", "attn_window_ms_per_step",
                 "attn_full_ms_per_step", "moe_grouped_mm_roofline",
                 "attn_window_roofline", "expert_load_max_over_mean"):
        assert lists[name] == [CELL]
    assert lists["device_idle_share.fit"] == ["timit_rf.fit", CELL]
    # the host-span readers apply to the new cell (its spans carry the
    # fit path's names) but test_span_metrics.py pins those lists to
    # the one cell and may not be edited here: PERF.md section 7
    for name in ("solve_device_ms_per_fit", "nonsolve_device_ms_per_fit",
                 "solve_gemm_roofline", "load_host_ms_per_fit",
                 "solve_host_ms_per_fit", "compiles_per_fit"):
        assert lists[name] == ["timit_rf.fit"]
    cfg = find.read_json("configs", "laguna_xs2.json")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 256,
                                "vocab_size": 100352}
    assert set(cfg["tolerances"]) >= {"loss0_rel", "loss1_rel", "grad_norms_rel_max",
                                      "quiet_decay_rel"}


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_training_cell(tmp_path, trace):
    env = {
        **os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
        "TMPDIR": str(tmp_path),
    }
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 29), "--seconds", "1", "--trace", trace,
         "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    facts = {k: v for ln in lines[:-1] for k, v in json.loads(ln).items()}
    # no fit after the warm-up asks jax for a program
    assert facts["window"]["compiles_inside"] == {
        "traces": 0, "backend_compiles": 0, "cache_hits": 0}
    assert facts["fits"]["rows_per_fit"] == 2 * 2 * 64
    assert not facts["check"]["mismatches"]
    assert facts["check"]["state_dtypes"] == ["float32"]
    if trace == "0":
        assert set(line["metrics"]) == {"fit_rows_per_s_per_chip", "setup_s"}
        assert all(v["value"] is None for v in line["metrics"].values())
