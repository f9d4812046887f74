"""The cell PR 35 added, ``zaya1_8b.train_8k``: the new reader on a known
record, what the manifest contains, the rehearsal of the cell at toy size
on an asked-for CPU (no time is taken), and the cell's check at toy
size: it passes the program and refuses every planted fault."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "layer_metrics")]

from harness import find  # noqa: E402

NAME = "zaya1_8b"
CELL = "zaya1_8b.train_8k"
SHARED = (
    "fit_rows_per_s_per_chip", "device_idle_share.fit", "train_step_mfu",
    "attn_full_ms_per_step", "attn_bwd_ms_per_step", "moe_experts_ms_per_step",
    "moe_grouped_mm_roofline", "expert_load_max_over_mean",
)


def measured(monkeypatch, counters):
    # the readers import the helper by name when they are loaded, which
    # find.layer_metric does afresh at every call
    import _laguna

    monkeypatch.setattr(_laguna, "counters", lambda m: counters)
    return {"trace": None, "facts": {}, "sizes": {}, "work": {}, "programs": {}, "peaks": None}


def test_the_pad_share_on_a_known_record(monkeypatch):
    m = measured(monkeypatch, {"routed_rows": 1536, "mm_rows": 2048, "steps": 2})
    # 512 of the 2 048 rows the product visited are no routed row
    assert find.layer_metric("moe_mm_pad_share").read(m) == pytest.approx(25.0)
    m = measured(monkeypatch, {"routed_rows": 2048, "mm_rows": 2048, "steps": 2})
    assert find.layer_metric("moe_mm_pad_share").read(m) == 0.0


@pytest.mark.parametrize(
    "counters", [None, {"ssm_rows": 10, "steps": 2}, {"routed_rows": 0, "mm_rows": 0, "steps": 2}])
def test_the_pad_share_finds_nothing_without_the_counters(monkeypatch, counters):
    """What a program without the span gives, one whose span carries no
    ``mm_rows``, and a model that routes nothing."""
    assert find.layer_metric("moe_mm_pad_share").read(measured(monkeypatch, counters)) is None


def test_what_the_manifest_contains():
    man = find.manifest()
    configs = {c["name"]: c for c in man["configs"]}
    assert configs[NAME]["source"] == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    assert configs[NAME]["file"] == "benchmarks/configs/zaya1_8b.json"
    assert configs[NAME]["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    cells = {w["name"]: w for w in man["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"], cells[CELL]["chips"]) == (
        NAME, "fit_loop", 1)
    assert cells[CELL]["why"] == find.cell(CELL)["why"] and len(cells[CELL]["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs)) and pairs.count((NAME, "fit_loop")) == 1
    # one four-chip cell of five
    assert len(man["workloads"]) == 5 and sum(w["chips"] == 4 for w in man["workloads"]) == 1
    metrics = {m["name"]: m for m in man["end_to_end"] + man["per_layer"]}
    for name in SHARED + ("moe_mm_pad_share",):
        assert CELL in metrics[name]["workloads"], name
    for name in ("attn_window_ms_per_step", "attn_window_roofline", "ssm_scan_ms_per_step",
                 "ssm_scan_roofline", "solve_gemm_roofline", "collective_ms_per_fit"):
        assert CELL not in metrics[name]["workloads"], name
    pad = metrics["moe_mm_pad_share"]
    assert pad == {"name": "moe_mm_pad_share", "unit": "%", "better": "lower",
                   "source": "program_counter", "layer": "Experts",
                   "moves": "fit_rows_per_s_per_chip", "workloads": [CELL]}
    assert metrics["expert_load_max_over_mean"]["source"] == "program_counter"
    # every cell's name on a list is a cell, and every metric has a reader
    for m in metrics.values():
        assert set(m.get("workloads", ())) <= set(cells), m["name"]
    for m in man["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    cfg = find.read_json("configs", NAME + ".json")
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 16, "vocab_size": 262272}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 8, 32784)
    assert cfg["train"] == {"steps": 8, "batch": 4, "seq": 8192, "lr": 0.0003,
                            "logit_chunk": 1024, "compute_dtype": "bfloat16", "remat": True}
    limits = find.config(NAME)[1].LIMITS
    assert set(cfg["tolerances"]) >= set(limits) and "route_flip_share" in limits
    # each limit beside the reason for it and its two readings
    assert set(cfg["tolerances"]) >= {
        "readings", "loss0_why", "loss1_why", "grad_norms_why", "grad_norms_routed_why",
        "grad_sums_why", "first_move_leaf_why", "first_move_why", "first_move_over_why", "init_z_why",
        "route_flip_why", "windows_why"}
    for name in ("zaya1_8b.py", "zaya1_8b_reference.py", "_zaya1_8b_controls.py"):
        assert os.path.isfile(os.path.join(BENCH, "configs", name)), name
    assert find.cell(CELL)["sizes_group"] == "train"


def _rehearse(tmp_path, trace):
    env = {
        **os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
        "TMPDIR": str(tmp_path),
    }
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 35), "--seconds", "1", "--trace", trace,
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
def test_rehearsal_of_the_cell(tmp_path, trace):
    line, facts = _rehearse(tmp_path, trace)
    # no fit after the warm-up asks jax for a program
    assert facts["window"]["compiles_inside"] == {
        "traces": 0, "backend_compiles": 0, "cache_hits": 0}
    assert facts["fits"]["rows_per_fit"] == 2 * 2 * 64
    check = facts["check"]
    assert not check["mismatches"] and check["state_dtypes"] == ["float32"]
    assert check["cca_rows_per_step"] == 2 * 128 and check["route_flip_share"] == 0.0
    assert 1 / 16 < check["router_gate_mean_step0"] < 1.0
    if trace == "0":
        assert set(line["metrics"]) == {"fit_rows_per_s_per_chip", "setup_s"}
        assert all(v["value"] is None for v in line["metrics"].values())
    else:
        # a CPU trace has no device plane: no device metric is printed,
        # and the readers of the program's counters, which look for them
        # in a traced fit, find none either
        assert not set(line["metrics"]) & {
            "train_step_mfu", "attn_full_ms_per_step", "moe_grouped_mm_roofline",
            "moe_mm_pad_share"}


# ------------------------------------------------------------- the check

@pytest.fixture(scope="module")
def adapter():
    cfg, mod = find.config(NAME)
    run = find.load_module("run.py")
    cell = find.cell(CELL)
    return mod, lambda rehearse: run.sizes_of(cfg, cell, mod, rehearse)


@pytest.fixture(scope="module")
def sound(adapter):
    mod, sizes_of = adapter
    toy = sizes_of(True)
    return mod.program_readings(7, toy), mod.reference_readings(7, toy)


def test_the_check_passes_the_program(adapter, sound):
    """The gate itself, at toy size: the program agrees with the
    reference, which drew the same windows itself and finds the stated
    init; a fit of the window that returned other losses is refused."""
    mod, sizes_of = adapter
    got, want = sound
    ok, detail = mod.compare(got, want, sizes_of(True), [])
    assert ok, detail["mismatches"]
    assert detail["loss0_rel"] < 1e-5 and detail["grad_norms_rel_max"] < 1e-4
    assert detail["grad_norms_routed_rel_max"] < 1e-4 and detail["route_flip_share"] == 0.0
    assert detail["grad_sums_tau_over_terms_max"] < 1e-4 and detail["first_move_over"] < 1e-6
    assert detail["grad_sums_gamma_over_terms_max"] < 1e-4
    assert detail["grad_sums_tau_worst"].endswith(".tau")
    assert detail["grad_sums_gamma_worst"] == "layer1.gamma"
    # tau in both layers, gamma in the second: held by the size of their terms
    assert set(detail["grad_sums_over_terms"]) == {"layer0.tau", "layer1.tau", "layer1.gamma"}
    assert all(0 < v < 8 for v in detail["grad_sums_size_over_terms"].values())
    assert detail["first_move_leaf_max"] < 0.1 and "beta" not in detail["first_move_leaf_worst"]
    assert detail["grad_norms_routed_worst"].rsplit(".", 1)[-1] in ("router", "experts")
    assert detail["grad_norms_worst"].rsplit(".", 1)[-1] not in ("tau", "gamma", "router", "experts")
    # the embedding, two layers of six groups, gamma in the second
    assert len(detail["grad_norms_rel"]) == 1 + 2 * 6 + 1
    assert {"embed", "layer0.attention", "layer0.convs", "layer0.tau", "layer0.scales",
            "layer0.router", "layer0.experts", "layer1.gamma"} <= set(detail["grad_norms_rel"])
    assert "layer0.gamma" not in detail["grad_norms_rel"]
    assert detail["first_move_rel"] < 0.02 and detail["cca_rows_per_step"] == 2 * 128
    assert 1 / 16 < detail["router_gate_mean_step0"] < 1.0
    assert detail["windows_differ"] == 0 and detail["init_z_max"] < 5.0
    assert all(w.shape == (2, 65) and 0 <= w.min() and w.max() < 256
               for w in want["windows"])
    assert want["choices"].shape == (2, 2, 64) and want["choices"].max() < 16
    ok, again = mod.compare(got, want, sizes_of(True), [{"losses": [detail["losses"][0], 0.0]}])
    assert not ok and "differs" in again["mismatches"][0][1]
    # a stated init that is not exactly so is refused
    off = {**want, "init": {**want["init"], "exact": False}}
    assert "init_exact" in [m[0] for m in mod.compare(got, off, sizes_of(True), [])[1]["mismatches"]]


# plant -> the limits that refuse it at toy size (float32 compute, so
# the rounding-sized limits read far under their chip readings)
PLANTS = {
    "bfloat16_state": {"first_move_over"},
    "half_a_batch": {"windows_differ", "grad_norms_rel_max"},
    "no_update": {"first_move_rel", "loss1_rel"},
    "ids_outside_the_slice": {"windows_differ"},
    "embedding_doubled": {"init_z_max"},
    "no_conv": {"grad_norms_rel_max", "route_flip_share"},
    "no_value_shift": {"grad_norms_rel_max", "route_flip_share"},
    "no_qk_mean": {"grad_norms_rel_max", "route_flip_share"},
    "no_l2_norm": {"grad_norms_rel_max", "route_flip_share"},
    "router_state_dropped": {"route_flip_share"},
    "gate_renormalised": {"grad_norms_rel_max", "grad_norms_routed_rel_max", "loss0_rel"},
    "wrong_share": {"grad_norms_routed_rel_max"},
    "tau_gradient_stopped": {"first_move_leaf_max"},
    "gamma_gradient_stopped": {"first_move_leaf_max"},
}


@pytest.mark.parametrize("plant", sorted(PLANTS))
def test_the_check_refuses_a_planted_fault(adapter, sound, plant):
    """The controls the builder runs on the chip
    (``benchmarks/configs/_zaya1_8b_controls.py``), at toy size: each
    fault comes out not correct, by the limits that are there for it."""
    mod, sizes_of = adapter
    controls = find.load_module("configs", "_zaya1_8b_controls.py")
    assert set(controls.plants(mod)) == set(PLANTS) | {"sound"}
    line = controls.run_plant(mod, plant, 7, sizes_of(True), sound[1])
    assert not line["correct"]
    assert PLANTS[plant] <= set(line["refused_by"]) | (
        {"windows_differ"} if line["windows_differ"] else set()), line
    if plant == "no_update":
        # a state that did not move
        assert line["first_move_rel"] == pytest.approx(1.0, abs=5e-3)
    if plant.endswith("_gradient_stopped"):
        # the leaf stayed where it was, and nothing else saw it
        assert line["first_move_leaf_max"] == pytest.approx(1.0, abs=1e-3)
        assert line["first_move_leaf_worst"].endswith(plant.split("_")[0])
        assert line["grad_norms_rel_max"] < 1e-4 and line["first_move_rel"] < 0.02
        # the whole of its sum is missing: a share of its terms that a
        # sound run's rounding never reaches
        leaf = plant.split("_")[0]
        assert line[f"grad_sums_{leaf}_over_terms_max"] > 0.01
        assert line[f"grad_sums_{leaf}_over_terms_max"] == pytest.approx(
            line["grad_sums_size_over_terms"][line[f"grad_sums_{leaf}_worst"]], rel=1e-3)
    if plant == "gate_renormalised":
        # nothing reaches the router
        assert line["grad_norms_routed_rel_max"] >= 1.0 - 1e-3
