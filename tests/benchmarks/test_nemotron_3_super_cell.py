"""The cell ``nemotron_3_super.train_8k``: the two new readers on known
records and on nothing, what the manifest contains, the rehearsal of the
cell at toy size on an asked-for CPU (no time is taken), and the cell's
check at toy size: it passes the program and refuses every planted
fault."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "layer_metrics")]

from harness import find  # noqa: E402

NAME = "nemotron_3_super"
CELL = "nemotron_3_super.train_8k"
SHARED = (
    "fit_rows_per_s_per_chip", "device_idle_share.fit", "train_step_mfu",
    "attn_full_ms_per_step", "attn_bwd_ms_per_step", "moe_experts_ms_per_step",
    "moe_grouped_mm_roofline", "expert_load_max_over_mean", "moe_mm_pad_share",
    "moe_dispatch_rows_over_routed", "moe_extra_windows_per_step", "ssm_scan_ms_per_step",
    "ssm_scan_roofline", "setup_import_s", "setup_backend_s", "setup_trace_s",
    "setup_lower_s", "setup_cache_read_s", "setup_compile_s", "setup_first_run_s",
    "setup_programs",
)


def measured(monkeypatch, counters, sizes=None):
    # the readers import the helper by name when they are loaded, which
    # find.layer_metric does afresh at every call
    import _laguna

    monkeypatch.setattr(_laguna, "counters", lambda m: counters)
    return {"trace": None, "facts": {}, "sizes": sizes or {"batch": 2, "seq": 8192},
            "work": {}, "programs": {}, "peaks": None}


@pytest.mark.parametrize("rows,share", [(8 * 2 * 8192, 100.0), (8 * 8192, 50.0)])
def test_the_mtp_row_share_on_a_known_record(monkeypatch, rows, share):
    m = measured(monkeypatch, {"mtp_rows": rows, "steps": 8})
    assert find.layer_metric("mtp_row_share").read(m) == pytest.approx(share)


@pytest.mark.parametrize("counters", [
    None, {"routed_rows": 10, "steps": 2}, {"mtp_rows": 0, "steps": 2}, {"mtp_rows": 5}])
def test_the_mtp_row_share_finds_nothing_without_the_counter(monkeypatch, counters):
    """A program without the span, a model without an MTP module (the
    parent's, and every other cell's), a span of no steps."""
    assert find.layer_metric("mtp_row_share").read(measured(monkeypatch, counters)) is None


@pytest.mark.parametrize("kernel,share", [(81920, 100.0), (0, 0.0), (40960, 50.0)])
def test_the_kernel_row_share_on_a_known_record(monkeypatch, kernel, share):
    m = measured(monkeypatch, {"ssm_rows": 81920, "ssm_kernel_rows": kernel, "steps": 8})
    assert find.layer_metric("ssm_kernel_row_share").read(m) == pytest.approx(share)


@pytest.mark.parametrize("counters", [
    None, {"ssm_rows": 0, "ssm_kernel_rows": 0}, {"ssm_rows": 100}, {"routed_rows": 3}])
def test_the_kernel_row_share_finds_nothing_without_the_counters(monkeypatch, counters):
    """No span; a model with no state-space layer; a program whose span
    counts scanned rows but not the kernel's."""
    read = find.layer_metric("ssm_kernel_row_share").read
    assert read(measured(monkeypatch, counters)) is None


def test_what_the_manifest_contains():
    man = find.manifest()
    configs = {c["name"]: c for c in man["configs"]}
    assert configs[NAME]["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
    assert configs[NAME]["file"] == "benchmarks/configs/nemotron_3_super.json"
    assert configs[NAME]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size", "mamba_num_heads", "n_groups",
        "num_attention_heads", "num_key_value_heads"]
    assert len(configs[NAME]["why"]) <= 200 and list(configs)[-1] == NAME
    cells = {w["name"]: w for w in man["workloads"]}
    assert list(cells)[-1] == CELL
    assert (cells[CELL]["config"], cells[CELL]["traffic"], cells[CELL]["chips"]) == (
        NAME, "fit_loop", 1)
    assert cells[CELL]["why"] == find.cell(CELL)["why"] and len(cells[CELL]["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs))
    # one four-chip cell of six
    assert len(man["workloads"]) == 6 and sum(w["chips"] == 4 for w in man["workloads"]) == 1
    metrics = {m["name"]: m for m in man["end_to_end"] + man["per_layer"]}
    for name in SHARED:
        assert metrics[name]["workloads"][-1] == CELL, name
    for name in ("attn_window_ms_per_step", "attn_window_roofline", "solve_gemm_roofline",
                 "collective_ms_per_fit"):
        assert CELL not in metrics[name]["workloads"], name
    assert list(metrics)[-2:] == ["mtp_row_share", "ssm_kernel_row_share"]
    assert metrics["mtp_row_share"] == {
        "name": "mtp_row_share", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "MTP", "moves": "fit_rows_per_s_per_chip", "workloads": [CELL]}
    assert metrics["ssm_kernel_row_share"] == {
        "name": "ssm_kernel_row_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "StateSpace",
        "moves": "fit_rows_per_s_per_chip",
        "workloads": ["granite_4_0_h_micro.train_8k", CELL]}
    cfg = find.read_json("configs", NAME + ".json")
    assert cfg["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512, "vocab_size": 131072,
        "mamba_num_heads": 128, "n_groups": 8, "num_attention_heads": 32,
        "num_key_value_heads": 2}
    assert cfg["train"] == {"steps": 8, "batch": 2, "seq": 8192, "lr": 0.0003,
                            "logit_chunk": 1024, "compute_dtype": "bfloat16", "remat": True}
    assert (cfg["deployment"]["tensor_parallel"], cfg["deployment"]["expert_parallel"]) == (4, 64)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    limits = find.config(NAME)[1].LIMITS
    assert set(cfg["tolerances"]) >= set(limits)
    for name in (NAME + ".py", NAME + "_reference.py", "_" + NAME + "_controls.py"):
        assert os.path.isfile(os.path.join(BENCH, "configs", name)), name
    assert find.cell(CELL)["sizes_group"] == "train"


def test_the_file_holds_every_number_of_the_published_config():
    """Every key of the published ``config.json`` is in the file under
    its own name, and every one whose value differs is in ``reduced``;
    no width is among them."""
    cfg = find.read_json("configs", NAME + ".json")
    published = {**cfg, **cfg["published"]}
    changed = [k for k, v in cfg["published"].items() if cfg[k] != v]
    assert changed == cfg["reduced"]
    widths = ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
              "moe_latent_size", "moe_intermediate_size",
              "moe_shared_expert_intermediate_size", "num_experts_per_tok", "expand")
    assert not set(widths) & set(cfg["reduced"])
    assert (published["hidden_size"], published["moe_latent_size"],
            published["moe_shared_expert_intermediate_size"]) == (4096, 1024, 5376)


def _rehearse(tmp_path, trace):
    env = {
        **os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
        "TMPDIR": str(tmp_path),
    }
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 40), "--seconds", "1", "--trace", trace,
         "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    facts = {k: v for ln in lines[:-1] for k, v in json.loads(ln).items()}
    return line, facts


def test_rehearsal_of_the_cell(tmp_path):
    line, facts = _rehearse(tmp_path, "1")
    # no fit after the warm-up asks jax for a program
    assert facts["window"]["compiles_inside"] == {
        "traces": 0, "backend_compiles": 0, "cache_hits": 0}
    assert facts["fits"]["rows_per_fit"] == 2 * 2 * 64
    check = facts["check"]
    assert not check["mismatches"] and check["state_dtypes"] == ["float32"]
    assert check["mtp_rows_per_step"] == 2 * 64 and check["route_diff_share"] == 0.0
    assert check["ssm_rows_per_step"] == 2 * 64  # one mixer in the toy's three layers
    # a CPU trace has no device plane, and the counters' readers look for
    # them in a traced fit: nothing to print
    assert not set(line["metrics"]) & {
        "train_step_mfu", "mtp_row_share", "ssm_kernel_row_share", "moe_grouped_mm_roofline"}


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
    reference, which drew the same windows of S + 2 itself and finds the
    stated init; a fit of the window that returned other losses is
    refused."""
    mod, sizes_of = adapter
    got, want = sound
    ok, detail = mod.compare(got, want, sizes_of(True), [])
    assert ok, detail["mismatches"]
    assert detail["loss0_rel"] < 1e-5 and detail["mtp0_rel"] < 1e-5
    assert detail["grad_norms_rel_max"] < 1e-4 and detail["grad_norms_routed_rel_max"] < 1e-4
    assert detail["grad_norms_per_head_rel_max"] < 1e-4 and detail["route_diff_share"] == 0.0
    assert detail["grad_norms_per_head_worst"].rsplit(".", 1)[-1] in ("A_log", "dt_bias", "D")
    assert detail["grad_norms_routed_worst"].rsplit(".", 1)[-1] in ("router", "experts")
    assert detail["first_move_over"] < 1e-5 and detail["first_move_rel"] < 0.05
    assert detail["windows_differ"] == 0 and detail["init_z_max"] < 5.0
    assert detail["idle_experts"] == 0
    assert all(w.shape == (2, 66) and 0 <= w.min() and w.max() < 256 for w in want["windows"])
    # two expert layers, 2 x 64 tokens, 6 of 32 a token
    assert want["choices"].shape == (2, 2, 64, 6) and want["choices"].max() < 32
    ok, again = mod.compare(got, want, sizes_of(True), [{"losses": [detail["losses"][0], 0.0]}])
    assert not ok and "differs" in again["mismatches"][0][1]
    off = {**want, "init": {**want["init"], "norm_scales_are_one": False}}
    assert "norm_scales_are_one" in [
        m[0] for m in mod.compare(got, off, sizes_of(True), [])[1]["mismatches"]]


# plant -> limits that refuse it at toy size (float32 compute, so the
# rounding-sized limits read far under their chip readings)
PLANTS = {
    "mtp_shift_one": {"mtp0_rel"},
    "mtp_dropped": {"grad_norms_rel_max", "loss0_rel"},
    "norm_ungrouped": {"grad_norms_per_head_rel_max"},
    "groups_shared": {"grad_norms_per_head_rel_max"},
    "relu_unsquared": {"grad_norms_rel_max"},
    "routed_scale_one": {"grad_norms_routed_rel_max"},
    "state_dropped": {"loss1_rel"},
    "bfloat16_state": {"first_move_over"},
    "no_update": {"first_move_rel", "loss1_rel"},
}


@pytest.mark.parametrize("plant", sorted(PLANTS))
def test_the_check_refuses_a_planted_fault(adapter, sound, plant):
    """The controls that are run on the chip
    (``benchmarks/configs/_nemotron_3_super_controls.py``), at toy size:
    each fault comes out not correct, by the limits that are there for
    it."""
    mod, sizes_of = adapter
    controls = find.load_module("configs", "_" + NAME + "_controls.py")
    assert set(controls.plants(mod)) == set(PLANTS) | {"sound"}
    line = controls.run_plant(mod, plant, 7, sizes_of(True), sound[1])
    assert not line["correct"]
    assert PLANTS[plant] <= set(line["refused_by"]), line
    if plant == "no_update":
        # a state that did not move
        assert line["first_move_rel"] == pytest.approx(1.0, abs=5e-3)


def test_the_sound_plant_is_correct(adapter, sound):
    mod, sizes_of = adapter
    controls = find.load_module("configs", "_" + NAME + "_controls.py")
    line = controls.run_plant(mod, "sound", 7, sizes_of(True), sound[1])
    assert line["correct"] and not line["refused_by"]
