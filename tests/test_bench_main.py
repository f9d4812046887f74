"""bench.py device handling and exit codes: no chip is a non-zero exit
with no line; an asked-for CPU run says ``platform: cpu`` and prints no
device metric; a section that raises is recorded and makes the exit code
non-zero AFTER the line is printed. (Plus the tuned-config plumbing of
the LM workloads.)"""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", REPO / "bench.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_workloads(monkeypatch, bench):
    """Every section main() runs, replaced by an instant fake — these
    tests are about main()'s device handling, not the workloads."""
    fakes = {
        "bench_mnist": lambda labels, data: {
            "samples_per_s": 10.0, "step_ms": 1.0, "solver_gflops": 1.0,
            "solver_tflops_per_s": 0.001, "e2e_tflops_per_s": 0.002,
        },
        "bench_cifar_conv": lambda: {
            "samples_per_s": 5.0, "conv_tflops_per_s": 0.001,
        },
        "bench_weighted": lambda: {"samples_per_s": 7.0, "tflops_per_s": 0.003},
        "bench_sift": lambda: {"images_per_s": 2.0},
        "dispatch_floor_ms": lambda: 0.1,
        "bench_cpu_numpy": lambda *a: 10.0,
        "bench_cpu_cifar_conv": lambda: 5.0,
        "bench_cpu_weighted": lambda: 7.0,
    }
    for name in (
        "lm_step_telemetry", "serve_latency", "fleet_latency", "goodput",
        "autotune", "chaos_drill", "obs_overhead", "solver_mfu",
        "refit_latency",
    ):
        fakes[f"bench_{name}"] = lambda name=name: {"section": name}
    for name, fn in fakes.items():
        monkeypatch.setattr(bench, name, fn)


def _line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _device_metric_keys(rec: dict) -> list[str]:
    return [
        k for k in rec
        if k.startswith("mfu") or "mfu_vs" in k or "tflops_per_chip" in k
        or k == "last_good_tpu"
    ]


def test_cpu_run_names_the_device_and_prints_no_device_metric(
    monkeypatch, capsys
):
    bench = _load_bench()
    _fake_workloads(monkeypatch, bench)
    assert bench.main([]) == 0  # conftest asked for JAX_PLATFORMS=cpu
    rec = _line(capsys)
    assert rec["platform"] == "cpu" and rec["device_kind"] == "cpu"
    assert rec["num_devices"] == 8
    assert rec["value"] == 10.0 and rec["serve_latency"] == {
        "section": "serve_latency"
    }
    assert _device_metric_keys(rec) == []
    assert "errors" not in rec
    assert "lm_train_tokens_per_s" not in rec  # chip-sized workloads skipped


def test_a_section_that_raises_exits_nonzero_after_the_line(
    monkeypatch, capsys
):
    bench = _load_bench()
    _fake_workloads(monkeypatch, bench)

    def boom():
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    monkeypatch.setattr(bench, "bench_cifar_conv", boom)
    monkeypatch.setattr(bench, "bench_goodput", boom)
    assert bench.main([]) == 1
    rec = _line(capsys)
    # the line still carries everything that did run
    assert rec["value"] == 10.0 and rec["autotune"] == {"section": "autotune"}
    assert set(rec["errors"]) == {"cifar_conv", "goodput"}
    assert "RESOURCE_EXHAUSTED" in rec["errors"]["goodput"]
    assert "cifar_conv_samples_per_s" not in rec and "goodput" not in rec


def test_no_chip_is_a_nonzero_exit_with_no_line():
    """JAX_PLATFORMS unset on a machine with no TPU: the backend's own
    error, no result line, nothing pasted in from an earlier run."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "Unable to initialize backend 'tpu'" in out.stderr
    assert "last_good_tpu" not in out.stdout + out.stderr


def test_git_sha_degrades_outside_a_repository(monkeypatch, tmp_path):
    """The chip tool's copy is not a git repository."""
    bench = _load_bench()
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    monkeypatch.setenv("GIT_CEILING_DIRECTORIES", str(tmp_path.parent))
    assert bench._git_sha() == "unknown"


def test_lm_tuned_env_knobs_applied_and_restored(monkeypatch):
    """bench_lm_train must apply the tuned artifact's env knobs (incl.
    the stage-2 push's ``env`` dict) for the tuned run only: set during
    the measured call, restored after — and restored BEFORE the default
    fallback rerun when the tuned config fails."""
    import os

    bench = _load_bench()
    tuned = {
        "shape": f"dim{bench.LM_DIM}_depth{bench.LM_DEPTH}_s{bench.LM_SEQ}",
        "batch": 32,
        "logit_chunk": 0,
        "dense_bwd": False,
        "remat": False,
        "env": {"KST_LOCAL_ATTN": "dense", "KST_FLASH_BLOCK_Q": "256"},
    }
    monkeypatch.setattr(bench, "_lm_tuned_config", lambda: tuned)
    monkeypatch.delenv("KST_LOCAL_ATTN", raising=False)
    monkeypatch.delenv("KST_FLASH_BLOCK_Q", raising=False)
    monkeypatch.setenv("KST_FLASH_DENSE_BWD_MAX", "12345")  # pre-existing

    seen = []

    def fake_rate(**kw):
        seen.append(
            {
                "batch": kw["batch"],
                "attn": os.environ.get("KST_LOCAL_ATTN"),
                "bq": os.environ.get("KST_FLASH_BLOCK_Q"),
                "dense_max": os.environ.get("KST_FLASH_DENSE_BWD_MAX"),
            }
        )
        return {"tokens_per_s": 1.0, "tflops_per_s": 1.0}

    monkeypatch.setattr(bench, "_lm_train_step_rate", fake_rate)
    res = bench.bench_lm_train()
    assert seen == [
        {"batch": 32, "attn": "dense", "bq": "256", "dense_max": "0"}
    ]
    assert res["tuned_config"]["env"] == tuned["env"]
    # restored: the knobs are gone, the pre-existing export is back
    assert "KST_LOCAL_ATTN" not in os.environ
    assert "KST_FLASH_BLOCK_Q" not in os.environ
    assert os.environ["KST_FLASH_DENSE_BWD_MAX"] == "12345"

    # failing tuned config: the default rerun must see a CLEAN env
    seen.clear()
    calls = {"n": 0}

    def fail_then_ok(**kw):
        calls["n"] += 1
        if calls["n"] == 1:
            fake_rate(**kw)
            raise RuntimeError("OOM")
        return fake_rate(**kw)

    monkeypatch.setattr(bench, "_lm_train_step_rate", fail_then_ok)
    res = bench.bench_lm_train()
    assert "tuned_config" not in res
    assert seen[0]["attn"] == "dense"
    assert seen[1] == {
        "batch": bench.LM_BATCH,
        "attn": None,
        "bq": None,
        "dense_max": "12345",
    }


def test_flash_tuned_env_parses_sweep_winner(tmp_path):
    """bench_lm_longctx's block override must round-trip the flash
    sweep's config tag — and degrade to no override on a malformed or
    absent artifact."""
    bench = _load_bench()
    art = tmp_path / "FLASH_SWEEP.json"
    art.write_text(
        json.dumps({"best": {"config": "q256_k512_bwd1024_c16"}})
    )
    assert bench._flash_tuned_env(str(art)) == {
        "KST_FLASH_BLOCK_Q": "256",
        "KST_FLASH_BLOCK_K": "512",
    }
    art.write_text(json.dumps({"best": None}))  # all-configs-failed sweep
    assert bench._flash_tuned_env(str(art)) == {}
    art.write_text("not json")
    assert bench._flash_tuned_env(str(art)) == {}
    assert bench._flash_tuned_env(str(tmp_path / "missing.json")) == {}
