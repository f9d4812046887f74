"""chip_smoke.py off the chip: its child supervision on canned children,
the script failing where the platform is not ``tpu``, and (slow) its
phase functions driven at toy size under an explicit CPU pin."""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_under_test", REPO / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _canned(*lines: str, then: str = "") -> list[str]:
    body = "".join(f"print({line!r}, flush=True)\n" for line in lines)
    return [sys.executable, "-c", "import time\n" + body + then]


_CPU = '12:00 INFO keystone_tpu.runtime: device {"platform": "cpu", "device_kind": "cpu", "count": 1, "compile_cache": "/x"}'
_TPU = _CPU.replace('"cpu"', '"tpu"', 1).replace('"cpu"', '"TPU v5 lite"')
_COMPILE = '12:01 INFO keystone_tpu.runtime: compile {"backend_compile_s": 1.5, "cache_hits": 2, "cache_misses": 0}'


def test_the_parent_is_stdlib_only(smoke):
    """Importing the script must not bring in jax (a parent that touched
    jax holds the chip) — nor numpy or the package."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); import chip_smoke; "
         "print(sorted(m for m in ('jax', 'numpy', 'keystone_tpu') "
         "if m in sys.modules))" % str(REPO)],
        capture_output=True, text=True, timeout=60,
    )
    assert out.stdout.strip() == "[]", out.stdout + out.stderr


def test_wrong_platform_fails_fast_and_stops_the_child(smoke):
    t0 = time.monotonic()
    child, why = smoke.run_to_end(
        _canned(_CPU, then="time.sleep(60)"), 30.0, "tpu"
    )
    assert why == "platform is 'cpu', not 'tpu'"
    assert child.proc.poll() is not None  # stopped, not left running
    assert time.monotonic() - t0 < 10


def test_device_and_compile_lines_are_read(smoke):
    child, why = smoke.run_to_end(_canned(_TPU, "work", _COMPILE), 30.0, "tpu")
    assert why is None
    assert child.device == {
        "platform": "tpu", "device_kind": "TPU v5 lite", "count": 1,
        "compile_cache": "/x",
    }
    res = smoke._result(child, True)
    assert (res["compile_s"], res["cache_hits"], res["cache_misses"]) == (1.5, 2, 0)


def test_a_child_that_outlives_its_bound_is_stopped(smoke):
    child, why = smoke.run_to_end(
        _canned(_TPU, then="time.sleep(60)"), 1.0, "tpu"
    )
    assert why == "outlived its bound of 1s"
    assert child.proc.poll() is not None


def test_nonzero_exit_and_missing_device_line_fail(smoke):
    _, why = smoke.run_to_end(_canned(_TPU, then="raise SystemExit(3)"), 30.0, "tpu")
    assert why == "exit code 3"
    _, why = smoke.run_to_end(_canned("no device line"), 30.0, "tpu")
    assert why == "the child never logged its device line"


def test_warm_start_verdict(smoke, monkeypatch):
    """Warm = what the cold fit wrote is read back, and clearly below
    it; a cache the machine came with is reported, not failed."""
    cold = {"ok": True, "wall_s": 30.0, "compile_s": 17.0,
            "cache_hits": 0, "cache_misses": 6}

    def warm_fit(**fields):
        base = {"ok": True, "wall_s": 20.0, "compile_s": 4.0,
                "cache_hits": 7, "cache_misses": 0, "device": None}
        monkeypatch.setattr(smoke, "phase_fit", lambda **kw: {**base, **fields})
        return smoke.phase_warm(cold)

    assert warm_fit()["ok"]
    # a program at the cache's admission threshold may be written late
    assert warm_fit(cache_misses=2)["ok"]
    assert not warm_fit(cache_hits=5)["ok"]  # an entry was not read back
    assert not warm_fit(cache_hits=0)["ok"]
    assert not warm_fit(wall_s=31.0)["ok"]  # not below cold
    assert not warm_fit(compile_s=11.0)["ok"]  # not clearly below cold
    cold.update(cache_hits=4, cache_misses=2)  # the first fit read a cache
    res = warm_fit(wall_s=31.0)
    assert res["ok"] and res["prewarmed"]


def _canned_phases(smoke, monkeypatch, device, **overrides):
    def phase(name):
        res = {"ok": True, "wall_s": 1.0, "device": device, "cache_misses": 6}
        return lambda **kw: {**res, **overrides.get(name, {})}

    for name in ("fit", "serve", "train", "kernels", "mesh"):
        monkeypatch.setattr(smoke, "phase_" + name, phase(name))
    monkeypatch.setattr(smoke, "phase_warm", lambda cold, **kw: phase("warm")())


def test_the_last_stdout_line_is_the_verdict_and_nothing_else(
    smoke, monkeypatch, capsys
):
    """A pass prints the per-phase report, then LAST a JSON object with
    exactly ``ok`` and ``device`` {platform, kind, count} — what the
    driver parses."""
    device = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 4,
              "compile_cache": "/x"}
    _canned_phases(smoke, monkeypatch, device)
    assert smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
    }
    report = json.loads(lines[-2])["report"]
    assert set(report["phases"]) == {
        "fit", "serve", "train", "kernels", "mesh", "warm"
    }
    assert {"jax", "libtpu"} <= set(report["versions"])
    assert len(lines) == 2


def test_a_failed_phase_prints_nothing_on_stdout(smoke, monkeypatch, capsys):
    device = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
    _canned_phases(
        smoke, monkeypatch, device, train={"ok": False, "why": "loss nan"}
    )
    assert smoke.main() == 1
    captured = capsys.readouterr()
    assert captured.out == "" and "loss nan" in captured.err


def test_the_script_fails_where_the_platform_is_not_tpu():
    """Under an explicit CPU pin the smoke exits non-zero, says the
    platform is not tpu, and prints no result line."""
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "platform is 'cpu', not 'tpu'" in out.stderr


@pytest.mark.slow
def test_phases_at_toy_size_under_a_cpu_pin(smoke):
    """Every phase function, through the real entry points, at toy size
    with the platform requirement turned to what this machine has."""
    cpu = {"require_platform": "cpu", "bound_s": 300.0}
    fit_kw = {"rows": 2000, "num_ffts": 4, "block_size": 2048}
    fit = smoke.phase_fit(**fit_kw, **cpu)
    assert fit["ok"], fit
    assert fit["device"]["platform"] == "cpu" and fit["test_error"] <= 0.02
    warm = smoke.phase_warm(fit, **fit_kw, **cpu)
    # (the wall comparison is a chip-size fact; at toy size only the
    # cache's part of the verdict is stable)
    assert warm["cache_hits"] and warm["cache_hits"] >= fit["cache_misses"], warm
    serve = smoke.phase_serve(rows=2000, num_ffts=4, **cpu)
    assert serve["ok"], serve
    assert serve["draining_seen"] and serve["exit_code"] == 0
    assert serve["aot_compiled"] == 3 and serve["rows_sent"] == 86
    train = smoke.phase_train(
        lm={"steps": 2, "dim": 32, "depth": 1, "num_heads": 2, "seq": 32,
            "batch": 2, "vocab": 64},
        compute_dtype="float32", **cpu,
    )
    assert train["ok"] and len(train["losses"]) == 2, train
    kernels = smoke.phase_kernels(
        kernels={"mm_shapes": [[8, 256]], "gram_shape": [300, 128],
                 "flash_shape": [1, 2, 128, 32], "interpret": True},
        **cpu,
    )
    assert kernels["ok"] and len(kernels["checks"]) == 4, kernels
    json.dumps([fit, warm, serve, train, kernels])  # the summary is JSON
