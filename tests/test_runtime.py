"""Process-level runtime setup: the platform rule and where the
persistent compilation cache lives (keystone_tpu/core/runtime.py)."""

import os
import pathlib
import subprocess
import sys

import jax
import pytest

from keystone_tpu.core import runtime

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _restore_jax_config():
    """The helpers mutate global jax config; keep it test-local."""
    before = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
        jax.config.jax_platforms,
    )
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])
    jax.config.update("jax_platforms", before[2])


# ------------------------------------------------------------------ cache


def test_cache_placed_from_outside_sets_no_directory(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, jax reads it itself: the
    program sets no cache directory in code."""
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
    assert runtime.enable_compilation_cache() == placed
    assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"
    assert not os.path.exists(placed)  # nothing created on jax's behalf


def test_cache_defaults_to_one_fixed_dir_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert runtime.enable_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_retired_cache_variables_are_ignored(tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    for name in ("KEYSTONE_COMPILE_CACHE_DIR", "KEYSTONE_XLA_CACHE"):
        monkeypatch.setenv(name, str(tmp_path / name))
    assert runtime.enable_compilation_cache() == str(REPO / ".jax_cache")


class _Captured(Exception):
    pass


def _capturing_fleet(seen: dict):
    def fake(*args, **kw):
        seen["env"] = kw["env"]
        raise _Captured

    return fake


@pytest.mark.parametrize("placed", [True, False])
def test_fleet_children_inherit_the_cache_placement(
    tmp_path, monkeypatch, placed
):
    """The router hands its replicas the environment as it found it: the
    variable when set, nothing in its place when not (each replica then
    resolves the in-checkout directory for itself)."""
    from keystone_tpu.serve import fleet

    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen: dict = {}
    monkeypatch.setattr(fleet, "Fleet", _capturing_fleet(seen))
    with pytest.raises(_Captured):
        fleet.main(["mnist", "--replicas", "2", "--port", "0"])
    env = seen["env"]
    assert env.get("JAX_COMPILATION_CACHE_DIR") == (
        str(tmp_path) if placed else None
    )
    assert not [k for k in env if k.startswith("KEYSTONE_") and "CACHE" in k]


def test_chaos_children_inherit_the_cache_placement(tmp_path, monkeypatch):
    from keystone_tpu.resilience import chaos
    from keystone_tpu.serve import fleet

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    seen: dict = {}
    monkeypatch.setattr(fleet, "Fleet", _capturing_fleet(seen))
    spec = {"workload": {"replica": "mnist", "replicas": 2}}
    with pytest.raises(_Captured):
        chaos._run_fleet(spec, str(tmp_path), "", str(tmp_path))
    env = seen["env"]
    assert env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    assert env["JAX_PLATFORMS"] == "cpu"  # pinned on purpose
    assert not [k for k in env if k.startswith("KEYSTONE_") and "CACHE" in k]


# --------------------------------------------------------------- platform


def test_select_platform_unset_means_tpu(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert runtime.select_platform() == "tpu"
    # exported for child processes, and in force for this one
    assert os.environ["JAX_PLATFORMS"] == "tpu"
    assert jax.config.jax_platforms == "tpu"


def test_select_platform_obeys_an_explicit_choice(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert runtime.select_platform() == "cpu"
    assert jax.config.jax_platforms == "cpu"


def test_entry_point_without_tpu_exits_nonzero():
    """JAX_PLATFORMS unset on a machine with no TPU: the launcher fails
    with the backend's own error instead of quietly running on the CPU.
    (JAX_PLATFORMS=cpu working is what the rest of this suite runs on —
    e.g. test_launcher_and_serialization drives the same launcher.)"""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, "-m", "keystone_tpu", "mnist-random-fft",
         "--synthetic", "64", "--num-ffts", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    if '"platform": "tpu"' in out.stderr:
        pytest.skip("this machine has a TPU")
    assert out.returncode != 0
    assert "Unable to initialize backend 'tpu'" in out.stderr
    assert "MnistRandomFFT:" not in out.stderr  # no result was produced


def test_fleet_refuses_many_replicas_on_an_accelerator(monkeypatch):
    """One process per chip: N replica processes cannot share the chip,
    and the router assigns none — it says so instead of balancing one
    live replica against N-1 crash-looping ones. One replica, or an
    asked-for CPU fleet, is not refused."""
    from keystone_tpu.serve import fleet

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(SystemExit, match="one accelerator per replica"):
        fleet.main(["mnist", "--replicas", "2", "--port", "0"])
    monkeypatch.setattr(fleet, "Fleet", _capturing_fleet({}))
    with pytest.raises(_Captured):
        fleet.main(["mnist", "--replicas", "1", "--port", "0"])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(_Captured):
        fleet.main(["mnist", "--replicas", "2", "--port", "0"])


def test_the_router_never_brings_a_backend_up(tmp_path):
    """A chip belongs to one process, so the fleet router (and what it
    imports: observe, resilience, the collector and SLO engine) must
    never initialise a backend. With JAX_PLATFORMS unset (= tpu) on a
    machine with no TPU any jax.devices() call raises — and the canned
    fleet game day (router + 3 stub replicas) still passes."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, "-m", "keystone_tpu", "chaos", "run",
         "fleet_game_day", "--report", str(tmp_path)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "PASS (5/5 invariants)" in out.stdout
