"""Per-node cost-profile registry — the KeystoneML operator profile,
TPU-native.

KeystoneML's optimizer samples each operator's time, memory, and output
size at runtime to drive caching and materialization decisions. On TPU
the compiler already knows most of that statically: lowering a jitted
node and asking the compiled executable for ``cost_analysis()`` (FLOPs,
bytes accessed) and ``memory_analysis()`` (argument/output/temp bytes)
yields the operator profile without running anything. This module
collects those profiles per pipeline node into a process-wide registry
and persists them next to the event log (``cost_profiles.json``) so
:mod:`.report` can join wall-time events against modeled FLOPs — the
substrate any principled fusion/caching decision in ``core/fusion.py``
needs.

Profile schema per node label::

    {"flops": float, "bytes_accessed": float, "argument_bytes": int,
     "output_bytes": int, "temp_bytes": int, "peak_bytes": int,
     "input_shapes": [...], "error": str (only when analysis failed)}
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Callable

import jax

from keystone_tpu.observe import events as _events

COST_FILE = "cost_profiles.json"


def _shapes(tree: Any) -> list[str]:
    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        out.append(
            f"{dtype}{list(shape)}" if shape is not None else type(leaf).__name__
        )
    return out


def analyze(fn: Callable, *args: Any, **kwargs: Any) -> dict:
    """Lower+compile ``fn(*args, **kwargs)`` and extract its cost profile.

    ``fn`` is jitted here (wrapping an already-jitted callable is fine —
    ``jax.jit`` of a jitted function reuses the inner trace). Analysis
    failures are captured as an ``{"error": ...}`` profile rather than
    raised: a node the compiler can't cost (host callbacks, non-jax
    python) should not abort profile collection for the rest.
    """
    try:
        compiled = jax.jit(fn).lower(*args, **kwargs).compile()
        cost = compiled.cost_analysis() or {}
        profile: dict[str, Any] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        }
        try:
            mem = compiled.memory_analysis()
        except Exception:  # noqa: BLE001 — backend without memory stats
            mem = None
        if mem is not None:
            arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
            out_b = int(getattr(mem, "output_size_in_bytes", 0))
            tmp_b = int(getattr(mem, "temp_size_in_bytes", 0))
            profile.update(
                argument_bytes=arg_b,
                output_bytes=out_b,
                temp_bytes=tmp_b,
                peak_bytes=arg_b + out_b + tmp_b,
            )
        return profile
    except Exception as e:  # noqa: BLE001
        return {"error": f"{type(e).__name__}: {str(e)[:200]}"}


class CostProfileRegistry:
    """Thread-safe map of node label → cost profile for one process."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._profiles: dict[str, dict] = {}
        self.device_kind: str | None = None
        self.num_devices: int | None = None

    def record(self, label: str, profile: dict) -> dict:
        with self._lock:
            self._profiles[label] = profile
        return profile

    def get(self, label: str) -> dict | None:
        """One node's recorded profile (the planner's join point — the
        cost source of choice before it falls back to a sampled pass)."""
        with self._lock:
            return self._profiles.get(label)

    def profile_node(self, node: Callable, batch: Any, label: str | None = None) -> dict:
        """Cost-profile one node applied to ``batch``. The node travels
        as a jit argument (pytree), matching how fitted nodes execute."""
        label = label or _events.node_label(node)
        profile = analyze(lambda n, b: n(b), node, batch)
        profile["input_shapes"] = _shapes(batch)
        return self.record(label, profile)

    def profile_pipeline(self, pipe, batch: Any) -> dict[str, dict]:
        """Profile each node of a fitted pipeline in sequence, feeding
        each node's (eagerly computed) output to the next so every
        profile reflects the shapes the node actually sees."""
        nodes = getattr(pipe, "nodes", None)
        if nodes is None:
            nodes = (pipe,)
        self._note_devices()
        from keystone_tpu.observe.instrument import InstrumentedNode

        out: dict[str, dict] = {}
        for i, node in enumerate(nodes):
            inner = node.inner if isinstance(node, InstrumentedNode) else node
            label = _events.node_label(inner, i)
            out[label] = self.profile_node(inner, batch, label=label)
            try:
                batch = inner(batch)
            except Exception as e:  # noqa: BLE001 — can't feed further nodes
                out[label].setdefault(
                    "error", f"apply failed: {type(e).__name__}"
                )
                break
        return out

    def _note_devices(self) -> None:
        devs = jax.devices()
        self.device_kind = devs[0].device_kind
        self.num_devices = len(devs)

    def snapshot(self) -> dict:
        with self._lock:
            profiles = dict(self._profiles)
        return {
            "device_kind": self.device_kind,
            "num_devices": self.num_devices,
            "profiles": profiles,
        }

    def save(self, run_dir: str) -> str:
        """Persist to ``<run_dir>/cost_profiles.json`` (atomic rename)."""
        path = os.path.join(run_dir, COST_FILE)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.snapshot(), f, indent=1)
        os.replace(tmp, path)
        return path

    def reset(self) -> None:
        with self._lock:
            self._profiles.clear()


_registry = CostProfileRegistry()


def get_cost_registry() -> CostProfileRegistry:
    return _registry


def record_pipeline_profile(
    pipe,
    probe: Any,
    registry: CostProfileRegistry | None = None,
    save_dir: str | None = None,
    sync: bool = True,
) -> dict[str, dict]:
    """One-call operator-profile sample for a fitted pipeline: an
    instrumented apply of ``probe`` (per-node wall-time events into the
    active sink + metrics) followed by per-node compiler cost profiles,
    optionally persisted to ``save_dir``. Uses a FRESH registry by
    default so one run's ``cost_profiles.json`` can't carry stale nodes
    from earlier runs in the same process. The probe passes through the
    pipeline twice (timed apply, then the profile feed-forward) — keep
    it bounded."""
    from keystone_tpu.observe.instrument import instrument

    registry = registry or CostProfileRegistry()
    instrument(pipe, sync=sync)(probe)
    profiles = registry.profile_pipeline(pipe, probe)
    if save_dir is not None:
        registry.save(save_dir)
    return profiles


def load_profiles(run_dir: str) -> dict:
    """Read a persisted ``cost_profiles.json``; empty snapshot shape when
    the run recorded none."""
    try:
        with open(os.path.join(run_dir, COST_FILE)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {"device_kind": None, "num_devices": None, "profiles": {}}
