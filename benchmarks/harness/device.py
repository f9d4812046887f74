"""Bring the backend up under the program's own platform rule and say
what it is. No TPU means no result: ``core/runtime.py`` raises the
backend's error when ``JAX_PLATFORMS`` is unset and no TPU initialises,
and an asked-for CPU is accepted only for a rehearsal."""

from __future__ import annotations


class Refused(SystemExit):
    """The run cannot stand for the cell: exit non-zero, print no result."""

    def __init__(self, why: str):
        super().__init__(f"benchmark refused: {why}")


def bring_up(chips: int, rehearse_cpu: bool) -> dict:
    from keystone_tpu.core.runtime import init_backend

    backend = init_backend()
    if rehearse_cpu:
        if backend["platform"] != "cpu":
            raise Refused("--rehearse-cpu wants JAX_PLATFORMS=cpu")
    elif backend["platform"] != "tpu":
        raise Refused(
            f"platform is {backend['platform']!r}, not 'tpu' (a CPU run "
            "is a rehearsal: ask for it with --rehearse-cpu)"
        )
    if backend["count"] != chips:
        raise Refused(
            f"the cell asks for {chips} chip(s), jax sees {backend['count']}"
        )
    return {
        "platform": backend["platform"],
        "kind": backend["device_kind"],
        "count": backend["count"],
    }


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
