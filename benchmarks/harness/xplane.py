"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

A TPU trace has one plane per chip (``/device:TPU:<n>``). Its line
``XLA Ops`` holds one event per executed HLO op (nested for loops and
calls) and ``XLA Modules`` one per run of a compiled program, named
``<jit name>(<fingerprint>)``. Busy time is the union of the op
intervals; a program's device time is the busy time inside its module
events; an op's time is its self time (children taken out). Everything
is averaged over the chips in the trace.

``reduce_planes`` takes any objects with the ``ProfileData`` shape
(``.name``, ``.lines`` → ``.name``, ``.events`` → ``.name``,
``.start_ns``, ``.duration_ns``), so a hand-built trace tests it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping (start, end) intervals."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(merged: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


class Covered:
    """Merged intervals that answer "how much of [s, e) is covered?" by
    bisection: a serving trace asks it once per batch."""

    def __init__(self, merged: list[tuple[float, float]]):
        self.starts = [a for a, _b in merged]
        self.ends = [b for _a, b in merged]
        self.before = [0.0]
        for a, b in merged:
            self.before.append(self.before[-1] + (b - a))

    def within(self, s: float, e: float) -> float:
        i = bisect.bisect_right(self.ends, s)  # first interval ending after s
        j = bisect.bisect_left(self.starts, e)  # first starting at or after e
        if i >= j:
            return 0.0
        total = self.before[j] - self.before[i]
        total -= max(0.0, s - self.starts[i])
        total -= max(0.0, self.ends[j - 1] - e)
        return total


def self_times(events: list[tuple[str, float, float]]) -> dict[str, float]:
    """name → self time, for (name, start, end) events that nest: an
    event's self time is its duration less that of its direct children."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda t: (t[1], -(t[2] - t[1]))):
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def program_name(module_event_name: str) -> str:
    return _FINGERPRINT.sub("", module_event_name)


def _events(plane, line_name: str) -> list[tuple[str, float, float]]:
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            s = float(ev.start_ns)
            out.append((ev.name, s, s + float(ev.duration_ns)))
    return out


def _reduce_plane(plane) -> dict | None:
    ops = _events(plane, OPS_LINE)
    if not ops:
        return None
    busy = union([(s, e) for _n, s, e in ops])
    modules = sorted(_events(plane, MODULES_LINE), key=lambda t: t[1])
    covered = Covered(busy)
    programs: dict[str, float] = {}
    runs: dict[str, int] = {}
    for name, s, e in modules:
        key = program_name(name)
        programs[key] = programs.get(key, 0.0) + covered.within(s, e)
        runs[key] = runs.get(key, 0) + 1
    # an idle gap is named by the programs on either side of it
    gaps: dict[str, float] = {}
    marks = modules or sorted(ops, key=lambda t: t[1])
    starts = [s for _n, s, _e in marks]
    for (_a, b), (c, _d) in zip(busy, busy[1:]):
        i = bisect.bisect_left(starts, b)
        before = program_name(marks[i - 1][0]) if i > 0 else "?"
        after = program_name(marks[i][0]) if i < len(marks) else "?"
        label = f"{before} -> {after}"
        gaps[label] = gaps.get(label, 0.0) + (c - b)
    return {
        "busy_ns": length(busy),
        "programs_ns": programs,
        "program_runs": runs,
        "ops_ns": self_times(ops),
        "gaps_ns": gaps,
    }


def _mean_dicts(dicts: list[dict[str, float]]) -> dict[str, float]:
    keys = {k for d in dicts for k in d}
    return {k: sum(d.get(k, 0.0) for d in dicts) / len(dicts) for k in keys}


def reduce_planes(planes, window_s: float) -> dict | None:
    """None when the trace has no device plane with an op in it (a CPU
    rehearsal); else seconds, averaged over the chips traced."""
    per_chip = [
        r
        for r in (
            _reduce_plane(p) for p in planes if DEVICE_PLANE.match(p.name)
        )
        if r is not None
    ]
    if not per_chip:
        return None
    ns = 1e-9
    return {
        "chips": len(per_chip),
        "window_s": window_s,
        "busy_s": sum(r["busy_ns"] for r in per_chip) / len(per_chip) * ns,
        "programs_s": {
            k: v * ns
            for k, v in _mean_dicts([r["programs_ns"] for r in per_chip]).items()
        },
        "program_runs": per_chip[0]["program_runs"],
        "ops_s": {
            k: v * ns
            for k, v in _mean_dicts([r["ops_ns"] for r in per_chip]).items()
        },
        "gaps_s": {
            k: v * ns
            for k, v in _mean_dicts([r["gaps_ns"] for r in per_chip]).items()
        },
    }


def reduce_dir(trace_dir: str, window_s: float):
    """Reduce the one trace ``jax.profiler`` wrote under ``trace_dir``:
    (the reduction or None, the trace's layout)."""
    import jax

    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        return None, {}
    planes = list(jax.profiler.ProfileData.from_file(sorted(paths)[-1]).planes)
    return reduce_planes(planes, window_s), layout(planes)


def layout(planes) -> dict:
    """plane -> line -> number of events: what the trace held, for the
    reader who has to find out why a reduction came back empty."""
    return {
        p.name: {ln.name: sum(1 for _ in ln.events) for ln in p.lines}
        for p in planes
        if p.name.startswith("/device:")
    }


def top(d: dict[str, float], n: int = 10, chars: int = 120) -> list[list]:
    """The n largest, names cut to ``chars`` (an op's name is its whole
    HLO line)."""
    return [
        [k[:chars], v]
        for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]
    ]


def ops_matching(trace: dict, needle: str) -> float:
    """Seconds of the ops whose name holds ``needle`` (self time)."""
    return sum(v for k, v in trace["ops_s"].items() if needle in k)


def programs_matching(trace: dict, names) -> float:
    """Device seconds inside the programs named in ``names``."""
    return sum(trace["programs_s"].get(n, 0.0) for n in names)
