"""``observe idle``: each idle gap of the device put down to what the
host was doing.

    python -m keystone_tpu observe idle <profile-dir> [<observe-run-dir>]

``<profile-dir>`` is what ``--profile DIR`` (or any
``jax.profiler.start_trace``) wrote. While that session was on, every
live :func:`keystone_tpu.observe.spans.span` also entered a
``TraceAnnotation`` of its name with its ``span`` / ``parent`` /
``trace`` ids as stats, so the ``.xplane.pb`` holds the host's spans
(plane ``/host:CPU``) and the device's ops (planes ``/device:TPU:<n>``,
line ``XLA Ops``) on one clock. The device is busy over the union of its
op intervals and idle in the gaps between them, within the window that
spans and ops cover together; every gap is split over the innermost
spans that overlap it, the rest is ``(no span)``. Several chips are
averaged.

With ``<observe-run-dir>`` (the same run's ``--observe DIR``) the spans
recorded after the fact, which have no twin in the profile (``jit.trace``,
``jit.lower``, ``jit.backend_compile``, ``jit.cache_read``), are placed
on the profile's timeline through the offset of the spans both files
hold, so a gap inside ``fit.score`` reads ``jit.backend_compile
fun=jit(score)``.

:func:`idle_by_span` takes any objects of the ``ProfileData`` shape
(``.name``, ``.lines`` -> ``.name``, ``.events`` -> ``.name``,
``.start_ns``, ``.duration_ns``, ``.stats``), so a hand-built trace
tests it. Nothing here comes from the benchmark: that is a yardstick of
its own (``benchmarks/harness/xplane.py``).
"""

from __future__ import annotations

import glob
import os
import re
import statistics
import sys
from typing import Any, Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANES = "/host:"
NO_SPAN = "(no span)"


def _union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def device_busy(planes) -> list[list[tuple[float, float]]]:
    """Per chip, the merged (start, end) ns intervals in which an op ran."""
    out = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = [
            (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns))
            for line in plane.lines
            if line.name == OPS_LINE
            for ev in line.events
        ]
        if ops:
            out.append(_union(ops))
    return out


def host_spans(planes) -> list[dict]:
    """The host-plane events that are a span's twin: those with a
    ``span`` stat (``label``, ``span``, ``parent``, ``start``, ``end``)."""
    out = []
    for plane in planes:
        if not plane.name.startswith(HOST_PLANES):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(getattr(ev, "stats", None) or ())
                if "span" not in stats:
                    continue
                start = float(ev.start_ns)
                out.append({
                    "label": ev.name,
                    "span": str(stats["span"]),
                    "parent": str(stats.get("parent") or ""),
                    "start": start,
                    "end": start + float(ev.duration_ns),
                })
    return out


def place_recorded(spans: list[dict], records: list[dict]) -> list[dict]:
    """``spans`` plus the records of a run's ``spans.jsonl`` that have no
    twin among them, moved from the process's ``perf_counter_ns`` clock
    onto the profile's by the median offset of the spans both hold.
    Records without ``t0_ns`` (written before spans had a clock) and
    records of traces the profile saw nothing of are left out."""
    have = {s["span"]: s for s in spans}
    shared = [r for r in records if r.get("span") in have and "t0_ns" in r]
    if not shared:
        return spans
    offset = statistics.median(
        have[r["span"]]["start"] - r["t0_ns"] for r in shared
    )
    traces = {r.get("trace") for r in shared}
    placed = list(spans)
    for r in records:
        if r.get("span") in have or "t0_ns" not in r or r.get("trace") not in traces:
            continue
        fun = r.get("fun")
        placed.append({
            "label": f"{r['name']} fun={fun}" if fun else str(r["name"]),
            "span": str(r["span"]),
            "parent": str(r.get("parent") or ""),
            "start": r["t0_ns"] + offset,
            "end": r["t1_ns"] + offset,
        })
    return placed


def _with_depth(spans: list[dict]) -> list[dict]:
    by_id = {s["span"]: s for s in spans}

    def depth(s: dict) -> int:
        n = 0
        seen = {s["span"]}
        while s["parent"] in by_id and s["parent"] not in seen:
            s = by_id[s["parent"]]
            seen.add(s["span"])
            n += 1
        return n

    return [{**s, "depth": depth(s)} for s in spans]


def _split(gap: tuple[float, float], spans: list[dict]) -> dict[str, float]:
    """label -> ns of one gap, each instant given to the innermost span
    over it (deepest in the tree, then the one that started last)."""
    a, b = gap
    over = [s for s in spans if s["start"] < b and s["end"] > a]
    cuts = sorted(
        {a, b, *(t for s in over for t in (s["start"], s["end"]) if a < t < b)}
    )
    out: dict[str, float] = {}
    for lo, hi in zip(cuts, cuts[1:]):
        inside = [s for s in over if s["start"] <= lo and s["end"] >= hi]
        label = (
            max(inside, key=lambda s: (s["depth"], s["start"]))["label"]
            if inside
            else NO_SPAN
        )
        out[label] = out.get(label, 0.0) + (hi - lo)
    return out


def idle_by_span(planes, records: list[dict] | None = None) -> dict[str, Any] | None:
    """The table of ``observe idle``; None when no device plane holds an
    op. Seconds; ``rows`` is ``[(label, idle_s, gaps)]``, largest first,
    ``(no span)`` last, averaged over the chips in the trace."""
    planes = list(planes)
    chips = device_busy(planes)
    if not chips:
        return None
    twins = host_spans(planes)
    # the window: what the spans' twins and the ops cover together
    # (post-hoc records do not stretch it)
    marks = [t for busy in chips for iv in busy for t in iv]
    marks += [t for s in twins for t in (s["start"], s["end"])]
    w0, w1 = min(marks), max(marks)
    spans = _with_depth(place_recorded(twins, records or []))
    idle: dict[str, float] = {}
    gaps: dict[str, float] = {}
    busy_ns = 0.0
    for busy in chips:
        busy_ns += sum(e - s for s, e in busy)
        edges = [w0, *(t for iv in busy for t in iv), w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            for label, ns in _split((a, b), spans).items():
                idle[label] = idle.get(label, 0.0) + ns
                gaps[label] = gaps.get(label, 0) + 1
    n = len(chips)
    rows = sorted(
        ((k, v / n * 1e-9, gaps[k] / n) for k, v in idle.items()),
        key=lambda r: (r[0] == NO_SPAN, -r[1]),
    )
    return {
        "chips": n,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns / n * 1e-9,
        "idle_s": sum(idle.values()) / n * 1e-9,
        "rows": rows,
    }


def render(table: dict[str, Any]) -> str:
    window, idle = table["window_s"], table["idle_s"]
    lines = [
        f"window {window:.4f} s on {table['chips']} chip(s): busy "
        f"{table['busy_s']:.4f} s, idle {idle:.4f} s "
        f"({100.0 * idle / window if window else 0.0:.2f} %)",
        f"{'span (innermost over the gap)':58} {'idle s':>8} {'of idle':>8} {'gaps':>6}",
    ]
    for label, s, gaps in table["rows"]:
        lines.append(
            f"{label[:58]:58} {s:8.4f} {100.0 * s / idle if idle else 0.0:7.2f}% "
            f"{gaps:6.0f}"
        )
    return "\n".join(lines)


def newest_profile(profile_dir: str) -> str | None:
    paths = glob.glob(
        os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True
    )
    return max(paths, key=os.path.getmtime) if paths else None


USAGE = (
    "usage: python -m keystone_tpu observe idle <profile-dir>"
    " [<observe-run-dir>]\n"
    "<profile-dir> is what --profile DIR wrote (the newest .xplane.pb\n"
    "under it is read); device idle time is put down to the host span\n"
    "that was open over each gap. <observe-run-dir> (the same run's\n"
    "--observe DIR) adds the jit.* spans, which are recorded after the\n"
    "fact and have no event in the profile"
)


def main(argv: list[str] | None = None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help") or len(argv) > 2:
        raise SystemExit(USAGE)
    path = newest_profile(argv[0])
    if path is None:
        raise SystemExit(f"no .xplane.pb under {argv[0]}")
    records = None
    if len(argv) == 2:
        from keystone_tpu.observe import spans as _spans

        try:
            records = _spans.read_spans(argv[1])
        except OSError as e:
            raise SystemExit(str(e)) from None
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    print(f"profile {path}")
    table = idle_by_span(planes, records)
    if table is None:
        names = sorted({s["label"] for s in host_spans(planes)})
        print(
            f"no /device:TPU:<n> plane with an '{OPS_LINE}' line (a CPU "
            "trace has none): no device idle time to put down. Host spans "
            f"in the trace: {', '.join(names) if names else 'none'}"
        )
        return
    print(render(table))
